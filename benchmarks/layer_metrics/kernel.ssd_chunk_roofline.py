"""The chunked form of the Mamba-2 recurrence against its roofline, in
percent: the time one chip needs at its peaks for the work it could not
avoid, over its measured self seconds in the traced slice (the operations
`kernel.ssd_chunk_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice and the sizes of `run["config"]` (lib/roofline_falcon_h1.py):

  FLOPs   `ssd_chunk_tokens` x layers x heads x 2 x 2 x P x N: the rank-one
          write and the read S C of the recurrence itself; the intra-chunk
          products are the form's own
  bytes   `ssd_chunk_rows` x layers x 2 x the state (P x N float32 a head),
          and each token's x, dt, B, C in and read out

Held against the bfloat16 peak though the form runs in float32 passes, and
a row's run is padded to 256 tokens whatever the budget left it: the share
reads low and never high."""

from lib.roofline_falcon_h1 import CHUNK, recurrence_roofline


def compute(run):
    return recurrence_roofline(run, CHUNK)
