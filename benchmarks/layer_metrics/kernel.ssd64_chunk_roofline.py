"""The chunked form of the Mamba-2 recurrence against its roofline at 128
heads of (64, 128), in percent: the time one chip needs at its peaks for the
work it could not avoid, over its measured self seconds in the traced slice
(the operations `kernel.ssd64_chunk_busy` sums). Layer: kernels. Moves
tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice and the sizes of `run["config"]` (lib/roofline_nemotron_h.py, the
counts lib/roofline_falcon_h1.py's over the pattern's M layers alone):

  FLOPs   `ssd_chunk_tokens` x 5 M layers x heads x 2 x 2 x P x N: the
          rank-one write and the read S C of the recurrence itself; the
          intra-chunk products are the form's own
  bytes   `ssd_chunk_rows` x M layers x 2 x the state (P x N float32 a
          head), and each token's x, dt, B, C in and read out

Held against the bfloat16 peak though the form runs in float32 passes, and
a row's run is padded to 256 tokens whatever the budget left it: the share
reads low and never high."""

from lib.roofline_nemotron_h import CHUNK, recurrence_roofline


def compute(run):
    return recurrence_roofline(run, CHUNK)
