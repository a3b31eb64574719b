"""The chunked form of the channel-gated delta rule against its roofline,
in percent: the time one chip needs at its peaks for the work it could not
avoid, over its measured self seconds in the traced slice (the operations
`kernel.kda_chunk_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice and the sizes of `run["config"]` (lib/roofline_kimi_linear.py):

  FLOPs   `kda_chunk_tokens` x KDA layers x heads x 3 x 2 x d_v x d_k:
          S k, the rank-one write and S q of the recurrence itself; the
          intra-chunk products and the triangular solve are the form's own
  bytes   `kda_chunk_rows` x KDA layers x 2 x the state (d_v x d_k float32
          a head), and each token's q, k, v and gates in and read out

Held against the bfloat16 peak though the form runs in float32, and a
row's run is padded to 256 tokens whatever the budget left it: the share
reads low and never high."""

from lib.roofline_kimi_linear import CHUNK, recurrence_roofline


def compute(run):
    return recurrence_roofline(run, CHUNK)
