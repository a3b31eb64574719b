"""The one-step form of the channel-gated delta rule against its roofline,
in percent: the time one chip needs at its peaks for the work it could not
avoid, over its measured self seconds in the traced slice (the operations
`kernel.kda_step_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice and the sizes of `run["config"]` (lib/roofline_kimi_linear.py):

  bytes   `kda_step_rows` x KDA layers x 2 x the state (32 x 128 x 128
          float32 = 2.10 MB at Kimi-Linear's widths): read once, written
          once; a row's q, k, v, its 128 gates a head and its read beside it
  FLOPs   `kda_step_rows` x KDA layers x heads x 3 x 2 x d_v x d_k

The bytes bound it. At 128 key lanes a state is whole lane tiles: what the
device moves is what is counted."""

from lib.roofline_kimi_linear import STEP, recurrence_roofline


def compute(run):
    return recurrence_roofline(run, STEP)
