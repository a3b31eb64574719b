"""The one-step form of the gated delta rule against its roofline, in
percent: the time one chip needs at its peaks for the work it could not
avoid, over its measured self seconds in the traced slice (the operations
`kernel.gdn_step_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice and the sizes of `run["config"]` (lib/roofline_gated_delta.py):

  bytes   `gdn_step_rows` x linear layers x 2 x the state (30 x 192 x 96
          float32 = 2.21 MB at Olmo-Hybrid-7B's widths): read once,
          written once; a row's q, k, v and read beside it
  FLOPs   `gdn_step_rows` x linear layers x heads x 3 x 2 x d_v x d_k

The bytes bound it. The device tiles the state's 96 lanes to 128, so the
kernel moves a third more than is counted: the share reads low and never
high."""

from lib.roofline_gated_delta import STEP, recurrence_roofline


def compute(run):
    return recurrence_roofline(run, STEP)
