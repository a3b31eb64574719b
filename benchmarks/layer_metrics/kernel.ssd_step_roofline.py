"""The one-step form of the Mamba-2 recurrence against its roofline, in
percent: the time one chip needs at its peaks for the work it could not
avoid, over its measured self seconds in the traced slice (the operations
`kernel.ssd_step_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice and the sizes of `run["config"]` (lib/roofline_falcon_h1.py):

  bytes   `ssd_step_rows` x layers x 2 x the state (32 x 128 x 256 float32
          = 4.19 MB at Falcon-H1's widths): read once, written once; a
          row's x, dt, B, C and its read beside it (37 KB)
  FLOPs   `ssd_step_rows` x layers x heads x 2 x 2 x P x N

The bytes bound it by construction: 8.4 MB moved for 4 MFLOP a row and
layer. At 256 state lanes a state is whole lane tiles: what the device moves
is what is counted."""

from lib.roofline_falcon_h1 import STEP, recurrence_roofline


def compute(run):
    return recurrence_roofline(run, STEP)
