"""The one-step form of the Mamba-2 recurrence against its roofline at 128
heads of (64, 128), in percent: the time one chip needs at its peaks for the
work it could not avoid, over its measured self seconds in the traced slice
(the operations `kernel.ssd64_step_busy` sums). Layer: kernels. Moves
tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice and the sizes of `run["config"]` (lib/roofline_nemotron_h.py, the
counts lib/roofline_falcon_h1.py's over the pattern's M layers alone):

  bytes   `ssd_step_rows` x 5 M layers x 2 x the state (128 x 64 x 128
          float32 = 4.19 MB): read once, written once; a row's x, dt, B, C
          and its read beside it (74 KB)
  FLOPs   `ssd_step_rows` x M layers x heads x 2 x 2 x P x N

The bytes bound it by construction. At 128 state lanes a state is whole
lane tiles: what the device moves is what is counted."""

from lib.roofline_nemotron_h import STEP, recurrence_roofline


def compute(run):
    return recurrence_roofline(run, STEP)
