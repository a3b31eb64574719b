"""The step form of the recurrence against its roofline, in percent: the
time one chip needs at its peaks for the work the kernel could not avoid,
over its measured self seconds in the traced slice (the ops
`kernel.state_step_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice (`<kind>_step_rows`: a row and a token each) and the sizes of
`run["config"]` (lib/roofline_sizes.py, lib/roofline_kinds.py; the count of
a delta rule in lib/roofline_gated_delta.py, of Mamba-2 in
lib/roofline_falcon_h1.py):

  bytes   rows x layers with the recurrence x 2 x the state (float32, its
          shape a head x heads: read and written once), and a token's
          inputs and its read, float32
  FLOPs   rows x layers x heads x (3, delta rule; 2, Mamba-2) x 2 x the
          state's elements

A decode row's step is bound by its state's bytes. Under-counted
throughout, so the share reads low and never high."""

from lib.roofline_kinds import recurrence_roofline


def compute(run):
    return recurrence_roofline(run, "step")
