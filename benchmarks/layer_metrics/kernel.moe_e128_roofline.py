"""The grouped matrix product of 128 whole routed experts against its
roofline, in percent: the time one chip needs at its peaks for the work the
product could not avoid, over its measured self seconds in the traced slice
(the ops `kernel.moe_e128_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice (`moe_experts_touched`, `moe_assignments`; those that carry
`run_width`) and the sizes of `run["config"]` (lib/roofline_sdar.py,
lib/roofline_moe_mla.py):

  bytes   experts touched x 3 x 2048 x 768 x 2 B (9.4 MB an expert)
  FLOPs   assignments x 3 x 2 x 2048 x 768

The activations' bytes are left out, and a touched expert's matrices are
counted once however many row tiles re-read them: the share reads low and
never high."""

from lib.roofline_sdar import experts_roofline


def compute(run):
    return experts_roofline(run)
