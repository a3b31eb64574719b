"""The grouped matrix product of the routed experts a lane HOLDS against its
roofline, in percent, at a LatentMoE's expert shapes: the time one chip
needs at its peaks for the work the product could not avoid, over its
measured self seconds in the traced slice (the ops `kernel.moe_latent_busy`
sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice (`moe_experts_touched`, `moe_assignments_held`) and the sizes of
`run["config"]` (lib/roofline_nemotron_h.py):

  bytes   experts touched x 2 x 1024 x 2688 x 2 B (11.0 MB an expert)
  FLOPs   held assignments x 2 x 2 x 1024 x 2688

The activations' bytes are left out, and a touched expert's matrices are
counted once however many row tiles re-read them: the share reads low and
never high."""

from lib.roofline_nemotron_h import experts_roofline


def compute(run):
    return experts_roofline(run)
