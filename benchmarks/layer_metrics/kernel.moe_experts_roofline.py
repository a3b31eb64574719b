"""The routed experts' grouped matrix product against its roofline, in
percent: the time one chip needs at its peaks for the work the product
could not avoid, over its measured self seconds in the traced slice (the
ops `kernel.moe_experts_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice (`moe_experts_touched`: the (layer, expert) pairs that took a
row; `moe_assignments_held`, the (token, expert) pairs that formed a row
HERE, on a lane that holds a share of the experts, else `moe_assignments`)
and the sizes of `run["config"]` (lib/roofline_sizes.py,
lib/roofline_kinds.py):

  bytes   experts touched x matrices an expert (gate, up and down of
          d_model x d_expert; up and down of d_latent x d_expert where the
          experts work in a latent) x bytes an element, once
  FLOPs   assignments x matrices x 2 x rows x cols

The activations' bytes are left out, and a touched expert's matrices are
counted once however many row tiles re-read them: the share reads low and
never high."""

from lib.roofline_kinds import experts_roofline


def compute(run):
    return experts_roofline(run)
