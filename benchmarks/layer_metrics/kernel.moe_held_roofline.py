"""The grouped matrix product of the routed experts a lane HOLDS against
its roofline, in percent: the time one chip needs at its peaks for the
work the product could not avoid, over its measured self seconds in the
traced slice (the ops `kernel.moe_held_busy` sums). Layer: kernels. Moves
tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice (`moe_experts_touched`, `moe_assignments_held`: the (layer,
expert) pairs that took a row and the (token, expert) pairs that formed a
row HERE, not the pairs routed to experts another chip holds) and the
sizes of `run["config"]` (lib/roofline_laguna.py):

  bytes   experts touched x 3 x d_model x d_expert x bytes an element
  FLOPs   held assignments x 3 x 2 x d_model x d_expert

The activations' bytes are left out, and a touched expert's matrices are
counted once however many row tiles re-read them: the share reads low and
never high."""

from lib import roofline, roofline_laguna, roofline_moe_mla

PATTERN = "ragged-dot"


def compute(run):
    kernel_s = roofline_moe_mla.kernel_seconds(run, PATTERN)
    peaks = run["peaks"]
    ticks = roofline_moe_mla.whole_ticks(run)
    touched = roofline_laguna.span_sum(ticks, "moe_experts_touched")
    held = roofline_laguna.span_sum(ticks, "moe_assignments_held")
    if not kernel_s or not held or not peaks:
        return None
    size = roofline_laguna.sizes(run["config"])
    floor_s = roofline.floor_seconds(
        roofline_moe_mla.expert_bytes(touched, size["d_model"],
                                      size["d_expert"],
                                      size["bytes_per_element"]),
        roofline_moe_mla.expert_flops(held, size["d_model"],
                                      size["d_expert"]),
        peaks)
    return 100.0 * floor_s / run["trace"]["planes"] / kernel_s
