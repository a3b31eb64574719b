"""The routed experts' grouped matrix product against its roofline, in
percent: the time one chip needs at its peaks for the work the product
could not avoid, over its measured self seconds in the traced slice (the
ops `kernel.moe_experts_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice (`moe_experts_touched`, `moe_assignments`: what the step counted,
summed over the expert layers) and the sizes of `run["config"]`
(lib/roofline_moe_mla.py):

  bytes   experts touched x 3 x d_model x d_expert x bytes an element: a
          touched expert's gate, up and down matrices once
  FLOPs   assignments x 3 x 2 x d_model x d_expert

The activations' bytes are left out, and a touched expert's matrices are
counted once however many row tiles re-read them: the share reads low and
never high."""

from lib import roofline, roofline_moe_mla

PATTERN = "ragged-dot"


def compute(run):
    kernel_s = roofline_moe_mla.kernel_seconds(run, PATTERN)
    peaks = run["peaks"]
    ticks = roofline_moe_mla.whole_ticks(run)
    touched = sum(attrs.get("moe_experts_touched", 0) for attrs in ticks)
    assignments = sum(attrs.get("moe_assignments", 0) for attrs in ticks)
    if not kernel_s or not assignments or not peaks:
        return None
    size = roofline_moe_mla.sizes(run["config"])
    floor_s = roofline.floor_seconds(
        roofline_moe_mla.expert_bytes(touched, size["d_model"],
                                      size["d_expert"],
                                      size["bytes_per_element"]),
        roofline_moe_mla.expert_flops(assignments, size["d_model"],
                                      size["d_expert"]),
        peaks)
    return 100.0 * floor_s / run["trace"]["planes"] / kernel_s
