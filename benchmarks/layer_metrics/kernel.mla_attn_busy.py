"""Share of the device's busy time spent in the latent-attention (MLA)
Pallas kernel, in percent: the trace's operations whose name carries the
kernel's name, over the union of all operation intervals. Layer: kernels.
Moves tokens_per_s."""

from lib.roofline_moe_mla import kernel_seconds

# `pl.pallas_call(..., name="mla_latent_read")` in
# tpu_engine/ops/latent_attention.py; Mosaic names the custom call after it.
PATTERN = "mla_latent"


def compute(run):
    seconds = kernel_seconds(run, PATTERN)
    return 100.0 * seconds / run["trace"]["busy_s"] if seconds else None
