"""Share of the device's busy time spent in the grouped matrix product of
the routed experts a lane HOLDS (a share of the model's), in percent: the
trace's operations whose name carries the product's name (the Mosaic
grouped matmul XLA makes of `jax.lax.ragged_dot`, and its metadata kernel),
over the union of all operation intervals. The router over all the
experts, the sort, the gather and the scatter-add around it are XLA fusions
and are not in it. Layer: kernels. Moves tokens_per_s."""

from lib.roofline_moe_mla import kernel_seconds

PATTERN = "ragged-dot"


def compute(run):
    seconds = kernel_seconds(run, PATTERN)
    return 100.0 * seconds / run["trace"]["busy_s"] if seconds else None
