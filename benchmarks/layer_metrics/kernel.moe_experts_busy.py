"""Share of the device's busy time spent in the grouped matrix product of
the routed experts, in percent: the trace's operations whose name carries
the product's name (the Mosaic grouped matmul XLA makes of
`jax.lax.ragged_dot`, and its metadata kernel), over the union of all
operation intervals. The router, the sort, the gather and the scatter-add
around it are XLA fusions and are not in it. Layer: kernels. Moves
tokens_per_s."""

from lib.roofline_moe_mla import kernel_seconds

# tpu_engine/ops/moe.py `routed_experts` calls jax.lax.ragged_dot; on a TPU
# the instructions are %ragged-dot-none and %ragged-dot-metadata.
PATTERN = "ragged-dot"


def compute(run):
    seconds = kernel_seconds(run, PATTERN)
    return 100.0 * seconds / run["trace"]["busy_s"] if seconds else None
