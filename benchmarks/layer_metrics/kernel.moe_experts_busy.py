"""Share of the device's busy time spent in the grouped matrix product of
the routed experts a lane holds (all of them, or its share), in percent:
the trace's operations whose name carries the product's name (the Mosaic
grouped matmul XLA makes of `jax.lax.ragged_dot`, and its metadata kernel:
%ragged-dot-none and %ragged-dot-metadata; tpu_engine/ops/moe.py
`routed_experts`), over the union of all operation intervals. The router,
the sort, the gather and the scatter-add around it are XLA fusions and are
not in it. Layer: kernels. Moves tokens_per_s."""

from lib.roofline_kinds import busy_share


def compute(run):
    return busy_share(run, "experts")
