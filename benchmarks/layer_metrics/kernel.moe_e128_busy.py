"""Share of the device's busy time spent in the grouped matrix product of
128 WHOLE routed experts a layer (every expert held: three matrices of 2048
x 768, soft-max routing top 8, no shared expert, no dense layer before
them), in percent: the trace's operations whose name carries the product's
name (the Mosaic grouped matmul XLA makes of `jax.lax.ragged_dot`, and its
metadata kernel), over the union of all operation intervals:
`kernel.moe_held_busy`'s operations, for the cell that metric's list does
not name. Layer: kernels. Moves tokens_per_s."""

from lib.roofline_sdar import EXPERTS, busy_share


def compute(run):
    return busy_share(run, EXPERTS)
