"""Share of the device's busy time spent in the grouped matrix product of
the routed experts a lane HOLDS, at 2304 x 1024 an expert matrix and ~4 rows
an expert in a decode tick of 128 rows, in percent: the trace's operations
whose name carries the product's name (the Mosaic grouped matmul XLA makes
of `jax.lax.ragged_dot`, and its metadata kernel), over the union of all
operation intervals: `kernel.moe_held_busy`'s operations, for the cell that
metric's list does not name. Layer: kernels. Moves tokens_per_s."""

from lib.roofline_kimi_linear import EXPERTS, busy_share


def compute(run):
    return busy_share(run, EXPERTS)
