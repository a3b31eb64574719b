"""Share of the device's busy time spent in the grouped matrix product of
the routed experts a lane HOLDS, where an expert is TWO matrices of 1024 x
2688 in a latent (relu^2 between them) and a token brings 22 pairs of which
a quarter form rows here, in percent: the trace's operations whose name
carries the product's name (the Mosaic grouped matmul XLA makes of
`jax.lax.ragged_dot`, and its metadata kernel), over the union of all
operation intervals: `kernel.moe_held_busy`'s operations, for the cell that
metric's list does not name. Layer: kernels. Moves tokens_per_s."""

from lib.roofline_nemotron_h import EXPERTS, busy_share


def compute(run):
    return busy_share(run, EXPERTS)
