"""Share of the device's busy time under the step's `moe/experts` part: the
gather of (token, expert) pairs, the grouped products WHATEVER implements
them, the activation between them and the weighted combine (and, where the
experts work in a latent, the projections to and from it). Read beside the
cell's `kernel.moe_*_busy`, which matches the string `ragged-dot`: this one
does not care what implements the product (XLA's own expansion of
`lax.ragged_dot` drops the path and names itself `ragged-dot-*`: the one
name lib/xplane_scopes.py `COMPILER_NAMED` puts to this part; a kernel of
the repo's own keeps its path).
Self seconds of the trace's ops under the part (lib/xplane_scopes.py: an op's
part is what its scope path in the trace's metadata names), over the union
of all operation intervals, in percent: the denominator `kernel.*_busy` has.
A program that opens no part (before PR 55) reads nothing; with parts in the
trace, 0.0 means no op ran under this one.
Layer: step function. Moves tokens_per_s."""

from lib.xplane_scopes import busy_share


def compute(run):
    return busy_share(run, "moe/experts")
