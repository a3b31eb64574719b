"""Share of the device's busy time under the step's feed-forward parts: `mlp`
(a dense feed-forward with its norm and residual add) and `moe` (`moe/route`,
`moe/experts`, `moe/shared`). In a dense cell it prices the products over a
chunk tick's padded slots.
Self seconds of the trace's ops under the part (lib/xplane_scopes.py: an op's
part is what its scope path in the trace's metadata names), over the union
of all operation intervals, in percent: the denominator `kernel.*_busy` has.
A program that opens no part (before PR 55) reads nothing; with parts in the
trace, 0.0 means no op ran under this one.
Layer: step function. Moves tokens_per_s."""

from lib.xplane_scopes import busy_share


def compute(run):
    return busy_share(run, "mlp", "moe")
