"""Share of the device's busy time under NO part of the step: the ops whose
scope path in the trace names none of `STEP_PARTS` (lib/xplane_scopes.py
`UNSCOPED`: the shells of loops and conditionals, what XLA adds between a
program's ops and the layer loop's own slicing of stacked weights, programs
that are no tick). The coverage of every `step.*_busy` above: they and this
one add up to the sum of self times over busy.
Self seconds of the trace's ops under the part (lib/xplane_scopes.py: an op's
part is what its scope path in the trace's metadata names), over the union
of all operation intervals, in percent: the denominator `kernel.*_busy` has.
A program that opens no part (before PR 55) reads nothing; with parts in the
trace, 0.0 means no op ran under this one.
Layer: step function. Moves tokens_per_s."""

from lib.xplane_scopes import UNSCOPED, busy_share


def compute(run):
    return busy_share(run, UNSCOPED)
