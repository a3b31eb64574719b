"""Share of the device's busy time under the step's `mixer/chunk` part: the
CHUNKED form of a recurrent layer whole (the loop over the tick's chunk
rows: each row's conv, the terms XLA prepares for the kernel, the `*_chunk`
kernel, the write of its outputs into the token list and of the conv tail
into the state row). Read beside the cell's `kernel.*_chunk_busy`, the kernel
alone.
Self seconds of the trace's ops under the part (lib/xplane_scopes.py: an op's
part is what its scope path in the trace's metadata names), over the union
of all operation intervals, in percent: the denominator `kernel.*_busy` has.
A program that opens no part (before PR 55) reads nothing; with parts in the
trace, 0.0 means no op ran under this one.
Layer: step function. Moves tokens_per_s."""

from lib.xplane_scopes import busy_share


def compute(run):
    return busy_share(run, "mixer/chunk")
