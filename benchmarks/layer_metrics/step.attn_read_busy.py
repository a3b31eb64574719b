"""Share of the device's busy time under the step's `attn/read` part: the read
of the pool WITH everything the call needs around it (the Pallas call, q's
relayouts and the output's, the gather of tall tiles, XLA's own read where no
kernel runs). Read beside the cell's `kernel.*_attn_busy`, the Pallas call
alone: the difference is what the call costs around the kernel.
Self seconds of the trace's ops under the part (lib/xplane_scopes.py: an op's
part is what its scope path in the trace's metadata names), over the union
of all operation intervals, in percent: the denominator `kernel.*_busy` has.
A program that opens no part (before PR 55) reads nothing; with parts in the
trace, 0.0 means no op ran under this one.
Layer: step function. Moves tokens_per_s."""

from lib.xplane_scopes import busy_share


def compute(run):
    return busy_share(run, "attn/read")
