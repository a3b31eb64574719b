"""Share of the device's busy time under the step's `attn` parts (`attn/qkv`,
`attn/write`, `attn/read`, `attn/out`: the projections and rotation, the new
tokens' write into the pool, the pool's read with its relayouts, the output
projection; tpu_engine/utils/tracing.py `STEP_PARTS`).
Self seconds of the trace's ops under the part (lib/xplane_scopes.py: an op's
part is what its scope path in the trace's metadata names), over the union
of all operation intervals, in percent: the denominator `kernel.*_busy` has.
A program that opens no part (before PR 55) reads nothing; with parts in the
trace, 0.0 means no op ran under this one.
Layer: step function. Moves tokens_per_s."""

from lib.xplane_scopes import busy_share


def compute(run):
    return busy_share(run, "attn")
