"""Share of the device's busy time spent in the ONE-STEP form of the
channel-gated delta rule (`ops/gated_delta.py` `gdn_step_rows` with a gate a
key channel: every decode row's state read, changed and written once a KDA
layer, where it lies in the pool), in percent: the trace's operations whose
name carries the kernel's name, `kda_step`, over the union of all operation
intervals. Layer: kernels. Moves tokens_per_s."""

from lib.roofline_kimi_linear import STEP, busy_share


def compute(run):
    return busy_share(run, STEP)
