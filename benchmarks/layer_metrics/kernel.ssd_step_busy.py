"""Share of the device's busy time spent in the ONE-STEP form of the Mamba-2
recurrence (`ops/ssd.py` `ssd_step_rows`: every decode row's state read,
decayed, written to and read through C once a layer, where it lies in the
pool), in percent: the trace's operations whose name carries the kernel's
name, `ssd_step`, over the union of all operation intervals. Layer:
kernels. Moves tokens_per_s."""

from lib.roofline_falcon_h1 import STEP, busy_share


def compute(run):
    return busy_share(run, STEP)
