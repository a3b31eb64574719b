"""Share of the device's busy time spent in the ONE-STEP form of the Mamba-2
recurrence (`ops/ssd.py` `ssd_step_rows`) on a model whose state is 128
heads of (64, 128) in 8 groups, 4.19 MB a row and layer as Falcon-H1's but
cut the other way (its heads are 32 of (128, 256) in 2 groups), in 5 of 11
layers, in percent: the trace's operations whose name carries the kernel's
name, `ssd_step`, over the union of all operation intervals
(`kernel.ssd_step_busy`'s operations, for the cell that metric's list does
not name). Layer: kernels. Moves tokens_per_s."""

from lib.roofline_nemotron_h import STEP, busy_share


def compute(run):
    return busy_share(run, STEP)
