"""Share of the device's busy time spent in the paged-attention kernel's
calls on SLIDING-WINDOW layers, in percent: the trace's operations whose
name carries that call's name, over the union of all operation intervals.
Layer: kernels. Moves tokens_per_s."""

from lib.roofline_laguna import WINDOW
from lib.roofline_moe_mla import kernel_seconds


def compute(run):
    seconds = kernel_seconds(run, WINDOW)
    return 100.0 * seconds / run["trace"]["busy_s"] if seconds else None
