"""Share of the device's busy time spent in the paged-attention kernel's
calls on FULL-attention layers of a model that also has window layers, in
percent: the trace's operations whose name carries the kernel's name and
not the window call's (lib/roofline_laguna.py), over the union of all
operation intervals. Layer: kernels. Moves tokens_per_s."""

from lib.roofline_laguna import full_attention_seconds


def compute(run):
    seconds = full_attention_seconds(run)
    return 100.0 * seconds / run["trace"]["busy_s"] if seconds else None
