"""Share of the device's busy time spent in the paged-attention kernel's
calls of a model whose full-attention layers are MHA at head size 128 (one
query head a KV head, 30 of them: 7.5 KB a token and tensor) beside
recurrent layers, in percent: the trace's operations whose name carries the
kernel's name, over the union of all operation intervals. Layer: kernels.
Moves tokens_per_s."""

from lib.roofline_gated_delta import PAGED, busy_share


def compute(run):
    return busy_share(run, PAGED)
