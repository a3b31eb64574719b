"""Share of the device's busy time spent in the paged-attention kernel's
calls of a model whose EVERY layer attends with five query heads a KV head
(20 over 4, head size 128: 2 KB a token and layer) beside a Mamba-2
recurrence, in percent: the trace's operations whose name carries the
kernel's name (`_paged_call`: a tick's short call, the 4 KV heads' 5 query
rows packed into one score tile, and its tall call, tiles of 128 slots = 640
query rows), over the union of all operation intervals. Layer: kernels.
Moves tokens_per_s."""

from lib.roofline_falcon_h1 import PAGED, busy_share


def compute(run):
    return busy_share(run, PAGED)
