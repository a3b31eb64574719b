"""Share of the device's busy time spent in the paged-attention kernel's
calls of a model that attends with SIXTEEN query heads a KV head (32 over 2,
head size 128, nothing rotated: 1,024 B a token in its one attention layer
of eleven), in percent: the trace's operations whose name carries the
kernel's name (`_paged_call`: a tick's short call, the 2 KV heads' 16 query
rows packed into one score tile, and its tall call, tiles of 8 slots = 128
query rows, so a chunk of ~210 tokens is ~27 tiles that each walk the row's
context), over the union of all operation intervals. Layer: kernels. Moves
tokens_per_s."""

from lib.roofline_nemotron_h import PAGED, busy_share


def compute(run):
    return busy_share(run, PAGED)
