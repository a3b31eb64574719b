"""Share of the device's busy time spent in the paged-attention kernel's
calls under the BLOCK-CAUSAL mask (a query sees its own block of 4 positions
whole and every earlier one), eight query heads a KV head (32 over 4, head
size 128: 2,048 B a token and layer), in percent: the trace's operations
whose name carries the call's name (`block_mask_read`: a tick's run call,
64 rows x 4 slots x 8 heads = 32 query rows a KV head, the four heads
packed into one score tile a row, and its tall call, tiles of 16 slots of a
prompt chunk), over the union of all operation intervals. Layer: kernels.
Moves tokens_per_s."""

from lib.roofline_sdar import BLOCK_READ, busy_share


def compute(run):
    return busy_share(run, BLOCK_READ)
