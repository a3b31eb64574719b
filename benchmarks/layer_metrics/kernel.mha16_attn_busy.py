"""Share of the device's busy time spent in the paged-attention kernel's
calls of a LOOPED model (MHA, 16 heads of 128 lanes, one plane of the cache
a (pass, layer): 192 calls of each class a tick), in percent: the trace's
operations whose name carries the kernel's name, over the union of all
operation intervals. Read on a lane whose `mixed_step` spans carry
`kv_planes`; nothing elsewhere. Layer: kernels. Moves tokens_per_s."""

from lib.roofline_ouro import busy_share


def compute(run):
    return busy_share(run)
