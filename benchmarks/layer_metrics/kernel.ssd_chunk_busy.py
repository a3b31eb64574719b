"""Share of the device's busy time spent in the CHUNKED form of the Mamba-2
recurrence (`ops/ssd.py` `ssd_chunk_row`: a prefilling row's run of tokens,
from the state its last chunk left), in percent: the trace's operations
whose name carries the kernel's name, `ssd_chunk` (the pass over the
sub-chunks: three matrix products each and the state carried in VMEM; what
XLA prepares for them, the decays' differences taken before the exponential
among it, and the write of their outputs into the token list carry no name
a reader can hold and are the rest), over the union of all operation
intervals. Layer: kernels. Moves tokens_per_s."""

from lib.roofline_falcon_h1 import CHUNK, busy_share


def compute(run):
    return busy_share(run, CHUNK)
