"""Share of the device's busy time spent in the CHUNKED form of the Mamba-2
recurrence (`ops/ssd.py` `ssd_chunk_row`: a prefilling row's run of tokens,
from the state its last chunk left) at 128 heads of (64, 128): a head a
grid step, 128 steps a row and layer where Falcon-H1's are 32, in percent:
the trace's operations whose name carries the kernel's name, `ssd_chunk`,
over the union of all operation intervals (`kernel.ssd_chunk_busy`'s
operations, for the cell that metric's list does not name; what XLA prepares
for the sub-chunks carries no name a reader can hold and is the rest).
Layer: kernels. Moves tokens_per_s."""

from lib.roofline_nemotron_h import CHUNK, busy_share


def compute(run):
    return busy_share(run, CHUNK)
