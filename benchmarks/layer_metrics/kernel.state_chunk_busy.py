"""Share of the device's busy time spent in the chunk form of the
recurrence (the pass over a prompt run's sub-chunks), in percent: the trace's
operations whose name carries the kernel's name, over the union of all
operation intervals. `sizes(run["config"])["recurrence"]`
(lib/roofline_sizes.py) says which kernel the configuration's step calls:
`gdn_chunk` or `kda_chunk` (tpu_engine/ops/gated_delta.py), `ssd_chunk`
(ops/ssd.py). What XLA does around the call (the conv, the norms, the
gates) is the rest of the step. Layer: kernels. Moves tokens_per_s."""

from lib.roofline_kinds import busy_share


def compute(run):
    return busy_share(run, "recurrence", "chunk")
