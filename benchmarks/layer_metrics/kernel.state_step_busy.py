"""Share of the device's busy time spent in the step form of the
recurrence (a decode row's step, every row of a tick in one call), in percent: the trace's
operations whose name carries the kernel's name, over the union of all
operation intervals. `sizes(run["config"])["recurrence"]`
(lib/roofline_sizes.py) says which kernel the configuration's step calls:
`gdn_step` or `kda_step` (tpu_engine/ops/gated_delta.py), `ssd_step`
(ops/ssd.py). What XLA does around the call (the conv, the norms, the
gates) is the rest of the step. Layer: kernels. Moves tokens_per_s."""

from lib.roofline_kinds import busy_share


def compute(run):
    return busy_share(run, "recurrence", "step")
