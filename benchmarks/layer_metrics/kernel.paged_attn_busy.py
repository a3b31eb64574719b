"""Share of the device's busy time spent in the paged read, the Pallas call
that attends a tick's rows over the block pool, in percent: the trace's
operations whose name carries the call's name, over the union of all
operation intervals. Every family's read goes through the pool, and
`sizes(run["config"])["attention"]["kernel"]` (lib/roofline_sizes.py) says
which call the configuration's step makes: `_paged_call`
(tpu_engine/ops/paged_attention.py; Mosaic names the custom call after it),
`block_mask_read` there where rows decode by blocks, `mla_latent_read`
(ops/latent_attention.py) where the pool holds a latent. Layer: kernels.
Moves tokens_per_s."""

from lib.roofline_kinds import busy_share


def compute(run):
    return busy_share(run, "attention")
