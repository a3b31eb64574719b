"""What tells the lanes of a model that DECODES BY BLOCKS (a run of
`block_length` tokens a generating row a tick, under a block-causal mask)
for the readers of its scheduler's passes, and the pairs such a mask keeps.
Its two kernels, the read under the block mask (`block_mask_read`) and the
grouped product of its whole experts, are read by
`kernel.paged_attn_roofline` and `kernel.moe_experts_roofline`
(`lib/roofline_kinds.py`): a row's whole context of K and V once a layer
and tick (`ctx_tokens_full`), the (query, key) pairs the mask keeps
(`attn_pairs`, which the lane counts as `block_pairs` does here), a touched
expert's three matrices once.
"""


def decodes_by_blocks(run):
    """Whether the run's configuration is of this family: its generating
    rows step a block (`block_length`). On any other, a reader here
    returns None."""
    return "block_length" in run["config"].get("kwargs", {})


def block_pairs(pos0, qlen, block_length):
    """(query, key) pairs the block-causal mask keeps for a row that feeds
    `qlen` tokens at `pos0` (both multiples of `block_length`): a query
    sees every position up to the end of its own block."""
    blocks = qlen // block_length
    return block_length * (blocks * pos0
                           + block_length * blocks * (blocks + 1) // 2)


def counted(run, group, key):
    """The lanes' (`stats_before`, `stats_after`) pairs of `stats()[group]`
    that count `key`, on a run of this family; [] on any other program or
    configuration."""
    if not decodes_by_blocks(run):
        return []
    return [(run["stats_before"][node][group], after[group])
            for node, after in run["stats_after"].items()
            if key in after.get(group, {})
            and group in run["stats_before"].get(node, {})]


def passes(before, after):
    """Run passes (denoise and commit) a lane counted over the window."""
    return sum(after[k] - before[k]
               for k in ("denoise_passes", "commit_passes"))
