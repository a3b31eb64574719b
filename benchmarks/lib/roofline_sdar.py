"""The operations and bytes that the kernels of a model that DECODES BY
BLOCKS (a Qwen3-MoE body under a block-causal mask: grouped-query attention
at eight query heads a KV head, 128 whole SwiGLU experts a layer, a run of
`block_length` tokens a generating row a tick) cannot avoid: what
`kernel.block_attn_roofline` and `kernel.moe_e128_roofline` divide, and what
tells this family's lanes, ticks and pools for the other readers of its
cell. `lib/roofline.py` has the rules, the attention byte count and
`floor_seconds`; `lib/roofline_moe_mla.py` the seconds of a kernel with a
name of its own, the ticks wholly inside the slice and what an expert of
three matrices costs; this file reads every size from THIS model's
configuration file and edits nothing there. Pure functions of sizes, pinned
by hand-computed cases.

Count only what no implementation could avoid (`lib/roofline.py`). The
block-mask read: a row's whole context of K and V once a layer and tick
(`ctx_tokens_full`: pos0 + q_len summed over the rows fed, 4 KV heads),
however many tall tiles of a chunk walk it again, and the (query, key) pairs
the mask keeps (`attn_pairs`: a query sees every position up to its own
block's end), 32 heads. The experts: a touched expert's three matrices of
2048 x 768 once however many row tiles re-read them, and three
matrix-vector products a (token, expert) pair; the router, the projections
and the head are dense products of the rest of the step. So a share reads
low and never over 100 %.

**Whose seconds.** The read under the block mask is a Pallas call with a
name of its own in a trace (`block_mask_read`:
`tpu_engine/ops/paged_attention.py`, `mask_block` > 1), both classes of
tile; the grouped product is the Mosaic kernel XLA makes of
`jax.lax.ragged_dot` (`ragged-dot`).
"""

from lib import roofline, roofline_moe_mla
from lib.roofline import DTYPE_BYTES

BLOCK_READ, EXPERTS = "block_mask_read", "ragged-dot"


def decodes_by_blocks(run):
    """Whether the run's configuration is of this family: its generating
    rows step a block (`block_length`). On any other, a reader here
    returns None."""
    return "block_length" in run["config"].get("kwargs", {})


def sizes(config):
    """What the counts need, from a configuration file's dict: the
    factory's keyword arguments as run and the lane's type."""
    kwargs = config["kwargs"]
    return {"layers": int(kwargs["n_layers"]),
            "heads": int(kwargs["n_heads"]),
            "kv_heads": int(kwargs["n_kv_heads"]),
            "head_dim": int(kwargs["head_dim"]),
            "d_model": int(kwargs["d_model"]),
            "d_expert": int(kwargs["d_ff_expert"]),
            "experts": int(kwargs["n_experts"]),
            "top_k": int(kwargs["top_k"]),
            "block_length": int(kwargs["block_length"]),
            "bytes_per_element": DTYPE_BYTES[config["serving"]["dtype"]]}


def block_pairs(pos0, qlen, block_length):
    """(query, key) pairs the block-causal mask keeps for a row that feeds
    `qlen` tokens at `pos0` (both multiples of `block_length`): a query
    sees every position up to the end of its own block."""
    blocks = qlen // block_length
    return block_length * (blocks * pos0
                           + block_length * blocks * (blocks + 1) // 2)


def block_ticks(run):
    """The attrs of the ticks wholly inside the slice that a lane of this
    family ran (they carry `run_width`)."""
    return [a for a in roofline_moe_mla.whole_ticks(run)
            if "run_width" in a]


def busy_share(run, kernel):
    """Percent of the device's busy time in the calls named `kernel` on a
    run of this family; None where the trace holds no such operation."""
    seconds = roofline_moe_mla.kernel_seconds(run, kernel)
    if not seconds or not decodes_by_blocks(run):
        return None
    return 100.0 * seconds / run["trace"]["busy_s"]


def attention_roofline(run):
    """Percent of its roofline that the block-mask reads reach: the floor
    seconds of the keys and values `ctx_tokens_full` counts (every layer, 4
    KV heads) and of the pairs the mask keeps (`attn_pairs`, 32 heads),
    over this family's ticks wholly inside the traced slice, against the
    calls' self seconds there."""
    ticks = block_ticks(run)
    tokens = sum(a.get("ctx_tokens_full", 0) for a in ticks)
    pairs = sum(a.get("attn_pairs", 0) for a in ticks)
    seconds = roofline_moe_mla.kernel_seconds(run, BLOCK_READ)
    if (not seconds or not tokens or not run["peaks"]
            or not decodes_by_blocks(run)):
        return None
    size = sizes(run["config"])
    floor_s = roofline.floor_seconds(
        roofline.attention_bytes(tokens, size["layers"], size["kv_heads"],
                                 size["head_dim"],
                                 size["bytes_per_element"]),
        roofline.attention_flops(pairs, size["layers"], size["heads"],
                                 size["head_dim"]),
        run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds


def experts_roofline(run):
    """Percent of its roofline that the grouped product of 128 whole
    experts reaches: touched experts' three matrices once and the
    assignments' FLOPs (`moe_experts_touched`, `moe_assignments`), over
    this family's ticks wholly inside the traced slice, against the
    product's self seconds there."""
    ticks = block_ticks(run)
    touched = sum(a.get("moe_experts_touched", 0) for a in ticks)
    pairs = sum(a.get("moe_assignments", 0) for a in ticks)
    seconds = roofline_moe_mla.kernel_seconds(run, EXPERTS)
    if (not seconds or not pairs or not run["peaks"]
            or not decodes_by_blocks(run)):
        return None
    size = sizes(run["config"])
    floor_s = roofline.floor_seconds(
        roofline_moe_mla.expert_bytes(touched, size["d_model"],
                                      size["d_expert"],
                                      size["bytes_per_element"]),
        roofline_moe_mla.expert_flops(pairs, size["d_model"],
                                      size["d_expert"]),
        run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds


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
