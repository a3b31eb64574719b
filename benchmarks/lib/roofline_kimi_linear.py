"""The operations and bytes that the kernels of a model with channel-gated
recurrent (KDA) layers, latent-attention layers and a held share of routed
experts cannot avoid, and the seconds each took in a trace: what
`kernel.kda_step_roofline`, `kernel.kda_chunk_roofline`,
`kernel.mla_nope_attn_roofline` and `kernel.moe_held2304_roofline` divide.
`lib/roofline.py` has the rules and `floor_seconds`; the counts themselves
are `lib/roofline_gated_delta.py`'s (a recurrent token and a state are
counted the same whether the decay is a head's or a channel's: the gate is
128 numbers more a token and head, counted here with q, k and v) and
`lib/roofline_moe_mla.py`'s (a latent read, an expert's matrices); this
file reads the sizes from THIS model's configuration, names its kernels and
edits nothing there. Pure functions of sizes, pinned by hand-computed cases.

Count only what no implementation could avoid (`lib/roofline.py`): a state,
d_v x d_k float32 a head, read and written ONCE a row and KDA layer in a
tick; a token's q, k, v and its d_k gates in and its read out once; the
latent pool's 576 USED lanes a context token and MLA layer (640 are stored:
the 64 shared key lanes are padded to a lane tile, so the kernel moves a
ninth more than is counted); a touched expert's three matrices once. So a
share reads low and never over 100 %.

**Whose seconds.** `tpu_engine/ops/gated_delta.py` names a channel gate's
two Pallas calls `kda_step` and `kda_chunk`; the latent read is
`mla_latent_read` (`ops/latent_attention.py`); the grouped product is the
Mosaic kernel XLA makes of `jax.lax.ragged_dot`. What XLA does around a
call (the convs, the low-rank gates, what a sub-chunk needs before the
state is touched, the router, the sort and the scatter-add) is the rest of
the step.
"""

from lib import roofline, roofline_gated_delta, roofline_moe_mla
from lib.roofline import DTYPE_BYTES
from lib.roofline_gated_delta import STATE_BYTES, busy_share  # noqa: F401

CHUNK, STEP, LATENT, EXPERTS = ("kda_chunk", "kda_step", "mla_latent",
                                "ragged-dot")


def sizes(config):
    """What the counts need, from a configuration file's dict: the
    factory's keyword arguments as run and the lane's type. `layers`:
    (MLA, KDA), of the first `n_layers` the source lists (1-based)."""
    kwargs = config["kwargs"]
    n = int(kwargs["n_layers"])
    kda = sum(1 for l in kwargs["kda_layers"] if l <= n)
    return {"layers": (n - kda, kda),
            "heads": int(kwargs["n_heads"]),
            "latent": int(kwargs["kv_lora_rank"]),
            "rope": int(kwargs["qk_rope"]),
            "lin_heads": int(kwargs["lin_heads"]),
            "key_dim": int(kwargs["lin_head_dim"]),
            "value_dim": int(kwargs["lin_head_dim"]),
            "d_model": int(kwargs["d_model"]),
            "d_expert": int(kwargs["d_ff_expert"]),
            "bytes_per_element": DTYPE_BYTES[config["serving"]["dtype"]]}


def holds_latent(pool):
    """Whether a lane's `stats()["kv_pool"]` is a LATENT pool's: its two
    tensors differ in width (`block_lanes`, which a lane that also owns
    state rows reports), a latent and its shared key lanes and not K and V
    a head. What tells this model's pool readers from the K/V hybrid's."""
    lanes = (pool or {}).get("block_lanes")
    return bool(lanes) and lanes[0] != lanes[1]


def recurrence_bytes(rows, tokens, size):
    """`roofline_gated_delta.recurrence_bytes` and each token's d_k gates
    a head, float32: the state once a row and KDA layer, q, k, v and g in,
    the read out."""
    gates = size["lin_heads"] * size["key_dim"] * STATE_BYTES
    return (roofline_gated_delta.recurrence_bytes(rows, tokens, size)
            + size["layers"][1] * tokens * gates)


def recurrence_roofline(run, kernel):
    """Percent of its roofline that one form of the channel-gated
    recurrence reaches: the floor seconds of what the `mixed_step` spans
    of the ticks wholly inside the traced slice say went through it
    (`kda_chunk_tokens` and `kda_chunk_rows`, or `kda_step_rows`: a row
    and a token each), against the self seconds of the calls named
    `kernel` there. None where the run has no trace, no peaks or no such
    counter."""
    ticks = roofline_moe_mla.whole_ticks(run)
    if kernel == CHUNK:
        rows = sum(a.get("kda_chunk_rows", 0) for a in ticks)
        tokens = sum(a.get("kda_chunk_tokens", 0) for a in ticks)
    else:
        rows = tokens = sum(a.get("kda_step_rows", 0) for a in ticks)
    seconds = roofline_moe_mla.kernel_seconds(run, kernel)
    if not seconds or not tokens or not run["peaks"]:
        return None
    size = sizes(run["config"])
    floor_s = roofline.floor_seconds(
        recurrence_bytes(rows, tokens, size),
        roofline_gated_delta.recurrence_flops(tokens, size), run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds


def latent_roofline(run):
    """Percent of its roofline that the MLA layers' absorbed read reaches:
    the floor seconds of the latents and shared key lanes
    `ctx_tokens_latent` counts (576 a token and MLA layer) and of the
    newest queries' FLOPs, over the ticks wholly inside the traced slice,
    against the calls' self seconds there."""
    ticks = roofline_moe_mla.whole_ticks(run)
    tokens = sum(a.get("ctx_tokens_latent", 0) for a in ticks)
    seconds = roofline_moe_mla.kernel_seconds(run, LATENT)
    if not seconds or not tokens or not run["peaks"]:
        return None
    size = sizes(run["config"])
    floor_s = roofline.floor_seconds(
        roofline_moe_mla.latent_bytes(tokens, size["layers"][0],
                                      size["latent"], size["rope"],
                                      size["bytes_per_element"]),
        roofline_moe_mla.latent_flops(tokens, size["layers"][0],
                                      size["heads"], size["latent"],
                                      size["rope"]),
        run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds


def experts_roofline(run):
    """Percent of its roofline that the grouped product of the HELD experts
    reaches: touched experts' three matrices once and the held
    assignments' FLOPs (`moe_experts_touched`, `moe_assignments_held`),
    over the ticks wholly inside the traced slice, against the product's
    self seconds there."""
    ticks = roofline_moe_mla.whole_ticks(run)
    touched = sum(a.get("moe_experts_touched", 0) for a in ticks)
    held = sum(a.get("moe_assignments_held", 0) for a in ticks)
    seconds = roofline_moe_mla.kernel_seconds(run, EXPERTS)
    if not seconds or not held or not run["peaks"]:
        return None
    size = sizes(run["config"])
    floor_s = roofline.floor_seconds(
        roofline_moe_mla.expert_bytes(touched, size["d_model"],
                                      size["d_expert"],
                                      size["bytes_per_element"]),
        roofline_moe_mla.expert_flops(held, size["d_model"],
                                      size["d_expert"]),
        run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds
