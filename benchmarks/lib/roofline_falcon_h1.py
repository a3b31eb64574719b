"""The operations and bytes that the kernels of a model whose EVERY layer
has a Mamba-2 recurrence beside grouped-query attention cannot avoid, and
what tells its lane's counters from another family's: what
`kernel.ssd_step_roofline`, `kernel.ssd_chunk_roofline` and
`kernel.gqa5_attn_roofline` divide (`lib/roofline.py` has the rules, the
attention counts and `floor_seconds`; `lib/roofline_moe_mla.py` the seconds
of a kernel with a name of its own and the ticks wholly inside the slice;
`lib/roofline_gated_delta.py` the busy share; this file adds what is this
model's own and edits nothing there). The counts are pure functions of
sizes, pinned by hand-computed cases.

Count only what no implementation could avoid. A token of the recurrence, a
head: the rank-one write dt x (outer) B into the state and the read S C, a
multiply-add over P x N each, 2 x 2 x P x N operations, however the chunked
form arranges them (its intra-chunk products are its own choice; the decay's
multiply is left out, an under-count). The state, P x N float32 a head, is
read and written ONCE a row and layer in a tick: a decode row's step cannot
do with less, a chunk's run of tokens need not do more. A token's x and dt
a head and its B and C a group come in, and its read goes out, once,
float32. Every layer attends too: a row's whole context of K and V is read
once a layer (`ctx_tokens_full`, 4 KV heads), its queries' FLOPs over 20
query heads. So a share reads low and never over 100 %.

**Whose seconds.** Both forms of the recurrence are Pallas calls with a name
of their own in a trace (`tpu_engine/ops/ssd.py`: `ssd_step`, a decode row's
step; `ssd_chunk`, the pass over a run's sub-chunks), as the paged read is
(`_paged_call`). What XLA does around a call (the conv, the gated norm, what
a sub-chunk needs before the state is touched) counts as the rest of the
step.
"""

from lib import roofline, roofline_moe_mla
from lib.roofline import DTYPE_BYTES
from lib.roofline_gated_delta import PAGED, STATE_BYTES, busy_share  # noqa: F401

# tpu_engine/ops/ssd.py names its two kernels.
CHUNK, STEP = "ssd_chunk", "ssd_step"


def sizes(config):
    """What the counts need, from a configuration file's dict: the
    factory's keyword arguments as run and the lane's type. Every layer has
    both mixers."""
    kwargs = config["kwargs"]
    return {"layers": int(kwargs["n_layers"]),
            "heads": int(kwargs["n_heads"]),
            "kv_heads": int(kwargs["n_kv_heads"]),
            "head_dim": int(kwargs["head_dim"]),
            "ssm_heads": int(kwargs["ssm_heads"]),
            "ssm_head_dim": int(kwargs["ssm_head_dim"]),
            "d_state": int(kwargs["d_state"]),
            "groups": int(kwargs["n_groups"]),
            "bytes_per_element": DTYPE_BYTES[config["serving"]["dtype"]]}


def state_bytes(size):
    """A row's recurrent state, one layer: P x N float32 a head."""
    return (size["ssm_heads"] * size["ssm_head_dim"] * size["d_state"]
            * STATE_BYTES)


def recurrence_flops(tokens, size):
    """The rank-one write and the read S C, every layer and head."""
    return (tokens * size["layers"] * size["ssm_heads"] * 2 * 2
            * size["ssm_head_dim"] * size["d_state"])


def recurrence_bytes(rows, tokens, size):
    """The state read and written once a row and layer, and each token's x
    and dt a head and B and C a group in and its read out."""
    lanes = (size["ssm_heads"] * (2 * size["ssm_head_dim"] + 1)
             + 2 * size["groups"] * size["d_state"])
    return size["layers"] * (rows * 2 * state_bytes(size)
                             + tokens * lanes * STATE_BYTES)


def holds_ssd(pool, config):
    """Whether `pool`, a lane's `stats()["kv_pool"]` (or a sample of it),
    is the K/V pool of THIS family's lane: the run's configuration states a
    Mamba-2 recurrence (`ssm_heads`), the lane reports the bytes of state
    its rows hold beside the blocks (`state_bytes_held`), and a block holds
    a K and a V of the configuration's KV heads (`block_lanes`). What tells
    this model's pool readers from the other two state families'."""
    kwargs = config.get("kwargs", {})
    if not pool or "ssm_heads" not in kwargs or "state_bytes_held" not in pool:
        return False
    lanes = int(kwargs["n_kv_heads"]) * int(kwargs["head_dim"])
    return list(pool.get("block_lanes") or ()) == [lanes, lanes]


def recurrence_roofline(run, kernel):
    """Percent of its roofline that one form of the recurrence reaches:
    the floor seconds of what the `mixed_step` spans of the ticks wholly
    inside the traced slice say went through it (`ssd_chunk_tokens` and
    `ssd_chunk_rows`, or `ssd_step_rows`: a row and a token each), against
    the self seconds of the calls named `kernel` there. None where the run
    has no trace, no peaks or no such counter."""
    ticks = roofline_moe_mla.whole_ticks(run)
    if kernel == CHUNK:
        rows = sum(a.get("ssd_chunk_rows", 0) for a in ticks)
        tokens = sum(a.get("ssd_chunk_tokens", 0) for a in ticks)
    else:
        rows = tokens = sum(a.get("ssd_step_rows", 0) for a in ticks)
    seconds = roofline_moe_mla.kernel_seconds(run, kernel)
    if not seconds or not tokens or not run["peaks"]:
        return None
    size = sizes(run["config"])
    floor_s = roofline.floor_seconds(recurrence_bytes(rows, tokens, size),
                                     recurrence_flops(tokens, size),
                                     run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds


def ssd_ticks(run):
    """The attrs of the ticks wholly inside the slice that a lane of this
    family ran (they carry `ssd_step_rows`)."""
    return [a for a in roofline_moe_mla.whole_ticks(run)
            if "ssd_step_rows" in a]


def attention_roofline(run):
    """Percent of its roofline that the paged reads at five query heads a
    KV head reach: the floor seconds of the keys and values
    `ctx_tokens_full` counts (every layer, 4 KV heads) and of the newest
    queries' FLOPs (20 heads), over this family's ticks wholly inside the
    traced slice, against the calls' self seconds there."""
    tokens = sum(a.get("ctx_tokens_full", 0) for a in ssd_ticks(run))
    seconds = roofline_moe_mla.kernel_seconds(run, PAGED)
    if not seconds or not tokens or not run["peaks"]:
        return None
    size = sizes(run["config"])
    floor_s = roofline.floor_seconds(
        roofline.attention_bytes(tokens, size["layers"], size["kv_heads"],
                                 size["head_dim"],
                                 size["bytes_per_element"]),
        roofline.attention_flops(tokens, size["layers"], size["heads"],
                                 size["head_dim"]),
        run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds
