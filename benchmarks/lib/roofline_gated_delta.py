"""The operations and bytes that the kernels of a model with gated-delta
(recurrent) layers and full-attention layers cannot avoid, and the seconds
its recurrence took in a trace: what `kernel.gdn_chunk_roofline`,
`kernel.gdn_step_roofline` and `kernel.mha128_attn_roofline` divide
(`lib/roofline.py` has the rules, the attention counts and `floor_seconds`;
`lib/roofline_moe_mla.py` the seconds of a kernel with a name of its own and
the ticks wholly inside the slice; this file adds what is this model's own
and edits nothing there). The counts are pure functions of sizes, pinned by
hand-computed cases.

Count only what no implementation could avoid. A token of the recurrence,
a head: S k, the rank-one write and S q, 2 x d_v x d_k operations each,
however the chunked form arranges them (its intra-chunk products and its
triangular solve are its own choice). The state, d_v x d_k float32 a head,
is read and written ONCE a row and layer in a tick: a decode row's step
cannot do with less, a chunk's run of tokens need not do more. A token's q,
k, v come in and its read goes out once, float32. The full layers read a
row's whole context once (`ctx_tokens_full`). So a share reads low and
never over 100 %.

**Whose seconds.** Both forms of the recurrence are Pallas calls with a
name of their own in a trace (`tpu_engine/ops/gated_delta.py`: `gdn_chunk`,
the triangular solves and the pass over a run's sub-chunks; `gdn_step`, a
decode row's step), as the paged read is (`_paged_call`). What XLA does
around a call (the conv, the norms, what a sub-chunk needs before the state
is touched) counts as the rest of the step.
"""

from lib import roofline, roofline_moe_mla
from lib.roofline import DTYPE_BYTES

# tpu_engine/ops/gated_delta.py names its two kernels; the Pallas call
# behind every paged read is named after `_paged_call` (this model has no
# window layer).
CHUNK, STEP, PAGED = "gdn_chunk", "gdn_step", "paged"
STATE_BYTES = 4          # the state, and what goes in and out of it: float32


def sizes(config):
    """What the counts need, from a configuration file's dict: the
    factory's keyword arguments as run and the lane's type. `layers`:
    (full, linear)."""
    kwargs = config["kwargs"]
    linear = sum(t == "linear_attention" for t in kwargs["layer_types"])
    return {"layers": (len(kwargs["layer_types"]) - linear, linear),
            "heads": int(kwargs["n_heads"]),
            "head_dim": int(kwargs["head_dim"]),
            "lin_heads": int(kwargs["lin_heads"]),
            "key_dim": int(kwargs["lin_key_dim"]),
            "value_dim": int(kwargs["lin_value_dim"]),
            "bytes_per_element": DTYPE_BYTES[config["serving"]["dtype"]]}


def state_bytes(size):
    """A row's recurrent state, one layer: d_v x d_k float32 a head."""
    return (size["lin_heads"] * size["value_dim"] * size["key_dim"]
            * STATE_BYTES)


def recurrence_flops(tokens, size):
    """S k, the rank-one write and S q, every linear layer and head."""
    return (tokens * size["layers"][1] * size["lin_heads"] * 3 * 2
            * size["value_dim"] * size["key_dim"])


def recurrence_bytes(rows, tokens, size):
    """The state read and written once a row and linear layer, and each
    token's q, k, v in and its read out."""
    lanes = size["lin_heads"] * 2 * (size["key_dim"] + size["value_dim"])
    return size["layers"][1] * (rows * 2 * state_bytes(size)
                                + tokens * lanes * STATE_BYTES)


def busy_share(run, kernel):
    """Percent of the device's busy time in the calls named `kernel`; None
    where the trace holds no such operation."""
    seconds = roofline_moe_mla.kernel_seconds(run, kernel)
    return 100.0 * seconds / run["trace"]["busy_s"] if seconds else None


def recurrence_roofline(run, kernel):
    """Percent of its roofline that one form of the recurrence reaches:
    the floor seconds of what the `mixed_step` spans of the ticks wholly
    inside the traced slice say went through it (`gdn_chunk_tokens` and
    `gdn_chunk_rows`, or `gdn_step_rows`: a row and a token each), against
    the self seconds of the calls named `kernel` there. None where the run
    has no trace, no peaks or no such counter."""
    ticks = roofline_moe_mla.whole_ticks(run)
    if kernel == CHUNK:
        rows = sum(a.get("gdn_chunk_rows", 0) for a in ticks)
        tokens = sum(a.get("gdn_chunk_tokens", 0) for a in ticks)
    else:
        rows = tokens = sum(a.get("gdn_step_rows", 0) for a in ticks)
    seconds = roofline_moe_mla.kernel_seconds(run, kernel)
    if not seconds or not tokens or not run["peaks"]:
        return None
    size = sizes(run["config"])
    floor_s = roofline.floor_seconds(recurrence_bytes(rows, tokens, size),
                                     recurrence_flops(tokens, size),
                                     run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds


def attention_roofline(run):
    """Percent of its roofline that the full layers' paged reads reach:
    the floor seconds of the keys and values `ctx_tokens_full` counts and
    of the newest queries' FLOPs, over the ticks wholly inside the traced
    slice, against the calls' self seconds there."""
    ticks = roofline_moe_mla.whole_ticks(run)
    tokens = sum(a.get("ctx_tokens_full", 0) for a in ticks)
    seconds = roofline_moe_mla.kernel_seconds(run, PAGED)
    if not seconds or not tokens or not run["peaks"]:
        return None
    size = sizes(run["config"])
    floor_s = roofline.floor_seconds(
        roofline.attention_bytes(tokens, size["layers"][0], size["heads"],
                                 size["head_dim"],
                                 size["bytes_per_element"]),
        roofline.attention_flops(tokens, size["layers"][0], size["heads"],
                                 size["head_dim"]),
        run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds
