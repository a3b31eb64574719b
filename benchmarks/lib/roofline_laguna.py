"""The operations and bytes that the TWO attention reads of a model with
window and full attention layers cannot avoid: what
`kernel.swa_attn_roofline` and `kernel.full_attn_roofline` divide by the
kernels' measured seconds (`lib/roofline.py` has the rules, the attention
counts and `floor_seconds`; `lib/roofline_moe_mla.py` the kernel's seconds
and the ticks wholly inside the slice; the held experts' product is
`kernel.moe_experts_roofline`'s, `lib/roofline_kinds.py`). One cell reads
two classes of attention, so these readers are its own and take their
sizes here. Pure functions of sizes, pinned by hand-computed cases.

Count only what no implementation could avoid. A window layer reads, for a
row, the keys and values its new tokens still see, ONCE (the span's
`ctx_tokens_window`: at most window - 1 + new tokens a row), however many
query tiles walk them again; a full layer the row's whole context once
(`ctx_tokens_full`). Of the pairs a tick routes only those to a HELD
expert form a row here (`moe_assignments_held`). So a share reads low and
never over 100 %.
"""

from lib import roofline, roofline_moe_mla
from lib.roofline import DTYPE_BYTES


def sizes(config):
    """What the counts need, from a configuration file's dict: the
    factory's keyword arguments as run and the lane's type. `layers`,
    `heads`: (full, window)."""
    kwargs = config["kwargs"]
    windowed = [t == "sliding_attention" for t in kwargs["layer_types"]]
    heads = kwargs["heads_per_layer"]

    def of_kind(kind):
        return [h for h, w in zip(heads, windowed) if w == kind]

    full, window = of_kind(False), of_kind(True)
    if len(set(full)) > 1 or len(set(window)) > 1:
        raise ValueError("layers of one kind differ in their head count")
    return {"layers": (len(full), len(window)),
            "heads": (full[0] if full else 0, window[0] if window else 0),
            "kv_heads": int(kwargs["n_kv_heads"]),
            "head_dim": int(kwargs["head_dim"]),
            "d_model": int(kwargs["d_model"]),
            "d_expert": int(kwargs["d_ff_expert"]),
            "bytes_per_element": DTYPE_BYTES[config["serving"]["dtype"]]}


def span_sum(ticks, key):
    """`key` summed over the ticks' span attrs; 0 where no tick carries it
    (a program without the counter: the reader then returns nothing)."""
    return sum(attrs.get(key, 0) for attrs in ticks)


# tpu_engine/ops/paged_attention.py: the Pallas call behind every paged
# read is named after `_paged_call`, and `swa_window_read` where it is
# given a window.
PAGED, WINDOW = "paged", "swa_window"


def full_attention_seconds(run):
    """Self seconds, in the traced slice, of the paged-attention calls on
    FULL layers: the operations whose name carries the kernel's name and
    not the window call's; None where the run has no trace or no such op."""
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    return sum(s for name, s in trace["op_seconds"].items()
               if PAGED in name.lower() and WINDOW not in name.lower()) \
        or None


def attention_roofline(run, kind, kernel_s):
    """Percent of their roofline that the attention calls of one kind of
    layer (0: full, 1: window) reach: the floor seconds of the keys and
    values the span attr `ctx_tokens_full` / `ctx_tokens_window` counts and
    of the newest queries' FLOPs, over the ticks wholly inside the traced
    slice, against `kernel_s`, the calls' self seconds there. None where
    the run has no trace, no peaks or no such counter."""
    tokens = span_sum(roofline_moe_mla.whole_ticks(run),
                      ("ctx_tokens_full", "ctx_tokens_window")[kind])
    if not kernel_s or not tokens or not run["peaks"]:
        return None
    size = sizes(run["config"])
    layers, heads = size["layers"][kind], size["heads"][kind]
    floor_s = roofline.floor_seconds(
        roofline.attention_bytes(tokens, layers, size["kv_heads"],
                                 size["head_dim"], size["bytes_per_element"]),
        roofline.attention_flops(tokens, layers, heads, size["head_dim"]),
        run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / kernel_s
