"""The operations and bytes that a latent-attention read and a grouped
expert product cannot avoid: what `kernel.mla_attn_roofline` and
`kernel.moe_experts_roofline` divide by the kernels' measured seconds
(`lib/roofline.py` has the rules and `floor_seconds`; this file adds the
counting for the two kernels of a latent, routed model and edits nothing
there). Pure functions of sizes, pinned by hand-computed cases.

Count only what no implementation could avoid. The latent pool stores 640
lanes a token where 576 are used: 576 are counted. The grouped product
reads an expert's three matrices once however many row tiles it takes, and
pads no row: touched experts and real assignments are counted. So a share
reads low and never over 100 %.
"""

from lib.metrics import lane_spans
from lib.roofline import DTYPE_BYTES


def kernel_seconds(run, pattern):
    """Self seconds, in the traced slice, of the operations whose name
    carries `pattern`; None where the run has no trace or no such op (a
    program without the kernel: the metric is then left out)."""
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    return sum(s for name, s in trace["op_seconds"].items()
               if pattern in name.lower()) or None


def whole_ticks(run):
    """The attrs of the `mixed_step` spans of the ticks that ran WHOLLY
    inside the traced slice (a tick cut by an edge of the slice has part
    of its kernel time outside the trace, so it is left out whole)."""
    window = run.get("slice")
    if not window:
        return []
    return [s["attrs"] for s in lane_spans(run, "mixed_step")
            if "start_ts" in s and window["begin"] <= s["start_ts"]
            and s["start_ts"] + s["duration_us"] / 1e6 <= window["end"]]


def sizes(config):
    """What the counts need, from a configuration file's dict: the
    factory's keyword arguments as run and the lane's type."""
    kwargs = config["kwargs"]
    return {"layers": int(kwargs["n_layers"]),
            "heads": int(kwargs["n_heads"]),
            "latent": int(kwargs["kv_lora_rank"]),
            "rope": int(kwargs["qk_rope"]),
            "d_model": int(kwargs["d_model"]),
            "d_expert": int(kwargs["d_ff_expert"]),
            "bytes_per_element": DTYPE_BYTES[config["serving"]["dtype"]]}


def latent_bytes(ctx_tokens, layers, latent, rope, bytes_per_element):
    """Bytes of the cache that attention over `ctx_tokens` context tokens
    (summed over the rows of a step) reads, every layer: each token's
    latent and rope key ONCE. Its keys and its values are the same bytes."""
    return ctx_tokens * layers * (latent + rope) * bytes_per_element


def latent_flops(pairs, layers, heads, latent, rope):
    """Floating-point operations of the absorbed read over `pairs` (query,
    key) pairs, every layer and head: a multiply-add over latent + rope
    lanes for the score and one over latent lanes for the weighted value."""
    return pairs * layers * heads * 2 * ((latent + rope) + latent)


def expert_bytes(experts_touched, d_model, d_expert, bytes_per_element):
    """Bytes of expert weights read: the gate, up and down matrices of
    every (layer, expert) that took at least one row, once."""
    return experts_touched * 3 * d_model * d_expert * bytes_per_element


def expert_flops(assignments, d_model, d_expert):
    """Floating-point operations of the routed experts: a (token, expert)
    assignment is three matrix-vector products of d_model x d_expert."""
    return assignments * 3 * 2 * d_model * d_expert
