"""The operations and bytes that a latent-attention read and a grouped
expert product cannot avoid, and what every reader of a kernel takes from a
trace: the kernel's seconds, its share of the busy time, and the ticks that
ran wholly inside the traced slice (`lib/roofline.py` has the rules and
`floor_seconds`). Pure functions of sizes, pinned by hand-computed cases.

Count only what no implementation could avoid. The latent pool stores 640
lanes a token where 576 are used: 576 are counted. The grouped product
reads an expert's matrices once however many row tiles it takes, and pads
no row: touched experts and real assignments are counted. So a share reads
low and never over 100 %.
"""

from lib.metrics import lane_spans


def kernel_seconds(run, pattern):
    """Self seconds, in the traced slice, of the operations whose name
    carries `pattern`; None where the run has no trace or no such op (a
    program without the kernel: the metric is then left out)."""
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    return sum(s for name, s in trace["op_seconds"].items()
               if pattern in name.lower()) or None


def busy_share(run, pattern):
    """Percent of the device's busy time in the operations whose name
    carries `pattern`; None where the trace holds no such operation."""
    seconds = kernel_seconds(run, pattern)
    return 100.0 * seconds / run["trace"]["busy_s"] if seconds else None


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


def expert_bytes(experts_touched, rows, cols, bytes_per_element, matrices=3):
    """Bytes of expert weights read: the `matrices` matrices of rows x cols
    (gate, up and down in the model's width; up and down where the experts
    work in a latent) of every (layer, expert) that took at least one row,
    once."""
    return experts_touched * matrices * rows * cols * bytes_per_element


def expert_flops(assignments, rows, cols, matrices=3):
    """Floating-point operations of the routed experts: a (token, expert)
    assignment is `matrices` matrix-vector products of rows x cols."""
    return assignments * matrices * 2 * rows * cols
