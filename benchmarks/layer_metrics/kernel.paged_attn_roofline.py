"""The paged-attention kernel against its roofline, in percent: the time
one chip needs at its peaks for the work the kernel could not avoid, over
the kernel's measured self seconds in the traced slice (the same ops
`kernel.paged_attn_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice (`run["slice"]`; a tick cut by an edge of the slice has part of
its kernel time outside the trace, so it is left out whole) and the sizes
of `run["config"]` (lib/roofline.py):

  bytes   `ctx_tokens` x layers x 2 x H_kv*D x the pool's bytes an element:
          every context token's K and V read once a layer
  FLOPs   `ctx_tokens` (query, key) pairs: each row's newest query against
          its context. Exact in a width-1 tick. A wider tick's chunk of c
          queries after w tokens attends c*w + c*(c+1)/2 pairs, but the
          span says neither which rows hold chunks nor how many, so only
          the last query of each is counted: an under-count, of FLOPs
          alone (the bytes are whole), by about the chunk's width. So the
          metric is listed only for cells whose ticks are mostly width 1:
          where every tick carries a chunk the share would come from the
          bytes alone and could not move with the chunked kernel's speed.

Under-counted throughout (queries, outputs and cache writes are not in the
bytes), so the share reads a little low and never high. Over several
lanes: the work of all lanes' ticks over the number of device planes,
against the kernel's seconds a plane (lib/xplane_reduce.py averages)."""

from lib import roofline
from lib.metrics import lane_spans

# As kernel.paged_attn_busy: Mosaic names the custom call after the kernel
# body `_paged_kernel` in tpu_engine/ops/paged_attention.py.
PATTERN = "paged"


def compute(run):
    trace, window, peaks = run["trace"], run.get("slice"), run["peaks"]
    if not trace or not trace["busy_s"] or not window or not peaks:
        return None
    kernel_s = sum(s for name, s in trace["op_seconds"].items()
                   if PATTERN in name.lower())
    ctx_tokens = sum(
        s["attrs"]["ctx_tokens"] for s in lane_spans(run, "mixed_step")
        if "ctx_tokens" in s["attrs"] and "start_ts" in s
        and window["begin"] <= s["start_ts"]
        and s["start_ts"] + s["duration_us"] / 1e6 <= window["end"])
    if not kernel_s or not ctx_tokens:
        return None
    size = roofline.attention_sizes(run["config"])
    floor_s = roofline.floor_seconds(
        roofline.attention_bytes(ctx_tokens, size["layers"], size["kv_heads"],
                                 size["head_dim"], size["bytes_per_element"]),
        roofline.attention_flops(ctx_tokens, size["layers"], size["heads"],
                                 size["head_dim"]),
        peaks)
    return 100.0 * floor_s / trace["planes"] / kernel_s
