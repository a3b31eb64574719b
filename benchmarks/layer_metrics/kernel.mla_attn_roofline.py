"""The latent-attention kernel against its roofline, in percent: the time
one chip needs at its peaks for the work the kernel could not avoid, over
the kernel's measured self seconds in the traced slice (the ops
`kernel.mla_attn_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, as `kernel.paged_attn_roofline` takes it: from the `mixed_step`
spans of the ticks that ran WHOLLY inside the slice and the sizes of
`run["config"]` (lib/roofline_moe_mla.py):

  bytes   `ctx_tokens` x layers x (latent + rope) x the pool's bytes an
          element: every context token's latent and rope key once a layer
  FLOPs   `ctx_tokens` (query, key) pairs x layers x heads x
          2 x ((latent + rope) + latent). Exact in a width-1 tick; a
          chunk's queries before its last are not counted (the span does
          not say which rows hold chunks), an under-count of FLOPs alone.

Under-counted throughout, so the share reads low and never high."""

from lib import roofline, roofline_moe_mla

PATTERN = "mla_latent"


def compute(run):
    kernel_s = roofline_moe_mla.kernel_seconds(run, PATTERN)
    peaks = run["peaks"]
    ctx_tokens = sum(attrs.get("ctx_tokens", 0)
                     for attrs in roofline_moe_mla.whole_ticks(run))
    if not kernel_s or not ctx_tokens or not peaks:
        return None
    size = roofline_moe_mla.sizes(run["config"])
    floor_s = roofline.floor_seconds(
        roofline_moe_mla.latent_bytes(ctx_tokens, size["layers"],
                                      size["latent"], size["rope"],
                                      size["bytes_per_element"]),
        roofline_moe_mla.latent_flops(ctx_tokens, size["layers"],
                                      size["heads"], size["latent"],
                                      size["rope"]),
        peaks)
    return 100.0 * floor_s / run["trace"]["planes"] / kernel_s
