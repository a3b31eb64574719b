"""The window layers' attention calls against their roofline, in percent:
the time one chip needs at its peaks for the work they could not avoid,
over their measured self seconds in the traced slice (the ops
`kernel.swa_attn_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice and the sizes of `run["config"]` (lib/roofline_laguna.py):

  bytes   `ctx_tokens_window` x window layers x 2 x KV heads x head width
          x bytes an element: every key and value a row's new tokens still
          see, once a layer, however many query tiles walk them
  FLOPs   `ctx_tokens_window` (query, key) pairs x window layers x their
          query heads x 4 x head width. Exact in a width-1 tick; a chunk's
          queries before its last are not counted, an under-count.

Under-counted throughout, so the share reads low and never high."""

from lib import roofline_laguna, roofline_moe_mla


def compute(run):
    return roofline_laguna.attention_roofline(
        run, 1, roofline_moe_mla.kernel_seconds(run, roofline_laguna.WINDOW))
