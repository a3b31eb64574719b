"""The full layers' attention calls, of a model that also has window
layers, against their roofline, in percent: the time one chip needs at its
peaks for the work they could not avoid, over their measured self seconds
in the traced slice (the ops `kernel.full_attn_busy` sums). Layer: kernels.
Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice and the sizes of `run["config"]` (lib/roofline_laguna.py):

  bytes   `ctx_tokens_full` x full layers x 2 x KV heads x head width x
          bytes an element: a row's whole context, once a layer
  FLOPs   `ctx_tokens_full` (query, key) pairs x full layers x their query
          heads x 4 x head width. Exact in a width-1 tick; a chunk's
          queries before its last are not counted, an under-count.

Under-counted throughout, so the share reads low and never high."""

from lib import roofline_laguna


def compute(run):
    return roofline_laguna.attention_roofline(
        run, 0, roofline_laguna.full_attention_seconds(run))
