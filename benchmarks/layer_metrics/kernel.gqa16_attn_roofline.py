"""The paged reads at sixteen query heads a KV head against their roofline,
in percent: the time one chip needs at its peaks for the work they could not
avoid, over their measured self seconds in the traced slice (the ops
`kernel.gqa16_attn_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice (those that carry `ssd_step_rows`: a lane with state rows) and the
sizes of `run["config"]` (lib/roofline_nemotron_h.py, lib/roofline.py):

  bytes   `ctx_tokens_full` x 1 attention layer x 2 x 2 KV heads x 128
          lanes x bytes an element: every key and value of a row's context,
          once, however many tall tiles of a chunk walk them again
  FLOPs   `ctx_tokens_full` (query, key) pairs x layers x 32 heads x 4 x
          128. Exact in a width-1 tick; a chunk's queries before its last
          are not counted, an under-count.

Under-counted throughout, so the share reads low and never high."""

from lib.roofline_nemotron_h import attention_roofline


def compute(run):
    return attention_roofline(run)
