"""The paged reads under the block-causal mask against their roofline, in
percent: the time one chip needs at its peaks for the work they could not
avoid, over their measured self seconds in the traced slice (the ops
`kernel.block_attn_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice (those that carry `run_width`: a block-decoding lane) and the
sizes of `run["config"]` (lib/roofline_sdar.py, lib/roofline.py):

  bytes   `ctx_tokens_full` x 7 layers x 2 x 4 KV heads x 128 lanes x bytes
          an element: every key and value of a row's context, once, however
          many tall tiles of a chunk walk them again
  FLOPs   `attn_pairs` ((query, key) pairs the mask keeps: a query sees
          every position up to its block's end) x layers x 32 heads x 4 x
          128

A run of 4 queries reads its context once for all four: the read is bound by
those bytes."""

from lib.roofline_sdar import attention_roofline


def compute(run):
    return attention_roofline(run)
