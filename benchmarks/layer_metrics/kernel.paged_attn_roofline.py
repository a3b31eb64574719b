"""The paged read against its roofline, in percent: the time one chip
needs at its peaks for the work the read could not avoid, over its
measured self seconds in the traced slice (the ops `kernel.paged_attn_busy`
sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice (`run["slice"]`; a tick cut by an edge of the slice has part of
its kernel time outside the trace, so it is left out whole) and the sizes
of `run["config"]` (lib/roofline_sizes.py, lib/roofline_kinds.py):

  bytes   context tokens x layers that attend (planes, under a loop) x the
          lanes a token takes in the pool (K and V of the KV heads, or a
          latent and its rope key) x the pool's bytes an element: every
          context token read once a layer, however many query tiles of a
          chunk walk it again
  FLOPs   (query, key) pairs x layers x query heads x 4 x head size (over a
          latent: 2 x ((latent + rope) + latent)). Pairs: each row's newest
          query against its context, exact in a width-1 tick. A wider
          tick's chunk of c queries after w tokens attends c*w + c*(c+1)/2
          pairs, but the span says neither which rows hold chunks nor how
          many, so only the last query of each is counted: an under-count,
          of FLOPs alone (the bytes are whole), by about the chunk's width.
          Under a block-causal mask the lane counts the pairs
          (`attn_pairs`) and they are whole.

So the metric is listed only for cells some of whose ticks are width 1 or
whose read is bound by its bytes: where every tick carries a chunk of an
MHA model the share would come from the bytes alone and could not move
with the chunked kernel's speed. Under-counted throughout (queries,
outputs and cache writes are not in the bytes), so the share reads a
little low and never high."""

from lib.roofline_kinds import attention_roofline


def compute(run):
    return attention_roofline(run)
