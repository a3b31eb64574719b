"""The latent-attention kernel of a model with one MLA layer in four
against its roofline, in percent: the time one chip needs at its peaks for
the work the kernel could not avoid, over its measured self seconds in the
traced slice (the ops `kernel.mla_nope_attn_busy` sums). Layer: kernels.
Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice and the sizes of `run["config"]` (lib/roofline_kimi_linear.py):

  bytes   `ctx_tokens_latent` x MLA layers x (512 + 64) x 2 B = 1,152 B a
          context token: its latent and its shared key lanes once. The pool
          STORES 1,280 B (the 64 lanes padded to a lane tile), a ninth more
          than is counted
  FLOPs   `ctx_tokens_latent` (query, key) pairs x MLA layers x 32 heads x
          2 x ((512 + 64) + 512): exact in a width-1 tick; a chunk's
          queries before its last are not counted

Under-counted throughout, so the share reads low and never high."""

from lib.roofline_kimi_linear import latent_roofline


def compute(run):
    return latent_roofline(run)
