"""A looped model's paged reads (MHA, 16 heads of 128 lanes) against their
roofline, in percent: the time one chip needs at its peaks for the work they
could not avoid, over their measured self seconds in the traced slice (the
ops `kernel.mha16_attn_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice and the sizes of `run["config"]` (lib/roofline_ouro.py,
lib/roofline.py):

  bytes   `ctx_tokens` x `kv_planes` (192) x 2 x 16 heads x 128 lanes x
          bytes an element: every key and value of a fed row's context once
          a plane, 8,192 B a (token, plane)
  FLOPs   `ctx_tokens` (query, key) pairs x planes x 16 heads x 4 x 128.
          Exact in a width-1 tick; a chunk's queries before its last are
          not counted, an under-count.

Under-counted throughout, so the share reads low and never high."""

from lib.roofline_ouro import attention_roofline


def compute(run):
    return attention_roofline(run)
