"""A tick's tokens, listed once: how a step of the mixed tick lists the
tokens it holds, where their K and V go in the block pool, and which of
their rows reach the head. Every family's `*_step_rows_ragged` and the
uniform step (`models.transformer`) take all three from here; what a
family IS (its projections, a latent or windowed read, a state mixer, its
feed-forward) stays in its file.

A tick of B rows x W slots holds at most `max_tokens` valid slots (a
static bound the scheduler states: its token budget plus a token a row),
row b's at slots [0, qlen[b]) and logical columns [pos0[b], pos0[b] +
qlen[b]). `tick_tokens` compacts them in row order (`ops.latent_attention`
`tile_plan`) into a list of static length, in one of two ranks:

- a TOKEN an entry (`per_tile` None): every array (M,), row b's new
  tokens side by side from entry `plan.start[b]` on;
- TILES of S slots (`per_tile` S): `slot`, `valid`, `logical` (N, S) and
  `row` (N, 1); a row's run fills ceil(qlen / S) tiles from tile
  `plan.start[b]` on, the last one part full.

Entries past the live count REPEAT the last live one with `valid` false
(with no live row: the last row's slot 0): their K and V go to the null
block 0, they reach no expert, and what is written back at a repeated
place must be the same value at every repeat.

The scopes (`utils.tracing.step_part`) are opened here: `plan`, `embed`,
`attn/write`, `attn/read`, `head`. A caller wraps only what it adds.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from tpu_engine.models.transformer import _write_pool
from tpu_engine.ops import latent_attention as la
from tpu_engine.ops import nn
from tpu_engine.ops import paged_attention as pa
from tpu_engine.utils.tracing import step_part


@dataclasses.dataclass(frozen=True)
class TickTokens:
    """The list (module docstring). `plan`: the `TilePlan` under it (what
    the latent read takes; `plan.start` a row's first entry or tile).
    `slot` is clipped to the step's width and `logical` = pos0[row] +
    slot: an invalid entry holds a place that exists."""
    plan: la.TilePlan
    row: jax.Array            # (M,) | (N, 1) int32
    slot: jax.Array           # (M,) | (N, S) int32
    valid: jax.Array          # (M,) | (N, S) bool
    logical: jax.Array        # (M,) | (N, S) int32
    pos0: jax.Array           # (B,)
    qlen: jax.Array           # (B,)
    width: int
    per_tile: Optional[int]   # None: a token an entry
    max_tokens: Optional[int]

    @property
    def n(self) -> int:
        """Entries (tiles) in the list."""
        return self.plan.row.shape[0]

    def blocks(self, table, block_size: int):
        """(blk, off) in the list's shape: where each entry's K and V go
        under `table` (B, nb), the null block for an invalid one. A column
        past the table's end (padding) is clipped onto its last."""
        with step_part("plan"):
            cols = jnp.minimum(self.logical,
                               table.shape[1] * block_size - 1)
            blk = jnp.where(self.valid, table[self.row, cols // block_size],
                            0)
            return blk, cols % block_size

    def classes(self, group: int, run_slots: int = 1) -> la.TileClasses:
        """The rows by the class that reads them
        (`ops.latent_attention.class_plan`) at `group` query heads a KV
        head; `run_slots` S > 1: a run of up to S tokens is the short
        class."""
        with step_part("plan"):
            return la.class_plan(self.qlen, self.width, group,
                                 self.max_tokens, run_slots=run_slots)

    def flat(self):
        """(base, row, slot) of the list read as tokens, what
        `ops.paged_attention.ragged_read_by_class` takes: row b's token s
        at flat entry base[b] + s."""
        if self.per_tile is None:
            return self.plan.start, self.row, self.slot
        with step_part("plan"):
            return (self.plan.start * self.per_tile,
                    jnp.repeat(self.plan.row, self.per_tile),
                    self.slot.reshape(-1))

    def paged_kv(self, table, block_size: int, group: int,
                 run_slots: int = 1) -> "PagedKV":
        """What the layers whose cache is K and V a head share a step."""
        return PagedKV(*self.blocks(table, block_size), table, self.pos0,
                       self.classes(group, run_slots), self.flat())

    def embed(self, params, tokens, dtype):
        """The embedding of the list's tokens, `tokens` (B, W)."""
        with step_part("embed"):
            return nn.embedding(params["tok_embed"],
                                tokens[self.row, self.slot]).astype(dtype)

    def _at(self, h, slots):
        """h's entries of the rows' new tokens at `slots` (B, ...)."""
        start = self.plan.start.reshape(
            (slots.shape[0],) + (1,) * (slots.ndim - 1))
        if self.per_tile is None:
            return h[jnp.minimum(start + slots, self.n - 1)]
        tile = jnp.minimum(start + slots // self.per_tile, self.n - 1)
        return h[tile, slots % self.per_tile]

    def head_rows(self, h, sample_slot):
        """The rows of `h` (the list's shape, then d) that reach the head.
        `sample_slot` (B,): slot sample_slot[b] of row b, (B, d); (B, n):
        a row's n slots side by side, (B * n, d) (a (B, n, vocab) result
        would be re-laid out for its reader: 155 MB a copy at 64 x 4 x
        151,936, 0.8 ms a tick on the chip); None: every slot, (B, W, d),
        a padding slot (no entry of the list) zero."""
        with step_part("head"):
            if sample_slot is not None:
                h = self._at(h, jnp.minimum(sample_slot, self.width - 1))
                return (h.reshape(-1, h.shape[-1]) if sample_slot.ndim == 2
                        else h)
            b, w = self.qlen.shape[0], self.width
            every = jnp.broadcast_to(jnp.arange(w)[None, :], (b, w))
            return jnp.where((every < self.qlen[:, None])[:, :, None],
                             self._at(h, every), 0)


class PagedKV(NamedTuple):
    """A step's writes and by-class reads of one K/V pool
    (`TickTokens.paged_kv`)."""
    blk: jax.Array
    off: jax.Array
    table: jax.Array
    pos0: jax.Array
    classes: la.TileClasses
    flat: tuple

    def attend(self, attn_fn, q, k, v, pool, layer, **read):
        """One layer: every token's K and V into its row's blocks of
        layer (plane) `layer` BEFORE the read (write-before-attend), then
        each row read by the class of its run
        (`ops.paged_attention.ragged_read_by_class`). q (..., H, D), k
        and v (..., H_kv, D) in the list's shape; `read`: what the read
        path takes besides (`mask_block`). Returns (o as q, pool)."""
        with step_part("attn/write"):
            pool = _write_pool(pool, layer, self.blk, self.off, k, v)
        with step_part("attn/read"):
            o = pa.ragged_read_by_class(
                attn_fn, q.reshape((-1,) + q.shape[-2:]), pool, layer,
                self.table, self.pos0, self.classes, *self.flat, **read)
            return o.reshape(q.shape), pool


def tick_tokens(pos0, qlen, width: int, max_tokens: Optional[int] = None,
                per_tile: Optional[int] = None,
                n_tiles: Optional[int] = None) -> TickTokens:
    """The list of a tick whose rows hold `qlen` new tokens from columns
    `pos0`, in a step `width` slots a row. `n_tiles`: the list's static
    length where the caller's rows allow a tighter one than
    `ops.latent_attention.tiles_bound` (a tile a row for the part-full
    ones): rows of whole tiles (`models.sdar`), the exact count at one
    slot a tile (`models.transformer.pool_write_slots`). A tick that holds
    more would lose the rest, so the caller that states `max_tokens`
    holds its ticks to it (`runtime.scheduler` `_tick_formed`)."""
    s = per_tile or 1
    if n_tiles is None:
        n_tiles = la.tiles_bound(qlen.shape[0], width, s, max_tokens)
    with step_part("plan"):
        plan = la.tile_plan(qlen, s, n_tiles)
        slot, valid = la.tile_slots(plan, qlen, s)               # (N, S)
        if per_tile is None:
            row, slot, valid = (plan.row, jnp.minimum(plan.tile, width - 1),
                                valid[:, 0])
        else:
            row, slot = plan.row[:, None], jnp.minimum(slot, width - 1)
        return TickTokens(plan, row, slot, valid, pos0[row] + slot, pos0,
                          qlen, width, per_tile, max_tokens)


def lm_head(params, h, eps: float, dtype):
    """The final RMS norm and the head matrix, logits in float32."""
    with step_part("head"):
        h = nn.rmsnorm(params["ln_f"], h, eps=eps)
        return nn.dense(params["head"], h, dtype=dtype).astype(jnp.float32)
