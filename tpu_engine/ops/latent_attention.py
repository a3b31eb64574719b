"""Latent (MLA) attention over the block pool: the absorbed read.

A latent-attention model (models.moonlight) caches ONE vector a token and
layer instead of every head's key and value: the normalised latent ``c``
(`kv_lora_rank` lanes) and the rotated shared rope key ``k_pe``. In the
block pool (`runtime.kv_blocks.BlockPool`) they live in the pair's two
tensors:

    pool.k  (L, NB, bs, PE_LANES)  ``k_pe`` in lanes [0, qk_rope), zeros above
    pool.v  (L, NB, bs, C)         ``c``

576 useful lanes are 4.5 lane tiles; stored as 128 + 512 = 640 so that each
tensor tiles on its own and the latent, read twice (as key and as value),
is one aligned operand. A roofline counts the 576.

The read is the ABSORBED form: the up-projection of keys is folded into the
query (``q_lat = q_nope @ W_UK``, C lanes a head) and the up-projection of
values is applied to the output, so every query head attends the SAME
latent head:

    score[h, s] = (q_lat[h] . c[s] + q_pe[h] . k_pe[s]) * scale
    out[h]      = sum_s softmax(score)[h, s] * c[s]             (C lanes)

with causal masking inside a row's new-token window (query slot i attends
kpos <= pos0 + i), the contract of `ops.paged_attention`'s ragged read.
`scale` is the model's (1/sqrt(qk_nope + qk_rope)), not 1/sqrt(C).

**Queries arrive in TILES, not in (row, slot) order.** A mixed tick of 32
rows x 256 slots carries at most the token budget of valid slots; the
step (models.moonlight) therefore keeps only the tiles that hold one.
`tile_plan` cuts each row's q_len new tokens into tiles of
`slots_per_tile` slots and lists them in row order: tile n is tile
`plan.tile[n]` of row `plan.row[n]`, its slot j the row's slot
`plan.tile[n] * S + j`; `plan.n_live` tiles are real, the rest of the
static list is dead (all slots invalid). Everything token-wise in the step
runs over (n_tiles, S) and the read takes q as (n_tiles, S, H, .).

**Two classes of tile, chosen by a row's q_len** (`class_plan`), for a read
whose geometry follows the query's height (`ops.paged_attention`: K and V
of every head, where one query row a head packs all heads into one score
tile and 128 query rows a head are a product of their own). A SHORT row
has one new token (a decode row, or a prompt's last token alone): one tile
a row, read at width 1. Every longer run is TALL: cut in tiles of
`tall_slots` slots (128 query rows a KV head or a few times that), each a
row of a second call with its own first column, q_len and table row, so a
tick that carries a chunk reads its decode rows as a decode-only tick does
and the chunk's run in tiles of its own. The
latent read above keeps ONE class (`tile_plan`: `models.moonlight`,
`models.kimi_linear`, and the token lists of `models.laguna` and
`models.olmo_hybrid`); the two-class plan is what those last two hand
`ops.paged_attention.ragged_read_by_class` for their full layers.

`latent_attention_reference` is the XLA gather path (the CPU serving path
and the correctness anchor); `latent_attention` is the Pallas TPU kernel
`_mla_latent_kernel`: one grid step a tile, each walking its own context
`_BLOCKS_PER_STEP` physical blocks a group, with its own double-buffered
DMAs out of the pools, which stay in HBM: picked by the layer index and
the block table in SMEM, so neither the layer nor the gather ever
materializes and no step is spent on context a tile cannot see. Selection
follows `ops.paged_attention` (`TPU_ENGINE_PAGED`).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")

# Lanes of the pool's rope-key tensor: one lane tile, `qk_rope` of them used.
PE_LANES = 128
# Query rows (slot x head) a tile: 8 slots of 16 heads. A decode row in a
# tick that carries a chunk pads to one tile, so a smaller tile keeps the
# step's token list short; 128 rows still fill the MXU's rows.
_ROW_TILE = 128
# Physical blocks folded per group: 16 blocks of 16 slots are 256 context
# tokens a matrix product, so a 2.5 k context is ten groups and not 160.
_BLOCKS_PER_STEP = 16


def pad_rope_lanes(x):
    """(..., qk_rope) -> (..., PE_LANES): zero lanes above the rope width,
    as the pool's rope-key tensor and the kernel's `q_pe` operand hold it."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, PE_LANES - x.shape[-1])])


class TilePlan(NamedTuple):
    """Which (row, tile-of-row) each query tile is (module docstring): ONE
    class of tile, every row's run cut at the same height. What the latent
    read takes, and what a step lays its token list out by. `TileClasses`
    holds one of these for the tall class."""
    row: jax.Array       # (n_tiles,) int32
    tile: jax.Array      # (n_tiles,) int32
    start: jax.Array     # (B,) int32: a row's first tile in the list
    n_live: jax.Array    # (1,) int32


def slots_per_tile(n_heads: int, width: int) -> int:
    """Slots a tile for a step of `width` slots a row: whole slots of
    `n_heads` query rows, `_ROW_TILE` rows at most."""
    return max(1, min(width, _ROW_TILE // n_heads))


def tiles_bound(batch: int, width: int, per_tile: int, max_tokens=None):
    """A static bound on a step's live tiles: every tile of every row, or
    with at most `max_tokens` valid slots, sum_b ceil(q_b / S) <=
    max_tokens / S + (rows that hold a slot)."""
    every = batch * -(-width // per_tile)
    if max_tokens is None:
        return every
    return min(every, batch + -(-max_tokens // per_tile))


def tile_plan(qlen, per_tile: int, n_tiles: int) -> TilePlan:
    """The tiles that hold a valid slot, in row order, in a list `n_tiles`
    long; entries past `n_live` repeat the last live tile (their slots are
    all invalid: `tile_slots`)."""
    tiles = (qlen + per_tile - 1) // per_tile
    ends = jnp.cumsum(tiles)
    n_live = jnp.minimum(ends[-1], n_tiles)
    item = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                       jnp.maximum(n_live - 1, 0))
    row = jnp.minimum(jnp.searchsorted(ends, item, side="right"),
                      qlen.shape[0] - 1).astype(jnp.int32)
    start = (ends - tiles).astype(jnp.int32)
    tile = jnp.maximum(item - start[row], 0).astype(jnp.int32)
    return TilePlan(row, tile, start, n_live.astype(jnp.int32).reshape(1))


def tile_slots(plan: TilePlan, qlen, per_tile: int):
    """(slot (n_tiles, S) int32: each tile slot's index in its row's new
    tokens; valid (n_tiles, S) bool)."""
    slot = plan.tile[:, None] * per_tile + jnp.arange(per_tile)[None, :]
    live = jnp.arange(plan.row.shape[0])[:, None] < plan.n_live
    return slot, live & (slot < qlen[plan.row][:, None])


class TileClasses(NamedTuple):
    """A tick's rows by the class that reads them (module docstring).
    `short`: the rows with ONE new token, read a row a tile at width 1.
    `tall`: the tiles of every longer run, `tall_slots` slots each, in row
    order (a `TilePlan` over the runs of the rows that are not short), or
    None where the step is a slot wide: no run is longer than one token
    and one call reads every row. `slot`, `valid`: each tall tile slot's
    index in its row's new tokens and whether it holds one. `runs`,
    `run_slots`: where the short class is a run of up to `run_slots` new
    tokens (a block-decoding model's block, read a row a tile at that
    width), the short rows' q_len, zero elsewhere; None where it is one
    token."""
    short: jax.Array             # (B,) bool
    tall: "TilePlan | None"
    slot: "jax.Array | None"     # (n_tall, T) int32, `tile_slots` of `tall`
    valid: "jax.Array | None"    # (n_tall, T) bool
    runs: "jax.Array | None" = None      # (B,) int32
    run_slots: int = 1


def tall_slots(width: int, group: int) -> int:
    """Slots a tall tile in a step of `width` slots a row, `group` query
    heads a KV head: the fewest whose query rows (slot x group head) are
    whole tiles of `_ROW_TILE` rows, the paged kernel's too. 128 at one
    head a KV head (one tile of the call's grid a tall tile), 64 at six
    (three): a run walks its context ceil(q_len * G / 128) times, the
    fewest any height gives, and a higher tile only pads the operand."""
    return min(width, _ROW_TILE // math.gcd(_ROW_TILE, group))


def class_plan(qlen, width: int, group: int, max_tokens=None,
               run_slots: int = 1) -> TileClasses:
    """The two classes of a tick whose rows hold `qlen` new tokens, in a
    step of `width` slots a row and at most `max_tokens` valid slots, for
    a read of `group` query heads a KV head: the class is chosen by
    `qlen`, a value the step observes, and by nothing else. The tall list
    is `tiles_bound` long (a tile a row and ceil(max_tokens / tall_slots)
    more). `run_slots` S > 1: the short class is every run of up to S
    tokens (`TileClasses.runs`)."""
    if run_slots > 1:
        short = (qlen > 0) & (qlen <= run_slots)
        short_runs = (jnp.where(short, qlen, 0).astype(jnp.int32), run_slots)
    else:
        short, short_runs = qlen == 1, ()
    if width == run_slots:
        return TileClasses(short, None, None, None, *short_runs)
    height = tall_slots(width, group)
    runs = jnp.where(short, 0, qlen)
    tall = tile_plan(runs, height,
                     tiles_bound(qlen.shape[0], width, height, max_tokens))
    return TileClasses(short, tall, *tile_slots(tall, runs, height),
                       *short_runs)


def class_counts(qlen, width: int, group: int, run_slots: int = 1):
    """(short, tall): the live tiles of each class in a tick, on the host
    from the rows' `qlen` (a numpy vector): what `class_plan` makes of the
    same values on the device."""
    runs = qlen[qlen > run_slots]
    return (int(((qlen > 0) & (qlen <= run_slots)).sum()),
            int((-(-runs // tall_slots(width, group))).sum()))


def latent_attention_reference(q_lat, q_pe, pe_pool, c_pool, layer, tables,
                               plan: TilePlan, pos0, lengths=None, *,
                               scale: float):
    """XLA gather path. q_lat: (N, S, H, C) query tiles; q_pe:
    (N, S, H, R), R <= PE_LANES; pe_pool: (L, NB, bs, PE_LANES); c_pool:
    (L, NB, bs, C); layer: the layer read; tables: (B, nb); plan: the
    tiles' rows; pos0: (B,) logical column of each row's first new token
    (`lengths` = pos0 + q_len is the kernel's; the mask here is
    positional). Returns (N, S, H, C) float32. Invalid slots hold
    garbage by contract."""
    del lengths
    n, per_tile = q_lat.shape[:2]
    cols = tables.shape[1] * c_pool.shape[2]
    rows = tables[plan.row]                                    # (N, nb)
    c = c_pool[layer, rows].reshape(n, cols, -1)
    pe = pe_pool[layer, rows].reshape(n, cols, -1)[..., :q_pe.shape[-1]]
    scores = (jnp.einsum("nshc,nkc->nhsk", q_lat, c.astype(q_lat.dtype),
                         preferred_element_type=jnp.float32)
              + jnp.einsum("nshr,nkr->nhsk", q_pe, pe.astype(q_pe.dtype),
                           preferred_element_type=jnp.float32)) * scale
    qpos = (pos0[plan.row] + plan.tile * per_tile)[:, None] \
        + jnp.arange(per_tile)[None, :]                         # (N, S)
    keep = jnp.arange(cols)[None, None, None, :] <= qpos[:, None, :, None]
    probs = jax.nn.softmax(jnp.where(keep, scores, _NEG_INF), axis=-1)
    return jnp.einsum("nhsk,nkc->nshc", probs.astype(c.dtype), c,
                      preferred_element_type=jnp.float32)


def _mla_latent_kernel(tables_ref, pos0_ref, lengths_ref, layer_ref,
                       row_ref, tile_ref, n_live_ref,
                       ql_ref, qp_ref, pe_hbm, c_hbm, o_ref,
                       pe_buf, c_buf, sems, m_sc, l_sc, acc_sc, *,
                       block_size: int, scale: float, n_heads: int,
                       blocks: int):
    """One query TILE a grid step. ql_ref (1, T, C) and qp_ref
    (1, T, PE_LANES): the tile's T = S * H query rows (row r = slot
    r // H, head r % H); pe_hbm/c_hbm: the whole pools, left in HBM; o_ref
    (1, T, C). The tile walks ITS OWN context, `blocks` physical blocks a
    group: the blocks' DMAs (picked by the layer index and the block
    table) fill one of two VMEM buffers while the other is folded into the
    running flash accumulators (m/l (T, 1), acc (T, C), f32). The latent
    tile is both key and value. A dead tile writes zeros."""
    n = pl.program_id(0)
    rows = ql_ref.shape[1]
    span = blocks * block_size

    @pl.when(n >= n_live_ref[0])
    def _dead():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(n < n_live_ref[0])
    def _tile():
        b = row_ref[n]
        first = pos0_ref[b] + tile_ref[n] * (rows // n_heads)
        layer = layer_ref[0]
        # Columns the tile's LAST slot sees, capped at pos0 + q_len.
        horizon = jnp.minimum(lengths_ref[b], first + rows // n_heads)
        groups = (horizon + span - 1) // span

        def copies(g, slot):
            out = []
            for i in range(blocks):
                blk = tables_ref[b, g * blocks + i]
                at = pl.ds(i * block_size, block_size)
                out.append(pltpu.make_async_copy(
                    pe_hbm.at[layer, blk], pe_buf.at[slot, at],
                    sems.at[0, slot, i]))
                out.append(pltpu.make_async_copy(
                    c_hbm.at[layer, blk], c_buf.at[slot, at],
                    sems.at[1, slot, i]))
            return out

        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)
        for copy in copies(0, 0):
            copy.start()
        qpos = first + jax.lax.broadcasted_iota(
            jnp.int32, (rows, span), 0) // n_heads
        contract_last = (((1,), (1,)), ((), ()))

        def fold(g, carry):
            slot = g % 2

            @pl.when(g + 1 < groups)
            def _prefetch():
                for copy in copies(g + 1, 1 - slot):
                    copy.start()

            for copy in copies(g, slot):
                copy.wait()
            kpos = g * span + jax.lax.broadcasted_iota(
                jnp.int32, (rows, span), 1)
            # Columns past the horizon hold whatever the null block or an
            # earlier group left there: zero them as values (0 * NaN).
            seen = (g * span + jax.lax.broadcasted_iota(
                jnp.int32, (span, 1), 0)) < horizon
            c = jnp.where(seen, c_buf[slot], 0).astype(c_buf.dtype)
            pe = jnp.where(seen, pe_buf[slot], 0).astype(pe_buf.dtype)
            s = (jax.lax.dot_general(ql_ref[0], c, contract_last,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qp_ref[0], pe, contract_last,
                                       preferred_element_type=jnp.float32))
            s = jnp.where(kpos <= qpos, s * scale, _NEG_INF)
            m = m_sc[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            safe_m = jnp.where(m_new == _NEG_INF, 0.0, m_new)
            p = jnp.exp(s - safe_m)
            corr = jnp.where(m == _NEG_INF, 0.0, jnp.exp(m - safe_m))
            l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
                p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[...] = m_new
            return carry

        jax.lax.fori_loop(0, groups, fold, 0)
        l = l_sc[...]
        o_ref[0] = (acc_sc[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "blocks"))
def _latent_call(q_lat, q_pe, pe_pool, c_pool, layer, tables, plan, pos0,
                 lengths, *, scale: float, interpret: bool,
                 blocks: int = _BLOCKS_PER_STEP):
    """The pallas_call. q_lat: (N, S, H, C); q_pe: (N, S, H, R); the pools
    whole, as they live on the device; layer: (1,); tables: (B, nb);
    lengths: pos0 + qlen. Returns (N, S, H, C) in q_lat's dtype."""
    n, per_tile, h, c = q_lat.shape
    bs = c_pool.shape[2]
    rows = per_tile * h
    ql = q_lat.reshape(n, rows, c)
    qp = pad_rope_lanes(q_pe).reshape(n, rows, PE_LANES)
    blocks = min(blocks, tables.shape[1])
    groups = pl.cdiv(tables.shape[1], blocks)
    # Table entries past the row's own width point at the null block.
    tables = jnp.pad(tables, ((0, 0), (0, groups * blocks - tables.shape[1])))

    def q_spec(last):
        return pl.BlockSpec((1, rows, last), lambda n, *_: (n, 0, 0))

    whole = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(_mla_latent_kernel, block_size=bs,
                               scale=scale, n_heads=h, blocks=blocks)
    span = blocks * bs
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # tables, pos0, lengths, layer, plan.row, plan.tile, plan.n_live
            num_scalar_prefetch=7,
            grid=(n,),
            in_specs=[q_spec(c), q_spec(PE_LANES), whole, whole],
            out_specs=q_spec(c),
            scratch_shapes=[pltpu.VMEM((2, span, PE_LANES), pe_pool.dtype),
                            pltpu.VMEM((2, span, c), c_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2, blocks)),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, c), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, rows, c), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_latent_read",
    )(tables, pos0, lengths, layer, plan.row, plan.tile, plan.n_live, ql, qp,
      pe_pool, c_pool)
    return out.reshape(n, per_tile, h, c)


def latent_attention(q_lat, q_pe, pe_pool, c_pool, layer, tables,
                     plan: TilePlan, pos0, lengths, *, scale: float,
                     interpret=None):
    """Pallas-kernel drop-in for `latent_attention_reference` (same
    contract; the output is in q_lat's dtype and a dead tile's is zero).
    `interpret=None` compiles on a TPU and runs the Pallas interpreter
    elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _latent_call(q_lat, q_pe, pe_pool, c_pool,
                        jnp.asarray(layer, jnp.int32).reshape(1),
                        jnp.asarray(tables, jnp.int32),
                        plan, jnp.asarray(pos0, jnp.int32),
                        jnp.asarray(lengths, jnp.int32),
                        scale=float(scale), interpret=bool(interpret))


def default_latent_attention():
    """Serving-path selection, the rule of `ops.paged_attention`
    (`TPU_ENGINE_PAGED`: "1" the kernel, "0" the XLA gather, unset/"auto"
    the kernel on a TPU only)."""
    import os

    mode = os.environ.get("TPU_ENGINE_PAGED", "auto")
    if mode == "1" or (mode == "auto" and jax.default_backend() == "tpu"):
        return latent_attention
    return latent_attention_reference


def parity_workload(q_lens, *, n_heads: int, latent: int, rope: int,
                    block_size: int, n_blocks: int, table_len: int, dtype,
                    max_tokens=None, seed: int = 0):
    """One random workload for the kernel/reference pair, one row per entry
    of `q_lens` (`ops.paged_attention.parity_workload`'s pattern): a
    two-layer pool of which the second layer is read, shuffled tables,
    ragged positions, the rows' new tokens cut into tiles by `tile_plan`.
    Returns the operands (q_lat, q_pe, pe_pool, c_pool, layer, tables,
    plan, pos0, lengths)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    batch, w = len(q_lens), max(q_lens)
    per_tile = slots_per_tile(n_heads, w)
    qlen = jnp.asarray(np.asarray(q_lens, np.int32))
    plan = tile_plan(qlen, per_tile,
                     tiles_bound(batch, w, per_tile, max_tokens))
    kq, kp, kc, ke = jax.random.split(jax.random.PRNGKey(seed), 4)
    slots = (2, n_blocks, block_size)
    tiles = (plan.row.shape[0], per_tile, n_heads)
    q_lat = jax.random.normal(kq, tiles + (latent,), dtype)
    q_pe = jax.random.normal(kp, tiles + (rope,), dtype)
    c_pool = jax.random.normal(kc, slots + (latent,), dtype)
    pe_pool = pad_rope_lanes(jax.random.normal(ke, slots + (rope,), dtype))
    tables = np.zeros((batch, table_len), np.int32)
    pos0 = np.zeros((batch,), np.int32)
    for r, ql in enumerate(q_lens):
        tables[r] = 1 + rng.permutation(n_blocks - 1)[:table_len]
        pos0[r] = int(rng.integers(0, table_len * block_size - ql + 1))
    pos0 = jnp.asarray(pos0)
    return (q_lat, q_pe, pe_pool, c_pool, jnp.int32(1), jnp.asarray(tables),
            plan, pos0, pos0 + qlen)


def reference_error(out, operands, scale: float) -> float:
    """Max |out - reference| over the VALID query slots of a
    `parity_workload`'s operands; the reference on f32 copies at the
    highest matmul precision, so on a TPU the distance measures the
    kernel."""
    plan, pos0, lengths = operands[6:9]
    _, valid = tile_slots(plan, lengths - pos0, out.shape[1])
    f32 = tuple(x.astype(jnp.float32) if isinstance(x, jax.Array)
                and jnp.issubdtype(x.dtype, jnp.floating) else x
                for x in operands)
    with jax.default_matmul_precision("highest"):
        ref = latent_attention_reference(*f32, scale=scale)
    diff = jnp.abs(out.astype(jnp.float32) - ref)
    return float(jnp.max(jnp.where(valid[:, :, None, None], diff, 0.0)))


def parity_check(q_lens=(1, 7, 16, 17), *, n_heads: int = 4,
                 latent: int = 32, rope: int = 8, block_size: int = 16,
                 n_blocks: int = 9, table_len: int = 4, dtype=jnp.float32,
                 max_tokens=None, interpret=None, seed: int = 0) -> float:
    """Max |kernel - reference| over one `parity_workload`."""
    operands = parity_workload(
        q_lens, n_heads=n_heads, latent=latent, rope=rope,
        block_size=block_size, n_blocks=n_blocks, table_len=table_len,
        dtype=dtype, max_tokens=max_tokens, seed=seed)
    scale = 1.0 / (latent + rope) ** 0.5
    return reference_error(
        latent_attention(*operands, scale=scale, interpret=interpret),
        operands, scale)
