"""Paged attention: queries over block-pooled KV.

The paged KV cache (runtime.kv_blocks) stores every row's keys/values in
fixed-size blocks of a shared pool instead of one dense per-row stripe;
a per-row **block table** maps logical column `c` to pool block
`table[c // bs]`, offset `c % bs`. This module is the attention read
side of that layout. Two read paths share one contract each with an
XLA reference:

- ragged (`ragged_paged_attention`): q_len >= 0 per row — the ragged
  tick serves decode rows (one token) and admitting rows (a prefill
  chunk) in ONE dispatch, with causal masking inside each row's
  new-token window (query slot i attends kpos <= pos0 + i); a decode
  row is a ragged row of q_len 1, and a call one slot wide is the
  packed one-query geometry below;
- its int8-pool variant (`quant_ragged_paged_attention`, --kv-quantize
  int8), which applies the per-slot scales inside the read.

Every read path takes the WHOLE pool, (L, NB, bs, H_kv*D) as
`runtime.kv_blocks.BlockPool` holds it (head h in lanes
[h*D, (h+1)*D)), plus the `layer` to read: the step carries the pool
through its layer loop and never slices a layer out.

What a block holds here: `block_size` tokens' keys (in `k_pool`) and
values (in `v_pool`) of every KV head, D lanes a head as the MODEL
states it (`cfg.d_head`); int8 pools add a scale a (slot, KV head). A
latent pool (one latent and one rope key a token, two tensors of
unequal width) has its own read, `ops.latent_attention`.

The `*_reference` functions are XLA `take`: gather the row's blocks
of that layer into a dense (B, S, H_kv, D) view and run the exact
`ops.attention.dot_product_attention` math (grouped, un-expanded,
masked). They are the correctness anchor and the CPU serving path: the
gathered view puts every logical column at the same index the dense
scheduler would, so reductions see identical operand layouts and seeded
token streams match the dense path.

The kernel side is ONE Pallas TPU kernel (`_paged_kernel`) behind both
entry points — the pool dtype is a static flag. **The grid is the query tiles, not the table.**
Query slots stack with the group heads on the sublane axis: q is laid
out (B, H_kv, W*G, D) with G = n_heads/kv_heads (row r = slot r//G,
head r%G), tiled `_ROW_TILE` rows at a time, and the grid is
(B, ceil(W*G / rows)) whatever the table's width: a tile of a row with
no new token there (q_len 0, or a decode row's tiles past its first)
writes zeros and costs one empty step. (A decode row's FIRST tile in a
call W slots wide is as tall as a chunk's, one live query row in 128 a
head: a step that knows its rows' runs makes two calls instead, see "Two
classes of tile" below.) A live tile walks ITS OWN
context: K and V stay whole in HBM, and a loop over
ceil(horizon / (blocks * bs)) groups — `horizon` is what the tile's
last slot sees — DMAs each group's physical blocks, all KV heads, a
(bs, H_kv*D) tile of the pool each, the same bytes in the same order as
the (bs, H_kv, D) block of the chain wire format, chosen by the layer
index and the block table in SMEM (neither the layer nor the gather
ever materializes), into one of two VMEM buffers while the other is
folded into running flash accumulators (f32 max / denominator /
weighted sum in VMEM scratch). A group's copies are those of its blocks
that hold a column the tile sees: none is started for a block wholly
past the horizon (or behind a window's lower bound), so a row of 48
columns under a group of 128 fetches 3 blocks, not 8, and a short row
under a long table costs its own blocks and nothing else — the
ragged-batch win the TPU paged-attention kernel exists for (PAPERS.md
"Ragged Paged Attention"). The one block the horizon falls in is fetched
whole; its columns past the horizon, and the buffer rows no copy filled,
are selected away as scores and as values.

**The walk is one DMA pipeline over the whole call** (PR 48). The grid's
steps run in order (both axes "arbitrary": one TensorCore a v5e chip, and
a step now depends on the one before it), and the fold of a tile's LAST
group starts the copies of the FIRST group of the grid's next step,
(b, t + 1) else (b + 1, 0), into the buffer it is not folding, when that
step is live: by the next step's own horizon, lower bound and table row,
the expressions that step uses for itself. Two words of SMEM scratch
carry "started, and into which buffer" across the step's edge. So a live
tile behind a live tile begins with its first group in flight, and only
the call's first tile or one behind a dead step starts its own and waits
with nothing to fold. No copy is ever started that the very next step
does not wait for, and the call's last live tile starts none. A buffer's
copies signal ONE semaphore a tensor, which counts bytes: a whole group is
straight-line code and one wait a tensor; a group cut by the horizon goes
by the bits of its block count (4 + 2 + 1), so no block costs a branch of
its own (on the chip sixteen predicated copies a group cost more than the
bytes they saved, PERF.md section 6, PR 48). `walk_counts` applies the
same rules on the host: a tick's span says how many of its tiles were
warm and how many tokens its DMAs fetched.

Tile geometry comes from the shapes the call sees (`_tile_geometry`),
one walk for both: a chunk's tile is 128 query rows a KV head against
groups of 16 blocks, (128, D) x (D, 256) a head; a decode row has ONE
query row a head, so its heads are packed into one score tile (row i =
head i) against groups of 8 blocks. **Where a head's D lanes are half a
lane tile (D = 64) a packed tile's fold is two products a group** (PR 61,
`_product_heads`): the packed heads' lanes of a buffer row are ONE
contraction. The queries are laid block-diagonally once a tile,
(M, pack * D) with head h's rows holding its D values in lanes
[h*D, (h+1)*D) and zero elsewhere, so one (M, pack * D) x (span, pack * D)
product gives every head's scores against its own keys (the zeros do
what a product a head and their sum did, every MXU pass contracts whole
lane tiles, no head's key slice is cut out of the buffer), and one
(M, span) x (span, pack * D) product gives the values, of which head h's
own rows and lanes are its output. Where D is a multiple of 128 a
product a head already contracts whole lane tiles of an aligned slice,
and the packed tile keeps it: head h's queries in their rows of a zero
(M, D) tile, the heads' products added up. One loop, over the products
of a chunk of heads; which a call takes follows from its shapes. VMEM
holds O(H_kv * _ROW_TILE * D) whatever W*G is.

**Two classes of tile.** The geometry is a CALL's, chosen by its width,
so a tick that carries a chunk would read its decode rows through the
chunk's tall tiles. `ragged_read_by_class` takes the tick's token list
and the plan `ops.latent_attention.class_plan` makes of the rows' q_len
and calls the read twice: the SHORT rows (one new token) as
(B, 1, H, D), a row a tile, the packed geometry a decode-only tick
runs; every longer run in TALL tiles of 128 query rows a KV head or a
few times that (`tall_slots`), a TILE a row of the call with its own
first column, q_len and table row. Both calls take what the read path
takes besides (`window`, `mask_block`). Who calls which: the steps that
run over the tick's tokens call `ragged_read_by_class`, through
`models.tick_tokens` `PagedKV.attend`: every layer that attends of
`models.olmo_hybrid`, `falcon_h1`, `nemotron_h`, `lfm2`, `granite_hybrid`,
`ouro` and `sdar`; `models.laguna` for its FULL layers alone (its window
layers, at a window of 512, stay a list tile of 8 slots a row of ONE
call: a tile walks ~520 columns); `models.smallthinker` for BOTH kinds
of layer, the window kind's calls handed `window` (at a window of 4096 a
list tile a row would walk the window once a tile of 8 slots, 32 times a
chunk; a tall tile of 128 slots walks it twice). The uniform step
(`models.transformer`, every slot of every row) calls a read path below
once, a ROW a row of the call.

An int8 pool's scales, (L, NB, bs, H_kv), cannot be fetched by the walk
(Mosaic refuses a DMA whose minor dimension is H_kv): the call gathers
the scales of the rows' tables, tokens on the lanes, and the kernel
multiplies a head's scores and probabilities by them — the reference's
product (int8 -> f32 is exact), the slot's scale applied once a
(query, token) instead of once a lane. That gather is the one part of
the int8 read that still grows with the table's width.

Selection mirrors `models.transformer.default_attention`:
`TPU_ENGINE_PAGED` "1" forces the kernel (interpreter off-TPU), "0"
forces the XLA reference, unset/"auto" picks the kernel on TPU only.
Under tensor-parallel serving the chosen path runs per head shard
(`shard_over_heads`; the merged H_kv*D axis shards in whole heads):
Mosaic kernels cannot be partitioned by GSPMD.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from tpu_engine.ops.attention import dot_product_attention

_NEG_INF = float("-inf")

# Query rows (slot x group head) per grid step. Bounds the kernel's VMEM
# (q/out tiles, the f32 accumulators and their lane-padded (rows, 1)
# softmax statistics) independently of the chunk width and group size.
_ROW_TILE = 128
# Physical blocks a live tile folds per group of its walk. A chunk's tile:
# 16 blocks of 16 slots are a 256-token key tile a matrix product (two
# 640 KB buffers a tensor at gpt2-large's 1280 lanes, 512 KB at Mistral's
# 1024). A decode row's (heads packed, `_tile_geometry`): 8, so a row of a
# few hundred tokens already overlaps its second group's DMAs with the
# first group's products (measured, PERF.md section 6, PR 29); since PR 48
# its first group's too, with the fold of the row before it.
_BLOCKS_PER_GROUP = 16
_BLOCKS_PER_GROUP_PACKED = 8
# What a group's four VMEM buffers (K and V, two each) may take: a pool of
# more lanes a token halves its group until they fit. 30 KV heads of 128
# lanes (Olmo-Hybrid's full layers, 7.5 KB a token and tensor) walk 8
# blocks a group, 3.9 MB; at 16 the call's 17.7 MB pass Mosaic's 16 MB.
# Every pool up to 2048 lanes in bfloat16 keeps its 16.
_KV_SCRATCH_BYTES = 4 << 20


def _dense_rows(q, k_pool, v_pool, k_scale, v_scale, layer, tables):
    """The rows' K and V of layer `layer` as the dense (B, nb*bs, H_kv, D)
    view the attention math reads: one XLA gather per tensor through the
    block tables, the layer never sliced out whole. An int8 pool
    dequantizes on the gathered copy (exact: int8 * f32 scale)."""
    b, nb = tables.shape
    cols = nb * k_pool.shape[2]

    def rows(pool, *heads):       # (L, NB, bs, X) -> (B, nb*bs, *heads)
        return pool[layer, tables].reshape(b, cols, *heads)

    d = q.shape[-1]
    kk, vv = rows(k_pool, -1, d), rows(v_pool, -1, d)
    if k_scale is not None:
        from tpu_engine.ops.quant import dequantize_kv

        kk = dequantize_kv(kk, rows(k_scale, -1))
        vv = dequantize_kv(vv, rows(v_scale, -1))
    return kk, vv


def _ragged_reference(q, k_pool, v_pool, k_scale, v_scale, layer, tables,
                      pos0, window=None, mask_block: int = 1, qlen=None):
    kk, vv = _dense_rows(q, k_pool, v_pool, k_scale, v_scale, layer, tables)
    kpos = jnp.arange(kk.shape[1])
    qpos = pos0[:, None] + jnp.arange(q.shape[1])[None, :]     # (B, W)
    if mask_block > 1:
        # Block-causal: a query sees its whole block of `mask_block`
        # positions, as far as the row's new tokens reach.
        qpos = qpos // mask_block * mask_block + (mask_block - 1)
        qpos = jnp.minimum(qpos, jnp.maximum(pos0 + qlen - 1, pos0)[:, None])
    valid = kpos[None, None, :] <= qpos[:, :, None]
    if window is not None:
        valid &= kpos[None, None, :] > qpos[:, :, None] - window
    return dot_product_attention(q, kk, vv, mask=valid.astype(jnp.int32))


# -- ragged (mixed prefill+decode) reference ---------------------------------
#
# The ragged tick (runtime.scheduler) folds admission
# prefill into the decode dispatch: one ragged batch where decode rows
# contribute ONE new token and admitting rows contribute a prefill chunk
# of up to W tokens (PAPERS.md "Ragged Paged Attention"). Row b's query
# slot i sits at logical position pos0[b] + i and attends causally within
# its own history (kpos <= pos0[b] + i); slots i >= qlen[b] are padding
# whose output the scheduler ignores.


def ragged_paged_attention_reference(q, k_pool, v_pool, layer, tables, pos0,
                                     qlen, *, window=None,
                                     mask_block: int = 1):
    """XLA gather path, ragged queries. q: (B, W, H, D);
    k_pool/v_pool: (L, NB, bs, H_kv*D), the whole pool; layer: int32
    scalar, the layer read; tables: (B, nb) int32 block ids (0 = the
    reserved null block: its columns must lie past what the row's
    slots see); pos0: (B,) logical position of each row's
    FIRST query slot; qlen: (B,) valid query slots (padding slots
    produce garbage the caller must ignore — masking them costs more
    than ignoring). `window` (a sliding-window layer): query slot i also
    needs kpos > pos0 + i - window; table entries wholly behind every
    slot's window may be the null block (the row gave those blocks
    back). `mask_block` L > 1 (a block-decoding model): query slot i at
    position p = pos0 + i sees kpos < (p // L + 1) * L, its whole block
    and every earlier one, and no column at or past pos0 + qlen (1 is the
    causal mask). Returns (B, W, H, D)."""
    # padding slots are ignored by contract, not masked
    return _ragged_reference(q, k_pool, v_pool, None, None, layer, tables,
                             pos0, window, mask_block, qlen)


# -- quantized (int8 block pool) references ----------------------------------
#
# The quantized pool (runtime.kv_blocks, --kv-quantize int8) stores block
# payloads int8 with one f32 scale per (block slot, kv-head) vector per
# layer. Both sides use int8 -> f32 (exact) times the slot's f32 scale —
# the reference on its gathered copy, lane by lane; the kernel on each
# streamed group in VMEM, the scale applied to the head's score and
# probability (module docstring) — so the dequantized pool never
# materializes in HBM, and rounding error comes only from the one-time
# int8 write at block-fill time.


def quant_ragged_paged_attention_reference(q, k_pool, v_pool, k_scale,
                                           v_scale, layer, tables, pos0,
                                           qlen):
    """`ragged_paged_attention_reference` over the int8 pool (same
    contract; padding slots produce garbage the caller ignores).
    k_pool/v_pool: (L, NB, bs, H_kv*D) int8; k_scale/v_scale:
    (L, NB, bs, H_kv) f32. The gathered view dequantizes to f32 (exact:
    int8 * f32 scale), then the identical dense attention math runs."""
    del qlen
    return _ragged_reference(q, k_pool, v_pool, k_scale, v_scale, layer,
                             tables, pos0)


# -- the kernel (both read paths) ---------------------------------------------


def _tile_geometry(rows_a_row: int, n_kv_heads: int):
    """(rows, pack, blocks) for a call whose batch rows hold `rows_a_row`
    query rows (slot x group head) a KV head. `rows`: query rows a tile
    and KV head, `_ROW_TILE` at most. `pack`: KV heads whose scores share
    ONE score tile of pack * rows rows, the largest divisor of H_kv that
    keeps it within `_ROW_TILE`: 1 for a chunk (128 rows a head fill the
    MXU's rows alone), every head for a decode row (gpt2-large: 20 rows,
    Mistral: 8 x 4), so a head with one query row is not a product and a
    softmax of its own (how many of them ONE product takes:
    `_product_heads`). `blocks`: physical blocks a group of the walk."""
    rows = min(rows_a_row, _ROW_TILE)
    pack = max(p for p in range(1, n_kv_heads + 1)
               if n_kv_heads % p == 0 and p * rows <= _ROW_TILE)
    return rows, pack, (_BLOCKS_PER_GROUP if pack == 1
                        else _BLOCKS_PER_GROUP_PACKED)


def _product_heads(pack: int, d_head: int) -> int:
    """KV heads ONE product of a tile's fold takes, of the `pack` that share
    its score tile: all of them where a head's D lanes are not whole lane
    tiles (D = 64: gpt2-large's 20 heads, LFM2's 8 x 4 rows), one where
    they are (D a multiple of 128). Measured, one call alone on a v5e at
    the cells' shapes (PERF.md section 6, PR 61), a product a head against
    a product a chunk: D = 64 98.7 -> 84.8 us (32 rows x 20 heads) and
    1554 -> 1243 (128 rows x 8 heads x 4): a head's product had contracted
    half a lane tile, from a key slice that starts mid-tile for every odd
    head. D = 128, where the MXU's passes are the same in number either
    way: 1502 | 1502 (30 heads), 264 | 261 (16), 537 | 537 (4 x 32 rows,
    the block mask), 336 | 337 (4 x 5), 618 | 617 (2 x 16), 154 | 153 (8 x
    4), and 967 -> 993, 595 -> 611 (8 x 6, two sets of contexts): nothing
    to gain and one shape that loses 2.7 %, so those keep a product a
    head."""
    return pack if d_head % 128 else 1


def _paged_kernel(tables_ref, pos0_ref, lengths_ref, layer_ref, q_ref, k_hbm,
                  v_hbm, *rest, block_size: int, blocks: int, scale: float,
                  group: int, pack: int, width: int, quant: bool,
                  window=None, mask_block: int = 1):
    """One query TILE a grid step (b, t): `rows` query rows of batch row
    b (row r = slot r // G, group head r % G) for ALL its KV heads.
    q_ref/o_ref (1, H_kv, rows, D); k_hbm/v_hbm: the whole pools, left in
    HBM. A tile with no valid slot writes zeros; a live one walks ITS
    OWN context, `blocks` physical blocks a group: the DMAs of a group's
    blocks that hold a column the tile sees (picked by `layer_ref` and
    the block table) fill one of two VMEM buffers (blocks, bs, H_kv*D)
    while the other is folded into the flash accumulators. The walk is
    one pipeline over the CALL: the fold of a tile's last group starts
    the first group of the grid's next step where that tile is live
    (`warm_sc`: (2,) in SMEM, whether this step's first group was
    started for it, and into which buffer), so only a tile after a dead
    step, or the call's first, waits for a fetch with nothing to fold.

    `pack` KV heads, a CHUNK, share a score tile of M = pack * rows rows
    (row i = head i // rows of the chunk, query row i % rows) and one
    softmax; the chunk's scores and values are the products of `width`
    heads each (`_product_heads`; `qx_sc` and acc: (H_kv/width, M,
    width * D), the same bytes either way). width == pack (D = 64): two
    products a chunk and group. The chunk's queries are laid block-
    diagonally once a tile (head h's rows hold its D values in its own
    lanes and zero in the other heads'), so ONE product over the chunk's
    pack * D lanes of the K buffer's rows is every head's scores against
    its own keys, and ONE product of the probabilities with the same
    lanes of the V buffer's rows goes into acc, of which head h's own
    rows and lanes are its output at the tile's end (the rest, a head's
    probabilities against another head's values, is never read).
    width == 1 (D a multiple of 128): head h's queries sit in their rows
    of a zero (M, D) tile, the heads' products with their own key lanes
    add up to the (M, span) scores, and of the (M, D) product with head
    h's values its rows alone are kept. pack == 1 is the same loop, a
    head a chunk and `q_ref` itself the queries.
    Statistics (m/l: (H_kv/pack, M, 1)) stay columns, so no step moves
    a vector between lanes and sublanes.
    Causal masking within the new-token window: query row r keeps
    kpos <= pos0 + r // G; a padding row (a slot past q_len) reads as
    the tile's last valid one, so no row keeps a column past the
    horizon, whose block was never fetched. `mask_block` L > 1 (static; a
    block-decoding model's block-causal mask): query row r at position
    p = pos0 + r // G keeps kpos < (p // L + 1) * L, and the tile's
    horizon is the end of its last row's block, capped at pos0 + q_len
    as before (L = 1 is the causal mask, the same program as without).

    `window` (static; a sliding-window layer) is the walk's OTHER end:
    query row r also needs kpos > pos0 + r // G - window, so the walk
    starts at the block that holds the first column the tile's FIRST row
    still sees, and nothing is spent on the context behind it. Table
    entries behind the window may be the null block.

    An int8 pool adds ks_ref/vs_ref (1, groups, H_kv', span) f32: the
    scales of the row's table, a group's tokens on the lanes (Mosaic
    takes no DMA whose minor dimension is H_kv, so the call gathers
    them). int8 -> f32 is exact and the slot's scale multiplies the
    head's score and probability instead of its D key and value lanes
    (a row of a packed tile belongs to one head, `head_rows`): the
    reference's product, rounded once at the int8 write."""
    if quant:
        ks_ref, vs_ref, *rest = rest
    o_ref, k_buf, v_buf, sems, warm_sc, *scratch = rest
    qx_sc = scratch.pop(0) if pack > 1 else None
    m_sc, l_sc, acc_sc = scratch
    b, t = pl.program_id(0), pl.program_id(1)
    n_b, n_t = pl.num_programs(0), pl.num_programs(1)
    n_kv_heads, rows, d_head = q_ref.shape[1:]
    m_rows = pack * rows
    lanes = width * d_head          # what ONE product contracts, or makes
    span = blocks * block_size

    def own_rows(h):                # head h's rows of its chunk's M
        return pl.ds(h % pack * rows, rows)

    def own_lanes(h):               # and its lanes of its product's
        return pl.ds(h % width * d_head, d_head)

    def tile(b, t):
        """(live, horizon, lower, first group) of grid step (b, t).
        `horizon`: the columns the tile's LAST query row sees, capped at
        pos0 + q_len; `lower`: the first column its FIRST row still sees."""
        pos0, first = pos0_ref[b], t * rows
        sees = pos0 + (first + rows - 1) // group + 1
        if mask_block > 1:
            sees = (sees + mask_block - 1) // mask_block * mask_block
        horizon = jnp.minimum(lengths_ref[b], sees)
        lower = 0 if window is None else jnp.maximum(
            pos0 + first // group - (window - 1), 0)
        return (first < (lengths_ref[b] - pos0) * group, horizon, lower,
                lower // span)

    live, horizon, lower, first_group = tile(b, t)
    # Was this step's first group started by the step before, and where?
    # (The scratch holds nothing at the call's first step.)
    warm = jnp.logical_and(jnp.logical_or(b > 0, t > 0), warm_sc[0] == 1)
    first_slot = jnp.where(warm, warm_sc[1], 0)
    warm_sc[0] = 0

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(live)
    def _tile():
        layer = layer_ref[0]
        pos0, first = pos0_ref[b], t * rows
        groups = (horizon + span - 1) // span
        # The grid's next step, (b, t + 1) else (b + 1, 0), as it will see
        # itself: the one tile this one may fetch for.
        wrap = t + 1 == n_t
        b_next = jnp.minimum(jnp.where(wrap, b + 1, b), n_b - 1)
        live_next, horizon_next, lower_next, first_group_next = tile(
            b_next, jnp.where(wrap, 0, t + 1))
        live_next = jnp.logical_and(        # the call's last step has none
            live_next, jnp.logical_not(jnp.logical_and(wrap, b + 1 == n_b)))

        def group_copies(b, g, slot, horizon, lower, do, wait=False):
            """Start, or wait for, the K and V copies of the blocks of row
            b's group g that hold a column in [lower, horizon), into
            buffer `slot`; none unless `do`. They are one run [lo, lo + n)
            of the group's blocks, and a start and its wait name the same
            run. A buffer's copies signal ONE semaphore a tensor, which
            counts bytes: a whole group (every group of a long walk but
            its ends) is straight-line code and one wait a tensor, a part
            of one goes by the bits of n, a power of two of blocks at a
            time, so no block costs a branch of its own."""
            j0 = g * blocks
            lo = 0 if window is None else jnp.maximum(
                lower // block_size - j0, 0)
            n = jnp.where(do, jnp.minimum(
                (horizon + block_size - 1) // block_size - j0, blocks) - lo,
                0)

            def run(first, count):
                for pool, buf, sem in ((k_hbm, k_buf, sems.at[0, slot]),
                                       (v_hbm, v_buf, sems.at[1, slot])):
                    if wait:
                        dst = buf.at[slot, pl.ds(0, count)]
                        pltpu.make_async_copy(dst, dst, sem).wait()
                        continue
                    for i in range(count):
                        pltpu.make_async_copy(
                            pool.at[layer, tables_ref[b, j0 + first + i]],
                            buf.at[slot, first + i], sem).start()

            @pl.when(n == blocks)
            def _():
                run(0, blocks)

            @pl.when(jnp.logical_and(n > 0, n < blocks))
            def _():
                first, size = lo, 1 << (blocks - 1).bit_length() >> 1
                while size:
                    pl.when(n & size != 0)(
                        functools.partial(run, first, size))
                    first, size = first + (n & size), size // 2

        group_copies(b, first_group, first_slot, horizon, lower,
                  jnp.logical_not(warm))
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)
        if pack > 1:
            # Head h's query rows hold its D values in its own lanes of
            # its product's (block-diagonal where a product takes the
            # chunk) and zero in every other row.
            qx_sc[...] = jnp.zeros(qx_sc.shape, qx_sc.dtype)
            for h in range(n_kv_heads):
                qx_sc[h // width, own_rows(h), own_lanes(h)] = q_ref[0, h]
        # qpos - (column inside a group): a column of group g is kept
        # where this reaches g * span. qpos stops at the horizon's last
        # column (a valid row's is below it already).
        shape = (m_rows, span)
        qpos = pos0 + (first + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0) % rows) // group
        if mask_block > 1:      # the last column of the query's block
            qpos = qpos // mask_block * mask_block + (mask_block - 1)
        reach = jnp.minimum(qpos, horizon - 1) \
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1)

        def heads(buf, slot, j):                # -> (span, width * D)
            x = buf[slot, :, :, j * lanes:(j + 1) * lanes]
            if quant:
                x = x.astype(jnp.float32)
            return x.reshape(span, lanes)

        def head_rows(x, j):
            """(H_kv', span), a head a row -> product j's (M, span): row i
            the row of ITS head (a product of one head: every row that
            head's, the other heads' rows hold zeros)."""
            first = j * width
            if width == 1 or rows == 1:
                return x[first:first + width]
            own = jax.lax.broadcasted_iota(jnp.int32, (m_rows, 1), 0) // rows
            out = x[first:first + 1]
            for i in range(1, width):
                out = jnp.where(own == i, x[first + i:first + i + 1], out)
            return out

        def fold(g, slot):
            # What the pipeline fetches while group g is folded: this
            # tile's next group, or after its last one the next step's
            # first, into the buffer this iteration does not fold.
            own = g + 1 < groups
            group_copies(jnp.where(own, b, b_next),
                      jnp.where(own, g + 1, first_group_next), 1 - slot,
                      jnp.where(own, horizon, horizon_next),
                      jnp.where(own, lower, lower_next),
                      jnp.logical_or(own, live_next))

            @pl.when(jnp.logical_and(jnp.logical_not(own), live_next))
            def _next_step_is_warm():
                warm_sc[0] = 1
                warm_sc[1] = 1 - slot

            group_copies(b, g, slot, horizon, lower, True, wait=True)
            keep = reach >= g * span
            if window is not None:
                keep &= reach < g * span + window
            # A block past the horizon (or behind `lower`) was not
            # fetched: its buffer rows hold an older group's bytes or
            # nothing yet. Selected away as scores, and as values.
            if quant:
                seen = (g * span + jax.lax.broadcasted_iota(
                    jnp.int32, (1, span), 1)) < horizon
                ks, vs = ks_ref[0, g], jnp.where(seen, vs_ref[0, g], 0.0)
            else:
                column = g * span + jax.lax.broadcasted_iota(
                    jnp.int32, (span, 1), 0)
                seen = column < horizon
                if window is not None:
                    seen &= column >= lower      # given back: the null block
            for c in range(n_kv_heads // pack):
                # The chunk's products, `width` heads each: ONE where a
                # product takes the chunk's lanes whole, else one a head.
                products = range(c * pack // width, (c + 1) * pack // width)
                s = None
                for j in products:
                    q = q_ref[0, j] if pack == 1 else qx_sc[j]
                    if quant:
                        q = q.astype(jnp.float32)
                    part = jax.lax.dot_general(
                        q, heads(k_buf, slot, j),
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    if quant:
                        part = part * head_rows(ks, j)
                    s = part if s is None else s + part
                s = jnp.where(keep, s * scale, _NEG_INF)       # (M, span)
                m = m_sc[c]                                    # (M, 1)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                safe_m = jnp.where(m_new == _NEG_INF, 0.0, m_new)
                p = jnp.exp(s - safe_m)
                corr = jnp.where(m == _NEG_INF, 0.0, jnp.exp(m - safe_m))
                l_sc[c] = l_sc[c] * corr + jnp.sum(p, axis=-1, keepdims=True)
                m_sc[c] = m_new
                for j in products:
                    v = heads(v_buf, slot, j)
                    if quant:
                        pv = p * head_rows(vs, j)
                    else:
                        v = jnp.where(seen, v, 0).astype(v.dtype)
                        pv = p.astype(v.dtype)
                    acc_sc[j] = acc_sc[j] * corr + jax.lax.dot_general(
                        pv, v, dimension_numbers=(((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
            return 1 - slot

        jax.lax.fori_loop(first_group, groups, fold, first_slot)
        for h in range(n_kv_heads):
            l = l_sc[h // pack, own_rows(h), :]
            o_ref[0, h] = (acc_sc[h // width, own_rows(h), own_lanes(h)]
                           / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def walk_tiles(pos0, qlen, *, width: int, group: int, kv_heads: int,
               block_size: int, window=None, mask_block: int = 1):
    """The kernel's own rules on the host (numpy), a tile of ONE
    `_paged_call` an entry, (B, T) in the grid's order: `live`, where the
    tile holds a valid slot, and the table entries [`lo`, `hi`) its DMAs
    fetch: the whole blocks of `block_size` columns that hold a column in
    [lower, horizon), whatever a group's size. Rows at `pos0` with `qlen`
    new tokens each, in a call `width` slots wide with `group` query heads
    a KV head."""
    import numpy as np

    pos0, qlen = np.asarray(pos0), np.asarray(qlen)
    rows = _tile_geometry(width * group, kv_heads)[0]
    first = np.arange(-(-width * group // rows))[None, :] * rows     # (1, T)
    live = first < (qlen * group)[:, None]
    sees = pos0[:, None] + (first + rows - 1) // group + 1
    sees = -(-sees // mask_block) * mask_block     # its last row's block end
    horizon = np.minimum((pos0 + qlen)[:, None], sees)
    lower = np.zeros_like(horizon) if window is None else np.maximum(
        pos0[:, None] + first // group - (window - 1), 0)
    return live, lower // block_size, -(-horizon // block_size)


def walk_counts(pos0, qlen, *, block_size: int, **call):
    """(live tiles, warm tiles, tokens fetched) of one `_paged_call`
    (`walk_tiles`' arguments). A tile is WARM where the grid's step right
    before it, (b, t - 1) else the last tile of row b - 1, is live too and
    so started its first group. What the span of a tick says of its read
    (`runtime.scheduler`), and what the tests hold the kernel's DMAs to."""
    live, lo, hi = walk_tiles(pos0, qlen, block_size=block_size, **call)
    order = live.ravel()
    return (int(order.sum()), int((order[1:] & order[:-1]).sum()),
            int((hi - lo)[live].sum()) * block_size)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "window", "mask_block"))
def _paged_call(q, k_pool, v_pool, k_scale, v_scale, layer, tables, pos0,
                lengths, *, interpret: bool, window=None,
                mask_block: int = 1):
    """The one pallas_call behind every read path. q: (B, W, H, D);
    k_pool/v_pool: (L, NB, bs, H_kv*D), the whole pool as it lives on
    the device — an operand left in HBM, never reshaped or sliced;
    k_scale/v_scale: (L, NB, bs, H_kv) f32 or None (full-precision
    pool); layer: (1,) the layer read; tables: (B, nb); pos0: (B,)
    logical position of each row's first query slot; lengths: (B,) pos0
    + qlen. Returns (B, W, H, D) in q's dtype. The grid is the query
    tiles, (B, ceil(W*G / rows)), whatever the table's width. With
    `window` the call is a sliding-window layer's and carries its own
    name in a trace (`swa_window_read`), with `mask_block` > 1 a
    block-decoding model's (`block_mask_read`)."""
    if mask_block > 1 and window is not None:
        raise ValueError("a block-causal read takes no window")
    b, w, h, d = q.shape
    bs = k_pool.shape[2]
    h_kv = k_pool.shape[3] // d
    g = h // h_kv
    quant = k_scale is not None
    # (B, W, H, D) -> (B, H_kv, W*G, D): slot-major within each KV head so
    # score row r maps to query slot r//G (head order matches
    # dot_product_attention's grouping).
    r = w * g
    qh = (q.reshape(b, w, h_kv, g, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, h_kv, r, d))
    rows, pack, blocks = _tile_geometry(r, h_kv)
    width = _product_heads(pack, d)
    r_pad = pl.cdiv(r, rows) * rows
    if r_pad != r:
        # Zero rows past W*G: computed like padding slots, sliced off.
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, r_pad - r), (0, 0)))
    while (blocks > 1 and 4 * blocks * bs * k_pool.shape[3]
           * k_pool.dtype.itemsize > _KV_SCRATCH_BYTES):
        blocks //= 2
    blocks = min(blocks, tables.shape[1])
    # A whole number of groups: entries past the table's width point at
    # the null block (and lie past every horizon).
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % blocks)))
    n_groups = tables.shape[1] // blocks

    def q_map(b, t, tables, pos0, lengths, layer):
        # A dead tile names the row's last live one: no DMA for it.
        last = jnp.maximum((lengths[b] - pos0[b]) * g - 1, 0) // rows
        return (b, 0, jnp.minimum(t, last), 0)

    def tile_spec(index_map):
        return pl.BlockSpec((1, h_kv, rows, d), index_map)

    def table_scales(pool):
        """(L, NB, bs, H_kv) -> (B, groups, H_kv', span): the scales of
        each row's table, a group's tokens on the lanes; H_kv' a whole
        number of sublane tiles."""
        x = pool[layer[0], tables].reshape(b, n_groups, blocks * bs, h_kv)
        return jnp.pad(x.transpose(0, 1, 3, 2),
                       ((0, 0), (0, 0), (0, -h_kv % 8), (0, 0)))

    whole = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs, operands = [tile_spec(q_map), whole, whole], [qh, k_pool, v_pool]
    if quant:
        operands += [table_scales(k_scale), table_scales(v_scale)]
        in_specs += [pl.BlockSpec((1,) + operands[-1].shape[1:],
                                  lambda b, t, *_: (b, 0, 0, 0))] * 2
    kernel = functools.partial(
        _paged_kernel, block_size=bs, blocks=blocks,
        scale=1.0 / math.sqrt(d), group=g, pack=pack, width=width,
        quant=quant,
        window=window, **({"mask_block": mask_block} if mask_block > 1
                          else {}))
    m_rows = pack * rows
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,        # tables, pos0, lengths, layer
            grid=(b, r_pad // rows),
            in_specs=in_specs,
            out_specs=tile_spec(lambda b, t, *_: (b, 0, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, blocks, bs, h_kv * d), k_pool.dtype),
                pltpu.VMEM((2, blocks, bs, h_kv * d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ] + [pltpu.VMEM((h_kv // width, m_rows, width * d), q.dtype)
                 ] * (pack > 1) + [
                pltpu.VMEM((h_kv // pack, m_rows, 1), jnp.float32),
                pltpu.VMEM((h_kv // pack, m_rows, 1), jnp.float32),
                pltpu.VMEM((h_kv // width, m_rows, width * d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h_kv, r_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        **({"name": "swa_window_read"} if window is not None
           else {"name": "block_mask_read"} if mask_block > 1 else {}),
    )(tables, pos0, lengths, layer, *operands)
    return (out[:, :, :r].reshape(b, h_kv, w, g, d)
            .transpose(0, 2, 1, 3, 4).reshape(b, w, h, d))


def _paged(q, k_pool, v_pool, k_scale, v_scale, layer, tables, pos0, qlen,
           interpret, window=None, mask_block: int = 1):
    """Entry-point glue: `interpret=None` auto-selects (compiled on TPU,
    the Pallas interpreter elsewhere; a `pltpu.InterpretParams` asks for
    the TPU interpreter, which runs the DMAs and semaphores as such); host
    ints become int32 arrays."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    pos0 = jnp.asarray(pos0, jnp.int32)
    return _paged_call(q, k_pool, v_pool, k_scale, v_scale,
                       jnp.asarray(layer, jnp.int32).reshape(1),
                       jnp.asarray(tables, jnp.int32), pos0,
                       pos0 + jnp.asarray(qlen, jnp.int32),
                       interpret=interpret, window=window,
                       mask_block=mask_block)


def ragged_paged_attention(q, k_pool, v_pool, layer, tables, pos0, qlen, *,
                           window=None, mask_block: int = 1, interpret=None):
    """Pallas-kernel drop-in for `ragged_paged_attention_reference` (same
    signature/contract)."""
    return _paged(q, k_pool, v_pool, None, None, layer, tables, pos0, qlen,
                  interpret, window, mask_block)


def quant_ragged_paged_attention(q, k_pool, v_pool, k_scale, v_scale, layer,
                                 tables, pos0, qlen, *, interpret=None):
    """Pallas-kernel drop-in for `quant_ragged_paged_attention_reference`
    (same signature/contract): the block DMAs are int8, about half the
    bf16 bytes, and the scales are applied in VMEM."""
    return _paged(q, k_pool, v_pool, k_scale, v_scale, layer, tables, pos0,
                  qlen, interpret)


def ragged_read_by_class(attn_fn, q, pool, layer, tables, pos0, classes,
                         base, row, slot, **read):
    """The ragged read of a tick's token LIST, each row by the class of
    its run (`ops.latent_attention.class_plan`). q: (M, H, D), row b's new
    token s at list index base[b] + s, and list entry m is slot `slot[m]`
    of row `row[m]`; pool: the (k, v) pair; tables: (B, nb); pos0: (B,).
    The SHORT rows' one token goes through `attn_fn` (a ragged read path
    above) as (B, 1, H, D), a ROW a row of the call, which is a
    decode-only tick's call: `_tile_geometry` packs the heads. Where the
    plan's short class is a RUN of up to S slots (`classes.runs`: a
    block-decoding model's block), the call is (B, S, H, D), S x G query
    rows a KV head and row, packed the same way. The TALL
    tiles go through it as (n_tall, T, H, D), a TILE a row of the call
    with its own first column, q_len and table row. No (B, W, ...) operand
    is made. `read`: what every call of `attn_fn` takes besides
    (`mask_block`). Returns (M, H, D); an entry that holds no token is
    garbage by contract."""
    m = q.shape[0]
    tall = classes.tall
    if classes.runs is None:
        short = classes.short.astype(jnp.int32)
        o = attn_fn(q[jnp.minimum(base, m - 1)][:, None], *pool, layer,
                    tables, pos0, short, **read)[:, 0]           # (B, H, D)

        def listed():
            return o[row]
    else:
        s = classes.run_slots
        at = jnp.minimum(base[:, None] + jnp.arange(s)[None, :], m - 1)
        o = attn_fn(q[at], *pool, layer, tables, pos0, classes.runs,
                    **read)                                   # (B, S, H, D)

        def listed():
            return o[row, jnp.minimum(slot, s - 1)]
    if tall is None:
        return listed()
    height = classes.slot.shape[1]
    at = jnp.minimum(base[tall.row][:, None] + classes.slot, m - 1)
    o_tall = attn_fn(q[at], *pool, layer, tables[tall.row],
                     pos0[tall.row] + tall.tile * height,
                     classes.valid.sum(-1).astype(jnp.int32), **read)
    tile = jnp.minimum(tall.start[row] + slot // height,
                       tall.row.shape[0] - 1)
    return jnp.where(classes.short[row][:, None, None], listed(),
                     o_tall[tile, slot % height])


# The two read paths: name -> (kernel entry point, XLA reference). The
# selectors, the start-up banner and the parity checks all read this.
READ_PATHS = {
    "ragged": (ragged_paged_attention, ragged_paged_attention_reference),
    "quant_ragged": (quant_ragged_paged_attention,
                     quant_ragged_paged_attention_reference),
}


def shard_over_heads(attn_fn, mesh, axis: str = "model"):
    """Tensor-parallel wrapper for any read path above (kernel or
    reference): run `attn_fn` once per head shard under `jax.shard_map`.
    Heads are independent, so there is no collective inside, and a
    Mosaic kernel — which GSPMD refuses to partition — sees only its
    local heads. q (B, W, H, D), the first operand, shards its heads on
    axis 2; the pool tensors (L, NB, bs, H_kv*D) and scales
    (L, NB, bs, H_kv) shard their last axis, whole heads to a shard
    (`BlockPool` requires H_kv % tp == 0); the layer index, the tables
    and the per-row vectors replicate."""
    def spec(i, x):
        if x.ndim != 4:
            return P()
        return P(None, None, axis, None) if i == 0 \
            else P(None, None, None, axis)

    def sharded(*args):
        return jax.shard_map(
            attn_fn, mesh=mesh,
            in_specs=tuple(spec(i, a) for i, a in enumerate(args)),
            out_specs=spec(0, args[0]), check_vma=False)(*args)

    return sharded


_PAGED_CACHE = {}


def _select_impl(kind: str):
    """One `TPU_ENGINE_PAGED` selection rule for both read paths —
    "1" forces the Pallas kernel (interpreter off-TPU — slow, for parity
    tests), "0" forces the XLA gather reference, unset/"auto" picks the
    kernel on TPU only."""
    import os

    mode = os.environ.get("TPU_ENGINE_PAGED", "auto")
    key = (kind, mode)
    fn = _PAGED_CACHE.get(key)
    if fn is None:
        kernel_fn, reference_fn = READ_PATHS[kind]
        if mode == "1" or (mode == "auto"
                           and jax.default_backend() == "tpu"):
            fn = kernel_fn
        else:
            fn = reference_fn
        _PAGED_CACHE[key] = fn
    return fn


def default_ragged_attention():
    """Serving-path paged-attention selection, one rule with
    `models.transformer.default_attention` (see `_select_impl`)."""
    return _select_impl("ragged")


def default_quant_ragged_attention():
    """Quantized-path selection (int8 pool, --kv-quantize) — the same
    `TPU_ENGINE_PAGED` knob and rule as the bf16 path."""
    return _select_impl("quant_ragged")


def selected_implementations() -> dict:
    """{read path: "pallas" | "xla"} as the serving path will run it in
    this process — what the start-up banner prints, so a lane that
    quietly serves the gather reference on a TPU is visible in the log."""
    return {kind: "pallas" if _select_impl(kind) is kernel_fn else "xla"
            for kind, (kernel_fn, _) in READ_PATHS.items()}


# Layers of a parity workload's pool, and the one read: not the first, so
# a read path that ignored `layer` misses parity.
_PARITY_LAYERS, _PARITY_LAYER = 2, 1


def parity_workload(kind: str, q_lens, *, n_heads: int, n_kv_heads: int,
                    d_head: int, block_size: int, n_blocks: int,
                    table_len: int, dtype, seed: int = 0, pos0=None,
                    window=None):
    """One random workload for the `READ_PATHS[kind]` pair, one row per
    entry of `q_lens`: (operands, qlen). The pool has two layers, both
    random, and the second is read. Rows get distinct
    shuffled tables and ragged positions so the skip/mask paths are
    exercised; with `pos0` (one column a row) the positions are those,
    and a row's table past its own pos0 + q_len columns is the null
    block, as the scheduler leaves it; with `window`, so is its table
    behind the first column its first new token still sees (the blocks a
    sliding-window layer gave back). Traceable (`jax.eval_shape` gives
    the operand shapes without generating them — ops.kernel_check
    AOT-compiles from those). Shared by the parity checks below and
    ops.kernel_check."""
    import numpy as np

    quant = kind.startswith("quant")
    rng = np.random.default_rng(seed)
    batch = len(q_lens)
    w = max(q_lens)
    shape = (_PARITY_LAYERS, n_blocks, block_size, n_kv_heads * d_head)
    if quant:
        # The int8 pool + f32 scales come from quantizing a random f32
        # pool with the ONE production write path, so parity inputs
        # carry exactly the value distribution serving writes.
        from tpu_engine.ops.quant import quantize_kv

        kq, kpool = jax.random.split(jax.random.PRNGKey(seed), 2)
        pool, scales = zip(*(
            quantize_kv(jax.random.normal(
                key, shape[:3] + (n_kv_heads, d_head)))
            for key in jax.random.split(kpool, 2)))
        pool = tuple(x.reshape(shape) for x in pool) + scales
    else:
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
        pool = (jax.random.normal(kk, shape, dtype),
                jax.random.normal(kv, shape, dtype))
    q = jax.random.normal(kq, (batch, w, n_heads, d_head), dtype)
    tables = np.zeros((batch, table_len), np.int32)
    placed, pos0 = pos0, np.zeros((batch,), np.int32)
    for r, ql in enumerate(q_lens):
        tables[r] = 1 + rng.permutation(n_blocks - 1)[:table_len]
        if placed is None:
            # Row history + this chunk must fit the table.
            pos0[r] = int(rng.integers(0, table_len * block_size - ql + 1))
        else:
            pos0[r] = placed[r]
            tables[r, -(-(placed[r] + ql) // block_size):] = 0
            if window is not None:
                tables[r, :max(placed[r] - window + 1, 0) // block_size] = 0
    qlen = jnp.asarray(np.asarray(q_lens, np.int32))
    return (q, *pool, jnp.int32(_PARITY_LAYER), jnp.asarray(tables),
            jnp.asarray(pos0), qlen), qlen


def reference_gap(reference_fn, out, operands, qlen):
    """Max |out - reference| over the VALID query slots (slot <
    qlen[row]; padding slots are garbage by contract), a traceable
    scalar. The reference runs on f32 copies of the operands at the
    highest matmul precision, so on a TPU the distance measures the
    kernel, not the reference's own bf16 passes (a no-op for the f32 CPU
    parity tests)."""
    f32 = tuple(x.astype(jnp.float32)
                if jnp.issubdtype(x.dtype, jnp.floating) else x
                for x in operands)
    with jax.default_matmul_precision("highest"):
        ref = reference_fn(*f32)
    diff = jnp.abs(out.astype(jnp.float32) - ref)
    valid = jnp.arange(out.shape[1])[None, :] < qlen[:, None]
    return jnp.max(jnp.where(valid[:, :, None, None], diff, 0.0))


def reference_error(reference_fn, out, operands, qlen) -> float:
    """`reference_gap` as a host float."""
    return float(reference_gap(reference_fn, out, operands, qlen))


def _parity(kind: str, q_lens, *, interpret, mask_block: int = 1,
            **shape) -> float:
    """Max |kernel - reference| over one `parity_workload`."""
    kernel_fn, reference_fn = READ_PATHS[kind]
    operands, qlen = parity_workload(kind, q_lens, **shape)
    if mask_block > 1:
        kernel_fn, reference_fn = (
            functools.partial(fn, mask_block=mask_block)
            for fn in (kernel_fn, reference_fn))
    if shape.get("window") is not None:
        kernel_fn, reference_fn = (
            functools.partial(fn, window=shape["window"])
            for fn in (kernel_fn, reference_fn))
    return reference_error(reference_fn,
                           kernel_fn(*operands, interpret=interpret),
                           operands, qlen)


# What the tile walk can get wrong, one workload each: name -> (q_lens,
# pos0, table_len) at block size 16 with 8 query heads over 2 KV heads of
# 16 lanes (G = 4). A width-1 call packs both heads into one 8-row score
# tile and walks 8-block groups (128 columns); a wider one tiles 128
# query rows (32 slots) a head and walks 16-block groups (256 columns).
WALK_CASES = {
    # A row with no new token between live rows: its tiles write zeros
    # and the rows around it are read as before.
    "dead-row-between-live-rows": ((1, 0, 7, 0, 1), (37, 9, 20, 50, 3), 4),
    # A decode row inside a wide tick: ONE live tile, 301 columns (two
    # groups), beside a 40-slot chunk of two tiles.
    "decode-row-in-wide-tick": ((1, 40, 1), (300, 9, 0), 20),
    # Contexts that end exactly on a group boundary (256, 512), one
    # column past it (257) and inside the first block (5).
    "ends-on-group-boundary": ((40, 40, 40, 3), (216, 217, 472, 2), 32),
    # The same at width 1 (groups of 128 columns): 128, 129, 256, 6.
    "ends-on-group-boundary-width-1": ((1, 1, 1, 1), (127, 128, 255, 5),
                                       16),
    # A table four times wider than the longest row (57 columns = 4
    # blocks of 16): everything past a row's own blocks is the null block.
    "table-four-times-wider": ((1, 17, 5), (30, 40, 0), 16),
    "table-four-times-wider-width-1": ((1, 1, 1), (30, 56, 0), 16),
    # A 64-slot chunk at G = 4 is two tiles of 32 slots; the first one's
    # horizon (272) already crosses the group boundary at 256.
    "chunk-tiles-straddle-a-group": ((64, 1), (240, 255), 20),
    # What the pipeline over the call's steps can get wrong. Width 1: a
    # live row fetches for the live row after it and for no dead one; a
    # row of one group leaves its neighbour the other buffer, a row of two
    # groups (201, 131 columns) the first again.
    "neighbours-dead-and-live-width-1": ((1, 0, 1, 1, 0, 1),
                                         (37, 9, 200, 130, 50, 3), 16),
    # The call's last step is live and warm: it starts no copy at all.
    "live-last-row-width-1": ((1, 1, 1), (10, 300, 140), 20),
    # Rows of exactly 1, 128 and 129 columns: one block, a whole group (the
    # straight-line fetch), one column into the second group.
    "rows-of-1-128-129-columns": ((1, 1, 1, 1), (0, 127, 128, 0), 16),
    # A horizon on a block's edge (16, 144 columns) and one past it (17,
    # 145): the last block fetched holds 16 columns, or one.
    "horizon-on-a-block-edge-width-1": ((1, 1, 1, 1), (15, 16, 143, 144),
                                        16),
    "horizon-on-a-block-edge": ((40, 40), (8, 9), 8),
    # Tiles of 32 slots: a tall tile, then the same row's dead tile (no
    # copy started for it, none for the row behind it: that row's first
    # tile is cold, its second warm), then a decode row's one live tile.
    "tall-tile-dead-tile-live-row": ((20, 64, 1), (300, 40, 500), 36),
}


def walk_parity_check(kind: str, case: str, *, interpret=None,
                      dtype=jnp.float32, seed: int = 0) -> float:
    """Max |kernel - reference| of `READ_PATHS[kind]` over one of
    `WALK_CASES`."""
    q_lens, pos0, table_len = WALK_CASES[case]
    return _parity(kind, q_lens, n_heads=8, n_kv_heads=2, d_head=16,
                   block_size=16, n_blocks=1 + len(q_lens) * table_len,
                   table_len=table_len, dtype=dtype, seed=seed, pos0=pos0,
                   interpret=interpret)


# The walk's other end, one workload each: name -> (q_lens, pos0, window,
# table_len) at block size 16 over 2 KV heads of 16 lanes. Run at G = 6
# and G = 9 (12 and 18 query heads), the two group sizes of a model whose
# window layers carry more query heads than its full ones; neither is a
# power of two, so a score tile's rows do not end on a slot.
WINDOW_CASES = {
    # Width 1 (heads packed, groups of 128 columns): a row still inside
    # its window, one on its edge, one past it, one whose walk starts five
    # groups in; 40 is not a whole number of blocks.
    "decode-rows-around-the-edge": ((1, 1, 1, 1), (5, 39, 40, 700), 40, 48),
    # Width 256: a first chunk (the window opens inside it), a later chunk
    # and a decode row in the same tick.
    "chunk-across-the-edge": ((256, 1, 100), (0, 300, 500), 40, 48),
    # A window wider than a group of the walk (256 columns): two or three
    # groups are read, the first and the last partly.
    "window-wider-than-a-group": ((64, 1), (600, 650), 300, 48),
    # A window of 700 columns: a chunk's tiles walk three or four groups
    # of 256, a decode row's packed tile six of 128, each from a lower
    # bound inside its first group; the table behind it is null.
    "window-wider-than-several-groups": ((64, 1), (900, 1040), 700, 72),
    # Tiles of eight slots as rows of the call, as a step over the tick's
    # tokens hands them over: any pos0, a short last tile.
    "tiles-of-eight-slots": ((8, 8, 1, 5), (0, 37, 520, 100), 40, 48),
    # A tile fetches its neighbour's first group by the NEIGHBOUR's lower
    # bound: walks that start five, zero, four and two groups in, side by
    # side (width 1), and the same between tiles of eight slots.
    "first-groups-differ-between-neighbours": (
        (1, 1, 1, 1), (700, 5, 650, 300), 40, 48),
    "first-groups-differ-between-tiles-of-eight": (
        (8, 8, 8), (700, 0, 333), 40, 48),
}


def window_parity_check(case: str, group: int, *, interpret=None,
                        dtype=jnp.float32, seed: int = 0) -> float:
    """Max |kernel - reference| of the ragged read with a lower bound
    over one of `WINDOW_CASES`, `group` query heads a KV head."""
    q_lens, pos0, window, table_len = WINDOW_CASES[case]
    return _parity("ragged", q_lens, n_heads=2 * group, n_kv_heads=2,
                   d_head=16, block_size=16,
                   n_blocks=1 + len(q_lens) * table_len,
                   table_len=table_len, dtype=dtype, seed=seed, pos0=pos0,
                   window=window, interpret=interpret)


def class_read(q, k_pool, v_pool, layer, tables, pos0, qlen, *, width: int,
               max_tokens=None, attn_fn=None, interpret=None,
               mask_block: int = 1, window=None):
    """`ragged_read_by_class` over a token list laid out a slot a tile, as
    `models.olmo_hybrid` lays its tick out: q (M, H, D), the rows' new
    tokens side by side in row order (`class_workload`). With `mask_block`
    L > 1 the read is a block-decoding model's: the block mask, and runs of
    up to L tokens the short class; with `window` a sliding-window
    layer's, both classes' calls handed the lower bound
    (`models.smallthinker`). Returns (M, H, D). What the parity checks and
    `ops.kernel_check` run."""
    from tpu_engine.ops import latent_attention as la

    if attn_fn is None:
        attn_fn = functools.partial(ragged_paged_attention,
                                    interpret=interpret)
    plan = la.tile_plan(qlen, 1, q.shape[0])
    group = q.shape[1] * q.shape[2] // k_pool.shape[3]
    read = {"mask_block": mask_block} if mask_block > 1 else {}
    if window is not None:
        read["window"] = window
    return ragged_read_by_class(
        attn_fn, q, (k_pool, v_pool), layer, tables, pos0,
        la.class_plan(qlen, width, group, max_tokens, run_slots=mask_block),
        plan.start, plan.row, plan.tile, **read)


def class_workload(q_lens, pos0, *, width: int, max_tokens=None, **shape):
    """`parity_workload("ragged", ...)` with its queries as the tick's
    token list: (q (M, H, D), pools, layer, tables, pos0, qlen), M the
    list's static length for a step of `width` slots a row."""
    from tpu_engine.ops import latent_attention as la

    (q, *rest), qlen = parity_workload("ragged", q_lens, pos0=pos0, **shape)
    plan = la.tile_plan(
        qlen, 1, la.tiles_bound(len(q_lens), width, 1, max_tokens))
    return (q[plan.row, jnp.minimum(plan.tile, q.shape[1] - 1)], *rest)


def class_read_error(out, operands, mask_block: int = 1,
                     window=None) -> float:
    """Max |out - reference| over the list entries that hold a token: the
    gather reference on the WHOLE batch, a row a row, in f32
    (`reference_error`)."""
    from tpu_engine.ops import latent_attention as la

    q, *rest, qlen = operands
    plan = la.tile_plan(qlen, 1, q.shape[0])
    width = max(int(qlen.max()), 1)
    listed = jnp.minimum(plan.start[:, None] + jnp.arange(width)[None, :],
                         q.shape[0] - 1)
    return reference_error(
        functools.partial(ragged_paged_attention_reference,
                          mask_block=mask_block, window=window),
        out[listed], (q[listed], *rest, qlen), qlen)


# What the two-class plan can get wrong, one tick each: name -> (q_lens,
# pos0, max_tokens) in a step of 256 slots a row at block size 16 under a
# table of 24 blocks. Run at one and at four query heads a KV head (tall
# tiles of 128 and of 32 slots).
CLASS_CASES = {
    "every-row-short": ((1, 1, 1, 1), (37, 0, 301, 128), None),
    "a-run-of-1-beside-short-rows": ((1, 1, 0, 1), (5, 300, 0, 77), None),
    "a-run-of-127-beside-short-rows": ((1, 127, 1), (40, 3, 200), None),
    "a-run-of-128-beside-short-rows": ((1, 128, 1), (40, 100, 200), None),
    "a-run-of-129-beside-short-rows": ((129, 1, 1), (64, 300, 9), None),
    "a-run-of-256-beside-short-rows": ((1, 256, 1), (40, 128, 383), None),
    # A row with no new token between live rows of both classes.
    "a-dead-row-between-live-rows": ((1, 0, 40, 0, 1), (37, 9, 20, 50, 3),
                                     None),
    # The tail of one prompt and the head of the next in one tick.
    "two-tall-runs-in-one-tick": ((1, 70, 1, 150), (90, 260, 17, 0), None),
    # The list is full: every one of `max_tokens` slots holds a token.
    "max-tokens-reached-exactly": ((1, 200, 1, 58), (12, 100, 300, 0), 260),
    # Runs of two and three tokens: too long for the short class, a tall
    # tile with a few live slots each.
    "runs-just-past-short": ((2, 1, 3, 1), (0, 33, 126, 255), None),
    # Both calls hold dead steps between live ones: the short call's first
    # two rows (a tall run, a free slot), the tall call's every tile past
    # the run's own (two of 128 slots at one head a KV head, five of 32 at
    # four).
    "a-tall-run-a-dead-row-then-short-rows": ((130, 0, 1, 1),
                                              (20, 0, 300, 77), None),
}


def class_parity_check(case: str, group: int, *, interpret=None,
                       dtype=jnp.float32, seed: int = 0) -> float:
    """Max |short + tall reads - reference| over one of `CLASS_CASES`,
    `group` query heads a KV head."""
    q_lens, pos0, max_tokens = CLASS_CASES[case]
    operands = class_workload(
        q_lens, pos0, width=256, max_tokens=max_tokens, n_heads=2 * group,
        n_kv_heads=2, d_head=16, block_size=16,
        n_blocks=1 + len(q_lens) * 24, table_len=24, dtype=dtype, seed=seed)
    return class_read_error(
        class_read(*operands, width=256, max_tokens=max_tokens,
                   interpret=interpret), operands)


# The two classes under a window, one tick each: name -> (q_lens, pos0,
# window, max_tokens) in a step of 256 slots a row at block size 16 under a
# table of 24 blocks, the table behind each row's window null as the
# scheduler leaves it. Run at seven query heads a KV head (a group coprime
# to the 128-row tile: tall tiles of 128 slots, seven tiles of the call's
# grid whose edges a slot's heads straddle).
WINDOW_CLASS_CASES = {
    # A first chunk, two tall tiles: the window (100) opens inside the
    # first. Beside it decode rows past their window (null blocks behind
    # them), short of it and on its edge.
    "a-chunk-the-window-opens-in-beside-decode-rows-around-the-edge": (
        (256, 1, 1, 1), (0, 370, 30, 99), 100, None),
}


def window_class_parity_check(case: str, group: int = 7, *, interpret=None,
                              dtype=jnp.float32, seed: int = 0) -> float:
    """Max |short + tall reads - reference| with a lower bound over one of
    `WINDOW_CLASS_CASES`, `group` query heads a KV head."""
    q_lens, pos0, window, max_tokens = WINDOW_CLASS_CASES[case]
    operands = class_workload(
        q_lens, pos0, width=256, max_tokens=max_tokens, n_heads=2 * group,
        n_kv_heads=2, d_head=16, block_size=16,
        n_blocks=1 + len(q_lens) * 24, table_len=24, dtype=dtype, seed=seed,
        window=window)
    return class_read_error(
        class_read(*operands, width=256, max_tokens=max_tokens,
                   interpret=interpret, window=window), operands,
        window=window)


# What the block-causal mask can get wrong, one workload each: name ->
# (q_lens, pos0, table_len) at block size 16 and blocks of 4 positions,
# sixteen query heads over 2 KV heads of 16 lanes (G = 8, the SDAR cell's).
# A call 4 slots wide packs both heads' 32 query rows into one score tile
# and walks 8-block groups; a wider one tiles 16 slots (128 query rows).
BLOCK_MASK_CASES = {
    # Runs of 4 at block starts: a first block, mid-context, on a group's
    # edge (the horizon 128 and 132 columns), a dead row between them.
    "runs-of-4": ((4, 4, 0, 4, 4), (0, 36, 9, 124, 128), 16),
    # Chunks in tall tiles: 64 slots are four tiles; one starts a prompt,
    # one continues it behind 240 columns; a run of 4 in the same wide call.
    "chunks-in-tall-tiles": ((64, 16, 4), (0, 240, 500), 36),
    # A chunk whose last block is cut by q_len (the row's tokens end
    # mid-block: no query sees a column at or past pos0 + q_len) and runs
    # that start mid-block: the mask is of POSITIONS, not of slots.
    "not-aligned-to-blocks": ((6, 3, 21), (0, 37, 130), 16),
    # The causal mask's own case under the same geometry: blocks of 1.
    "blocks-of-one-are-causal": ((4, 17, 1), (36, 3, 200), 16),
}


def block_mask_parity_check(case: str, *, interpret=None,
                            dtype=jnp.float32, seed: int = 0) -> float:
    """Max |kernel - reference| of the ragged read under the block-causal
    mask over one of `BLOCK_MASK_CASES`."""
    q_lens, pos0, table_len = BLOCK_MASK_CASES[case]
    return _parity("ragged", q_lens, n_heads=16, n_kv_heads=2, d_head=16,
                   block_size=16, n_blocks=1 + len(q_lens) * table_len,
                   table_len=table_len, dtype=dtype, seed=seed, pos0=pos0,
                   mask_block=1 if case.startswith("blocks-of-one") else 4,
                   interpret=interpret)


# The run class beside the tall class, one tick each: name -> (q_lens, pos0,
# max_tokens) in a step of 64 slots a row at block size 16 under a table of
# 24 blocks, every q_len and pos0 a multiple of 4 as a block-decoding lane
# forms them.
BLOCK_CLASS_CASES = {
    "every-row-a-run": ((4, 4, 4, 4), (36, 0, 300, 128), None),
    "runs-beside-a-chunk": ((4, 64, 4, 0, 4), (40, 128, 200, 0, 8), None),
    "a-chunk-of-one-block-is-a-run": ((4, 48, 4), (0, 16, 252), None),
    "the-list-is-full": ((4, 60, 4, 16), (12, 100, 300, 0), 84),
}


def block_class_parity_check(case: str, *, interpret=None,
                             dtype=jnp.float32, seed: int = 0) -> float:
    """Max |run + tall reads - reference| under the block mask over one of
    `BLOCK_CLASS_CASES`, eight query heads a KV head."""
    q_lens, pos0, max_tokens = BLOCK_CLASS_CASES[case]
    operands = class_workload(
        q_lens, pos0, width=64, max_tokens=max_tokens, n_heads=16,
        n_kv_heads=2, d_head=16, block_size=16,
        n_blocks=1 + len(q_lens) * 24, table_len=24, dtype=dtype, seed=seed)
    return class_read_error(
        class_read(*operands, width=64, max_tokens=max_tokens,
                   interpret=interpret, mask_block=4), operands,
        mask_block=4)


def ragged_parity_check(q_lens=(1, 7, 16, 17), n_heads: int = 4,
                        n_kv_heads: int = 2, d_head: int = 8,
                        block_size: int = 16, n_blocks: int = 33,
                        table_len: int = 6, dtype=jnp.float32,
                        seed: int = 0, interpret=None) -> float:
    """Ragged-path parity: mixed decode (q_len 1) rows and prefill-chunk
    rows in the same batch, the ragged tick's shape; `q_lens` all 1 is
    a decode-only tick's packed one-query call. Shared by
    tests/test_mixed_step.py, diagnostics.py --kernel-parity /
    --mixed-parity and chip_smoke.py's kernel phase."""
    return _parity("ragged", tuple(q_lens), n_heads=n_heads,
                   n_kv_heads=n_kv_heads, d_head=d_head,
                   block_size=block_size, n_blocks=n_blocks,
                   table_len=table_len, dtype=dtype, seed=seed,
                   interpret=interpret)


def quant_ragged_parity_check(q_lens=(1, 7, 16, 17), n_heads: int = 4,
                              n_kv_heads: int = 2, d_head: int = 8,
                              block_size: int = 16, n_blocks: int = 33,
                              table_len: int = 6, dtype=jnp.float32,
                              seed: int = 0, interpret=None) -> float:
    """`ragged_parity_check` for the QUANTIZED ragged path (mixed decode
    + prefill-chunk rows over the int8 pool, the --kv-quantize serving
    shape). Shared by tests/test_kv_quant.py, diagnostics.py
    --quant-parity and chip_smoke.py's kernel phase."""
    return _parity("quant_ragged", tuple(q_lens), n_heads=n_heads,
                   n_kv_heads=n_kv_heads, d_head=d_head,
                   block_size=block_size, n_blocks=n_blocks,
                   table_len=table_len, dtype=dtype, seed=seed,
                   interpret=interpret)


def spec_verify_parity_check(k: int = 4, **kw) -> float:
    """Ragged parity at the SPECULATIVE verify-window shapes the
    --spec-k scheduler dispatches each tick: an undrafted decode row
    (q_len 1), two full verify windows (q_len k+1 — one of them placed
    to cross a block boundary by the random pos0 draw), and prefill-
    chunk rows at the block size and one past it, all in ONE ragged
    batch. Shared by tests and diagnostics.py --spec-parity."""
    return ragged_parity_check(q_lens=(1, k + 1, k + 1, 16, 17), **kw)
