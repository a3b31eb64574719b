"""Paged attention: queries over block-pooled KV.

The paged KV cache (runtime.kv_blocks) stores every row's keys/values in
fixed-size blocks of a shared pool instead of one dense per-row stripe;
a per-row **block table** maps logical column `c` to pool block
`table[c // bs]`, offset `c % bs`. This module is the attention read
side of that layout. Four read paths share one contract each with an
XLA reference:

- decode (`paged_attention`): one query token per row;
- ragged (`ragged_paged_attention`): q_len >= 1 per row — the mixed
  scheduler (--mixed-step) serves decode rows (one token) and admitting
  rows (a prefill chunk) in ONE dispatch, with causal masking inside
  each row's new-token window (query slot i attends kpos <= pos0 + i);
- the two int8-pool variants (`quant_*`, --kv-quantize int8), which
  apply the per-slot scales inside the read.

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

The kernel side is ONE Pallas TPU kernel (`_paged_kernel`) behind all
four entry points — decode is the ragged read at q_len 1, and the pool
dtype is a static flag. Grid (B, row tiles, n_blocks) with the block
axis sequential; each step DMAs ONE whole physical block — all KV heads,
a (bs, H_kv*D) tile of the pool, the same bytes in the same order as the
(bs, H_kv, D) block of the chain wire format — chosen by the layer index
and the block table via scalar prefetch (the index map reads
`(layer[0], tables[b, j])`; neither the layer nor the gather ever
materializes). A static loop over KV heads slices each head's (bs, D)
lanes and folds it into running flash accumulators (f32 max /
denominator / weighted sum in VMEM scratch). Blocks entirely past what
the row tile can see are skipped with `pl.when`, so a short row in a
long-table batch costs only its own blocks — the ragged-batch win the
TPU paged-attention kernel exists for (PAPERS.md "Ragged Paged
Attention").

Query slots stack with the group heads on the sublane axis: q is laid
out (B, H_kv, W*G, D) with G = n_heads/kv_heads (row r = slot r//G,
head r%G), tiled `_ROW_TILE` rows at a time, so VMEM holds
O(H_kv * _ROW_TILE * D) whatever W*G is.

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


def _decode_reference(q, k_pool, v_pool, k_scale, v_scale, layer, tables,
                      pos_vec):
    kk, vv = _dense_rows(q, k_pool, v_pool, k_scale, v_scale, layer, tables)
    kpos = jnp.arange(kk.shape[1])[None, :]
    valid = (kpos <= pos_vec[:, None]).astype(jnp.int32)
    return dot_product_attention(q, kk, vv, mask=valid)


def _ragged_reference(q, k_pool, v_pool, k_scale, v_scale, layer, tables,
                      pos0):
    kk, vv = _dense_rows(q, k_pool, v_pool, k_scale, v_scale, layer, tables)
    kpos = jnp.arange(kk.shape[1])
    qpos = pos0[:, None] + jnp.arange(q.shape[1])[None, :]     # (B, W)
    valid = (kpos[None, None, :] <= qpos[:, :, None]).astype(jnp.int32)
    return dot_product_attention(q, kk, vv, mask=valid)


def paged_attention_reference(q, k_pool, v_pool, layer, tables, pos_vec):
    """XLA gather path. q: (B, 1, H, D); k_pool/v_pool:
    (L, NB, bs, H_kv*D), the whole pool; layer: int32 scalar, the layer
    read; tables: (B, nb) int32 block ids (0 = the reserved null block —
    its columns must be masked by `pos_vec`); pos_vec: (B,) last valid
    logical column per row (columns kpos <= pos are attended). Returns
    (B, 1, H, D)."""
    return _decode_reference(q, k_pool, v_pool, None, None, layer, tables,
                             pos_vec)


# -- ragged (mixed prefill+decode) reference ---------------------------------
#
# The mixed scheduler (runtime.scheduler, --mixed-step) folds admission
# prefill into the decode dispatch: one ragged batch where decode rows
# contribute ONE new token and admitting rows contribute a prefill chunk
# of up to W tokens (PAPERS.md "Ragged Paged Attention"). Row b's query
# slot i sits at logical position pos0[b] + i and attends causally within
# its own history (kpos <= pos0[b] + i); slots i >= qlen[b] are padding
# whose output the scheduler ignores.


def ragged_paged_attention_reference(q, k_pool, v_pool, layer, tables, pos0,
                                     qlen):
    """XLA gather path, ragged queries. q: (B, W, H, D);
    k_pool/v_pool: (L, NB, bs, H_kv*D); layer: int32 scalar; tables:
    (B, nb) int32 block ids; pos0: (B,) logical position of each row's
    FIRST query slot; qlen: (B,) valid query slots (padding slots
    produce garbage the caller must ignore — masking them costs more
    than ignoring). Returns (B, W, H, D)."""
    del qlen  # padding slots are ignored by contract, not masked
    return _ragged_reference(q, k_pool, v_pool, None, None, layer, tables,
                             pos0)


# -- quantized (int8 block pool) references ----------------------------------
#
# The quantized pool (runtime.kv_blocks, --kv-quantize int8) stores block
# payloads int8 with one f32 scale per (block slot, kv-head) vector per
# layer. Both sides dequantize the same way (int8 -> f32 is exact, times
# the slot's f32 scale) — the reference on its gathered copy, the kernel
# per streamed block in VMEM, so the dequantized pool never materializes
# in HBM — and rounding error comes only from the one-time int8 write at
# block-fill time.


def quant_paged_attention_reference(q, k_pool, v_pool, k_scale, v_scale,
                                    layer, tables, pos_vec):
    """`paged_attention_reference` over the int8 pool. k_pool/v_pool:
    (L, NB, bs, H_kv*D) int8; k_scale/v_scale: (L, NB, bs, H_kv) f32.
    The gathered view dequantizes to f32 (exact: int8 * f32 scale), then
    the identical dense attention math runs."""
    return _decode_reference(q, k_pool, v_pool, k_scale, v_scale, layer,
                             tables, pos_vec)


def quant_ragged_paged_attention_reference(q, k_pool, v_pool, k_scale,
                                           v_scale, layer, tables, pos0,
                                           qlen):
    """`ragged_paged_attention_reference` over the int8 pool (same
    contract; padding slots produce garbage the caller ignores)."""
    del qlen
    return _ragged_reference(q, k_pool, v_pool, k_scale, v_scale, layer,
                             tables, pos0)


# -- the kernel (all four read paths) -----------------------------------------


def _paged_kernel(tables_ref, pos0_ref, lengths_ref, layer_ref, q_ref, k_ref,
                  v_ref, *rest, block_size: int, scale: float, group: int,
                  n_kv_heads: int, d_head: int, quant: bool):
    """One (row, row-tile, block) grid step over ALL KV heads.
    q_ref/o_ref (1, H_kv, T, D) — T query rows of the row's W*G (row
    r = slot r//G, head r%G); k_ref/v_ref (1, bs, H_kv*D) — the physical
    block the index map picked by `layer_ref` and the table (only the
    index maps read `layer_ref`), head h in lanes [h*D, (h+1)*D);
    quantized pools add ks_ref/vs_ref (1, bs, H_kv) f32.
    Scratch (m/l: (H_kv, T, 1), acc: (H_kv, T, D), f32) carries the
    online softmax across the sequential block axis; the statistics
    stay (T, 1) columns so no step moves a vector between lanes and
    sublanes. Causal masking within the new-token window: score row r
    keeps kpos <= pos0 + r//G."""
    del layer_ref
    if quant:
        ks_ref, vs_ref, o_ref, m_sc, l_sc, acc_sc = rest
    else:
        o_ref, m_sc, l_sc, acc_sc = rest
    b = pl.program_id(0)
    t = pl.program_id(1)
    j = pl.program_id(2)
    nb = pl.num_programs(2)
    rows = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    pos0 = pos0_ref[b]
    # Columns this tile's LAST query row can see, capped at the row's
    # pos0 + qlen: later blocks are fully masked for every row here.
    horizon = jnp.minimum(lengths_ref[b],
                          pos0 + (t * rows + rows - 1) // group + 1)

    # Blocks wholly past the horizon do no work at all — a decode row
    # (q_len 1) in a batch with a wide prefill chunk costs only its own
    # history's blocks, and a chunk's early row tiles skip its late
    # columns.
    @pl.when(j * block_size < horizon)
    def _live_block():
        shape = (rows, block_size)
        kpos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        qpos = pos0 + (t * rows + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0)) // group
        keep = kpos <= qpos
        for h in range(n_kv_heads):
            lanes = slice(h * d_head, (h + 1) * d_head)
            q = q_ref[0, h]                    # (T, D)
            k = k_ref[0, :, lanes]             # (bs, D)
            v = v_ref[0, :, lanes]
            if quant:
                # Fused dequant in VMEM, the reference's own arithmetic
                # (int8 -> f32 is exact, then one f32 multiply by the
                # slot's scale): rounding error comes only from the
                # one-time int8 write at block-fill time.
                k = k.astype(jnp.float32) * ks_ref[0, :, h:h + 1]
                v = v.astype(jnp.float32) * vs_ref[0, :, h:h + 1]
                q = q.astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (T, bs)
            s = jnp.where(keep, s, _NEG_INF)
            m = m_sc[h]                                       # (T, 1)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            safe_m = jnp.where(m_new == _NEG_INF, 0.0, m_new)
            p = jnp.exp(s - safe_m)
            corr = jnp.where(m == _NEG_INF, 0.0, jnp.exp(m - safe_m))
            l_sc[h] = l_sc[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_sc[h] = acc_sc[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[h] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        for h in range(n_kv_heads):
            l = l_sc[h]
            o_ref[0, h] = (acc_sc[h] / jnp.where(l == 0.0, 1.0, l)
                           ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_call(q, k_pool, v_pool, k_scale, v_scale, layer, tables, pos0,
                lengths, *, interpret: bool):
    """The one pallas_call behind every read path. q: (B, W, H, D);
    k_pool/v_pool: (L, NB, bs, H_kv*D), the whole pool as it lives on
    the device — an operand, never reshaped or sliced; k_scale/v_scale:
    (L, NB, bs, H_kv) f32 or None (full-precision pool); layer: (1,)
    the layer read; tables: (B, nb); pos0: (B,) logical position of each
    row's first query slot; lengths: (B,) pos0 + qlen. Returns
    (B, W, H, D) in q's dtype."""
    b, w, h, d = q.shape
    bs = k_pool.shape[2]
    h_kv = k_pool.shape[3] // d
    nb = tables.shape[1]
    g = h // h_kv
    quant = k_scale is not None
    # (B, W, H, D) -> (B, H_kv, W*G, D): slot-major within each KV head so
    # score row r maps to query slot r//G (head order matches
    # dot_product_attention's grouping).
    r = w * g
    qh = (q.reshape(b, w, h_kv, g, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, h_kv, r, d))
    rows = min(r, _ROW_TILE)
    r_pad = pl.cdiv(r, rows) * rows
    if r_pad != r:
        # Zero rows past W*G: computed like padding slots, sliced off.
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, r_pad - r), (0, 0)))
    q_spec = pl.BlockSpec(
        (1, h_kv, rows, d),
        lambda b, t, j, tables, pos0, lengths, layer: (b, 0, t, 0))

    # The layer index and the block table ARE the index map: step
    # (b, t, j) DMAs physical block tables[b, j] of layer layer[0] — no
    # layer is sliced out and no gathered copy exists.
    def block_spec(last):
        return pl.BlockSpec(
            (None, 1, bs, last),
            lambda b, t, j, tables, pos0, lengths, layer:
                (layer[0], tables[b, j], 0, 0))

    in_specs = [q_spec, block_spec(h_kv * d), block_spec(h_kv * d)]
    operands = [qh, k_pool, v_pool]
    if quant:
        in_specs += [block_spec(h_kv), block_spec(h_kv)]
        operands += [k_scale, v_scale]
    kernel = functools.partial(
        _paged_kernel, block_size=bs, scale=1.0 / math.sqrt(d), group=g,
        n_kv_heads=h_kv, d_head=d, quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,        # tables, pos0, lengths, layer
            grid=(b, r_pad // rows, nb),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((h_kv, rows, 1), jnp.float32),
                pltpu.VMEM((h_kv, rows, 1), jnp.float32),
                pltpu.VMEM((h_kv, rows, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h_kv, r_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables, pos0, lengths, layer, *operands)
    return (out[:, :, :r].reshape(b, h_kv, w, g, d)
            .transpose(0, 2, 1, 3, 4).reshape(b, w, h, d))


def _paged(q, k_pool, v_pool, k_scale, v_scale, layer, tables, pos0, qlen,
           interpret):
    """Entry-point glue: `interpret=None` auto-selects (compiled on TPU,
    the Pallas interpreter elsewhere); host ints become int32 arrays."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    pos0 = jnp.asarray(pos0, jnp.int32)
    return _paged_call(q, k_pool, v_pool, k_scale, v_scale,
                       jnp.asarray(layer, jnp.int32).reshape(1),
                       jnp.asarray(tables, jnp.int32), pos0,
                       pos0 + jnp.asarray(qlen, jnp.int32),
                       interpret=bool(interpret))


def paged_attention(q, k_pool, v_pool, layer, tables, pos_vec, *,
                    interpret=None):
    """Pallas-kernel drop-in for `paged_attention_reference` (same
    signature/contract): the ragged read at q_len 1."""
    return _paged(q, k_pool, v_pool, None, None, layer, tables, pos_vec, 1,
                  interpret)


def ragged_paged_attention(q, k_pool, v_pool, layer, tables, pos0, qlen, *,
                           interpret=None):
    """Pallas-kernel drop-in for `ragged_paged_attention_reference` (same
    signature/contract)."""
    return _paged(q, k_pool, v_pool, None, None, layer, tables, pos0, qlen,
                  interpret)


def quant_paged_attention(q, k_pool, v_pool, k_scale, v_scale, layer, tables,
                          pos_vec, *, interpret=None):
    """Pallas-kernel drop-in for `quant_paged_attention_reference` (same
    signature/contract): the block DMA is int8 + a scale vector — about
    half the bf16 bytes per block — and dequant happens in VMEM."""
    return _paged(q, k_pool, v_pool, k_scale, v_scale, layer, tables,
                  pos_vec, 1, interpret)


def quant_ragged_paged_attention(q, k_pool, v_pool, k_scale, v_scale, layer,
                                 tables, pos0, qlen, *, interpret=None):
    """Pallas-kernel drop-in for `quant_ragged_paged_attention_reference`
    (same signature/contract)."""
    return _paged(q, k_pool, v_pool, k_scale, v_scale, layer, tables, pos0,
                  qlen, interpret)


# The four read paths: name -> (kernel entry point, XLA reference). The
# selectors, the start-up banner and the parity checks all read this.
READ_PATHS = {
    "paged": (paged_attention, paged_attention_reference),
    "ragged": (ragged_paged_attention, ragged_paged_attention_reference),
    "quant_paged": (quant_paged_attention, quant_paged_attention_reference),
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
    """One `TPU_ENGINE_PAGED` selection rule for all four read paths —
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


def default_paged_attention():
    """Serving-path paged-attention selection, one rule with
    `models.transformer.default_attention` (see `_select_impl`)."""
    return _select_impl("paged")


def default_ragged_attention():
    """Ragged-variant selection — the same env knob and rule as
    `default_paged_attention` governs both read paths."""
    return _select_impl("ragged")


def default_quant_paged_attention():
    """Quantized decode-path selection (int8 pool, --kv-quantize) — the
    same `TPU_ENGINE_PAGED` knob and rule as the bf16 paths."""
    return _select_impl("quant_paged")


def default_quant_ragged_attention():
    """Quantized ragged-path selection — one rule for all four paths."""
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
                    table_len: int, dtype, seed: int = 0):
    """One random workload for the `READ_PATHS[kind]` pair, one row per
    entry of `q_lens`: (operands, qlen). The pool has two layers, both
    random, and the second is read. Rows get distinct
    shuffled tables and ragged positions so the skip/mask paths are
    exercised. Traceable (`jax.eval_shape` gives the operand shapes
    without generating them — ops.kernel_check AOT-compiles from those).
    Shared by the parity checks below and ops.kernel_check."""
    import numpy as np

    decode, quant = "ragged" not in kind, kind.startswith("quant")
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
    pos0 = np.zeros((batch,), np.int32)
    for r, ql in enumerate(q_lens):
        tables[r] = 1 + rng.permutation(n_blocks - 1)[:table_len]
        # Row history + this chunk must fit the table.
        pos0[r] = int(rng.integers(0, table_len * block_size - ql + 1))
    qlen = jnp.asarray(np.asarray(q_lens, np.int32))
    where = (jnp.int32(_PARITY_LAYER), jnp.asarray(tables),
             jnp.asarray(pos0))
    if not decode:
        where += (qlen,)
    return (q, *pool, *where), qlen


def reference_error(reference_fn, out, operands, qlen) -> float:
    """Max |out - reference| over the VALID query slots (slot <
    qlen[row]; padding slots are garbage by contract). The reference
    runs on f32 copies of the operands at the highest matmul precision,
    so on a TPU the distance measures the kernel, not the reference's
    own bf16 passes (a no-op for the f32 CPU parity tests)."""
    f32 = tuple(x.astype(jnp.float32)
                if jnp.issubdtype(x.dtype, jnp.floating) else x
                for x in operands)
    with jax.default_matmul_precision("highest"):
        ref = reference_fn(*f32)
    diff = jnp.abs(out.astype(jnp.float32) - ref)
    valid = jnp.arange(out.shape[1])[None, :] < qlen[:, None]
    return float(jnp.max(jnp.where(valid[:, :, None, None], diff, 0.0)))


def _parity(kind: str, q_lens, *, interpret, **shape) -> float:
    """Max |kernel - reference| over one `parity_workload`."""
    kernel_fn, reference_fn = READ_PATHS[kind]
    operands, qlen = parity_workload(kind, q_lens, **shape)
    return reference_error(reference_fn,
                           kernel_fn(*operands, interpret=interpret),
                           operands, qlen)


def parity_check(batch: int = 2, n_heads: int = 4, n_kv_heads: int = 2,
                 d_head: int = 8, block_size: int = 16, n_blocks: int = 9,
                 table_len: int = 4, dtype=jnp.float32, seed: int = 0,
                 interpret=None) -> float:
    """Decode-path parity (`_parity` at q_len 1 per row) — shared by
    tests/test_paged_kv.py, diagnostics.py --kernel-parity and
    chip_smoke.py's kernel phase."""
    return _parity("paged", (1,) * batch, n_heads=n_heads,
                   n_kv_heads=n_kv_heads, d_head=d_head,
                   block_size=block_size, n_blocks=n_blocks,
                   table_len=table_len, dtype=dtype, seed=seed,
                   interpret=interpret)


def ragged_parity_check(q_lens=(1, 7, 16, 17), n_heads: int = 4,
                        n_kv_heads: int = 2, d_head: int = 8,
                        block_size: int = 16, n_blocks: int = 33,
                        table_len: int = 6, dtype=jnp.float32,
                        seed: int = 0, interpret=None) -> float:
    """Ragged-path parity: mixed decode (q_len 1) rows and prefill-chunk
    rows in the same batch, the --mixed-step shape. Shared by
    tests/test_mixed_step.py, diagnostics.py --mixed-parity and
    chip_smoke.py's kernel phase."""
    return _parity("ragged", tuple(q_lens), n_heads=n_heads,
                   n_kv_heads=n_kv_heads, d_head=d_head,
                   block_size=block_size, n_blocks=n_blocks,
                   table_len=table_len, dtype=dtype, seed=seed,
                   interpret=interpret)


def quant_parity_check(batch: int = 2, n_heads: int = 4, n_kv_heads: int = 2,
                       d_head: int = 8, block_size: int = 16,
                       n_blocks: int = 9, table_len: int = 4,
                       dtype=jnp.float32, seed: int = 0,
                       interpret=None) -> float:
    """`parity_check` for the QUANTIZED decode path (int8 pool). Shared
    by tests/test_kv_quant.py, diagnostics.py --quant-parity and
    chip_smoke.py's kernel phase."""
    return _parity("quant_paged", (1,) * batch, n_heads=n_heads,
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
    + prefill-chunk rows over the int8 pool, the --kv-quantize
    --mixed-step serving shape)."""
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
