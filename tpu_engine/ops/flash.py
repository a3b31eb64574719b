"""Pallas TPU flash attention — the framework's hot-op kernel.

The reference's compute hot loop is an opaque ONNX `Session::Run`
(``/root/reference/src/inference_engine.cpp:176-183``); it has no custom
kernels at all. Here the attention core — where transformer serving spends
its FLOPs and HBM bandwidth — is a hand-tiled Pallas kernel:

- Grid: (batch·heads, Sq/BLOCK_Q, Sk/BLOCK_K). The key axis is a
  *sequential* ("arbitrary") grid dimension: each step streams one
  (BLOCK_K, D) key/value tile through VMEM and folds it into the running
  flash accumulators (f32 max / denominator / weighted sum) held in VMEM
  scratch — the (S, S) score matrix never exists and VMEM holds O(BLOCK·D)
  regardless of sequence length. (The previous design staged the whole
  (S, D) K/V per program: ~16 MB VMEM capped it at S≈8k; streaming removes
  the cap — S=16k+ compiles and runs on one chip.)
- Causal programs skip key blocks strictly above the diagonal with
  `pl.when` — ~2× fewer MXU ops than masking a full sweep.
- Matmuls run on the MXU in the input dtype with f32 accumulation
  (`preferred_element_type`); masks/softmax arithmetic in f32 on the VPU.

`flash_attention` matches `ops.attention.dot_product_attention`'s contract
(causal flag, (B, Sk) padding mask, fully-masked rows → 0) so it drops into
`transformer_apply(attn_fn=...)`. On non-TPU backends it runs the same
kernel through the Pallas interpreter (tests exercise exactness on the CPU
mesh); on TPU it compiles to Mosaic.

**Differentiable (training-grade).** A `jax.custom_vjp` pairs the forward
with hand-tiled backward kernels (`_bwd_dq_kernel`, `_bwd_dkv_kernel`): the
forward additionally emits the per-row logsumexp, and the backward
recomputes each probability tile from it (O(block²) recompute, never an
(S, S) residual), sweeping k blocks for dq and q blocks for dk/dv. Without
this, `jax.grad` through a raw `pallas_call` fails — and the layer stack
defaults to this kernel on TPU, so fine-tuning would crash there
(tests/test_flash_backward.py pins grads to the XLA reference).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                  m_sc, l_sc, acc_sc, *,
                  block_q: int, block_k: int, scale: float,
                  causal: bool, has_mask: bool,
                  window=None):
    """One (head, q-block, k-block) grid step. Block shapes (leading 1 =
    head slot): q_ref/o_ref (1, block_q, D); k_ref/v_ref (1, block_k, D);
    mask_ref (1, 1, block_k) and lse_ref (1, 1, block_q) — the singleton
    middle axis makes the block's last two dims (1, block) against an
    array whose second-to-last dim IS 1, which the Pallas TPU lowering
    accepts at any block size. Scratch (m/l: (block_q,), acc:
    (block_q, D), all f32) carries the online softmax across the
    sequential k axis. lse: per-row logsumexp of the masked scaled
    scores — the residual the backward kernels use to recompute p
    without storing the (S, S) probability matrix."""
    iq = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def fold_block():
        q = q_ref[0]  # (block_q, D) — stays in the MXU dtype (bf16 on TPU)
        k = k_ref[0]
        v = v_ref[0]
        # Both dots run on the MXU in the input dtype, accumulating f32.
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (block_q, block_k)
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = qpos >= kpos
            if window is not None:
                # Sliding-window band (Mistral): at most the last `window`
                # key positions per query.
                keep = keep & (qpos - kpos < window)
            s = jnp.where(keep, s, _NEG_INF)
        if has_mask:
            mb = mask_ref[0, 0, :]
            s = jnp.where(mb[None, :] > 0, s, _NEG_INF)

        m = m_sc[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        safe_m = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.exp(s - safe_m[:, None])
        corr = jnp.where(m == _NEG_INF, 0.0, jnp.exp(m - safe_m))
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    if causal:
        # Key blocks strictly past this q block's last row are all masked —
        # skip their MXU work entirely; with a sliding window, blocks
        # entirely BELOW the band skip too.
        run = j * block_k < (iq + 1) * block_q
        if window is not None:
            run = run & ((j + 1) * block_k > iq * block_q - window + 1)

        @pl.when(run)
        def _masked_sweep():
            fold_block()
    else:
        fold_block()

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_sc[...]
        out = acc_sc[...] / jnp.where(l == 0.0, 1.0, l)[:, None]
        o_ref[0] = out.astype(o_ref.dtype)
        # Fully-masked rows (l == 0) store -inf: backward turns their
        # probabilities into exact zeros.
        lse_ref[0, 0] = jnp.where(l > 0.0, m_sc[...] + jnp.log(
            jnp.where(l > 0.0, l, 1.0)), _NEG_INF).astype(jnp.float32)


def _pad_to(x, axis: int, size: int):
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flash_fwd_call(cfg, qh, kh, vh, mask):
    """Forward pallas_call over heads-layout operands. qh (BH, Sq_p, D);
    kh/vh (BH, Sk_p, D); mask (B, 1, Sk_p). Returns (out, lse) with lse
    (BH, 1, Sq_p)."""
    causal, block_q, block_k, scale, has_mask, h, interpret, window = cfg
    bh, sq_p, d = qh.shape
    sk_p = kh.shape[1]
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k,
        scale=scale, causal=causal, has_mask=has_mask, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, sq_p // block_q, sk_p // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, j: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, j: (bh, j, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bh, iq, j, h=h: (bh // h, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, j: (bh, iq, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, iq, j: (bh, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_p, d), vh.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh, mask)
    return out, lse


def _recompute_p(q, k, lse, mb, iq, j, *, block_q, block_k, scale,
                 causal, has_mask, window=None):
    """Rebuild the probability tile p = exp(s - lse) exactly as the forward
    masked it (the flash-backward trick: O(block²) recompute instead of an
    (S, S) residual)."""
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        keep = qpos >= kpos
        if window is not None:
            keep = keep & (qpos - kpos < window)
        s = jnp.where(keep, s, _NEG_INF)
    if has_mask:
        s = jnp.where(mb[None, :] > 0, s, _NEG_INF)
    # lse = -inf marks fully-masked rows: their p must be exactly 0.
    lse_safe = jnp.where(lse == _NEG_INF, 0.0, lse)
    return jnp.where(s == _NEG_INF, 0.0, jnp.exp(s - lse_safe[:, None]))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_sc, *,
                   block_q: int, block_k: int, scale: float,
                   causal: bool, has_mask: bool, window=None):
    """dq for one q block: sequential sweep over k blocks.
    dq = sum_j (p ∘ (do·vᵀ − Δ)) @ k · scale."""
    iq = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    def fold():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        p = _recompute_p(q, k, lse_ref[0, 0], mask_ref[0, 0, :], iq, j,
                         block_q=block_q, block_k=block_k, scale=scale,
                         causal=causal, has_mask=has_mask, window=window)
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        ds = p * (dp - delta_ref[0, 0][:, None])
        dq_sc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        run = j * block_k < (iq + 1) * block_q
        if window is not None:
            run = run & ((j + 1) * block_k > iq * block_q - window + 1)

        @pl.when(run)
        def _masked():
            fold()
    else:
        fold()

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_sc, dv_sc, *,
                    block_q: int, block_k: int, scale: float,
                    causal: bool, has_mask: bool, window=None):
    """dk/dv for one k block: sequential sweep over q blocks.
    dv = sum_i pᵀ @ do;  dk = sum_i (p ∘ (do·vᵀ − Δ))ᵀ @ q · scale."""
    j = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    def fold():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        p = _recompute_p(q, k, lse_ref[0, 0], mask_ref[0, 0, :], iq, j,
                         block_q=block_q, block_k=block_k, scale=scale,
                         causal=causal, has_mask=has_mask, window=window)
        pt = p.astype(do.dtype)
        dv_sc[...] += jax.lax.dot_general(
            pt, do, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bk, d)
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None])
        dk_sc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        run = (iq + 1) * block_q > j * block_k
        if window is not None:
            run = run & ((j + 1) * block_k > iq * block_q - window + 1)

        @pl.when(run)
        def _masked():
            fold()
    else:
        fold()

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_bwd_call(cfg, qh, kh, vh, mask, out, lse, do):
    causal, block_q, block_k, scale, has_mask, h, interpret, window = cfg
    bh, sq_p, d = qh.shape
    sk_p = kh.shape[1]
    # Δ_i = Σ_d do_i·o_i — tiny elementwise reduce; XLA fuses it.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]                  # (BH, 1, Sq_p)

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, a, b_: (bh, a, 0))
    qrow = pl.BlockSpec((1, 1, block_q), lambda bh, a, b_: (bh, 0, a))
    common = dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal, has_mask=has_mask,
                          window=window),
        grid=(bh, sq_p // block_q, sk_p // block_k),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_k, d), lambda bh, iq, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, j: (bh, j, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bh, iq, j, h=h: (bh // h, 0, j)),
            q_spec,   # do
            qrow,     # lse
            qrow,     # delta
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), qh.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        **common,
    )(qh, kh, vh, mask, do, lse, delta)

    k_spec = pl.BlockSpec((1, block_k, d), lambda bh, j, iq: (bh, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal, has_mask=has_mask,
                          window=window),
        grid=(bh, sk_p // block_k, sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, j, iq: (bh, iq, 0)),
            k_spec,
            k_spec,
            pl.BlockSpec((1, 1, block_k),
                         lambda bh, j, iq, h=h: (bh // h, 0, j)),
            pl.BlockSpec((1, block_q, d), lambda bh, j, iq: (bh, iq, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, j, iq: (bh, 0, iq)),
            pl.BlockSpec((1, 1, block_q), lambda bh, j, iq: (bh, 0, iq)),
        ],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk_p, d), kh.dtype),
            jax.ShapeDtypeStruct((bh, sk_p, d), vh.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        **common,
    )(qh, kh, vh, mask, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_core(cfg, qh, kh, vh, mask):
    out, _ = _flash_fwd_call(cfg, qh, kh, vh, mask)
    return out


def _flash_core_fwd(cfg, qh, kh, vh, mask):
    out, lse = _flash_fwd_call(cfg, qh, kh, vh, mask)
    return out, (qh, kh, vh, mask, out, lse)


def _flash_core_bwd(cfg, res, do):
    qh, kh, vh, mask, out, lse = res
    dq, dk, dv = _flash_bwd_call(cfg, qh, kh, vh, mask, out, lse, do)
    # int mask: float0 cotangent (non-differentiable input).
    dmask = np.zeros(mask.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dmask


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret", "window"))
def _flash_call(q, k, v, mask, *, causal: bool, block_q: int, block_k: int,
                interpret: bool, window=None):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    has_mask = mask is not None

    # Pad sequence dims to block multiples; padded keys are masked out,
    # padded query rows are sliced off after.
    sq_p = pl.cdiv(sq, block_q) * block_q
    sk_p = pl.cdiv(sk, block_k) * block_k
    if sk_p != sk and not has_mask:
        mask = jnp.ones((b, sk), jnp.int32)
        has_mask = True
    if has_mask:
        mask = _pad_to(mask.astype(jnp.int32), 1, sk_p)
    else:
        mask = jnp.ones((b, sk_p), jnp.int32)  # dummy operand, never read
    mask = mask[:, None, :]  # (B, 1, Sk) — see _flash_kernel docstring

    # (B, S, H, D) → (B·H, S, D): each program owns one head's sequence.
    def to_heads(x, s_pad):
        x = _pad_to(x, 1, s_pad)
        return x.transpose(0, 2, 1, 3).reshape(b * h, s_pad, x.shape[-1])

    qh, kh, vh = to_heads(q, sq_p), to_heads(k, sk_p), to_heads(v, sk_p)

    cfg = (causal, block_q, block_k, scale, has_mask, h, interpret, window)
    out = _flash_core(cfg, qh, kh, vh, mask)

    out = out.reshape(b, h, sq_p, d).transpose(0, 2, 1, 3)
    return out[:, :sq]


def flash_attention(q, k, v, *, causal: bool = False, mask=None,
                    block_q: int = 512, block_k: int = 512,
                    interpret=None, window=None):
    """Drop-in for `dot_product_attention` backed by the Pallas kernel.

    q: (B, Sq, H, D); k, v: (B, Sk, H, D); mask: optional (B, Sk) 1=valid.
    `interpret=None` auto-selects: compiled on TPU, interpreter elsewhere.

    Compiled by Mosaic for the v5e at every serving bucket S=16…1024
    (tests/test_kernels_tpu_compile.py, chip_smoke.py); its time against
    the XLA-fused path is not measured on the installed stack (PERF.md).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = min(block_q, max(q.shape[1], 1))
    # Mosaic lane alignment: k/v/mask tiles sit on the 128-lane axis, so
    # never shrink block_k below one lane tile — short sequences instead
    # pad k/v to 128 inside `_flash_call` and the generated padding mask
    # kills the extra columns. (block_k 16/32/64 → "Mosaic failed …
    # cannot statically prove that index in dimension 2 is a multiple of
    # 128" at every prompt bucket < 128.)
    block_k = max(128, min(block_k, max(k.shape[1], 1)))
    if window is not None and not causal:
        raise ValueError("window (sliding-window attention) requires causal")
    return _flash_call(q, k, v, mask, causal=causal, block_q=block_q,
                       block_k=block_k, interpret=bool(interpret),
                       window=None if window is None else int(window))
