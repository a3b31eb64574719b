"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has no long-context support at all (SURVEY.md §5: inputs are
opaque flat vectors, ``/root/reference/src/worker_node.cpp:17``; sequence
scaling is bounded by the single-graph shape). The TPU-native framework makes
sequence parallelism first-class: sequences too long for one chip's HBM shard
over a ``seq`` mesh axis and attention runs as a blockwise ring.

Two strategies, both exact (not approximations):

- **Ring attention** (`ring_attention`): Q stays put; K/V shards rotate
  around the ring via `jax.lax.ppermute` (ICI neighbor exchange — each step
  is a nearest-neighbor hop, the cheapest collective on a torus). Softmax
  is accumulated online flash-style (running max / denominator in f32), so
  the result is bit-comparable to full attention without ever materializing
  the (S, S) score matrix on one chip. HBM per chip: O(S/n · S/n) scores.

- **Ulysses all-to-all** (`ulysses_attention`): `all_to_all` swaps the
  shard axis from sequence to heads — each chip then holds the FULL
  sequence for H/n heads, runs ordinary attention, and a second
  `all_to_all` swaps back. Two collectives total (vs n-1 ring hops);
  preferable when n_heads % n == 0 and S²·H/n fits in HBM.

Both run under `jax.shard_map` over a mesh with a ``seq`` axis and compose
with data/tensor parallelism on the other axes (the `data` axis shards B,
the `model` axis shards H — ring rotates only along ``seq``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG_INF = float("-inf")


def _online_block(q, k, v, o, m, l, *, qpos, kpos, kv_mask):
    """One blockwise-attention accumulation step (all f32 accumulators).

    q: (B, Sq, H, D); k, v: (B, Sk, H, D); o: (B, H, Sq, D) f32;
    m, l: (B, H, Sq) f32 running max / denominator.
    qpos: (Sq,) global query positions or None (no causal mask).
    kpos: (Sk,) global key positions for this block.
    kv_mask: (B, Sk) 1=valid or None.
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    s = s / math.sqrt(d)
    if qpos is not None:
        s = jnp.where(qpos[None, None, :, None] >= kpos[None, None, None, :],
                      s, _NEG_INF)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] > 0, s, _NEG_INF)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # Fully-masked-so-far rows have m_new == -inf; exp(s - safe_m) is then
    # exp(-inf) = 0 for every (also -inf) score, which is the right answer.
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - safe_m[..., None])
    # Rescale the old accumulator; rows that were fully masked carry o=l=0,
    # so the correction factor there is irrelevant — force 0 to avoid inf-inf.
    corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return o_new, m_new, l_new


def _finalize(o, l, out_dtype):
    """o: (B, H, Sq, D) f32, l: (B, H, Sq) → (B, Sq, H, D) in out_dtype."""
    out = o / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.transpose(0, 2, 1, 3).astype(out_dtype)


def _ring_shard_fn(q, k, v, kv_mask, *, axis_name: str, axis_size: int,
                   chunk: int, causal: bool, has_mask: bool):
    """Per-device body under shard_map: q,k,v are (B, S/n, H, D) shards."""
    b, sq, h, d = q.shape
    my = jax.lax.axis_index(axis_name)
    qpos = my * chunk + jnp.arange(sq) if causal else None
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    o = jnp.zeros((b, h, sq, d), jnp.float32)
    m = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)

    def step(t, carry):
        o, m, l, k, v, kv_mask = carry
        # At step t this device holds the shard that originated on
        # device (my - t) mod n — its keys' global positions start there.
        src = jax.lax.rem(my - t + axis_size, axis_size)
        kpos = src * chunk + jnp.arange(k.shape[1])
        o, m, l = _online_block(
            q, k, v, o, m, l, qpos=qpos, kpos=kpos,
            kv_mask=kv_mask if has_mask else None)
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        if has_mask:
            kv_mask = jax.lax.ppermute(kv_mask, axis_name, perm)
        return o, m, l, k, v, kv_mask

    o, m, l, _, _, _ = jax.lax.fori_loop(
        0, axis_size, step, (o, m, l, k, v, kv_mask))
    return _finalize(o, l, v.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, axis_name: str = "seq",
                   causal: bool = False, kv_mask=None,
                   batch_axis: Optional[str] = None):
    """Exact attention over sequences sharded on ``axis_name``.

    q, k, v: (B, S, H, D) with S sharded over ``axis_name`` (S must divide
    evenly by the axis size). kv_mask: optional (B, S) 1=valid padding mask,
    sharded the same way. ``batch_axis``: optional mesh axis sharding B (data
    parallelism composes freely — the ring rotates only along ``axis_name``).

    Returns (B, S, H, D) sharded like q. Head dim may additionally be
    sharded over a tensor-parallel axis by the caller's in_shardings; the
    ring body is per-head independent.
    """
    n = mesh.shape[axis_name]
    s = q.shape[1]
    if s % n != 0:
        raise ValueError(f"seq len {s} not divisible by {axis_name}={n}")
    chunk = s // n
    has_mask = kv_mask is not None
    if not has_mask:
        # shard_map needs a concrete operand; pass a dummy it never reads.
        kv_mask = jnp.ones((q.shape[0], s), jnp.int32)

    bspec = batch_axis  # None → replicated batch
    spec4 = P(bspec, axis_name, None, None)
    spec2 = P(bspec, axis_name)
    fn = functools.partial(
        _ring_shard_fn, axis_name=axis_name, axis_size=n, chunk=chunk,
        causal=causal, has_mask=has_mask)
    sharded = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(spec4, spec4, spec4, spec2),
        out_specs=spec4,
        check_vma=False)
    return sharded(q, k, v, kv_mask)


def _ulysses_shard_fn(q, k, v, kv_mask, *, axis_name: str, causal: bool,
                      has_mask: bool):
    """Per-device body: swap shard axis seq→heads, full attention, swap back.

    Shards arrive as (B, S/n, H, D); all_to_all yields (B, S, H/n, D).
    """
    from tpu_engine.ops.attention import dot_product_attention

    def a2a(x, split, concat):
        return jax.lax.all_to_all(x, axis_name, split_axis=split,
                                  concat_axis=concat, tiled=True)

    qf, kf, vf = (a2a(t, 2, 1) for t in (q, k, v))  # (B, S, H/n, D)
    mask = None
    if has_mask:
        # (B, S/n) shards → full (B, S) on every device.
        mask = jax.lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)
    out = dot_product_attention(qf, kf, vf, causal=causal, mask=mask)
    return a2a(out, 1, 2)  # back to (B, S/n, H, D)


def ulysses_attention(q, k, v, mesh: Mesh, *, axis_name: str = "seq",
                      causal: bool = False, kv_mask=None,
                      batch_axis: Optional[str] = None):
    """All-to-all (DeepSpeed-Ulysses-style) sequence-parallel attention.

    Same contract as `ring_attention`; requires n_heads % axis_size == 0.
    Two all_to_all collectives instead of n-1 ppermute hops — better when
    the full (S, S) score matrix for H/n heads fits in HBM.
    """
    n = mesh.shape[axis_name]
    if q.shape[2] % n != 0:
        raise ValueError(f"n_heads {q.shape[2]} not divisible by {axis_name}={n}")
    if q.shape[1] % n != 0:
        raise ValueError(f"seq len {q.shape[1]} not divisible by {axis_name}={n}")
    has_mask = kv_mask is not None
    if not has_mask:
        kv_mask = jnp.ones((q.shape[0], q.shape[1]), jnp.int32)

    bspec = batch_axis
    spec4 = P(bspec, axis_name, None, None)
    spec2 = P(bspec, axis_name)
    fn = functools.partial(_ulysses_shard_fn, axis_name=axis_name,
                           causal=causal, has_mask=has_mask)
    sharded = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(spec4, spec4, spec4, spec2),
        out_specs=spec4,
        check_vma=False)
    return sharded(q, k, v, kv_mask)


def seq_sharding(mesh: Mesh, axis_name: str = "seq", ndim: int = 4,
                 batch_axis: Optional[str] = None) -> NamedSharding:
    """NamedSharding placing dim 1 (sequence) on ``axis_name``."""
    spec = [batch_axis, axis_name] + [None] * (ndim - 2)
    return NamedSharding(mesh, P(*spec))
