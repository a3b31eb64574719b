"""Pipeline parallelism: GPipe-style microbatching over a ``stage`` mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2 checklist: PP ❌).
Here it's a first-class strategy: the framework's models stack per-layer
params on a leading L axis (models.transformer scans one block over them),
and that axis is exactly the pipeline shard dim — stage s owns layers
[s·L/S, (s+1)·L/S).

Schedule (inference/forward): the batch splits into M microbatches; at step
t every stage applies its local layers to its current activation and hands
the result to the next stage over ``jax.lax.ppermute`` (nearest-neighbor
ICI hop). After S-1 warm-up steps the pipe is full; total steps M + S - 1,
bubble fraction (S-1)/(M+S-1) — choose M >= S for efficiency. All shapes
static; the step loop is a ``lax.fori_loop``; stages compute every step
(bubble work is discarded, the standard trade for a compile-once schedule).

Exactness: the pipelined forward equals the unsharded layer scan bit-for-bit
modulo f32 reduction order (tests assert allclose).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_shard_fn(params_local, x_stream, *, block_fn: Callable,
                       axis_name: str, n_stages: int, n_micro: int):
    """Per-stage body. params_local: (L/S, ...) pytree slice;
    x_stream: (M, mb, ...) microbatch stream (meaningful on stage 0)."""
    stage = jax.lax.axis_index(axis_name)
    is_first = stage == 0
    is_last = stage == n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local_layers(h):
        h, _ = jax.lax.scan(lambda c, lp: (block_fn(lp, c), None),
                            h, params_local)
        return h

    mb_shape = x_stream.shape[1:]
    recv0 = jnp.zeros(mb_shape, x_stream.dtype)
    out0 = jnp.zeros((n_micro,) + mb_shape, x_stream.dtype)

    def step(t, carry):
        recv, outbuf = carry
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        inp = jnp.where(is_first,
                        jax.lax.dynamic_index_in_dim(x_stream, mb_idx, 0,
                                                     keepdims=False),
                        recv)
        h = local_layers(inp)
        # Last stage: step t completes microbatch t-(S-1).
        out_idx = t - (n_stages - 1)
        write = is_last & (out_idx >= 0) & (out_idx < n_micro)
        upd = jax.lax.dynamic_update_index_in_dim(
            outbuf, h, jnp.clip(out_idx, 0, n_micro - 1), 0)
        outbuf = jnp.where(write, upd, outbuf)
        recv = jax.lax.ppermute(h, axis_name, perm)
        return recv, outbuf

    _, outbuf = jax.lax.fori_loop(0, n_micro + n_stages - 1, step,
                                  (recv0, out0))
    # Only the last stage holds real outputs (zeros elsewhere): psum
    # broadcasts them to every stage so the result is replicated.
    outbuf = jnp.where(is_last, outbuf, jnp.zeros_like(outbuf))
    return jax.lax.psum(outbuf, axis_name)


def pipeline_apply(block_fn: Callable, stacked_params, x, mesh: Mesh, *,
                   axis_name: str = "stage",
                   n_microbatches: Optional[int] = None):
    """Run ``scan(block_fn)`` over L stacked layers as an S-stage pipeline.

    block_fn(layer_params, h) -> h  (one layer; h is a single array).
    stacked_params: pytree of (L, ...) arrays, L % S == 0.
    x: (B, ...) batch, B % M == 0. Returns (B, ...) like the plain scan.
    """
    n_stages = mesh.shape[axis_name]
    n_micro = n_microbatches or n_stages
    b = x.shape[0]
    if b % n_micro != 0:
        raise ValueError(f"batch {b} not divisible by {n_micro} microbatches")
    leading = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if leading % n_stages != 0:
        raise ValueError(f"{leading} layers not divisible by {n_stages} stages")

    x_stream = x.reshape((n_micro, b // n_micro) + x.shape[1:])
    fn = functools.partial(_pipeline_shard_fn, block_fn=block_fn,
                           axis_name=axis_name, n_stages=n_stages,
                           n_micro=n_micro)
    sharded = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis_name), stacked_params),
                  P()),
        out_specs=P(),
        check_vma=False)
    out = sharded(stacked_params, x_stream)
    return out.reshape((b,) + out.shape[2:])
