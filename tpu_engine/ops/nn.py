"""Functional NN building blocks (pure JAX, param-pytree style).

The model zoo (``tpu_engine.models``) is built on these instead of a heavy
framework layer: every op is a pure function over explicit parameter dicts,
which keeps pytrees transparent for ``jax.sharding`` annotation (tensor
parallelism shards these dicts directly) and lets XLA fuse elementwise work
into the surrounding matmuls/convs.

Conventions: NHWC activations, HWIO conv kernels (TPU-native layouts),
bfloat16-friendly — master params are stored float32; `dense` and `conv2d`
cast a kernel that is not yet in the apply dtype, so the MXU runs bf16
while accumulation stays f32. A caller that applies the same kernels again
and again hands them over already cast (a generation lane's compiled steps
read `models.transformer.step_weights`' tree, made once when the lane gets
its weights): the cast here is then the identity, and no step repeats it.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def he_normal(key, shape, fan_in, dtype=jnp.float32):
    std = math.sqrt(2.0 / fan_in)
    return jax.random.normal(key, shape, dtype) * std


# -- dense ------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int):
    kw, _ = jax.random.split(key)
    return {
        "kernel": he_normal(kw, (in_dim, out_dim), in_dim),
        "bias": jnp.zeros((out_dim,), jnp.float32),
    }


def dense(params, x, dtype=None):
    quantized = "kernel_q" in params
    kernel = params["kernel_q"] if quantized else params["kernel"]
    if dtype is not None:
        x = x.astype(dtype)
        kernel = kernel.astype(dtype)
    elif quantized:
        kernel = kernel.astype(x.dtype)
    # f32 accumulation on the MXU regardless of input dtype.
    y = jax.lax.dot_general(
        x, kernel, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if quantized:
        # Weight-only int8 (ops.quant): per-output-channel scale applied to
        # the OUTPUT — exact for X @ (Wq*s_j), while weights stream from
        # HBM at 1 byte each (the int8->MXU-dtype convert fuses into the
        # matmul's weight read).
        y = y * params["kernel_scale"]
    return y + params["bias"]


# -- conv -------------------------------------------------------------------

def conv_init(key, kh: int, kw: int, in_ch: int, out_ch: int):
    fan_in = kh * kw * in_ch
    return {"kernel": he_normal(key, (kh, kw, in_ch, out_ch), fan_in)}


def conv2d(params, x, stride: int = 1, padding="SAME", dtype=None):
    quantized = "kernel_q" in params
    kernel = params["kernel_q"] if quantized else params["kernel"]
    if dtype is not None:
        x = x.astype(dtype)
        kernel = kernel.astype(dtype)
    elif quantized:
        kernel = kernel.astype(x.dtype)
    y = jax.lax.conv_general_dilated(
        x,
        kernel,
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    )
    if quantized:
        y = y * params["kernel_scale"]  # per-out-channel, exact (ops.quant)
    return y


# -- norm -------------------------------------------------------------------

def batchnorm_init(ch: int):
    return {
        "scale": jnp.ones((ch,), jnp.float32),
        "bias": jnp.zeros((ch,), jnp.float32),
        "mean": jnp.zeros((ch,), jnp.float32),
        "var": jnp.ones((ch,), jnp.float32),
    }


def batchnorm(params, x, eps: float = 1e-5):
    """Inference-mode batch norm using stored statistics. XLA folds the
    per-channel affine into the adjacent conv."""
    inv = jax.lax.rsqrt(params["var"] + eps) * params["scale"]
    return x * inv + (params["bias"] - params["mean"] * inv)


def layernorm_init(dim: int):
    return {"scale": jnp.ones((dim,), jnp.float32), "bias": jnp.zeros((dim,), jnp.float32)}


def rmsnorm_init(dim: int):
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rmsnorm(params, x, eps: float = 1e-6):
    """RMSNorm (llama family): scale-only, no mean subtraction. Computed in
    f32 on the VPU like layernorm; callers cast back to the MXU dtype."""
    x = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * params["scale"]


def layernorm(params, x, eps: float = 1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return y * params["scale"] + params["bias"]


# -- pooling ----------------------------------------------------------------

def max_pool(x, window: int, stride: int, padding="SAME"):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max,
        (1, window, window, 1), (1, stride, stride, 1), padding,
    )


def global_avg_pool(x):
    return jnp.mean(x, axis=(1, 2))


# -- activations / misc -----------------------------------------------------

relu = jax.nn.relu
gelu = jax.nn.gelu
silu = jax.nn.silu


def embedding_init(key, vocab: int, dim: int):
    return {"table": jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02}


def embedding(params, ids):
    return params["table"][ids]


def count_params(params) -> int:
    return int(sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params)))
