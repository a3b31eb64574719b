"""Plain float32 pieces that more than one reference file shares: the two
norms, a dense layer, rotary positions, causal attention over a whole
sequence. jax.numpy only; no code of the program under test. A reference
file says what it is made of by which of these it calls."""

import jax
import jax.numpy as jnp


def layernorm(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rmsnorm(p, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def dense(p, x):
    return x @ p["kernel"] + p["bias"]


def rope(x, theta):
    """x: (T, H, D) at positions 0..T-1; rotate-half convention."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, n_heads, n_kv_heads, rope_theta=None):
    """Causal self-attention over the whole sequence. x: (T, d_model).
    Query head h reads KV head h // (n_heads // n_kv_heads); rotary
    positions on q and k where `rope_theta` is given."""
    t = x.shape[0]
    q = dense(p["wq"], x).reshape(t, n_heads, -1)
    k = dense(p["wk"], x).reshape(t, n_kv_heads, -1)
    v = dense(p["wv"], x).reshape(t, n_kv_heads, -1)
    if rope_theta is not None:
        q, k = rope(q, rope_theta), rope(k, rope_theta)
    group = n_heads // n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, -1)
    return dense(p["wo"], out)
