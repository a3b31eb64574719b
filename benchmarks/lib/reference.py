"""The plain reference: what the served path is compared with.

The forward pass of the two dialects the cells run, in straightforward
jax.numpy and float32 under jax.default_matmul_precision("highest"): no
kernels, no cache, no batching, no code of the program under test. It reads
the server's own parameter arrays (float32 already, layers stacked on a
leading axis) and a small dict of sizes from the configuration file.

  gpt2     LayerNorm, learned positions, tanh-GELU MLP, multi-head attention
  mistral  RMSNorm, rotary positions (rotate-half, theta from the file),
           SwiGLU MLP, grouped-query attention (query head h reads KV head
           h // (n_heads // n_kv_heads)), no sliding window

Departures from the published models, which the served program shares: the
LM head is a separate matrix (the checkpoints tie it to the embedding), and
the llama-dialect projections carry zero biases.

`check_served` decides `correct`: the tokens the server generated greedily
are teacher-forced through ONE full forward pass of the reference, and at
every generated position the served token's reference logit must lie within
a tolerance of the reference's largest. Logits and not tokens: with random
weights the two largest logits are often closer than bf16 rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np

DIALECTS = ("gpt2", "mistral")


def _layernorm(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rmsnorm(p, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _dense(p, x):
    return x @ p["kernel"] + p["bias"]


def _rope(x, theta):
    """x: (T, H, D) at positions 0..T-1; rotate-half convention."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, sizes):
    """Causal self-attention over the whole sequence. x: (T, d_model)."""
    t = x.shape[0]
    h, h_kv = sizes["n_heads"], sizes["n_kv_heads"]
    q = _dense(p["wq"], x).reshape(t, h, -1)
    k = _dense(p["wk"], x).reshape(t, h_kv, -1)
    v = _dense(p["wv"], x).reshape(t, h_kv, -1)
    if sizes["dialect"] == "mistral":
        q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
    group = h // h_kv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, -1)
    return _dense(p["wo"], out)


def _mlp(p, x, sizes):
    if sizes["dialect"] == "mistral":
        return _dense(p["proj"],
                      jax.nn.silu(_dense(p["gate"], x)) * _dense(p["up"], x))
    return _dense(p["proj"], jax.nn.gelu(_dense(p["fc"], x),
                                         approximate=True))


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32. `sizes` is a
    hashable tuple of (key, value) pairs (see `sizes_of`)."""
    sizes = dict(sizes)
    norm = _rmsnorm if sizes["dialect"] == "mistral" else _layernorm
    eps = sizes["ln_eps"]
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][tokens]
        if sizes["dialect"] == "gpt2":
            x = x + params["pos_embed"]["table"][: tokens.shape[0]]

        def block(x, p):
            x = x + _attention(p["attn"], norm(p["ln1"], x, eps), sizes)
            x = x + _mlp(p["mlp"], norm(p["ln2"], x, eps), sizes)
            return x, None

        x, _ = jax.lax.scan(block, x, params["blocks"])
        return _dense(params["head"], norm(params["ln_f"], x, eps))


_forward_jit = jax.jit(forward, static_argnums=(2,))


def sizes_of(reference):
    """The configuration file's `reference` block as a hashable tuple."""
    if reference["dialect"] not in DIALECTS:
        raise ValueError(f"unknown reference dialect "
                         f"{reference['dialect']!r}; known: {DIALECTS}")
    return tuple(sorted(reference.items()))


def served_gaps(params, sizes, prompt, generated, pad_to):
    """Teacher-force `generated` after `prompt` through the reference.
    Returns, per generated position, (reference's largest logit - the
    served token's reference logit) and the logits' standard deviation
    there. Sequences are right-padded to `pad_to` so every sample of a
    configuration runs the same compiled program; causal attention keeps
    padding from reaching the positions read."""
    seq = list(prompt) + list(generated[:-1])
    if len(seq) > pad_to:
        raise ValueError(f"sample of {len(seq)} tokens exceeds the "
                         f"reference's padded length {pad_to}")
    tokens = np.zeros((pad_to,), np.int32)
    tokens[: len(seq)] = seq
    logits = _forward_jit(params, jnp.asarray(tokens), sizes)
    at = np.asarray(logits[len(prompt) - 1: len(seq)])   # (n_generated, V)
    served = at[np.arange(len(generated)), np.asarray(generated)]
    return at.max(-1) - served, at.std(-1)


def check_served(params, reference, samples, tolerance, min_exact_share,
                 pad_to):
    """samples: [(prompt, generated)]. The served path is correct when, at
    every generated position, the served token's reference logit is within
    `tolerance` standard deviations (of that position's logits) of the
    reference's largest, and at least `min_exact_share` of the served
    tokens ARE the reference's arg-max. Returns (ok, details)."""
    sizes = sizes_of(reference)
    worst, exact, total = 0.0, 0, 0
    for prompt, generated in samples:
        gaps, stds = served_gaps(params, sizes, prompt, generated, pad_to)
        worst = max(worst, float((gaps / stds).max()))
        exact += int((gaps == 0.0).sum())
        total += len(generated)
    share = exact / max(1, total)
    ok = bool(np.isfinite(worst) and worst <= tolerance
              and share >= min_exact_share)
    return ok, {"worst_gap_in_logit_std": worst, "exact_share": share,
                "positions": total, "tolerance": tolerance,
                "min_exact_share": min_exact_share}
