"""The laguna dialect: RMSNorm blocks whose attention is FULL on some layers
and a SLIDING WINDOW on the others, each kind with its own number of query
heads and its own rope, a per-head gate on the attention output, a SwiGLU
FFN in the first `n_dense_layers` layers and sigmoid-routed SwiGLU experts
with one shared expert in the others. The served program keeps a window
layer's keys and values in blocks it gives back once the window has passed
them and reads a tile's own columns; this file attends a whole sequence
under a mask, so the two are held against each other.

Layer l, stream h (T x d), H_l = `heads_per_layer[l]` query heads over
`n_kv_heads` KV heads of D lanes (the arrays' shapes give D):

  1  x = RMS(h). q = x Wq (H_l x D), k = x Wk, v = x Wv (n_kv_heads x D).
     q and k RMS-normalised a head over its D lanes with a learned scale.
  2  rope, rotate-half pairing. Window layer: `window_rope_theta` over all
     D lanes. Full layer: the first `partial_rotary` x D lanes only, YaRN
     frequencies as transformers' `_compute_yarn_parameters` blends them
     (`rope_theta`, `yarn_factor`, `yarn_original_max`, `yarn_beta_fast`,
     `yarn_beta_slow`), cos and sin times `yarn_attention_factor`.
  3  scores q.k / sqrt(D), causal; on a window layer key j is seen by
     query i iff i - window < j <= i. Query head n reads KV head
     n // (H_l / n_kv_heads).
  4  g = softplus(x Wg), one number a head; head n's output times g_n;
     h += concat(o) Wo.
  5  y = RMS(h). Dense layer: h += Wdown(silu(Wgate y) * Wup y). Expert
     layer: s = sigmoid(y Wr) over ALL `n_experts` outputs, the top
     `top_k` of s + b chosen, w = s[chosen] / sum x `routed_scale`;
     h += sum_e w_e SwiGLU_e(y) + SwiGLU_shared(y).
  6  final RMS, head.

**One chip's share of a deployment is what is handed over.** The tree
holds `held_count` of the `n_experts` routed experts, from expert
`held_first` on (expert parallelism over the chips that share each layer,
cut to one of them), and as many rows of the vocabulary as the head is
wide. A (token, expert) pair routed to an expert outside the share adds
nothing HERE, in the program and in this file alike: the partial sum goes
on to the next layer. The router keeps all its outputs and its top-k.

Sizes read from the configuration's `reference` block: heads_per_layer
and windowed (one number a layer, 0/1 for the latter, comma-separated in
a string: the block's values are hashed), n_kv_heads, window, window_rope_theta,
rope_theta, partial_rotary, yarn_*, n_dense_layers, top_k, routed_scale,
held_first, ln_eps. Parameter tree: tok_embed, layers (a list), ln_f, head;
a block is ln1, attn{wq, wk, wv, wo, wg, q_norm, k_norm}, ln2, mlp; an
expert block's mlp is router{kernel, bias}, shared{gate, up, proj},
experts{gate_up (held, d, 2f), down (held, f, d)}.

The server's leaves are bfloat16 and fill most of the chip. They are
exactly representable in float32 and are upcast a piece at a time: a
projection when it is used, ONE expert inside the loop over experts, a
slice of the vocabulary inside the head; attention runs a block of
`QUERY_BLOCK` queries at a time. The transients stay under 1.5 GB at 2 k
tokens.

Optional keys of the block serve the controls of `correct` (tests, and one
run each on the chip); every one must read NOT correct:
  `drop`: "window" (window layers attend the whole context), "gate" (no
  gate on the heads' outputs), "partial_rope" (full layers rotate all D
  lanes), "bias" (no selection bias), "shared" (no shared expert);
  `experts_as`: the expert banks rounded to a narrower type first
  ("float8_e4m3fn").
"""

import jax
import jax.numpy as jnp
import numpy as np

from references._plain import dense, rmsnorm

HEAD_SLICES = 16
QUERY_BLOCK = 256


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _swiglu(p, x):
    p = _f32(p)
    return dense(p["proj"], jax.nn.silu(dense(p["gate"], x))
                 * dense(p["up"], x))


def _yarn_inv_freq(dim, base, factor, original_max, beta_fast, beta_slow):
    """The inverse frequencies of `dim` rotated lanes' pairs: 1 / (factor x
    f) below the correction range, 1 / f above it, a linear ramp between."""
    def correction_dim(rotations):
        return (dim * np.log(original_max / (rotations * 2 * np.pi))
                / (2 * np.log(base)))

    low = max(int(np.floor(correction_dim(beta_fast))), 0)
    high = min(int(np.ceil(correction_dim(beta_slow))), dim - 1)
    if low == high:
        high += 0.001
    freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (ramp / (factor * freqs) + (1.0 - ramp) / freqs).astype(np.float32)


def _rope(x, inv_freq, factor):
    """x: (T, H, D) at positions 0..T-1: the first 2 x len(inv_freq) lanes
    rotate in pairs (i, i + half), the others pass."""
    rot = 2 * len(inv_freq)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def _attention(p, x, layer, sizes):
    t = x.shape[0]
    heads, kv_heads = sizes["heads_per_layer"][layer], sizes["n_kv_heads"]
    windowed = bool(sizes["windowed"][layer])
    drop = sizes.get("drop")
    q = dense(_f32(p["wq"]), x).reshape(t, heads, -1)
    k = dense(_f32(p["wk"]), x).reshape(t, kv_heads, -1)
    v = dense(_f32(p["wv"]), x).reshape(t, kv_heads, -1)
    d = q.shape[-1]
    q = rmsnorm(p["q_norm"], q, sizes["ln_eps"])
    k = rmsnorm(p["k_norm"], k, sizes["ln_eps"])
    if windowed:
        inv = 1.0 / sizes["window_rope_theta"] ** (
            np.arange(0, d, 2, dtype=np.float64) / d)
        rope = inv.astype(np.float32), 1.0
    else:
        lanes = d if drop == "partial_rope" else int(
            d * sizes["partial_rotary"])
        rope = (_yarn_inv_freq(lanes, sizes["rope_theta"],
                               sizes["yarn_factor"],
                               sizes["yarn_original_max"],
                               sizes["yarn_beta_fast"],
                               sizes["yarn_beta_slow"]),
                sizes["yarn_attention_factor"])
    q, k = _rope(q, *rope), _rope(k, *rope)
    group = heads // kv_heads
    q = q.reshape(t, kv_heads, group, d)
    kpos = jnp.arange(t)

    def block(i):
        """QUERY_BLOCK queries against the whole sequence."""
        qb = jax.lax.dynamic_slice_in_dim(q, i * QUERY_BLOCK, QUERY_BLOCK)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        seen = kpos[None, :] <= qpos[:, None]
        if windowed and drop != "window":
            seen &= kpos[None, :] > qpos[:, None] - sizes["window"]
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) / np.sqrt(float(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", probs, v)

    o = jax.lax.map(block, jnp.arange(t // QUERY_BLOCK))
    o = o.reshape(t, heads, d)
    if drop != "gate":
        o = o * jax.nn.softplus(dense(_f32(p["wg"]), x))[:, :, None]
    return dense(_f32(p["wo"]), o.reshape(t, -1))


def _experts(p, x, sizes):
    """Every HELD expert over every token, one expert's weights upcast at
    a time, each masked and weighted by the router's choice among all the
    experts; a pair routed outside the share adds nothing."""
    router = p["router"]
    s = jax.nn.sigmoid(x @ router["kernel"])
    biased = s if sizes.get("drop") == "bias" else s + router["bias"]
    _, chosen = jax.lax.top_k(biased, sizes["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * sizes["routed_scale"]
    # (T, E): an expert's weight for a token, zero where it was not chosen.
    gates = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weight)
    bank, first = p["experts"], sizes["held_first"]
    narrow = sizes.get("experts_as")

    def one(y, e):
        def leaf(a):
            a = jax.lax.dynamic_index_in_dim(a, e, keepdims=False)
            if narrow:
                a = a.astype(jnp.dtype(narrow))
            return a.astype(jnp.float32)
        gate, up = jnp.split(x @ leaf(bank["gate_up"]), 2, axis=-1)
        out = (jax.nn.silu(gate) * up) @ leaf(bank["down"])
        mine = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1)
        return y + mine * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(bank["down"].shape[0]))
    if sizes.get("drop") != "shared":
        y = y + _swiglu(p["shared"], x)
    return y


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32."""
    sizes = dict(sizes)
    for key in ("heads_per_layer", "windowed"):
        sizes[key] = [int(v) for v in sizes[key].split(",")]
    eps = sizes["ln_eps"]
    t = tokens.shape[0]
    pad = -t % QUERY_BLOCK
    tokens = jnp.pad(tokens, (0, pad))
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][tokens].astype(jnp.float32)
        for layer, p in enumerate(params["layers"]):
            x = x + _attention(p["attn"], rmsnorm(p["ln1"], x, eps), layer,
                               sizes)
            y = rmsnorm(p["ln2"], x, eps)
            x = x + (_swiglu(p["mlp"], y) if layer < sizes["n_dense_layers"]
                     else _experts(p["mlp"], y, sizes))
        x = rmsnorm(params["ln_f"], x, eps)[:t]
        kernel, bias = params["head"]["kernel"], params["head"]["bias"]
        vocab = kernel.shape[1]
        width = vocab // HEAD_SLICES

        def head_slice(i, logits):
            k = jax.lax.dynamic_slice_in_dim(kernel, i * width, width, 1)
            return jax.lax.dynamic_update_slice_in_dim(
                logits, x @ k.astype(jnp.float32), i * width, 1)

        return jax.lax.fori_loop(
            0, HEAD_SLICES, head_slice,
            jnp.zeros((t, vocab), jnp.float32)) + bias
