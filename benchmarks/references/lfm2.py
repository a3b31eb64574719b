"""The lfm2 dialect (LFM2-MoE): a layer's operator is a GATED SHORT
CONVOLUTION or causal grouped-query attention by a published list, its
feed-forward a dense SwiGLU in the leading layers and sigmoid-routed SwiGLU
experts with no shared expert in the others. The served program keeps a
conv layer's last two inputs in a state pool's row and an attention layer's
keys and values in a paged pool between ticks, finds a token's predecessors
by a gather over the tick's token list or in that row, and sorts a tick's
(token, expert) pairs into one grouped product over the experts its chip
holds; this file pads one whole sequence and shifts it, attends under a
causal mask, and applies every held expert to every token under the
router's mask, so the two are held against each other.

Stream h (T x d), eps `ln_eps`, no bias; layer l of kind `layers[l]` (C a
conv, A attention):

  0  h = E[ids]
  1  r = RMS1_l(h);  h += Op_l(r);  h += FFN_l(RMS2_l(h))
  C  [B | C | x] = r W_in (d -> 3d, in that order); u = B * x;
     c_t = sum_{j<K} w_j * u_{t-(K-1)+j}: a depthwise causal conv of K taps
     (weights (K, d), the LAST row the current token's), u zero before
     token 0, no bias; y = (C * c) W_out. No activation.
  A  q = RMS_q(r Wq), k = RMS_k(r Wk): a norm over each head's lanes with a
     learned scale; v = r Wv; rotate-half RoPE at `rope_theta` over all of a
     head's lanes of q and k; scores q.k / sqrt(D), causal; query head i
     reads KV head i // (n_heads / n_kv_heads); y = concat Wo.
  F  l < `n_dense_layers`: (silu(z W1) * z W3) W2; else s = sigmoid(z W_r)
     over all the experts; the `top_k` largest of s + bias are chosen; w =
     s[chosen] / sum(s[chosen]) * `routed_scale`; y = sum over the chosen
     HELD experts e of w_e (silu(z G_e) * z U_e) D_e, [G_e | U_e] the
     bank's `gate_up` (the bank holds experts `held_first` on, as many as it
     is long: a pair routed outside the share adds nothing, in the program
     and here alike). No shared expert.
  2  logits = RMS_f(h) W_head

Departures from the published model, the configuration's `assumed`: the
norms on q and k and the head's width are the family's and named by no key;
one final norm before an untied head; the published cache keeps K columns
of u where K - 1 are read.

Sizes read from the configuration's `reference` block: `layers`, `n_heads`,
`n_kv_heads`, `n_dense_layers`, `ln_eps`, `top_k`, `routed_scale`,
`held_first`, `rope_theta`. Parameter tree: tok_embed, layers (a list),
ln_f, head; a block is ln1, ln2, conv {w_in, taps, w_out} or attn {wq, wk,
wv, wo, q_norm, k_norm}, and mlp, a SwiGLU {gate, up, proj} or {router,
experts {gate_up, down}}.

The server's leaves are bfloat16 and fill most of the chip. They are
exactly representable in float32 and are upcast a projection at a time, ONE
expert inside the loop over experts, a slice of the vocabulary inside the
head; attention runs a block of `QUERY_BLOCK` queries at a time.

Optional keys of the block serve the controls of `correct` (tests, and runs
on the chip):
  `drop`: "conv", "attention", "experts" (every layer of the kind adds
  nothing); "conv_tail" (at every multiple of `chunk` tokens, a prompt's
  chunk boundaries in the served program, the conv sees nothing before the
  boundary); "taps" (the taps' order reversed); "gate" (C left out: y = c
  W_out); "bias" (no selection bias); "qk_norm" (q and k not normalised);
  "rotate" (nothing rotated); "other_share" (the held experts taken for the
  next chip's: `held_first` moved by the bank's length); `rope_theta` and
  `top_k` themselves may be given changed;
  `weights_as`: every matrix rounded to a narrower type first
  ("float8_e4m3fn"); `experts_as`: the experts' matrices alone.
"""

import jax
import jax.numpy as jnp
import numpy as np

from references._plain import rmsnorm, rope

QUERY_BLOCK = 256
HEAD_SLICES = 16


def _matrix(a, narrow):
    if narrow:
        a = a.astype(jnp.dtype(narrow))
    return a.astype(jnp.float32)


def _dense(p, x, sizes):
    return x @ _matrix(p["kernel"], sizes.get("weights_as")) + p["bias"]


def _conv(p, r, sizes):
    t, drop, chunk = r.shape[0], sizes.get("drop"), int(sizes.get("chunk", 0))
    gate_b, gate_c, x = jnp.split(_dense(p["w_in"], r, sizes), 3, axis=-1)
    u = gate_b * x
    taps = p["taps"][::-1] if drop == "taps" else p["taps"]
    width = taps.shape[0]
    ext = jnp.pad(u, ((width - 1, 0), (0, 0)))
    at = jnp.arange(t)
    c = 0.0
    for j in range(width):
        lag = ext[j:j + t]                   # the token width - 1 - j back
        if drop == "conv_tail":
            lag = jnp.where((at - (width - 1 - j) >= at // chunk * chunk)
                            [:, None], lag, 0.0)
        c = c + taps[j] * lag
    return _dense(p["w_out"], c if drop == "gate" else gate_c * c, sizes)


def _attention(p, r, sizes):
    t, heads, kv_heads = r.shape[0], sizes["n_heads"], sizes["n_kv_heads"]
    eps, drop = sizes["ln_eps"], sizes.get("drop")
    q = _dense(p["wq"], r, sizes).reshape(t, heads, -1)
    k = _dense(p["wk"], r, sizes).reshape(t, kv_heads, -1)
    v = _dense(p["wv"], r, sizes).reshape(t, kv_heads, -1)
    if drop != "qk_norm":
        q, k = rmsnorm(p["q_norm"], q, eps), rmsnorm(p["k_norm"], k, eps)
    if drop != "rotate":
        q, k = rope(q, sizes["rope_theta"]), rope(k, sizes["rope_theta"])
    k, v = (jnp.repeat(y, heads // kv_heads, axis=1) for y in (k, v))
    d = q.shape[-1]
    kpos = jnp.arange(t)

    def block(i):
        """QUERY_BLOCK queries against the whole sequence."""
        qb = jax.lax.dynamic_slice_in_dim(q, i * QUERY_BLOCK, QUERY_BLOCK)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        seen = kpos[None, :] <= qpos[:, None]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(float(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    o = jax.lax.map(block, jnp.arange(t // QUERY_BLOCK))
    return _dense(p["wo"], o.reshape(t, -1), sizes)


def _swiglu(p, z, sizes):
    return _dense(p["proj"], jax.nn.silu(_dense(p["gate"], z, sizes))
                  * _dense(p["up"], z, sizes), sizes)


def _experts(p, z, sizes):
    """Every HELD expert over every token, one expert's weights upcast at
    a time, each masked and weighted by the router's choice among ALL the
    experts; a pair routed outside the share adds nothing."""
    router, drop = p["router"], sizes.get("drop")
    s = jax.nn.sigmoid(z @ router["kernel"])
    biased = s if drop == "bias" else s + router["bias"]
    _, chosen = jax.lax.top_k(biased, sizes["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * sizes["routed_scale"]
    # (T, E): an expert's weight for a token, zero where it was not chosen.
    gates = jnp.zeros_like(s).at[
        jnp.arange(z.shape[0])[:, None], chosen].set(weight)
    bank = p["experts"]
    held = bank["down"].shape[0]
    first = sizes["held_first"]
    if drop == "other_share":
        first = (first + held) % s.shape[-1]
    narrow = sizes.get("experts_as") or sizes.get("weights_as")

    def one(y, e):
        def leaf(a):
            return _matrix(jax.lax.dynamic_index_in_dim(a, e, keepdims=False),
                           narrow)
        gate, up = jnp.split(z @ leaf(bank["gate_up"]), 2, axis=-1)
        out = (jax.nn.silu(gate) * up) @ leaf(bank["down"])
        mine = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1)
        return y + mine * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(z), jnp.arange(held))
    return y


# A layer's kind -> (its key in a block, the `drop` that leaves it out, it).
_OPERATORS = {"C": ("conv", "conv", _conv),
              "A": ("attn", "attention", _attention)}


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32."""
    sizes = dict(sizes)
    eps, drop = sizes["ln_eps"], sizes.get("drop")
    t = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -t % QUERY_BLOCK))
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][tokens].astype(jnp.float32)
        for layer, (kind, p) in enumerate(zip(sizes["layers"],
                                              params["layers"])):
            key, dropped_by, op = _OPERATORS[kind]
            if drop != dropped_by:
                x = x + op(p[key], rmsnorm(p["ln1"], x, eps), sizes)
            z = rmsnorm(p["ln2"], x, eps)
            if layer < sizes["n_dense_layers"]:
                x = x + _swiglu(p["mlp"], z, sizes)
            elif drop != "experts":
                x = x + _experts(p["mlp"], z, sizes)
        x = rmsnorm(params["ln_f"], x, eps)[:t]
        kernel, bias = params["head"]["kernel"], params["head"]["bias"]
        vocab = kernel.shape[1]
        width = vocab // HEAD_SLICES

        def head_slice(i, logits):
            k = jax.lax.dynamic_slice_in_dim(kernel, i * width, width, 1)
            return jax.lax.dynamic_update_slice_in_dim(
                logits, x @ _matrix(k, sizes.get("weights_as")),
                i * width, 1)

        return jax.lax.fori_loop(
            0, HEAD_SLICES, head_slice,
            jnp.zeros((t, vocab), jnp.float32)) + bias
