"""The olmo_hybrid dialect: blocks whose norm FOLLOWS the branch, a mixer
that is a gated-delta-rule recurrence on some layers and full causal
attention (MHA, no rotary embedding) on the others, a SwiGLU FFN. The served
program carries a recurrent layer's state between ticks in a pool's row,
runs a prompt's chunk through a chunked matrix form of the recurrence and a
decode row through one step of it; this file runs the recurrence as it is
written, a `lax.scan` over the tokens of one whole sequence, so the two are
held against each other.

Layer l, stream h (T x d):

  1  h += RMS(Mixer_l(h)); h += RMS(SwiGLU(h)): the norm (learned scale,
     eps `ln_eps`) is on the branch's OUTPUT, the mixer reads h itself.
  2  Full layer (`linear[l]` 0): q, k, v = h Wq, h Wk, h Wv, `n_heads`
     heads of D lanes each (the shapes give D); q and k RMS-normalised
     over ALL n_heads x D lanes with a learned scale; no rotary embedding;
     scores q.k / sqrt(D), causal; concat Wo.
  3  Linear layer (`linear[l]` 1): q~, k~ = h Wq, h Wk (`lin_heads` x d_k),
     v~ = h Wv (`lin_heads` x d_v); the three, side by side, pass a causal
     depthwise conv over the last `width` tokens (weights (width, lanes),
     the last row the current token's, no bias), then SiLU; q and k
     L2-normalised a head (x / sqrt(sum x^2 + 1e-6)), q times 1/sqrt(d_k);
     b = 2 sigmoid(h Wb) a head (`neg_eigval` 1: b in (0, 2); else 1 x),
     g = -exp(A_log) softplus(h Wa + dt_bias), a = exp(g); the state S
     (d_v x d_k a head, zero before token 0) follows

         S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T,  o_t = S_t q_t;

     y = RMS_head(o) (d_v lanes, learned scale) x SiLU(h Wz); y Wo.
  4  final RMS, head.

Sizes read from the configuration's `reference` block: `linear` (one 0/1 a
layer, comma-separated in a string: the block's values are hashed),
`n_heads`, `lin_heads`, `lin_key_dim`, `neg_eigval`, `ln_eps`. Parameter
tree: tok_embed, layers (a list), ln_f, head; a block is ln1, ln2, mlp
{gate, up, proj} and attn {wq, wk, wv, wo, q_norm, k_norm} or lin {wq, wk,
wv, wz, wo, wa, wb, conv, A_log, dt_bias, o_norm}.

The server's leaves are bfloat16 and fill most of the chip. They are
exactly representable in float32 and are upcast a projection at a time, a
slice of the vocabulary inside the head; attention runs a block of
`QUERY_BLOCK` queries at a time.

Optional keys of the block serve the controls of `correct` (tests, and runs
on the chip); every one must read NOT correct, and on the chip every one
does but the two that round the state to bfloat16, which move a logit by
less than the bfloat16 weights do (the configuration's `correct.why`; the
float32 tier-1 tests tell them apart):
  `drop`: "decay" (a = 1), "double" (b not doubled), and, at every
  multiple of `chunk` tokens (a prompt's chunk boundaries in the served
  program): "conv_tail" (the conv sees nothing before the boundary),
  "state" (the state set to zero), "state_bf16" (the state rounded to
  bfloat16 there); "state_bf16_step" (the state rounded to bfloat16 after
  EVERY token, as a decode tick would leave it);
  `weights_as`: every matrix rounded to a narrower type first
  ("float8_e4m3fn").
"""

import jax
import jax.numpy as jnp
import numpy as np

from references._plain import rmsnorm

HEAD_SLICES = 16
QUERY_BLOCK = 256


def _dense(p, x, sizes):
    kernel = p["kernel"]
    if sizes.get("weights_as"):
        kernel = kernel.astype(jnp.dtype(sizes["weights_as"]))
    return x @ kernel.astype(jnp.float32) + p["bias"]


def _swiglu(p, x, sizes):
    return _dense(p["proj"], jax.nn.silu(_dense(p["gate"], x, sizes))
                  * _dense(p["up"], x, sizes), sizes)


def _attention(p, x, sizes):
    t, heads = x.shape[0], sizes["n_heads"]
    q = rmsnorm(p["q_norm"], _dense(p["wq"], x, sizes), sizes["ln_eps"])
    k = rmsnorm(p["k_norm"], _dense(p["wk"], x, sizes), sizes["ln_eps"])
    q, k, v = (y.reshape(t, heads, -1)
               for y in (q, k, _dense(p["wv"], x, sizes)))
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


def _linear(p, x, sizes):
    t, heads, dk = x.shape[0], sizes["lin_heads"], sizes["lin_key_dim"]
    drop, chunk = sizes.get("drop"), int(sizes.get("chunk", 0))
    at = jnp.arange(t)
    mixed = jnp.concatenate([_dense(p[w], x, sizes)
                             for w in ("wq", "wk", "wv")], axis=-1)
    width = p["conv"].shape[0]
    ext = jnp.pad(mixed, ((width - 1, 0), (0, 0)))
    out = 0.0
    for j in range(width):
        tap = ext[j:j + t]                   # the token width - 1 - j back
        if drop == "conv_tail":
            tap = jnp.where((at - (width - 1 - j) >= at // chunk * chunk)
                            [:, None], tap, 0.0)
        out = out + p["conv"][j] * tap
    out = jax.nn.silu(out)
    q, k, v = (y.reshape(t, heads, -1)
               for y in jnp.split(out, (heads * dk, 2 * heads * dk), -1))

    def unit(y):
        return y / jnp.sqrt((y * y).sum(-1, keepdims=True) + 1e-6)

    q, k = unit(q) / np.sqrt(float(dk)), unit(k)
    beta = jax.nn.sigmoid(_dense(p["wb"], x, sizes))
    if sizes["neg_eigval"] and drop != "double":
        beta = 2.0 * beta
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        _dense(p["wa"], x, sizes) + p["dt_bias"])
    a = jnp.ones_like(g) if drop == "decay" else jnp.exp(g)

    def token(s, x_t):
        q_t, k_t, v_t, a_t, b_t, i = x_t
        if drop in ("state", "state_bf16"):
            fault = (jnp.zeros_like(s) if drop == "state"
                     else s.astype(jnp.bfloat16).astype(jnp.float32))
            s = jnp.where((i > 0) & (i % chunk == 0), fault, s)
        elif drop == "state_bf16_step":
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        s = a_t[:, None, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hvk,hk->hv", s, k_t))
        s = s + u[:, :, None] * k_t[:, None, :]
        return s, jnp.einsum("hvk,hk->hv", s, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros((heads, v.shape[-1], dk), jnp.float32),
        (q, k, v, a, beta, at))
    y = rmsnorm(p["o_norm"], o, sizes["ln_eps"]).reshape(t, -1)
    return _dense(p["wo"], y * jax.nn.silu(_dense(p["wz"], x, sizes)),
                  sizes)


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32."""
    sizes = dict(sizes)
    linear = [int(v) for v in sizes["linear"].split(",")]
    eps = sizes["ln_eps"]
    t = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -t % QUERY_BLOCK))
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][tokens].astype(jnp.float32)
        for p, is_linear in zip(params["layers"], linear):
            y = (_linear(p["lin"], x, sizes) if is_linear
                 else _attention(p["attn"], x, sizes))
            x = x + rmsnorm(p["ln1"], y, eps)
            x = x + rmsnorm(p["ln2"], _swiglu(p["mlp"], x, sizes), eps)
        x = rmsnorm(params["ln_f"], x, eps)[:t]
        kernel, bias = params["head"]["kernel"], params["head"]["bias"]
        vocab = kernel.shape[1]
        width = vocab // HEAD_SLICES

        def head_slice(i, logits):
            k = jax.lax.dynamic_slice_in_dim(kernel, i * width, width, 1)
            if sizes.get("weights_as"):
                k = k.astype(jnp.dtype(sizes["weights_as"]))
            return jax.lax.dynamic_update_slice_in_dim(
                logits, x @ k.astype(jnp.float32), i * width, 1)

        return jax.lax.fori_loop(
            0, HEAD_SLICES, head_slice,
            jnp.zeros((t, vocab), jnp.float32)) + bias
