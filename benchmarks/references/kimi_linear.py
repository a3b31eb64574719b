"""The kimi_linear dialect: pre-norm RMSNorm blocks whose mixer is Kimi Delta
Attention (a gated-delta-rule recurrence whose decay is a key CHANNEL's) on
some layers and latent attention (MLA, nothing rotated) on the others, a
SwiGLU FFN in the first `n_dense_layers` layers and sigmoid-routed SwiGLU
experts with one shared expert in the others. The served program carries a
KDA layer's state between ticks in a pool's row, runs a prompt's chunk
through a chunked matrix form of the recurrence and a decode row through one
step of it, and caches only a latent a token for the MLA layers, read
absorbed; this file scans the recurrence a token at a time over one whole
sequence and makes every head's key and value from the latent under a mask,
so the two are held against each other.

Layer l, stream h (T x d):

  1  h += Mixer_l(RMS(h)); h += FFN_l(RMS(h)), eps `ln_eps`; x = RMS(h).
  2  KDA layer (`linear[l]` 1): q~, k~, v~ = x Wq, x Wk, x Wv (`lin_heads`
     heads of `lin_key_dim` lanes; the shapes give d_v); the three, side by
     side, pass a causal depthwise conv over the last `width` tokens
     (weights (width, lanes), the last row the current token's, no bias),
     then SiLU; q and k L2-normalised a head (x / sqrt(sum x^2 + 1e-6)), q
     times 1/sqrt(d_k); g = -exp(A_log[head]) softplus(x Wf_down Wf_up +
     dt_bias), one number a head AND key channel, a = exp(g);
     b = sigmoid(x Wb) a head; the state S (d_v x d_k a head, zero before
     token 0) follows

         S_t = S_{t-1} Diag(a_t) + b_t (v_t - S_{t-1} Diag(a_t) k_t) k_t^T,
         o_t = S_t q_t;

     y = (RMS_head(o) (d_v lanes, learned scale) x sigmoid(x Wg_down
     Wg_up)) Wo.
  3  MLA layer (`linear[l]` 0): q = x Wq -> per head q_nope ‖ q_pe;
     [c_raw ‖ k_pe] = x Wkv_a; c = RMS(c_raw) (its own scale, eps
     `kv_norm_eps`); NOTHING is rotated: k_pe is one plain key of `qk_rope`
     lanes for all heads; [k_nope ‖ v]_head = c Wkv_b;
     score = (q_nope.k_nope + q_pe.k_pe) / sqrt(nope + rope), causal
     soft-max, out = concat_heads(sum p v) Wo.
  4  FFN: layer l < `n_dense_layers`: Wdown(silu(Wgate x) * Wup x). Else:
     s = sigmoid(x Wr) over ALL the router's outputs, the top `top_k` of
     s + b chosen, w = s[chosen] / sum x `routed_scale`;
     sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x).
  5  final RMS, head.

**One chip's share of a deployment is what is handed over** (as
references/laguna.py): the tree holds the routed experts from expert
`held_first` on, as many as the banks are long, and as many rows of the
vocabulary as the head is wide. A (token, expert) pair routed outside the
share adds nothing HERE, in the program and in this file alike.

Sizes read from the configuration's `reference` block: `linear` (one 0/1 a
layer, comma-separated in a string: the block's values are hashed),
`n_heads`, `qk_nope`, `qk_rope`, `lin_heads`, `lin_key_dim`,
`n_dense_layers`, `top_k`, `routed_scale`, `held_first`, `ln_eps`,
`kv_norm_eps`. Parameter tree: tok_embed, layers (a list), ln_f, head; a
block is ln1, ln2, mlp ({gate, up, proj} or {router{kernel, bias},
shared{gate, up, proj}, experts{gate_up (held, d, 2f), down (held, f, d)}})
and attn {wq, wkv_a, kv_norm, wkv_b, wo} or lin {wq, wk, wv, wo, wf_down,
wf_up, wg_down, wg_up, wb, conv, A_log, dt_bias, o_norm}.

The server's leaves are bfloat16 and fill most of the chip. They are
exactly representable in float32 and are upcast a projection at a time, ONE
expert inside the loop over experts, a slice of the vocabulary inside the
head; attention runs a block of `QUERY_BLOCK` queries at a time.

Optional keys of the block serve the controls of `correct` (tests, and runs
on the chip); every one must read NOT correct, and which of them the chip's
limits cannot tell the configuration's `correct.why` says:
  `drop`: "decay" (a = 1); "gate_mean" (a channel's gate replaced by the
  mean over its head's channels: the scalar-gate rule under this name);
  "rotate" (q_pe and k_pe rotated, rotate-half at `rope_theta`); "bias" (no
  selection bias); "shared" (no shared expert); "other_half" (the held
  experts taken for the other chip's: `held_first` moved by the banks'
  length); and, at every multiple of `chunk` tokens (a prompt's chunk
  boundaries in the served program): "conv_tail" (the conv sees nothing
  before the boundary), "state" (the state set to zero), "state_bf16" (the
  state rounded to bfloat16 there); "state_bf16_step" (the state rounded
  to bfloat16 after EVERY token);
  `weights_as`: every matrix rounded to a narrower type first
  ("float8_e4m3fn").
"""

import jax
import jax.numpy as jnp
import numpy as np

from references._plain import rmsnorm, rope

HEAD_SLICES = 16
QUERY_BLOCK = 256


def _matrix(a, sizes):
    if sizes.get("weights_as"):
        a = a.astype(jnp.dtype(sizes["weights_as"]))
    return a.astype(jnp.float32)


def _dense(p, x, sizes):
    return x @ _matrix(p["kernel"], sizes) + p["bias"]


def _swiglu(p, x, sizes):
    return _dense(p["proj"], jax.nn.silu(_dense(p["gate"], x, sizes))
                  * _dense(p["up"], x, sizes), sizes)


def _mla(p, x, sizes):
    t = x.shape[0]
    heads, nope, rot = sizes["n_heads"], sizes["qk_nope"], sizes["qk_rope"]
    q = _dense(p["wq"], x, sizes).reshape(t, heads, nope + rot)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv_a = _dense(p["wkv_a"], x, sizes)
    rank = kv_a.shape[-1] - rot
    c = rmsnorm(p["kv_norm"], kv_a[:, :rank], sizes["kv_norm_eps"])
    k_pe = kv_a[:, rank:]
    if sizes.get("drop") == "rotate":
        q_pe = rope(q_pe, sizes["rope_theta"])
        k_pe = rope(k_pe[:, None], sizes["rope_theta"])[:, 0]
    kv = _dense(p["wkv_b"], c, sizes).reshape(t, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    kpos = jnp.arange(t)

    def block(i):
        """QUERY_BLOCK queries against the whole sequence."""
        def queries(y):
            return jax.lax.dynamic_slice_in_dim(y, i * QUERY_BLOCK,
                                                QUERY_BLOCK)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        scores = (jnp.einsum("qhn,khn->hqk", queries(q_nope), k_nope)
                  + jnp.einsum("qhr,kr->hqk", queries(q_pe), k_pe))
        scores = scores / np.sqrt(float(nope + rot))
        seen = kpos[None, :] <= qpos[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khv->qhv", probs, v)

    o = jax.lax.map(block, jnp.arange(t // QUERY_BLOCK))
    return _dense(p["wo"], o.reshape(t, -1), sizes)


def _kda(p, x, sizes):
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

    def low_rank(name):
        return _dense(p[name + "_up"], _dense(p[name + "_down"], x, sizes),
                      sizes)

    q, k = unit(q) / np.sqrt(float(dk)), unit(k)
    beta = jax.nn.sigmoid(_dense(p["wb"], x, sizes))
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        low_rank("wf") + p["dt_bias"]).reshape(t, heads, dk)
    if drop == "gate_mean":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    a = jnp.ones_like(g) if drop == "decay" else jnp.exp(g)

    def token(s, x_t):
        q_t, k_t, v_t, a_t, b_t, i = x_t
        if drop in ("state", "state_bf16"):
            fault = (jnp.zeros_like(s) if drop == "state"
                     else s.astype(jnp.bfloat16).astype(jnp.float32))
            s = jnp.where((i > 0) & (i % chunk == 0), fault, s)
        elif drop == "state_bf16_step":
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        s = s * a_t[:, None, :]
        u = b_t[:, None] * (v_t - jnp.einsum("hvk,hk->hv", s, k_t))
        s = s + u[:, :, None] * k_t[:, None, :]
        return s, jnp.einsum("hvk,hk->hv", s, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros((heads, v.shape[-1], dk), jnp.float32),
        (q, k, v, a, beta, at))
    y = rmsnorm(p["o_norm"], o, sizes["ln_eps"]).reshape(t, -1)
    return _dense(p["wo"], y * jax.nn.sigmoid(low_rank("wg")), sizes)


def _experts(p, x, sizes):
    """Every HELD expert over every token, one expert's weights upcast at
    a time, each masked and weighted by the router's choice among all the
    experts; a pair routed outside the share adds nothing."""
    router = p["router"]
    drop = sizes.get("drop")
    s = jax.nn.sigmoid(x @ router["kernel"])
    biased = s if drop == "bias" else s + router["bias"]
    _, chosen = jax.lax.top_k(biased, sizes["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * sizes["routed_scale"]
    # (T, E): an expert's weight for a token, zero where it was not chosen.
    gates = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weight)
    bank = p["experts"]
    held = bank["down"].shape[0]
    first = sizes["held_first"]
    if drop == "other_half":
        first = (first + held) % s.shape[-1]

    def one(y, e):
        def leaf(a):
            return _matrix(jax.lax.dynamic_index_in_dim(a, e, keepdims=False),
                           sizes)
        gate, up = jnp.split(x @ leaf(bank["gate_up"]), 2, axis=-1)
        out = (jax.nn.silu(gate) * up) @ leaf(bank["down"])
        mine = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1)
        return y + mine * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    if drop != "shared":
        y = y + _swiglu(p["shared"], x, sizes)
    return y


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32."""
    sizes = dict(sizes)
    linear = [int(v) for v in sizes["linear"].split(",")]
    eps = sizes["ln_eps"]
    t = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -t % QUERY_BLOCK))
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][tokens].astype(jnp.float32)
        for layer, (p, is_linear) in enumerate(zip(params["layers"], linear)):
            y = rmsnorm(p["ln1"], x, eps)
            x = x + (_kda(p["lin"], y, sizes) if is_linear
                     else _mla(p["attn"], y, sizes))
            y = rmsnorm(p["ln2"], x, eps)
            x = x + (_swiglu(p["mlp"], y, sizes)
                     if layer < sizes["n_dense_layers"]
                     else _experts(p["mlp"], y, sizes))
        x = rmsnorm(params["ln_f"], x, eps)[:t]
        kernel, bias = params["head"]["kernel"], params["head"]["bias"]
        vocab = kernel.shape[1]
        width = vocab // HEAD_SLICES

        def head_slice(i, logits):
            k = jax.lax.dynamic_slice_in_dim(kernel, i * width, width, 1)
            return jax.lax.dynamic_update_slice_in_dim(
                logits, x @ _matrix(k, sizes), i * width, 1)

        return jax.lax.fori_loop(
            0, HEAD_SLICES, head_slice,
            jnp.zeros((t, vocab), jnp.float32)) + bias
