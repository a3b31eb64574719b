"""The granite_hybrid dialect (Granite-4.0-H, `granitemoehybrid`): EVERY
layer is a mixer and then an expert block. The mixer is, by a published list, a
Mamba-2 state-space recurrence or causal grouped-query attention with
nothing rotated; the expert block is soft-max-routed SwiGLU experts beside
one shared SwiGLU; four published scalars sit on the stream. The served
program keeps a mamba layer's state in a pool's row and the attention
layer's keys and values in a paged pool between ticks, runs a prompt's chunk
through a chunked matrix form of the recurrence and a decode row through one
step of it, scales q before a kernel that divides by sqrt(D) itself, and
sorts a tick's (token, expert) pairs into one grouped product over the
experts its chip holds; this file runs the recurrence as it is written, a
`lax.scan` over its tokens, attends under a causal mask over one whole
sequence with the published scale on the scores, and applies every held
expert to every token under the router's mask, so the two are held against
each other.

Stream h (T x d), eps `ln_eps`, no bias but the conv's; layer l of kind
`layers[l]`:

  0  h = `embedding_multiplier` * E[ids]
  1  u = RMS_l(h);   h += `residual_multiplier` * mixer(u)
     v = RMS'_l(h);  h += `residual_multiplier` * (experts(v) + shared(v))
  mamba      [z | x | B | C | dt] = u W_in, d_ssm | d_ssm | g N | g N | H
     lanes in that order (H = `ssm_heads`, g = `n_groups`, N = `d_state`,
     d_ssm = W_out's rows); x, B, C side by side pass a causal depthwise conv
     over the last `width` tokens (weights (width, lanes), the last row the
     current token's) plus its bias, then SiLU; x: H heads of P lanes, B and
     C: g groups of N lanes, head i reads group i // (H / g) (g = 1 as
     published: every head reads the same lanes); dt = softplus(dt +
     dt_bias), A = -exp(A_log) a head; the state S (P x N a head, zero
     before token 0) follows

         S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,
         o_t = S_t C_t + D x_t;

     y = RMS_grouped(o * SiLU(z)) W_out: the gate first, then the norm over
     each group's d_ssm / g lanes, with a learned scale of d_ssm lanes.
  attention  q = u Wq (`n_heads` heads), k, v = u Wk, u Wv (`n_kv_heads`;
     query head i reads KV head i // (n_heads / n_kv_heads)); NO rotation;
     scores q.k * `attention_multiplier`, causal; y = concat Wo.
  experts    l = v W_r over all the experts; the `top_k` largest are chosen;
     w = soft-max over the CHOSEN logits; r = sum over the chosen HELD
     experts e of w_e (SiLU(g_e) * p_e) W_out[e], [g_e | p_e] = v W_in[e]
     (the bank holds experts `held_first` on, as many as it is long: a pair
     routed outside the share adds nothing, in the program and here alike).
  shared     [g | p] = v W_s_in; (SiLU(g) * p) W_s_out.
  2  logits = RMS_f(h) W_head / `logits_scaling`

Sizes read from the configuration's `reference` block: `layers` (a letter
a layer, M a mamba mixer and A an attention one), `n_heads`, `n_kv_heads`,
`ssm_heads`, `n_groups`, `d_state`, `ln_eps`, `top_k`, `held_first`, `embedding_multiplier`, `residual_multiplier`,
`attention_multiplier`, `logits_scaling`. Parameter tree: tok_embed, layers
(a list), ln_f, head; a block is ln1, ln2, one of ssm {w_in, conv,
conv_bias, A_log, dt_bias, D, norm, w_out} and attn {wq, wk, wv, wo}, and
mlp {router {kernel}, shared {gate_up, proj}, experts {gate_up, down}}.

The server's leaves are bfloat16 and fill most of the chip. They are
exactly representable in float32 and are upcast a projection at a time, ONE
expert inside the loop over experts, a slice of the vocabulary inside the
head; attention runs a block of `QUERY_BLOCK` queries at a time.

Optional keys of the block serve the controls of `correct` (tests, and runs
on the chip); which of them the chip's limits cannot tell the
configuration's `correct.why` says:
  `drop`: "mamba", "attention", "experts" (every layer's part of the kind
  adds nothing); "shared" (no shared expert); "score_scale" (the scores
  times 1/sqrt(D) in place of `attention_multiplier`); "residual"
  (`residual_multiplier` 1); "embedding" (`embedding_multiplier` 1);
  "logits" (`logits_scaling` 1: moves no arg-max); "rotate" (q and k
  rotated, rotate-half at `rope_theta`, all lanes); "decay" (exp(dt A) =
  1); "skip" (D = 0); "softmax_all" (the weights a soft-max over ALL
  the logits, the chosen ones NOT renormalised); "other_share" (the held
  experts taken for the next chip's: `held_first` moved by the bank's
  length); and, at every multiple of `chunk` tokens (a prompt's chunk
  boundaries in the served program): "conv_tail" (the conv sees nothing
  before the boundary), "state" (the state set to zero); `top_k` itself
  may be given smaller (the top 10 cut to the top 6);
  `weights_as`: every matrix rounded to a narrower type first
  ("float8_e4m3fn"); `experts_as`: the routed experts' banks alone.
"""

import jax
import jax.numpy as jnp
import numpy as np

from references._plain import rmsnorm, rope

QUERY_BLOCK = 256
HEAD_SLICES = 16


def _matrix(a, sizes, key="weights_as"):
    narrower = sizes.get(key) or sizes.get("weights_as")
    if narrower:
        a = a.astype(jnp.dtype(narrower))
    return a.astype(jnp.float32)


def _dense(p, x, sizes):
    return x @ _matrix(p["kernel"], sizes) + p["bias"]


def _attention(p, u, sizes):
    t, heads, kv_heads = u.shape[0], sizes["n_heads"], sizes["n_kv_heads"]
    drop = sizes.get("drop")
    q = _dense(p["wq"], u, sizes).reshape(t, heads, -1)
    k = _dense(p["wk"], u, sizes).reshape(t, kv_heads, -1)
    v = _dense(p["wv"], u, sizes).reshape(t, kv_heads, -1)
    if drop == "rotate":
        q, k = rope(q, sizes["rope_theta"]), rope(k, sizes["rope_theta"])
    k, v = (jnp.repeat(y, heads // kv_heads, axis=1) for y in (k, v))
    scale = (1.0 / np.sqrt(float(q.shape[-1])) if drop == "score_scale"
             else sizes["attention_multiplier"])
    kpos = jnp.arange(t)

    def block(i):
        """QUERY_BLOCK queries against the whole sequence."""
        qb = jax.lax.dynamic_slice_in_dim(q, i * QUERY_BLOCK, QUERY_BLOCK)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        seen = kpos[None, :] <= qpos[:, None]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    o = jax.lax.map(block, jnp.arange(t // QUERY_BLOCK))
    return _dense(p["wo"], o.reshape(t, -1), sizes)


def _mamba(p, u, sizes):
    t, heads, groups, n = (u.shape[0], sizes["ssm_heads"], sizes["n_groups"],
                           sizes["d_state"])
    drop, chunk = sizes.get("drop"), int(sizes.get("chunk", 0))
    d_ssm = p["w_out"]["kernel"].shape[0]
    at = jnp.arange(t)
    proj = _dense(p["w_in"], u, sizes)
    z = proj[:, :d_ssm]
    mixed = proj[:, d_ssm:2 * d_ssm + 2 * groups * n]      # x | B | C
    dt = proj[:, 2 * d_ssm + 2 * groups * n:]
    width = p["conv"].shape[0]
    ext = jnp.pad(mixed, ((width - 1, 0), (0, 0)))
    out = p["conv_bias"]
    for j in range(width):
        tap = ext[j:j + t]                   # the token width - 1 - j back
        if drop == "conv_tail":
            tap = jnp.where((at - (width - 1 - j) >= at // chunk * chunk)
                            [:, None], tap, 0.0)
        out = out + p["conv"][j] * tap
    out = jax.nn.silu(out)
    x = out[:, :d_ssm].reshape(t, heads, -1)
    b = out[:, d_ssm:d_ssm + groups * n].reshape(t, groups, n)
    c = out[:, d_ssm + groups * n:].reshape(t, groups, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = (jnp.ones_like(dt) if drop == "decay"
         else jnp.exp(dt * -jnp.exp(p["A_log"])))

    def token(s, x_t):
        xt, bt, ct, at_, dtt, i = x_t
        # Head i reads group i // (H / g).
        bt, ct = (jnp.repeat(y, heads // groups, axis=0) for y in (bt, ct))
        if drop == "state":
            s = jnp.where((i > 0) & (i % chunk == 0), jnp.zeros_like(s), s)
        s = (at_[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, ct)

    _, o = jax.lax.scan(
        token, jnp.zeros((heads, x.shape[-1], n), jnp.float32),
        (x, b, c, a, dt, at))
    if drop != "skip":
        o = o + p["D"][:, None] * x
    y = (o.reshape(t, -1) * jax.nn.silu(z)).reshape(t, groups, -1)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True) + sizes["ln_eps"])
    return _dense(p["w_out"], y.reshape(t, -1) * p["norm"]["scale"], sizes)


def _swiglu(x, w_in, w_out):
    gate, up = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out


def _experts(p, v, sizes):
    """Every HELD expert over every token, one expert's weights upcast at a
    time, each masked and weighted by the router's choice among ALL the
    experts; a pair routed outside the share adds nothing."""
    drop = sizes.get("drop")
    logits = v @ p["router"]["kernel"]
    picked, chosen = jax.lax.top_k(logits, sizes["top_k"])
    weight = (jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), chosen,
                                  axis=-1) if drop == "softmax_all"
              else jax.nn.softmax(picked, axis=-1))
    # (T, E): an expert's weight for a token, zero where it was not chosen.
    gates = jnp.zeros_like(logits).at[
        jnp.arange(v.shape[0])[:, None], chosen].set(weight)
    bank = p["experts"]
    held = bank["gate_up"].shape[0]
    first = sizes["held_first"]
    if drop == "other_share":
        first = (first + held) % logits.shape[-1]

    def one(r, e):
        def leaf(a):
            return _matrix(jax.lax.dynamic_index_in_dim(a, e, keepdims=False),
                           sizes, "experts_as")
        out = _swiglu(v, leaf(bank["gate_up"]), leaf(bank["down"]))
        mine = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1)
        return r + mine * out, None

    r, _ = jax.lax.scan(one, jnp.zeros_like(v), jnp.arange(held))
    return r


def _expert_block(p, v, sizes):
    drop = sizes.get("drop")
    y = jnp.zeros_like(v) if drop == "experts" else _experts(p, v, sizes)
    if drop != "shared":
        shared = p["shared"]
        y = y + _swiglu(v, _matrix(shared["gate_up"]["kernel"], sizes),
                        _matrix(shared["proj"]["kernel"], sizes))
    return y


_MIXERS = {"M": ("mamba", "ssm", _mamba),
           "A": ("attention", "attn", _attention)}


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32."""
    sizes = dict(sizes)
    eps, drop = sizes["ln_eps"], sizes.get("drop")
    into = 1.0 if drop == "residual" else sizes["residual_multiplier"]
    t = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -t % QUERY_BLOCK))
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][tokens].astype(jnp.float32)
        if drop != "embedding":
            x = x * sizes["embedding_multiplier"]
        for kind, p in zip(sizes["layers"], params["layers"]):
            name, key, mixer = _MIXERS[kind]
            if drop != name:
                x = x + into * mixer(p[key], rmsnorm(p["ln1"], x, eps), sizes)
            x = x + into * _expert_block(p["mlp"], rmsnorm(p["ln2"], x, eps),
                                         sizes)
        x = rmsnorm(params["ln_f"], x, eps)[:t]
        kernel, bias = params["head"]["kernel"], params["head"]["bias"]
        vocab = kernel.shape[1]
        width = vocab // HEAD_SLICES

        def head_slice(i, logits):
            k = jax.lax.dynamic_slice_in_dim(kernel, i * width, width, 1)
            return jax.lax.dynamic_update_slice_in_dim(
                logits, x @ _matrix(k, sizes), i * width, 1)

        logits = jax.lax.fori_loop(
            0, HEAD_SLICES, head_slice,
            jnp.zeros((t, vocab), jnp.float32)) + bias
        return logits if drop == "logits" else logits / sizes["logits_scaling"]
