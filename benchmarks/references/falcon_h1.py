"""The falcon_h1 dialect: every block has TWO mixers on one normed input,
causal grouped-query attention with rotary positions and a Mamba-2
state-space recurrence, whose outputs are summed into the stream; then a
SwiGLU; muP multipliers on every branch. The served program keeps a layer's
keys and values in a paged pool and its recurrent state in a pool's row
between ticks, runs a prompt's chunk through a chunked matrix form of the
recurrence and a decode row through one step of it; this file attends under
a causal mask over one whole sequence and runs the recurrence as it is
written, a `lax.scan` over its tokens, so the two are held against each
other. It shares nothing with references/mamba2.py (one group, a conv over
x alone: another dialect).

Stream h (T x d), every layer alike, eps `ln_eps`:

  0  h = E[ids] * embedding_multiplier
  1  u = RMS_in(h)
  2  Attention: q = (u attention_in_multiplier) Wq, k = ((u
     attention_in_multiplier) Wk) * key_multiplier, v = (u
     attention_in_multiplier) Wv; `n_heads` query heads over `n_kv_heads`
     KV heads (query head i reads KV head i // (n_heads / n_kv_heads)), D
     lanes each (the shapes give D); rotate-half RoPE, `rope_theta`, over
     all D lanes of q and k; scores q.k / sqrt(D), causal;
     y_att = (concat Wo) * attention_out_multiplier.
  3  Mamba-2: [z | x | B | C | dt] = ((u ssm_in_multiplier) W_in) *
     mup_vector, the five `ssm_multipliers` over d_ssm | d_ssm | g N | g N
     | H lanes in that order (H = `ssm_heads`, g = `n_groups`, N =
     `d_state`, d_ssm = W_out's rows); x, B, C side by side pass a causal
     depthwise conv over the last `width` tokens (weights (width, lanes),
     the last row the current token's) plus its bias, then SiLU; x: H
     heads of P lanes, B and C: g groups of N lanes, head i reads group
     i // (H / g); dt = softplus(dt + dt_bias), A = -exp(A_log) a head;
     the state S (P x N a head, zero before token 0) follows

         S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,
         o_t = S_t C_t + D x_t;

     y_ssm = (RMS_grouped(o * SiLU(z)) W_out) * ssm_out_multiplier: the
     gate first, then the norm over each group's d_ssm / g lanes, with a
     learned scale of d_ssm lanes.
  4  h += y_att + y_ssm
  5  v = RMS_ff(h); h += ((v W_up) * SiLU((v W_gate) * mlp_multipliers[0]))
     W_down * mlp_multipliers[1]
  6  logits = (RMS_f(h) W_head) * lm_head_multiplier

Sizes read from the configuration's `reference` block: `n_heads`,
`n_kv_heads`, `rope_theta`, `ssm_heads`, `n_groups`, `d_state`, `ln_eps`
and the multipliers under their published names (`ssm_multipliers` and
`mlp_multipliers` comma-separated in a string: the block's values are
hashed). Parameter tree: tok_embed, layers (a list), ln_f, head; a block is
ln1, ln2, mlp {gate, up, proj}, attn {wq, wk, wv, wo} and ssm {w_in, conv,
conv_bias, A_log, dt_bias, D, norm, w_out}.

The server's leaves are bfloat16 and fill most of the chip. They are
exactly representable in float32 and are upcast a projection at a time, a
slice of the vocabulary inside the head; attention runs a block of
`QUERY_BLOCK` queries at a time.

Optional keys of the block serve the controls of `correct` (tests, and runs
on the chip); every one must read NOT correct:
  `drop`: "ssm" (the Mamba branch adds nothing), "attention" (the attention
  branch adds nothing), "decay" (exp(dt A) = 1), "group" (every head reads
  group 0's B and C), "skip" (D = 0), the name of a multiplier (it counts
  as 1; of `ssm_multipliers` and `mlp_multipliers` every entry), and, at
  every multiple of `chunk` tokens (a prompt's chunk boundaries in the
  served program): "conv_tail" (the conv sees nothing before the
  boundary), "state" (the state set to zero), "state_bf16" (the state
  rounded to bfloat16 there);
  `weights_as`: every matrix rounded to a narrower type first
  ("float8_e4m3fn").
"""

import jax
import jax.numpy as jnp
import numpy as np

from references._plain import rmsnorm, rope

QUERY_BLOCK = 256
# The head's slices of the vocabulary: the first count that divides it.
HEAD_SLICES = (20, 16, 8, 4, 2, 1)


def _multiplier(sizes, name):
    """A multiplier as published, 1 where the control drops it; the two
    lists as tuples."""
    value = sizes[name]
    if isinstance(value, str):
        value = tuple(float(v) for v in value.split(","))
    if sizes.get("drop") == name:
        return (1.0,) * len(value) if isinstance(value, tuple) else 1.0
    return value


def _dense(p, x, sizes):
    kernel = p["kernel"]
    if sizes.get("weights_as"):
        kernel = kernel.astype(jnp.dtype(sizes["weights_as"]))
    return x @ kernel.astype(jnp.float32) + p["bias"]


def _swiglu(p, v, sizes):
    gate_m, down_m = _multiplier(sizes, "mlp_multipliers")
    gate = jax.nn.silu(_dense(p["gate"], v, sizes) * gate_m)
    return _dense(p["proj"], _dense(p["up"], v, sizes) * gate, sizes) * down_m


def _attention(p, u, sizes):
    t, heads, kv_heads = u.shape[0], sizes["n_heads"], sizes["n_kv_heads"]
    x = u * _multiplier(sizes, "attention_in_multiplier")
    q = _dense(p["wq"], x, sizes).reshape(t, heads, -1)
    k = (_dense(p["wk"], x, sizes)
         * _multiplier(sizes, "key_multiplier")).reshape(t, kv_heads, -1)
    v = _dense(p["wv"], x, sizes).reshape(t, kv_heads, -1)
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
    return (_dense(p["wo"], o.reshape(t, -1), sizes)
            * _multiplier(sizes, "attention_out_multiplier"))


def _mamba(p, u, sizes):
    t, heads, groups, n = (u.shape[0], sizes["ssm_heads"], sizes["n_groups"],
                           sizes["d_state"])
    drop, chunk = sizes.get("drop"), int(sizes.get("chunk", 0))
    d_ssm = p["w_out"]["kernel"].shape[0]
    at = jnp.arange(t)
    proj = _dense(p["w_in"], u * _multiplier(sizes, "ssm_in_multiplier"),
                  sizes)
    parts, bound = [], 0
    for lanes, m in zip((d_ssm, d_ssm, groups * n, groups * n, heads),
                        _multiplier(sizes, "ssm_multipliers")):
        parts.append(proj[:, bound:bound + lanes] * m)
        bound += lanes
    z, x, b, c, dt = parts
    mixed = jnp.concatenate([x, b, c], axis=-1)
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
    if drop == "group":
        b, c = (jnp.broadcast_to(y[:, :1], y.shape) for y in (b, c))
    # Head i reads group i // (H / g).
    b, c = (jnp.repeat(y, heads // groups, axis=1) for y in (b, c))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = (jnp.ones_like(dt) if drop == "decay"
         else jnp.exp(dt * -jnp.exp(p["A_log"])))

    def token(s, x_t):
        xt, bt, ct, at_, dtt, i = x_t
        if drop in ("state", "state_bf16"):
            fault = (jnp.zeros_like(s) if drop == "state"
                     else s.astype(jnp.bfloat16).astype(jnp.float32))
            s = jnp.where((i > 0) & (i % chunk == 0), fault, s)
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
    y = y.reshape(t, -1) * p["norm"]["scale"]
    return (_dense(p["w_out"], y, sizes)
            * _multiplier(sizes, "ssm_out_multiplier"))


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32."""
    sizes = dict(sizes)
    eps, drop = sizes["ln_eps"], sizes.get("drop")
    t = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -t % QUERY_BLOCK))
    with jax.default_matmul_precision("highest"):
        x = (params["tok_embed"]["table"][tokens].astype(jnp.float32)
             * _multiplier(sizes, "embedding_multiplier"))
        for p in params["layers"]:
            u = rmsnorm(p["ln1"], x, eps)
            y = 0.0
            if drop != "attention":
                y = y + _attention(p["attn"], u, sizes)
            if drop != "ssm":
                y = y + _mamba(p["ssm"], u, sizes)
            x = x + y
            x = x + _swiglu(p["mlp"], rmsnorm(p["ln2"], x, eps), sizes)
        x = rmsnorm(params["ln_f"], x, eps)[:t]
        kernel, bias = params["head"]["kernel"], params["head"]["bias"]
        vocab = kernel.shape[1]
        slices = next(n for n in HEAD_SLICES if vocab % n == 0)
        width = vocab // slices

        def head_slice(i, logits):
            k = jax.lax.dynamic_slice_in_dim(kernel, i * width, width, 1)
            if sizes.get("weights_as"):
                k = k.astype(jnp.dtype(sizes["weights_as"]))
            return jax.lax.dynamic_update_slice_in_dim(
                logits, x @ k.astype(jnp.float32), i * width, 1)

        logits = jax.lax.fori_loop(
            0, slices, head_slice, jnp.zeros((t, vocab), jnp.float32))
        return (logits + bias) * _multiplier(sizes, "lm_head_multiplier")
