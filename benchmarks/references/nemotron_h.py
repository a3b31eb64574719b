"""The nemotron_h dialect: a layer is ONE mixer of three kinds by a pattern
string (M a Mamba-2 state-space recurrence, * causal grouped-query attention
with nothing rotated, E a LatentMoE whose routed experts read and write a
latent narrower than the model beside one shared expert), and nothing
follows a mixer. The served program keeps an M layer's state in a pool's row
and a * layer's keys and values in a paged pool between ticks, runs a
prompt's chunk through a chunked matrix form of the recurrence and a decode
row through one step of it, and sorts a tick's (token, expert) pairs into
one grouped product over the experts its chip holds; this file runs the
recurrence as it is written, a `lax.scan` over its tokens, attends under a
causal mask over one whole sequence, and applies every held expert to every
token under a mask, so the two are held against each other.

Stream h (T x d), eps `ln_eps`, no bias but the conv's; layer l of kind
`pattern[l]`:

  0  h = E[ids]
  1  u = RMS_l(h);  h += mixer(u)
  M  [z | x | B | C | dt] = u W_in, d_ssm | d_ssm | g N | g N | H lanes in
     that order (H = `ssm_heads`, g = `n_groups`, N = `d_state`, d_ssm =
     W_out's rows); x, B, C side by side pass a causal depthwise conv over
     the last `width` tokens (weights (width, lanes), the last row the
     current token's) plus its bias, then SiLU; x: H heads of P lanes, B and
     C: g groups of N lanes, head i reads group i // (H / g); dt =
     softplus(dt + dt_bias), A = -exp(A_log) a head; the state S (P x N a
     head, zero before token 0) follows

         S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,
         o_t = S_t C_t + D x_t;

     y = RMS_grouped(o * SiLU(z)) W_out: the gate first, then the norm over
     each group's d_ssm / g lanes, with a learned scale of d_ssm lanes.
  *  q = u Wq (`n_heads` heads), k, v = u Wk, u Wv (`n_kv_heads`; query head
     i reads KV head i // (n_heads / n_kv_heads)); NO rotation; scores
     q.k / sqrt(D), causal; y = concat Wo.
  E  s = sigmoid(u W_r) over all the experts; the `top_k` largest of s +
     bias are chosen; w = s[chosen] / sum(s[chosen]) * `routed_scale`;
     v = u W_lat_down; r = sum over the chosen HELD experts e of w_e
     relu(v W_up[e])^2 W_down[e] (the bank holds experts `held_first` on, as
     many as it is long: a pair routed outside the share adds nothing, in
     the program and here alike); y = r W_lat_up + relu(u W_s_up)^2 W_s_down.
  2  logits = RMS_f(h) W_head

Sizes read from the configuration's `reference` block: `pattern`, `n_heads`,
`n_kv_heads`, `ssm_heads`, `n_groups`, `d_state`, `ln_eps`, `top_k`,
`routed_scale`, `held_first`. Parameter tree: tok_embed, layers (a list),
ln_f, head; a block is ln1 and one of ssm {w_in, conv, conv_bias, A_log,
dt_bias, D, norm, w_out}, attn {wq, wk, wv, wo}, mlp {router, latent_down,
latent_up, shared {up, proj}, experts {up, down}}.

The server's leaves are bfloat16 and fill most of the chip. They are
exactly representable in float32 and are upcast a projection at a time, ONE
expert inside the loop over experts, a slice of the vocabulary inside the
head; attention runs a block of `QUERY_BLOCK` queries at a time.

Optional keys of the block serve the controls of `correct` (tests, and runs
on the chip); which of them the chip's limits cannot tell the
configuration's `correct.why` says:
  `drop`: "mamba", "attention", "experts" (every layer of the kind adds
  nothing); "decay" (exp(dt A) = 1); "group" (every head reads group 0's B
  and C); "skip" (D = 0); "rotate" (q and k rotated, rotate-half at
  `rope_theta`, all lanes); "latent" (the latent projection skipped: the
  experts read the first lanes of u); "silu" (SiLU in place of relu^2, in
  the routed and the shared experts); "bias" (no selection bias); "shared"
  (no shared expert); "other_share" (the held experts taken for the next
  chip's: `held_first` moved by the bank's length); and, at every multiple
  of `chunk` tokens (a prompt's chunk boundaries in the served program):
  "conv_tail" (the conv sees nothing before the boundary), "state" (the
  state set to zero), "state_bf16" (the state rounded to bfloat16 there);
  "state_bf16_step" (the state rounded to bfloat16 after EVERY token);
  `top_k` itself may be given smaller (the top-22 cut to top-8);
  `weights_as`: every matrix rounded to a narrower type first
  ("float8_e4m3fn").
"""

import jax
import jax.numpy as jnp
import numpy as np

from references._plain import rmsnorm, rope

QUERY_BLOCK = 256
HEAD_SLICES = 16


def _matrix(a, sizes):
    if sizes.get("weights_as"):
        a = a.astype(jnp.dtype(sizes["weights_as"]))
    return a.astype(jnp.float32)


def _dense(p, x, sizes):
    return x @ _matrix(p["kernel"], sizes) + p["bias"]


def _act(x, sizes):
    if sizes.get("drop") == "silu":
        return jax.nn.silu(x)
    return jnp.square(jnp.maximum(x, 0.0))


def _attention(p, u, sizes):
    t, heads, kv_heads = u.shape[0], sizes["n_heads"], sizes["n_kv_heads"]
    q = _dense(p["wq"], u, sizes).reshape(t, heads, -1)
    k = _dense(p["wk"], u, sizes).reshape(t, kv_heads, -1)
    v = _dense(p["wv"], u, sizes).reshape(t, kv_heads, -1)
    if sizes.get("drop") == "rotate":
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
        elif drop == "state_bf16_step":
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
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


def _latent_moe(p, u, sizes):
    """Every HELD expert over every token's latent, one expert's weights
    upcast at a time, each masked and weighted by the router's choice among
    ALL the experts; a pair routed outside the share adds nothing."""
    router, drop = p["router"], sizes.get("drop")
    s = jax.nn.sigmoid(u @ router["kernel"])
    biased = s if drop == "bias" else s + router["bias"]
    _, chosen = jax.lax.top_k(biased, sizes["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * sizes["routed_scale"]
    # (T, E): an expert's weight for a token, zero where it was not chosen.
    gates = jnp.zeros_like(s).at[
        jnp.arange(u.shape[0])[:, None], chosen].set(weight)
    bank = p["experts"]
    held, lanes = bank["up"].shape[:2]
    first = sizes["held_first"]
    if drop == "other_share":
        first = (first + held) % s.shape[-1]
    v = u[:, :lanes] if drop == "latent" else _dense(p["latent_down"], u,
                                                     sizes)

    def one(r, e):
        def leaf(a):
            return _matrix(jax.lax.dynamic_index_in_dim(a, e, keepdims=False),
                           sizes)
        out = _act(v @ leaf(bank["up"]), sizes) @ leaf(bank["down"])
        mine = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1)
        return r + mine * out, None

    r, _ = jax.lax.scan(one, jnp.zeros_like(v), jnp.arange(held))
    y = _dense(p["latent_up"], r, sizes)
    if drop != "shared":
        y = y + _dense(p["shared"]["proj"],
                       _act(_dense(p["shared"]["up"], u, sizes), sizes),
                       sizes)
    return y


_MIXERS = {"M": ("mamba", "ssm", _mamba), "*": ("attention", "attn",
                                                _attention),
           "E": ("experts", "mlp", _latent_moe)}


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32."""
    sizes = dict(sizes)
    eps, drop = sizes["ln_eps"], sizes.get("drop")
    t = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -t % QUERY_BLOCK))
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][tokens].astype(jnp.float32)
        for kind, p in zip(sizes["pattern"], params["layers"]):
            name, key, mixer = _MIXERS[kind]
            if drop != name:
                x = x + mixer(p[key], rmsnorm(p["ln1"], x, eps), sizes)
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
