"""Plain reference of the SDAR family (SDAR-30B-A3B-Chat, `sdar_moe`): a
Qwen3-MoE body under a block-causal mask, and the replay of its
block-diffusion generation. jax.numpy in float32 at the highest matmul
precision; no kernel, no cache, no code of the program under test.

The body. L = `block_length`, blk(p) = p // L, x a token's hidden state:

  h = x + Attn(RMS(x));  y = h + MoE(RMS(h));  final RMS;  logits = y W_head
  Attn: q = x Wq (H heads), k, v = x Wk, x Wv (H_kv heads); q and k
        RMS-normalised a head with a learned scale (assumed: the Qwen3
        convention); rotate-half rope over all lanes, `rope_theta`; scores
        / sqrt(D); the query at position i sees the key at position j iff
        blk(j) <= blk(i): every earlier block, and its own block whole.
  MoE:  p = softmax(x Wr) over all experts; the top `top_k`; weights
        p_e / sum_chosen p; sum_e w_e W_down,e (silu(x W_gate,e) * x W_up,e).
        (With the weights normalised, a soft-max taken AFTER the choice
        over the chosen logits gives the same numbers: exp(l_e) / sum_chosen
        exp(l). `drop: "norm_topk"` is the control that differs.)
  The logits at position i are the distribution of the token AT position i
  (assumed: no shift, as SDAR's published generate.py reads them).

`body(params, tokens, sizes)` is that over one whole sequence: what a
prefill chunk and a commit pass of the served path must equal, and, for a
sequence that ends in a block with `mask_token_id` at its masked
positions, what a denoise pass must equal at that block.

`forward(params, tokens, sizes)` answers lib/reference.py's contract, which
hands over the FINAL tokens only (prompt + generated[:-1], zero-padded) and
reads row p - 1 as the logits that chose token p. Under the `sequential`
rule with n = `tokens_per_pass` a pass the whole denoising is a function of
the final tokens: pass s of a block sees its first s n positions final and
the rest MASK. So S = L / n noisy streams run beside the clean one,
noisy_s = every block as pass s sees it; a clean query at i sees the clean
keys of blk(j) <= blk(i); a noisy_s query at i sees the clean keys of
blk(j) < blk(i) and the noisy_s keys of blk(j) = blk(i); each stream rotates
by its own positions. Row p - 1 is noisy stream (p mod L) // n at position
p. Positions inside the prompt are never read; the dropped last token is
revealed last and never needed; padding lies in later blocks and reaches
nothing read. (A block that holds a prompt's tail starts with those
positions final in every pass: `correct`'s prompts are multiples of L, and
tier-1 covers the tail with a pass-by-pass loop over `body`.)

Every expert is held: no share of a layer is taken. The server's leaves are
bfloat16, exactly representable in float32, and are upcast a piece at a
time. Every expert is applied to every token under the router's mask: the
masked positions of the noisy streams enter the first layers as one vector
and choose the same eight experts, half of all the tokens at once, so a
gather of "the tokens that chose it" would need the whole list's room
anyway (measured on the chip: a capacity of four times an even load
overflowed on the first seed).

Sizes read from the configuration's `reference` block: n_heads, n_kv_heads,
rope_theta, ln_eps, top_k, block_length, tokens_per_pass, mask_token_id.
Optional keys serve the controls of `correct` (tests, and one run each on
the chip); every one must read NOT correct:
  `drop`: "block_mask" (the causal mask j <= i in place of the block mask),
  "commit" (no commit pass: later blocks read every earlier block as its
  LAST denoise pass left it, its last n positions MASK), "qk_norm" (q and k
  not normalised), "norm_topk" (the chosen probabilities not normalised);
  `router_as`: the router's logits computed in a narrower type ("bfloat16")
  where the file says float32; `experts_as`: the expert banks rounded to a
  narrower type first ("float8_e4m3fn").
"""

import jax
import jax.numpy as jnp
import numpy as np

from references._plain import dense, rmsnorm

HEAD_SLICES = 16
QUERY_BLOCK = 256


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rope(x, theta):
    """x: (T, H, D) at positions 0..T-1; rotate-half pairs."""
    t, _, d = x.shape
    inv = (1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
           ).astype(np.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, sizes):
    """x: (streams, T, d), stream 0 clean. Each stream's queries against
    the CLEAN keys of earlier blocks and its OWN keys of the same block."""
    n_streams, t, _ = x.shape
    heads, kv_heads = sizes["n_heads"], sizes["n_kv_heads"]
    run, drop = sizes["block_length"], sizes.get("drop")

    def project(xs):
        q = dense(_f32(p["wq"]), xs).reshape(t, heads, -1)
        k = dense(_f32(p["wk"]), xs).reshape(t, kv_heads, -1)
        v = dense(_f32(p["wv"]), xs).reshape(t, kv_heads, -1)
        if drop != "qk_norm":
            q = rmsnorm(p["q_norm"], q, sizes["ln_eps"])
            k = rmsnorm(p["k_norm"], k, sizes["ln_eps"])
        return (_rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"]),
                v)

    q, k, v = jax.vmap(project)(x)              # (streams, T, heads, D)
    d = q.shape[-1]
    q = q.reshape(n_streams, t, kv_heads, heads // kv_heads, d)
    # What later blocks read of an earlier one: its committed K and V.
    stored = n_streams - 1 if drop == "commit" else 0
    kpos = jnp.arange(t)

    def block(args):
        """QUERY_BLOCK queries of one stream against [stored ; own]."""
        s, i = args
        qb = jax.lax.dynamic_slice_in_dim(q[s], i * QUERY_BLOCK, QUERY_BLOCK)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        earlier = kpos[None, :] // run < qpos[:, None] // run
        same = kpos[None, :] // run == qpos[:, None] // run
        if drop == "block_mask":
            same &= kpos[None, :] <= qpos[:, None]
        seen = jnp.concatenate([earlier, same], axis=-1)       # (Q, 2T)
        keys = jnp.concatenate([k[stored], k[s]])
        values = jnp.concatenate([v[stored], v[s]])
        scores = jnp.einsum("qhgd,khd->hgqk", qb, keys) / np.sqrt(float(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", probs, values)

    n_blocks = t // QUERY_BLOCK
    grid = (jnp.repeat(jnp.arange(n_streams), n_blocks),
            jnp.tile(jnp.arange(n_blocks), n_streams))
    o = jax.lax.map(block, grid).reshape(n_streams, t, heads * d)
    return dense(_f32(p["wo"]), o)


def _experts(p, x, sizes):
    """x: (N, d). Every expert over every token, one expert's weights
    upcast at a time, each masked and weighted by the router's choice."""
    n = x.shape[0]
    kernel = p["router"]["kernel"]
    if sizes.get("router_as"):
        narrow = jnp.dtype(sizes["router_as"])
        logits = (x.astype(narrow) @ kernel.astype(narrow)).astype(
            jnp.float32)
    else:
        logits = x @ kernel
    probs = jax.nn.softmax(logits, axis=-1)
    picked, chosen = jax.lax.top_k(probs, sizes["top_k"])
    if sizes.get("drop") != "norm_topk":
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    # (N, E): an expert's weight for a token, zero where it was not chosen.
    gates = jnp.zeros_like(probs).at[
        jnp.arange(n)[:, None], chosen].set(picked)
    bank = p["experts"]
    narrow = sizes.get("experts_as")

    def one(y, e):
        def leaf(a):
            a = jax.lax.dynamic_index_in_dim(a, e, keepdims=False)
            if narrow:
                a = a.astype(jnp.dtype(narrow))
            return a.astype(jnp.float32)

        gate, up = jnp.split(x @ leaf(bank["gate_up"]), 2, axis=-1)
        out = (jax.nn.silu(gate) * up) @ leaf(bank["down"])
        mine = jax.lax.dynamic_index_in_dim(gates, e, axis=1)
        return y + mine * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(bank["down"].shape[0]))
    return y


def _hidden(params, streams, sizes):
    """streams: (S, T) int32 token ids, stream 0 clean -> the final normed
    hidden states (S, T, d)."""
    eps = sizes["ln_eps"]
    x = params["tok_embed"]["table"][streams].astype(jnp.float32)
    s, t, d = x.shape
    for p in params["layers"]:
        x = x + _attention(p["attn"], rmsnorm(p["ln1"], x, eps), sizes)
        y = rmsnorm(p["ln2"], x, eps).reshape(s * t, d)
        x = x + _experts(p["mlp"], y, sizes).reshape(s, t, d)
    return rmsnorm(params["ln_f"], x, eps)


def _head(params, x):
    """x: (T, d) -> logits (T, vocab), a slice of the vocabulary at a time."""
    kernel, bias = params["head"]["kernel"], params["head"]["bias"]
    vocab = kernel.shape[1]
    slices = HEAD_SLICES if vocab % HEAD_SLICES == 0 else 1
    width = vocab // slices

    def head_slice(i, logits):
        k = jax.lax.dynamic_slice_in_dim(kernel, i * width, width, 1)
        b = jax.lax.dynamic_slice_in_dim(bias, i * width, width)
        return jax.lax.dynamic_update_slice_in_dim(
            logits, x @ k.astype(jnp.float32) + b, i * width, 1)

    # The loop's carry IS the result: at 2304 x 151,936 a second copy of it
    # (a bias added afterwards) is 1.4 GB the chip does not have.
    return jax.lax.fori_loop(
        0, slices, head_slice, jnp.zeros((x.shape[0], vocab), jnp.float32))


def _padded(tokens):
    return jnp.pad(tokens, (0, -tokens.shape[0] % QUERY_BLOCK))


def body(params, tokens, sizes):
    """The body over one whole sequence under the block-causal mask.
    tokens: (T,) int32 -> logits (T, vocab) float32, row i the
    distribution of the token AT position i."""
    sizes = dict(sizes)
    t = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, _padded(tokens)[None], sizes)[0, :t]
        return _head(params, x)


def forward(params, tokens, sizes):
    """tokens: (T,) int32, the final tokens -> (T, vocab) float32, row
    p - 1 the logits of the denoise pass that revealed position p."""
    sizes = dict(sizes)
    run, per_pass = sizes["block_length"], sizes["tokens_per_pass"]
    t = tokens.shape[0]
    padded = _padded(tokens)
    at = jnp.arange(padded.shape[0]) % run
    noisy = [jnp.where(at < s * per_pass, padded, sizes["mask_token_id"])
             for s in range(run // per_pass)]
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, jnp.stack([padded] + noisy), sizes)
        p = jnp.minimum(jnp.arange(1, t + 1), padded.shape[0] - 1)
        return _head(params, x[1 + (p % run) // per_pass, p])
