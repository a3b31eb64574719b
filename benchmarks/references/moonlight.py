"""The moonlight (deepseek_v3) dialect: RMSNorm blocks, latent attention
(MLA, `q_lora_rank` null) in its EXPANDED, published form, a SwiGLU FFN in
the first `n_dense_layers` layers and sigmoid-routed SwiGLU experts with a
shared expert in the others. The served program caches only the latent and
reads it absorbed; this file makes every head's key and value from the
latent, so the two forms are held against each other.

  MLA   q = x Wq -> per head q_nope ‖ q_pe;  [c_raw ‖ k_pe_raw] = x Wkv_a;
        c = RMS(c_raw) (its own scale, eps `kv_norm_eps`);
        k_pe = RoPE(k_pe_raw), one for all heads;  q_pe = RoPE(q_pe);
        [k_nope ‖ v]_head = c Wkv_b;
        score = (q_nope.k_nope + q_pe.k_pe) / sqrt(nope + rope), causal
        soft-max, out = concat_heads(sum p v) Wo.
  MoE   s = sigmoid(x Wg); the top `top_k` of s + b are chosen;
        w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scale;
        y = sum_i w_i E_i(x) + S(x). EVERY expert is applied to every token
        and masked by the router's choice: no sort, no groups, no drop.

Departures from the published model, which the served program shares:
rotate-half RoPE (the source interleaves pairs; random weights cannot tell
them apart), zero biases on the projections, `b` random at about a tenth
of the scores' spread.

Sizes read from the configuration's `reference` block: n_heads, qk_nope,
qk_rope, v_head, top_k, routed_scale, ln_eps, kv_norm_eps, rope_theta.
Parameter tree: tok_embed, dense and moe (two stacks of blocks, each on a
leading layer axis), ln_f, head; a block is ln1, attn{wq, wkv_a, kv_norm,
wkv_b, wo}, ln2, mlp; an expert block's mlp is router{kernel, bias},
shared{gate, up, proj}, experts{gate_up (E, d, 2f), down (E, f, d)}.

The server's leaves are bfloat16 and fill half the chip. They are exactly
representable in float32, and are upcast here a piece at a time: a layer
inside the scan over layers, ONE expert inside the loop over experts, a
slice of the vocabulary inside the head. The transients stay under 2 GB
whatever the expert count.

Two optional keys of the block serve the controls of `correct` (tests, and
one run on the chip): `drop` leaves out one term ("shared", "bias", "k_pe")
and `experts_as` rounds the expert banks to a narrower type first
("float8_e4m3fn"): either must read NOT correct.
"""

import jax
import jax.numpy as jnp

from references._plain import dense, rmsnorm, rope

HEAD_SLICES = 16


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _swiglu(p, x):
    return dense(p["proj"], jax.nn.silu(dense(p["gate"], x))
                 * dense(p["up"], x))


def _mla(p, x, sizes):
    t = x.shape[0]
    heads, nope, rot = sizes["n_heads"], sizes["qk_nope"], sizes["qk_rope"]
    q = dense(p["wq"], x).reshape(t, heads, nope + rot)
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:], sizes["rope_theta"])
    kv_a = dense(p["wkv_a"], x)
    rank = kv_a.shape[-1] - rot
    c = rmsnorm(p["kv_norm"], kv_a[:, :rank], sizes["kv_norm_eps"])
    k_pe = rope(kv_a[:, None, rank:], sizes["rope_theta"])[:, 0]
    kv = dense(p["wkv_b"], c).reshape(t, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = jnp.einsum("qhn,khn->hqk", q_nope, k_nope)
    if sizes.get("drop") != "k_pe":
        scores = scores + jnp.einsum("qhr,kr->hqk", q_pe, k_pe)
    scores = scores / jnp.sqrt(float(nope + rot))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return dense(p["wo"], jnp.einsum("hqk,khv->qhv", probs, v)
                 .reshape(t, -1))


def _experts(p, bank, first, x, sizes):
    """Every expert over every token, one expert's weights upcast at a
    time, each masked and weighted by the router's choice."""
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    biased = s if sizes.get("drop") == "bias" else s + p["router"]["bias"]
    _, chosen = jax.lax.top_k(biased, sizes["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * sizes["routed_scale"]
    n_experts = s.shape[-1]
    # (T, E): an expert's weight for a token, zero where it was not chosen.
    gates = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weight)
    narrow = sizes.get("experts_as")

    def one(y, e):
        def leaf(a):
            a = jax.lax.dynamic_index_in_dim(a, first + e, keepdims=False)
            if narrow:
                a = a.astype(jnp.dtype(narrow))
            return a.astype(jnp.float32)
        gate, up = jnp.split(x @ leaf(bank["gate_up"]), 2, axis=-1)
        out = (jax.nn.silu(gate) * up) @ leaf(bank["down"])
        return y + gates[:, e, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_experts))
    if sizes.get("drop") != "shared":
        y = y + _swiglu(p["shared"], x)
    return y


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32."""
    sizes = dict(sizes)
    eps = sizes["ln_eps"]
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][tokens].astype(jnp.float32)

        def dense_block(x, p):
            p = _f32(p)
            x = x + _mla(p["attn"], rmsnorm(p["ln1"], x, eps), sizes)
            return x + _swiglu(p["mlp"], rmsnorm(p["ln2"], x, eps)), None

        if "dense" in params:
            x, _ = jax.lax.scan(dense_block, x, params["dense"])
        if "moe" in params:
            stack = params["moe"]
            banks = stack["mlp"]["experts"]
            n_experts = banks["down"].shape[1]
            # All layers' experts on one axis, indexed an expert at a time.
            bank = jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[2:]), banks)
            rest = dict(stack, mlp={k: v for k, v in stack["mlp"].items()
                                    if k != "experts"})

            def moe_block(x, layer):
                p, k = layer
                p = _f32(p)
                x = x + _mla(p["attn"], rmsnorm(p["ln1"], x, eps), sizes)
                h = rmsnorm(p["ln2"], x, eps)
                return x + _experts(p["mlp"], bank, k * n_experts, h,
                                    sizes), None

            n_layers = banks["down"].shape[0]
            x, _ = jax.lax.scan(moe_block, x, (rest, jnp.arange(n_layers)))
        x = rmsnorm(_f32(params["ln_f"]), x, eps)
        kernel, bias = params["head"]["kernel"], params["head"]["bias"]
        vocab = kernel.shape[1]
        width = vocab // HEAD_SLICES

        def head_slice(i):
            k = jax.lax.dynamic_slice_in_dim(kernel, i * width, width, 1)
            return x @ k.astype(jnp.float32)

        logits = jax.lax.map(head_slice, jnp.arange(HEAD_SLICES))
        return jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], vocab) + bias
