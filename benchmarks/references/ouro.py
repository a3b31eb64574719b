"""The ouro dialect (a looped language model): ONE stack of llama-dialect
layers with a second norm on each branch's output, applied `ut_steps` times
over the same weights, the model's final norm after every pass, and an exit
gate that picks the pass whose stream the head reads. The served program
keeps the keys and values of every (pass, layer) in a plane of a paged pool
and runs a tick's tokens through one compiled layer body; this file has no
cache: each pass attends its own K and V over the whole sequence, which is
what the planes must reproduce.

Stream x (S x d), L layers, T = `ut_steps` passes, H = `n_heads` heads = KV
heads of D lanes (the arrays' shapes give L and D):

  0  x = E[token].
  1  pass t = 0..T-1, layer l = 0..L-1, the same weights every pass:
       a = Attn_l(RMS1_l(x)); x += RMS2_l(a)
       m = Wdown(silu(Wgate y) * Wup y), y = RMS3_l(x); x += RMS4_l(m)
     (four learned RMSNorm scales a layer: ASSUMED, the sandwich norm of the
     published model; no key of its config names it).
  2  Attn: q, k, v = x Wq, x Wk, x Wv, rotate-half rope (`rope_theta`) over
     all D lanes, scores q.k / sqrt(D), causal, the heads' outputs to Wo. In
     pass t the keys and values are pass t's own (ASSUMED: the per-pass
     cache of the published forward; sharing the last pass's cache at decode
     is not modelled).
  3  after every pass x = RMS_f(x) (ASSUMED), h_t = x,
     lam_t = sigmoid(h_t . w_g + b_g).
  4  p_t = lam_t prod_{j<t}(1 - lam_j) for t < T - 1, p_{T-1} the rest; a
     token exits at the first t with sum_{j<=t} p_j >= `exit_threshold`,
     else at T - 1; logits = h_exit W_head. At the published threshold 1
     every token exits at T - 1. All T passes always run (ASSUMED).

Sizes read from the configuration's `reference` block: n_heads, ln_eps,
rope_theta, ut_steps, exit_threshold. Parameter tree: tok_embed, blocks
{ln1, attn{wq, wk, wv, wo}, ln1_out, ln2, mlp{gate, up, proj}, ln2_out}
stacked on a leading layer axis, ln_f, gate, head.

The server's leaves are bfloat16 and fill most of the chip beside a pool of
8 GB. They are exactly representable in float32 and are upcast a LAYER at a
time, inside the scan over the layers (a layer is 51 M parameters, 0.2 GB in
float32), the head once (0.4 GB); the logits of 640 positions are 0.13 GB.

Optional keys of the block serve the controls of `correct` (tests, and one
run each on the chip); every one must read NOT correct:
  `drop`: "branch_norms" (no norm on the branches' outputs: the llama
  block), "pass_norm" (no final norm between passes; the head still reads a
  normed stream), "own_cache" (pass t attends pass 0's K and V: a cache
  shared across passes);
  `weights_as`: the layers' matrices rounded to a narrower type first
  ("float8_e4m3fn");
  `ut_steps` one less than the configuration's.
"""

import jax
import jax.numpy as jnp

from references._plain import dense, rmsnorm, rope


def _attend(q, k, v):
    """Causal attention over the whole sequence. q, k, v: (S, H, D) rotated
    -> (S, H * D)."""
    s = q.shape[0]
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, -1)


def _layer(p, x, sizes, shared=None):
    """One application of one layer. Returns (x, (k, v)): the rotated keys
    and the values this application made, (S, H, D). `shared`: the (k, v)
    it attends instead of its own (the `own_cache` control)."""
    eps, heads, drop = sizes["ln_eps"], sizes["n_heads"], sizes.get("drop")
    narrow = sizes.get("weights_as")

    def matrix(d):
        kernel = d["kernel"]
        if narrow:
            kernel = kernel.astype(jnp.dtype(narrow))
        return {"kernel": kernel.astype(jnp.float32),
                "bias": d["bias"].astype(jnp.float32)}

    def branch(scale, y):
        return y if drop == "branch_norms" else rmsnorm(scale, y, eps)

    s = x.shape[0]
    y = rmsnorm(p["ln1"], x, eps)
    q, k, v = (dense(matrix(p["attn"][w]), y).reshape(s, heads, -1)
               for w in ("wq", "wk", "wv"))
    q, k = rope(q, sizes["rope_theta"]), rope(k, sizes["rope_theta"])
    seen = (k, v) if shared is None else shared
    x = x + branch(p["ln1_out"], dense(matrix(p["attn"]["wo"]),
                                       _attend(q, *seen)))
    y = rmsnorm(p["ln2"], x, eps)
    m = dense(matrix(p["mlp"]["proj"]), jax.nn.silu(
        dense(matrix(p["mlp"]["gate"]), y)) * dense(matrix(p["mlp"]["up"]), y))
    return x + branch(p["ln2_out"], m), (k, v)


def body(params, tokens, sizes, keep_kv=False):
    """The T passes. tokens: (S,) int32. Returns (streams (T, S, d): the
    normed stream h_t after each pass; planes: with `keep_kv` the pair
    (K, V), each (T, L, S, H * D), plane [t, l] what pass t of layer l
    made, else None)."""
    sizes = dict(sizes)
    eps, drop = sizes["ln_eps"], sizes.get("drop")
    keep_first = drop == "own_cache"
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][tokens].astype(jnp.float32)
        streams, planes, first = [], [], None
        for t in range(int(sizes["ut_steps"])):
            def layer(x, xs):
                p, shared = xs
                x, kv = _layer(p, x, sizes, shared)
                return x, (kv if keep_kv or (keep_first and t == 0)
                           else None)

            shared = first if keep_first and t > 0 else None
            x, made = jax.lax.scan(layer, x, (params["blocks"], shared))
            if keep_first and t == 0:
                first = made
            if keep_kv:
                planes.append(made)
            h = rmsnorm(params["ln_f"], x, eps)
            streams.append(h)
            x = x if drop == "pass_norm" else h
        def stacked(i):
            """(T, L, S, H, D) -> (T, L, S, H * D) of K (0) or V (1)."""
            x = jnp.stack([made[i] for made in planes])
            return x.reshape(x.shape[:3] + (-1,))

        return jnp.stack(streams), ((stacked(0), stacked(1)) if keep_kv
                                    else None)


def exit_pass(gate, streams, threshold):
    """(S,) int32: the pass each position exits at (rule 4)."""
    steps = streams.shape[0]
    lam = jax.nn.sigmoid(dense(jax.tree.map(
        lambda a: a.astype(jnp.float32), gate), streams)[..., 0])  # (T, S)
    out = jnp.full(lam.shape[1:], steps - 1, jnp.int32)
    stay, cum = jnp.ones_like(lam[0]), jnp.zeros_like(lam[0])
    for t in range(steps - 1):
        cum = cum + lam[t] * stay
        stay = stay * (1.0 - lam[t])
        out = jnp.where((cum >= threshold) & (out == steps - 1), t, out)
    return out


def forward(params, tokens, sizes):
    """tokens: (S,) int32 -> logits (S, vocab) float32."""
    streams, _ = body(params, tokens, sizes)
    sizes = dict(sizes)
    with jax.default_matmul_precision("highest"):
        h = streams[-1]
        if sizes["exit_threshold"] < 1:
            at = exit_pass(params["gate"], streams, sizes["exit_threshold"])
            h = jnp.take_along_axis(streams, at[None, :, None], axis=0)[0]
        head = jax.tree.map(lambda a: a.astype(jnp.float32), params["head"])
        return dense(head, h)
