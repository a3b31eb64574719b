"""The smallthinker dialect: RMSNorm blocks whose experts are chosen from
the layer's INPUT, before its attention; attention that is a SLIDING
WINDOW on some layers and FULL on the others, rotated on some and not on
the others (two lists; as published a window layer is rotated and a full
layer is not); ReGLU experts, soft-max routed, no shared expert, no dense
layer. The served program routes inside the tick's token list, keeps a
window layer's keys and values in blocks it gives back once the window has
passed them and reads each row by the class of its run; this file attends
a whole sequence under a mask and applies every expert to every token, so
the two are held against each other.

Layer l, stream h (T x d), `n_heads` query heads over `n_kv_heads` KV heads
of D lanes (the arrays' shapes give D):

  1  p = softmax(h Wr) over ALL the experts, from the layer's input h,
     un-normed; the top `top_k` chosen, w = p[chosen] / sum.
  2  x = RMS(h; ln1). q = x Wq, k = x Wk, v = x Wv; no bias, no q/k norm.
  3  `rotated[l]`: q and k rotated over all D lanes, rotate-half pairing,
     `rope_theta`; else nothing is rotated.
  4  scores q.k / sqrt(D), causal; `windowed[l]`: key j is seen by query i
     iff i - window < j <= i. Query head n reads KV head n // (n_heads /
     n_kv_heads). h1 = h + concat(o) Wo.
  5  z = RMS(h1; ln2). h_out = h1 + sum_e w_e Wdown_e (relu(Wgate_e z) *
     (Wup_e z)), the gate the first half of `gate_up`, with w and the
     choice FROM LINE 1.
  6  final RMS, head.

Departures from the publication: its secondary experts and its LM-head
predictor (a sparse head) are not modelled, here or in the program: its
`config.json` carries neither.

The tree may hold a share of the experts (`held_first`, as many as the
bank is long): a (token, expert) pair routed outside it adds nothing, in
the program and in this file alike. The benchmark's cell holds them all.

Sizes read from the configuration's `reference` block: n_heads, n_kv_heads,
windowed and rotated (0/1 a layer, comma-separated in a string: the block's
values are hashed), window, rope_theta, top_k, held_first, ln_eps.
Parameter tree: tok_embed, layers (a list), ln_f, head; a block is ln1,
attn{wq, wk, wv, wo}, ln2, mlp{router{kernel}, experts{gate_up (held, d,
2f), down (held, f, d)}}.

The server's leaves are bfloat16 and fill half the chip beside 3.5 GB of
pools. They are exactly representable in float32 and are upcast a piece at
a time: a projection when it is used, ONE expert inside the loop over
experts; attention runs a block of `QUERY_BLOCK` queries at a time.

**The head's rows.** The whole vocabulary over a context of 8 k tokens is
5 GB of float32 logits, which the chip does not have beside the server.
With `tail_rows` R in the block, `forward` computes the head for R rows
alone, those around the sequence's end (lib/reference.py: "it may compute
only the last few hundred query positions ... `served_gaps` reads the
generated positions alone"), and returns them as `TailRows`: indexed by a
slice of absolute positions, as `served_gaps` indexes, it gives those rows
and raises where it does not hold them. The end is where the right-padding
(token 0) starts, found inside the jitted program; a served token 0 at the
very end would be taken for padding, so the rows reach `TAIL_SLACK` past
it: what is read there is the same as if the zeros were tokens, which they
then are. Without the key the whole (T, vocab) array is returned.

Optional keys of the block serve the controls of `correct` (tests, and runs
on the chip); every one must read NOT correct:
  `control`: "window" (window layers attend the whole context),
  "rotate_full" (every layer rotated), "late_router" (the router reads
  RMS(h1; ln2), as every other family here routes), "silu" (a SwiGLU
  expert);
  `experts_as`: the expert banks rounded to a narrower type first
  ("float8_e4m3fn").
"""

import jax
import jax.numpy as jnp
import numpy as np

from references._plain import dense, rmsnorm

HEAD_SLICES = 16
QUERY_BLOCK = 256
TAIL_SLACK = 32


@jax.tree_util.register_pytree_node_class
class TailRows:
    """Rows [start, start + R) of a sequence's (T, vocab) logits."""

    def __init__(self, rows, start):
        self.rows, self.start = rows, start

    def tree_flatten(self):
        return (self.rows, self.start), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)

    def __getitem__(self, at):
        start = int(self.start)
        lo, hi = at.start - start, at.stop - start
        if at.step is not None or lo < 0 or hi > self.rows.shape[0]:
            raise IndexError(
                f"rows {at.start}:{at.stop} asked of a reference that "
                f"computed {start}:{start + self.rows.shape[0]}")
        return np.asarray(self.rows[lo:hi])


def _rope(x, theta):
    """x: (T, H, D) at positions 0..T-1, all D lanes rotated in pairs
    (i, i + D / 2)."""
    d = x.shape[-1]
    inv = (1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)).astype(
        np.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _route(router, x, sizes):
    """(T, E): an expert's weight for a token, zero where it was not
    chosen."""
    p = jax.nn.softmax(x @ router["kernel"], axis=-1)
    picked, chosen = jax.lax.top_k(p, sizes["top_k"])
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(p).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weight)


def _attention(p, x, layer, sizes):
    t = x.shape[0]
    heads, kv_heads = sizes["n_heads"], sizes["n_kv_heads"]
    control = sizes.get("control")
    windowed = bool(sizes["windowed"][layer]) and control != "window"
    q = dense(_f32(p["wq"]), x).reshape(t, heads, -1)
    k = dense(_f32(p["wk"]), x).reshape(t, kv_heads, -1)
    v = dense(_f32(p["wv"]), x).reshape(t, kv_heads, -1)
    if sizes["rotated"][layer] or control == "rotate_full":
        q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
    d = q.shape[-1]
    q = q.reshape(t, kv_heads, heads // kv_heads, d)
    kpos = jnp.arange(t)

    def block(i):
        """QUERY_BLOCK queries against the whole sequence."""
        qb = jax.lax.dynamic_slice_in_dim(q, i * QUERY_BLOCK, QUERY_BLOCK)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        seen = kpos[None, :] <= qpos[:, None]
        if windowed:
            seen &= kpos[None, :] > qpos[:, None] - sizes["window"]
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) / np.sqrt(float(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", probs, v)

    o = jax.lax.map(block, jnp.arange(t // QUERY_BLOCK))
    return dense(_f32(p["wo"]), o.reshape(t, -1))


def _experts(bank, z, gates, sizes):
    """Every held expert over every token, one expert's weights upcast at
    a time, each weighted by `gates` (T, E), the router's choice among all
    the experts; a pair routed outside the share adds nothing."""
    first, narrow = sizes["held_first"], sizes.get("experts_as")
    act = jax.nn.silu if sizes.get("control") == "silu" else jax.nn.relu

    def one(y, e):
        def leaf(a):
            a = jax.lax.dynamic_index_in_dim(a, e, keepdims=False)
            if narrow:
                a = a.astype(jnp.dtype(narrow))
            return a.astype(jnp.float32)
        gate, up = jnp.split(z @ leaf(bank["gate_up"]), 2, axis=-1)
        out = (act(gate) * up) @ leaf(bank["down"])
        mine = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1)
        return y + mine * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(z),
                        jnp.arange(bank["down"].shape[0]))
    return y


def _head(params, x):
    """x: (R, d) normalised -> (R, vocab), a slice of the vocabulary at a
    time."""
    kernel, bias = params["head"]["kernel"], params["head"]["bias"]
    vocab = kernel.shape[1]
    width = vocab // HEAD_SLICES

    def head_slice(i, logits):
        k = jax.lax.dynamic_slice_in_dim(kernel, i * width, width, 1)
        return jax.lax.dynamic_update_slice_in_dim(
            logits, x @ k.astype(jnp.float32), i * width, 1)

    return jax.lax.fori_loop(
        0, HEAD_SLICES, head_slice,
        jnp.zeros((x.shape[0], vocab), jnp.float32)) + bias


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32, or `TailRows` of
    them where the block states `tail_rows`."""
    sizes = dict(sizes)
    for key in ("windowed", "rotated"):
        sizes[key] = [int(v) for v in sizes[key].split(",")]
    eps = sizes["ln_eps"]
    late = sizes.get("control") == "late_router"
    t = tokens.shape[0]
    padded = jnp.pad(tokens, (0, -t % QUERY_BLOCK))
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][padded].astype(jnp.float32)
        for layer, p in enumerate(params["layers"]):
            router = p["mlp"]["router"]
            gates = None if late else _route(router, x, sizes)
            x = x + _attention(p["attn"], rmsnorm(p["ln1"], x, eps), layer,
                               sizes)
            z = rmsnorm(p["ln2"], x, eps)
            if late:
                gates = _route(router, z, sizes)
            x = x + _experts(p["mlp"]["experts"], z, gates, sizes)
        x = rmsnorm(params["ln_f"], x, eps)[:t]
        rows = sizes.get("tail_rows")
        if not rows or rows >= t:
            return _head(params, x)
        # The sequence ends where the right-padding starts.
        end = jnp.max(jnp.where(tokens != 0, jnp.arange(t) + 1, 0))
        start = jnp.clip(end + TAIL_SLACK - rows, 0, t - rows)
        return TailRows(
            _head(params, jax.lax.dynamic_slice_in_dim(x, start, rows)),
            start)
