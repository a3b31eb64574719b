"""LFM2-MoE family: a layer's operator is, by a published list, a GATED
SHORT CONVOLUTION (no soft-max, no recurrence: two products around a
depthwise causal conv of three taps) or grouped-query attention, and its
feed-forward a dense SwiGLU in the leading layers and sigmoid-routed
SwiGLU experts, with no shared expert, in the others.

Source of the default geometry: LFM2-24B-A2B
(https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json,
`model_type: lfm2_moe`). Stream h (T x d), eps `norm_eps`, no bias
anywhere; layer l of kind `layer_types[l]`:

    h0 = E[ids]
    r  = RMS1_l(h);  h += Op_l(r);  h += FFN_l(RMS2_l(h))
    logits = RMS_f(h) W_head       (ASSUMED: one final norm before the
             head, the model's `embedding_norm`, and an untied head: the
             catalog counts 268 M in the embedding and the head. A tied
             head changes bytes and no equation.)
    conv   [B | C | x] = r W_in (d -> 3d); u = B * x;
           c_t = sum_{j<K} w_j * u_{t-(K-1)+j}, w (K, d) a depthwise causal
           conv of K = `conv_L_cache` = 3 taps, the LAST row the current
           token's, u zero before position 0, no bias (`conv_bias` false);
           y = (C * c) W_out (d -> d). No activation. What a row keeps of
           this layer between ticks is (u_{t-2}, u_{t-1}): K - 1 vectors
           of d lanes, float32 (NOTED: the published cache keeps K
           columns; K - 1 are read).
    full_attention
           q = RMS_q(r Wq), k = RMS_k(r Wk), each norm over a head's lanes
           with a learned scale and `norm_eps` (ASSUMED: no key of the
           config names them; the head is d / n_heads = 64 lanes wide);
           v = r Wv; rotate-half RoPE at `rope_theta` over all of a head's
           lanes of q and k; scores / sqrt(D), causal, query head i reads
           KV head i // (n_heads / n_kv_heads); y = a Wo.
    FFN    l < `num_dense_layers`: (silu(z W1) * z W3) W2, `d_ff` wide;
           else s = sigmoid(z W_r) float32 over `n_routed` experts, the top
           `top_k` of s + b are CHOSEN (b = `expert_bias`, selection only),
           w = s[chosen] / sum * `routed_scale`
           (`ops.moe.sigmoid_topk_route`, one group), y = sum_k w_k
           SwiGLU_{e_k}(z), each `d_ff_expert` wide. No shared expert.

**A chip's share** (`models.laguna`): `held` = (first, count), the routed
experts whose weights THIS tree holds; a pair routed outside the share
forms no row and adds nothing here.

**Two pools, the second a row of ONE array.** The `kv_and_state` family of
`models.olmo_hybrid`: an attention layer's K and V go to the block pool
(`cfg.kv_block_kinds[0]`, the attention layers alone), a conv layer's two
vectors to one row of the state pool (`cfg.state_row_shapes` =
((K - 1, d),): no recurrent state beside the tail), `cfg.pool_layer[l]`
the layer of its kind's pool. **The conv operator has no chunked form and
needs none**: token t reads u of itself and its K - 1 predecessors, so a
run of one token and a run of 256 are the same body over the tick's token
LIST (`_conv_rows`): a predecessor is the list's entry before where it lies
in the same run and the row's tail where the run starts, by a gather; the
tail written back is the last K - 1 of (old tail ++ the run's u). No loop
over rows, no kernel, nothing under `mixer/chunk`.

Parameter tree: `tok_embed`, `layers` (a list: the layers are of four
shapes), `ln_f`, `head`. A block is `ln1`, `ln2`, `conv` {w_in, taps (K, d)
float32, w_out} or `attn` {wq, wk, wv, wo, q_norm, k_norm}, and `mlp` (a
SwiGLU, or {router {kernel, bias}, experts {gate_up, down}} with the HELD
experts alone). Weights are made in `param_dtype` directly, an expert at a
time (`models.laguna._bank`): no float32 copy of a bank exists anywhere.
**The draw** is the other families' (unit-variance stream, every matrix
N(0, 1/fan_in), what writes into the stream 1/sqrt(2 L) smaller). B * x of
two unit normals has a unit second moment; the taps are N(0, 1/K) each, so
EVERY lag carries a third of c's variance and c is of unit spread: a tail
lost at a chunk boundary moves two of three terms of the first tokens
behind it. q's and k's norm scales are `models.sdar`'s (scores of spread
4); the selection bias about a tenth of the scores' spread.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_engine.models.laguna import _bank, _rope
from tpu_engine.models.moonlight import (
    _dense_init,
    _normal,
    _residual_gain,
    _swiglu_init,
)
from tpu_engine.models.nemotron_h import _attn_output
from tpu_engine.models.registry import ModelSpec, causal_lm_spec, register
from tpu_engine.models.tick_tokens import lm_head, tick_tokens
from tpu_engine.models.sdar import _SCORE_SPREAD, _inv_freq, _norm_scale
from tpu_engine.models.transformer import (
    TransformerConfig,
    _mlp,
    index_in_kind,
    kv_kind_config,
)
from tpu_engine.ops import nn
from tpu_engine.ops.attention import KVCache, dot_product_attention
from tpu_engine.ops.moe import routed_experts, sigmoid_topk_route
from tpu_engine.utils.tracing import step_part

# A layer's kind, as the published `layer_types` writes it.
CONV, ATTENTION = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2Config(TransformerConfig):
    """The base fields this family fixes: rmsnorm, rope, swiglu; `n_heads`
    and `n_kv_heads` are the attention layers', `d_ff` the dense layers'."""
    layer_types: Tuple[str, ...] = ()
    conv_width: int = 3                     # K: `conv_L_cache`
    n_dense_layers: int = 2
    d_ff_expert: int = 1536
    n_routed: int = 64
    top_k: int = 4
    routed_scale: float = 1.0
    held: Tuple[int, int] = (0, 64)         # (first, count) of n_routed
    param_dtype: str = "bfloat16"

    # The registry derives family and TP rule from these two; the
    # scheduler names a tick's state work by the third.
    serving_state_family = "kv_and_state"
    tp_partition_rule = ("unshardable: a row's conv tail is one state row "
                         "a layer, which no shard map over lanes carries "
                         "yet, and the lane holds one chip's share of the "
                         "experts already")
    recurrence = "conv"

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers:
            raise ValueError("layer_types needs one entry a layer")
        for kind in self.layer_types:
            if kind not in (CONV, ATTENTION):
                raise ValueError(f"{kind!r} is no layer kind: a layer is "
                                 f"{CONV!r} or {ATTENTION!r}")
        if not self.n_full_layers or not self.n_linear_layers:
            raise ValueError("a row owns a chain and a state row: "
                             f"layer_types needs a {CONV!r} and a "
                             f"{ATTENTION!r}")
        if self.conv_width < 2:
            raise ValueError("a conv of one tap keeps no tail")
        first, count = self.held
        if not (0 <= first and count > 0
                and first + count <= self.n_routed):
            raise ValueError(f"held={self.held} is no share of "
                             f"{self.n_routed} experts")

    @property
    def n_linear_layers(self) -> int:
        """The layers that own a row of the state pool: the conv layers."""
        return self.layer_types.count(CONV)

    @property
    def n_full_layers(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def kv_block_kinds(self) -> Tuple[TransformerConfig]:
        """What the block pool is sized by: the attention layers alone."""
        return (kv_kind_config(self, self.n_full_layers),)

    @property
    def pool_layer(self) -> Tuple[int, ...]:
        """Layer l's index among the layers of its kind."""
        return index_in_kind(self.layer_types)

    @property
    def state_row_shapes(self) -> Tuple[Tuple[int, ...], ...]:
        """A row's state, a conv layer: ONE array, the last K - 1 of u
        (float32). Nothing recurrent lies beside it."""
        return ((self.conv_width - 1, self.d_model),)


# -- parameters -----------------------------------------------------------------

def _block_init(key, cfg: Lfm2Config, layer: int):
    dtype = jnp.dtype(cfg.param_dtype)
    d, dh = cfg.d_model, cfg.d_head
    out_gain = _residual_gain(cfg)
    k_op, k_ffn = jax.random.split(key)
    block = {"ln1": nn.rmsnorm_init(d), "ln2": nn.rmsnorm_init(d)}
    if cfg.layer_types[layer] == CONV:
        ki, kt, ko = jax.random.split(k_op, 3)
        block["conv"] = {
            "w_in": _dense_init(ki, d, 3 * d, dtype),
            "taps": _normal(kt, (cfg.conv_width, d), cfg.conv_width,
                            jnp.float32),
            "w_out": _dense_init(ko, d, d, dtype, out_gain),
        }
    else:
        kq, kk, kv, ko = jax.random.split(k_op, 4)
        block["attn"] = {
            "wq": _dense_init(kq, d, cfg.n_heads * dh, dtype),
            "wk": _dense_init(kk, d, cfg.kv_heads * dh, dtype),
            "wv": _dense_init(kv, d, cfg.kv_heads * dh, dtype),
            "wo": _dense_init(ko, cfg.n_heads * dh, d, dtype, out_gain),
            "q_norm": _norm_scale(dh, math.sqrt(_SCORE_SPREAD)),
            "k_norm": _norm_scale(dh, math.sqrt(_SCORE_SPREAD)),
        }
    if layer < cfg.n_dense_layers:
        block["mlp"] = _swiglu_init(k_ffn, d, cfg.d_ff, dtype, out_gain)
        return block
    kr, kbias, kgu, kdn = jax.random.split(k_ffn, 4)
    e, f, count = cfg.n_routed, cfg.d_ff_expert, cfg.held[1]
    block["mlp"] = {
        # As models.moonlight draws them: unit-variance logits, a
        # selection bias of about a tenth of the scores' spread.
        "router": {"kernel": _normal(kr, (d, e), d, jnp.float32),
                   "bias": 0.02 * jax.random.normal(kbias, (e,),
                                                    jnp.float32)},
        "experts": {"gate_up": _bank(kgu, (count, d, 2 * f), d, dtype),
                    "down": _bank(kdn, (count, f, d), f / out_gain ** 2,
                                  dtype)},
    }
    return block


def lfm2_init(key, cfg: Lfm2Config):
    dtype = jnp.dtype(cfg.param_dtype)
    k_tok, k_head, *k_layers = jax.random.split(key, 2 + cfg.n_layers)
    return {
        "tok_embed": {"table": jax.random.normal(
            k_tok, (cfg.vocab, cfg.d_model), dtype)},
        "layers": [_block_init(k, cfg, l) for l, k in enumerate(k_layers)],
        "ln_f": nn.rmsnorm_init(cfg.d_model),
        "head": _dense_init(k_head, cfg.d_model, cfg.vocab, dtype),
    }


# -- one layer's pieces ----------------------------------------------------------

def _conv_inputs(cp, r, dtype):
    """r: (..., d) normalised. Returns (u = B * x, C), float32."""
    gate_b, gate_c, x = jnp.split(
        nn.dense(cp["w_in"], r, dtype=dtype).astype(jnp.float32), 3, axis=-1)
    return gate_b * x, gate_c


def _conv_output(cp, c, gate_c, dtype):
    return nn.dense(cp["w_out"], (gate_c * c).astype(dtype), dtype=dtype)


def _attn_inputs(ap, r, positions, cfg: Lfm2Config, dtype):
    """r: (S, d) normalised, at `positions` (S,). Returns q (S, H, D), k
    and v (S, H_kv, D): q and k normalised a head and rotated, as the pool
    holds k."""
    def heads(name):
        y = nn.dense(ap[name], r, dtype=dtype)
        return y.reshape(y.shape[0], -1, cfg.d_head)

    inv = _inv_freq(cfg)
    q = nn.rmsnorm(ap["q_norm"], heads("wq"), eps=cfg.ln_eps).astype(dtype)
    k = nn.rmsnorm(ap["k_norm"], heads("wk"), eps=cfg.ln_eps).astype(dtype)
    return (_rope(q, positions, inv, 1.0), _rope(k, positions, inv, 1.0),
            heads("wv").astype(dtype))


def _experts_ffn(mp, z, valid, cfg: Lfm2Config, dtype, held, max_tokens):
    """z: (N, d) normalised; valid: (N,); `mp["experts"]` holds the `held`
    experts alone. Returns (y (N, d), rows (n_routed,): the rows each HELD
    expert took, zero elsewhere)."""
    experts, weights = sigmoid_topk_route(z, mp["router"], cfg.top_k,
                                          cfg.routed_scale)
    # The bank's group 0 is expert `held[0]`.
    return routed_experts(
        z, valid, experts, weights, mp["experts"], first_group=-held[0],
        n_experts=cfg.n_routed, held=held, max_tokens=max_tokens,
        dtype=dtype)


def _run_layers(params, h, carry, cfg: Lfm2Config, conv, attend, valid,
                dtype, held, max_tokens):
    """The layers in order (a Python loop: they differ in shape), h: (N, d).
    `conv(at, cp, r, carry)` and `attend(at, ap, r, carry)` -> (the
    operator's output, carry), `at` the layer of the kind's pool. Returns
    (h, carry, rows (L_moe, n_routed))."""
    rows = []
    for layer, (kind, at, bp) in enumerate(zip(
            cfg.layer_types, cfg.pool_layer, params["layers"])):
        enter, leave = (("mixer/in", "mixer/out") if kind == CONV
                        else ("attn/qkv", "attn/out"))
        with step_part(enter):
            r = nn.rmsnorm(bp["ln1"], h, eps=cfg.ln_eps)
        if kind == CONV:
            y, carry = conv(at, bp["conv"], r, carry)
        else:
            y, carry = attend(at, bp["attn"], r, carry)
        with step_part(leave):
            h = (h + y).astype(dtype)
        if layer < cfg.n_dense_layers:
            with step_part("mlp"):
                z = nn.rmsnorm(bp["ln2"], h, eps=cfg.ln_eps)
                h = (h + _mlp(bp["mlp"], z, dtype, cfg)).astype(dtype)
        else:
            with step_part("moe/route"):
                z = nn.rmsnorm(bp["ln2"], h, eps=cfg.ln_eps)
            y, taken = _experts_ffn(bp["mlp"], z, valid, cfg, dtype, held,
                                    max_tokens)
            rows.append(taken)
            with step_part("moe/experts"):
                h = (h + y).astype(dtype)
    rows = (jnp.stack(rows) if rows
            else jnp.zeros((0, cfg.n_routed), jnp.int32))
    return h, carry, rows


# -- the one-shot forward --------------------------------------------------------

def lfm2_apply(params, tokens, cfg: Lfm2Config, *, dtype=jnp.bfloat16):
    """Full-sequence causal forward from an empty state over the held
    experts. tokens: (B, S) int32 -> logits (B, S, vocab) float32."""
    b, s = tokens.shape
    h = nn.embedding(params["tok_embed"], tokens).astype(dtype)
    taps = cfg.conv_width
    positions = jnp.arange(s)

    def by_row(fn):
        """An operator over one sequence, over the batch's flattened rows."""
        def call(at, p, r, carry):
            y = jax.vmap(lambda row: fn(p, row))(r.reshape(b, s, -1))
            return y.reshape(b * s, -1), carry
        return call

    def attend(ap, r):
        q, k, v = _attn_inputs(ap, r, positions, cfg, dtype)
        o = dot_product_attention(q[None], k[None], v[None], causal=True)[0]
        return _attn_output(ap, o, dtype)

    def conv(cp, r):
        u, gate_c = _conv_inputs(cp, r, dtype)
        ext = jnp.pad(u, ((taps - 1, 0), (0, 0)))     # zero before token 0
        c = sum(cp["taps"][j] * ext[j:j + s] for j in range(taps))
        return _conv_output(cp, c, gate_c, dtype)

    h, _, _ = _run_layers(params, h.reshape(b * s, -1), (), cfg,
                          by_row(conv), by_row(attend),
                          jnp.ones((b * s,), bool), dtype, cfg.held, None)
    return lm_head(params, h.reshape(b, s, -1), cfg.ln_eps, dtype)


# -- the served step: the mixed tick over the block pool and the state pool -------

def _conv_rows(cp, r, tails, at: int, start, rows, row, slot, pos0, qlen,
               cfg: Lfm2Config, dtype):
    """The conv operator over the tick's token list, decoding and
    prefilling rows alike. r: (M, d) normalised, list entry i slot
    `slot[i]` of row `row[i]`, row b's new tokens at [start[b], start[b] +
    qlen[b]); tails: the state pool's one array (L_conv, R, K - 1, d)
    float32, row b's at `rows[b]` of layer `at` (the null row 0 for a free
    slot), read as zero where the row starts at position 0. Token i's lag
    j is the list's entry i - j where its slot is j or more, else the
    tail's; the tail written back is the last K - 1 of (old tail ++ the
    run's u): a run of one token shifts it by one, a row with no token
    keeps it. Returns (the operator's output (M, d), tails)."""
    m, keep = r.shape[0], cfg.conv_width - 1
    with step_part("mixer/in"):
        # Written here, once: `gate_c` is read two parts later
        # (`models.falcon_h1._ssm_inputs` says why).
        u, gate_c = jax.lax.optimization_barrier(_conv_inputs(cp, r, dtype))
    with step_part("mixer/step"):
        old = jnp.where((pos0 == 0)[:, None, None], 0.0, tails[at, rows])
        at_list = jnp.arange(m)
        c = cp["taps"][keep] * u
        for lag in range(1, keep + 1):
            before = jnp.where(
                (slot >= lag)[:, None], u[jnp.maximum(at_list - lag, 0)],
                old[row, jnp.clip(keep - lag + slot, 0, keep - 1)])
            c = c + cp["taps"][keep - lag] * before
        # Entry e of (old tail ++ the run's u), e = qlen .. qlen + K - 2.
        e = qlen[:, None] + jnp.arange(keep)[None, :]            # (B, K-1)
        new = jnp.where(
            (e >= keep)[:, :, None],
            u[jnp.clip(start[:, None] + e - keep, 0, m - 1)],
            jnp.take_along_axis(old, jnp.minimum(e, keep - 1)[:, :, None],
                                axis=1))
        tails = tails.at[at, rows].set(new)
    with step_part("mixer/out"):
        return _conv_output(cp, c, gate_c, dtype), tails


def lfm2_step_rows_ragged(params, tokens, caches, tables, pos0, qlen,
                          cfg: Lfm2Config, *, dtype=jnp.bfloat16,
                          attn_fn=None, sample_slot=None, held=None,
                          max_tokens: Optional[int] = None):
    """This family's step of the mixed tick, over the tick's token list
    (`models.tick_tokens`, a token an entry).

    caches: (the block pool's K/V pair, (attention layers, NB, bs,
    H_kv*D); the state pool's arrays, a tuple of ONE: the conv tails,
    `_conv_rows`), both updated in place (donate them); tables: (the rows'
    block table (B, nb); the rows' state row (B,), the null row 0 for a
    free slot). An attention layer is `PagedKV.attend` at G = n_heads /
    n_kv_heads; a conv layer is `_conv_rows`, one body whatever the rows'
    runs.

    ``held`` = (first, count): the experts `params` holds (default
    `cfg.held`). Returns (logits, caches, rows (L_moe, n_routed) int32:
    the rows each held expert took)."""
    from tpu_engine.ops import paged_attention as pa

    if attn_fn is None:
        attn_fn = pa.default_ragged_attention()
    held = held or cfg.held
    (pool, (tails,)), (table, rows) = caches, tables
    tt = tick_tokens(pos0, qlen, tokens.shape[1], max_tokens)
    kv = tt.paged_kv(table, pool.k.shape[2], cfg.n_heads // cfg.kv_heads)
    h = tt.embed(params, tokens, dtype)

    def conv(at, cp, r, carry):
        pool, tails = carry
        y, tails = _conv_rows(cp, r, tails, at, tt.plan.start, rows, tt.row,
                              tt.slot, pos0, qlen, cfg, dtype)
        return y, (pool, tails)

    def attend(at, ap, r, carry):
        pool, tails = carry
        with step_part("attn/qkv"):
            q, k, v = _attn_inputs(ap, r, tt.logical, cfg, dtype)
        o, pool = kv.attend(attn_fn, q, k, v, pool, at)
        with step_part("attn/out"):
            return _attn_output(ap, o, dtype), (pool, tails)

    h, (pool, tails), taken = _run_layers(
        params, h, (tuple(pool), tails), cfg, conv, attend, tt.valid, dtype,
        held, max_tokens)
    return (lm_head(params, tt.head_rows(h, sample_slot), cfg.ln_eps, dtype),
            (KVCache(*pool), (tails,)), taken)


# -- registry ----------------------------------------------------------------------

def _lm_spec(name: str, cfg: Lfm2Config, seq_len: int) -> ModelSpec:
    return causal_lm_spec(name, cfg, seq_len, lfm2_init, lfm2_apply,
                          ragged_step=lfm2_step_rows_ragged, held=cfg.held)


def _cfg(**kw) -> Lfm2Config:
    n = kw["n_layers"]
    # The source lists the whole model's layers; a cut keeps the first
    # `n_layers` of what it is given.
    return Lfm2Config(
        vocab=kw["vocab"], n_layers=n, d_model=kw["d_model"],
        n_heads=kw["n_heads"], n_kv_heads=kw["n_kv_heads"],
        d_ff=kw["d_ff_dense"], max_seq=kw["max_seq"], causal=True,
        norm="rmsnorm", pos="rope", mlp_act="swiglu", ln_eps=kw["ln_eps"],
        rope_theta=kw["rope_theta"],
        layer_types=tuple(kw["layer_types"])[:n],
        conv_width=kw["conv_width"], n_dense_layers=kw["n_dense_layers"],
        d_ff_expert=kw["d_ff_expert"], n_routed=kw["n_experts"],
        top_k=kw["top_k"], routed_scale=kw["routed_scale"],
        held=(kw["held_first"], kw["held_count"] or kw["n_experts"]),
        param_dtype=kw["param_dtype"])


_PUBLISHED_LAYERS = ((CONV, CONV, ATTENTION, CONV) * 10)[:40]


@register("lfm2")
def make_lfm2(seq_len: int = 128, vocab: int = 65536, n_layers: int = 40,
              layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS,
              d_model: int = 2048, n_heads: int = 32, n_kv_heads: int = 8,
              d_ff_dense: int = 11776, d_ff_expert: int = 1536,
              n_experts: int = 64, top_k: int = 4,
              routed_scale: float = 1.0, n_dense_layers: int = 2,
              conv_width: int = 3, held_first: int = 0, held_count: int = 0,
              max_seq: int = 16384, ln_eps: float = 1e-5,
              rope_theta: float = 1e6,
              param_dtype: str = "bfloat16") -> ModelSpec:
    """LFM2-24B-A2B's published geometry; every width a keyword.
    `held_count` 0 holds every expert."""
    return _lm_spec("lfm2", _cfg(**{k: v for k, v in locals().items()
                                    if k != "seq_len"}), seq_len)


@register("lfm2-small-test")
def make_lfm2_small(seq_len: int = 16, vocab: int = 256, n_layers: int = 5,
                    layer_types: Tuple[str, ...] = (
                        CONV, ATTENTION, CONV, CONV, ATTENTION),
                    d_model: int = 48, n_heads: int = 4, n_kv_heads: int = 2,
                    d_ff_dense: int = 96, d_ff_expert: int = 32,
                    n_experts: int = 16, top_k: int = 4,
                    routed_scale: float = 1.0, n_dense_layers: int = 1,
                    conv_width: int = 3, held_first: int = 0,
                    held_count: int = 0, max_seq: int = 128,
                    ln_eps: float = 1e-5, rope_theta: float = 1e6,
                    param_dtype: str = "float32") -> ModelSpec:
    """Tiny config for tests: a conv layer with the dense feed-forward,
    then attention, conv, conv, attention with 16 experts top 4, 4 query
    heads over 2 KV heads of 12 lanes, float32."""
    return _lm_spec("lfm2-small-test",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)
