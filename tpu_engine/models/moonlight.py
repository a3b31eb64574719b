"""Moonlight / DeepSeek-V3 family: latent attention (MLA) and sparse experts
routed without drops, one or more dense layers before the expert layers.

Source of the default geometry: Moonlight-16B-A3B
(https://huggingface.co/moonshotai/Moonlight-16B-A3B, `model_type:
deepseek_v3`, `q_lora_rank: null`). A layer, `x` a token's hidden state:

- block     ``h += MLA(RMS(h))``, ``h += FFN(RMS(h))``; final RMSNorm; a
            separate LM head.
- MLA       ``q = x Wq`` -> per head ``q_nope`` ‖ ``q_pe``;
            ``[c_raw ‖ k_pe_raw] = x Wkv_a``; ``c = RMS(c_raw)`` (its own
            scale and eps); ``k_pe = RoPE(k_pe_raw)``, one for all heads;
            ``[k_nope ‖ v]_head = c Wkv_b``;
            ``score = (q_nope.k_nope + RoPE(q_pe).k_pe) / sqrt(nope+rope)``,
            causal soft-max, ``out = concat_heads(sum p v) Wo``.
- FFN       the first `n_dense_layers` layers: SwiGLU of width `d_ff_dense`;
            the others: ``s = sigmoid(x Wg)`` in float32, the top `top_k` of
            ``s + b`` chosen, weights ``s[chosen]`` normalised times
            `routed_scale`, ``y = sum w_i E_i(x) + S(x)`` with each ``E_i`` a
            SwiGLU of width `d_ff_expert` and ``S`` one SwiGLU of width
            ``n_shared * d_ff_expert``. No token is dropped.

Two forms of the same attention. `moonlight_apply` (the one-shot forward)
computes it EXPANDED, as published. The served step
(`moonlight_step_rows_ragged`, the mixed tick of runtime.scheduler) caches
only ``(c, k_pe)`` a token and layer in the block pool and reads it
ABSORBED (`ops.latent_attention`): ``q_lat = q_nope W_UK``, scores over the
latent, ``o_head = (sum p c) W_UV``, `W_UK`/`W_UV` the two halves of
`Wkv_b`. The cache holds ``c`` after its norm and ``k_pe`` after its
rotation.

Parameter tree: `tok_embed`, `dense` and `moe` (two stacks of blocks, each
stacked on a leading layer axis and scanned in turn with the layer index
running through), `ln_f`, `head`. A block is `ln1`, `attn` {wq, wkv_a,
kv_norm, wkv_b, wo}, `ln2`, `mlp`; an expert block's `mlp` is {router
{kernel, bias: float32}, shared {gate, up, proj}, experts {gate_up
(E, d, 2f), down (E, f, d)}}. Weights are made in `param_dtype` directly
(norm scales, the router and its bias stay float32): at the published
widths a float32 copy of the expert banks alone is 13 GB.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_engine.models.registry import ModelSpec, causal_lm_spec, register
from tpu_engine.models.tick_tokens import lm_head, tick_tokens
from tpu_engine.models.transformer import (
    TransformerConfig,
    _mlp,
    _write_pool,
)
from tpu_engine.ops import nn
from tpu_engine.ops.attention import KVCache, rope
from tpu_engine.ops.latent_attention import PE_LANES, pad_rope_lanes
from tpu_engine.ops.moe import routed_experts, sigmoid_topk_route
from tpu_engine.utils.tracing import step_part


@dataclasses.dataclass(frozen=True)
class MoonlightConfig(TransformerConfig):
    """Every width is stated; none is derived from `d_model / n_heads`.
    The base fields this family fixes: rmsnorm, rope, swiglu, one (latent)
    KV head, `d_ff` = the dense layers' width."""
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128
    kv_lora_rank: int = 512
    d_ff_expert: int = 1408
    n_routed: int = 64
    top_k: int = 6
    n_shared: int = 2
    routed_scale: float = 2.446
    n_dense_layers: int = 1
    kv_norm_eps: float = 1e-6
    param_dtype: str = "bfloat16"

    # The registry derives family and TP rule from these two.
    serving_state_family = "kv_latent"
    tp_partition_rule = ("unshardable: the latent pool has one KV head and "
                         "no head axis to split; experts shard over chips, "
                         "which the served path cannot do yet")

    @property
    def d_head(self) -> int:
        """A query/key head as the scores see it."""
        return self.qk_nope + self.qk_rope

    @property
    def kv_lanes(self) -> Tuple[int, int]:
        """The latent pool: (rope-key lanes, latent lanes) a token and
        layer (`ops.latent_attention` states the layout)."""
        return (PE_LANES, self.kv_lora_rank)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_nope + self.qk_rope)


# -- parameters -----------------------------------------------------------------

def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, dtype)
            * jnp.asarray(1.0 / math.sqrt(fan_in), dtype))


def _dense_init(key, d_in, d_out, dtype, gain: float = 1.0):
    return {"kernel": _normal(key, (d_in, d_out), d_in / gain ** 2, dtype),
            "bias": jnp.zeros((d_out,), jnp.float32)}


def _swiglu_init(key, d, f, dtype, out_gain):
    kg, ku, kp = jax.random.split(key, 3)
    return {"gate": _dense_init(kg, d, f, dtype),
            "up": _dense_init(ku, d, f, dtype),
            "proj": _dense_init(kp, f, d, dtype, out_gain)}


def _residual_gain(cfg: "MoonlightConfig") -> float:
    """What writes into the residual stream (wo, every FFN's output
    matrix) is drawn 1/sqrt(2 L) smaller, the GPT-2 / Megatron rule, and
    the embedding has unit variance: a layer then moves the stream by a
    fraction of its size, as in a trained model. With unscaled outputs
    over a 0.02 embedding the last layers ARE the stream, and one router
    choice flipped by bfloat16 rounding moves a logit by two standard
    deviations (PERF.md, PR 28: 70 % of served tokens were the float32
    reference's arg-max; with this rule 95 %)."""
    return 1.0 / math.sqrt(2.0 * cfg.n_layers)


def _block_init(key, cfg: MoonlightConfig, moe: bool):
    dtype = jnp.dtype(cfg.param_dtype)
    d, h = cfg.d_model, cfg.n_heads
    out_gain = _residual_gain(cfg)
    kq, ka, kb, ko, kf, kr, kbias, ks, kgu, kdn = jax.random.split(key, 10)
    block = {
        "ln1": nn.rmsnorm_init(d),
        "attn": {
            "wq": _dense_init(kq, d, h * cfg.d_head, dtype),
            "wkv_a": _dense_init(ka, d, cfg.kv_lora_rank + cfg.qk_rope,
                                 dtype),
            "kv_norm": nn.rmsnorm_init(cfg.kv_lora_rank),
            "wkv_b": _dense_init(kb, cfg.kv_lora_rank,
                                 h * (cfg.qk_nope + cfg.v_head), dtype),
            "wo": _dense_init(ko, h * cfg.v_head, d, dtype, out_gain),
        },
        "ln2": nn.rmsnorm_init(d),
    }
    if not moe:
        block["mlp"] = _swiglu_init(kf, d, cfg.d_ff, dtype, out_gain)
        return block
    e, f = cfg.n_routed, cfg.d_ff_expert
    block["mlp"] = {
        # Unit-variance logits on a normalised input; a selection bias of
        # about a tenth of the scores' spread (sigmoid of a unit normal
        # spreads by 0.21): enough to change some choices, so a test can
        # tell it was dropped, without deciding them.
        "router": {"kernel": _normal(kr, (d, e), d, jnp.float32),
                   "bias": 0.02 * jax.random.normal(kbias, (e,),
                                                    jnp.float32)},
        "shared": _swiglu_init(ks, d, cfg.n_shared * f, dtype, out_gain),
        "experts": {"gate_up": _normal(kgu, (e, d, 2 * f), d, dtype),
                    "down": _normal(kdn, (e, f, d), f / out_gain ** 2,
                                    dtype)},
    }
    return block


def moonlight_init(key, cfg: MoonlightConfig):
    """Leaves in `cfg.param_dtype` from the start, a layer at a time
    (`lax.map` over the layer keys writes each layer into its stack in
    place), so no float32 copy and no whole-stack temporary exists."""
    dtype = jnp.dtype(cfg.param_dtype)
    k_tok, k_dense, k_moe, k_head = jax.random.split(key, 4)

    def stack(k, n, moe):
        return jax.lax.map(lambda kk: _block_init(kk, cfg, moe),
                           jax.random.split(k, n))

    params = {
        "tok_embed": {"table": jax.random.normal(
            k_tok, (cfg.vocab, cfg.d_model), dtype)},
        "ln_f": nn.rmsnorm_init(cfg.d_model),
        "head": _dense_init(k_head, cfg.d_model, cfg.vocab, dtype),
    }
    if cfg.n_dense_layers:
        params["dense"] = stack(k_dense, cfg.n_dense_layers, False)
    if cfg.n_moe_layers:
        params["moe"] = stack(k_moe, cfg.n_moe_layers, True)
    return params


# -- one layer's pieces ----------------------------------------------------------

def _attn_inputs(ap, x, positions, cfg: MoonlightConfig, dtype):
    """x: (B, S, d) normalised. Returns q_nope (B, S, H, nope), q_pe
    (B, S, H, rope) rotated, c (B, S, C) normalised, k_pe (B, S, rope)
    rotated — c and k_pe as the cache holds them, in `dtype`.
    `positions=None`: a model that rotates nothing (`models.kimi_linear`,
    `mla_use_nope`); the 64 lanes are then plain key lanes the heads
    share."""
    b, s, _ = x.shape
    q = nn.dense(ap["wq"], x, dtype=dtype).astype(dtype)
    q = q.reshape(b, s, cfg.n_heads, cfg.d_head)
    q_nope, q_pe = q[..., :cfg.qk_nope], q[..., cfg.qk_nope:]
    kv_a = nn.dense(ap["wkv_a"], x, dtype=dtype)
    c = nn.rmsnorm(ap["kv_norm"], kv_a[..., :cfg.kv_lora_rank],
                   eps=cfg.kv_norm_eps).astype(dtype)
    k_pe = kv_a[..., cfg.kv_lora_rank:].astype(dtype)[:, :, None, :]
    if positions is not None:
        q_pe = rope(q_pe, positions, cfg.rope_theta)
        k_pe = rope(k_pe, positions, cfg.rope_theta)
    return q_nope, q_pe, c, k_pe[:, :, 0]


def _kv_b_halves(ap, cfg: MoonlightConfig, dtype):
    """`Wkv_b` (C, H * (nope + v)) as W_UK (C, H, nope), W_UV (C, H, v)."""
    w = ap["wkv_b"]["kernel"].astype(dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope + cfg.v_head)
    return w[..., :cfg.qk_nope], w[..., cfg.qk_nope:]


def _attn_expanded(ap, x, positions, cfg: MoonlightConfig, dtype):
    """The published form over a whole causal sequence: every head's key
    and value are made from the latent."""
    b, s, _ = x.shape
    q_nope, q_pe, c, k_pe = _attn_inputs(ap, x, positions, cfg, dtype)
    w_uk, w_uv = _kv_b_halves(ap, cfg, dtype)
    k_nope = jnp.einsum("bsc,chn->bshn", c, w_uk,
                        preferred_element_type=jnp.float32).astype(dtype)
    v = jnp.einsum("bsc,chv->bshv", c, w_uv,
                   preferred_element_type=jnp.float32).astype(dtype)
    scores = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe,
                           preferred_element_type=jnp.float32))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(
        jnp.where(causal, scores * cfg.attn_scale, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhv->bqhv", probs.astype(dtype), v,
                   preferred_element_type=jnp.float32).astype(dtype)
    return nn.dense(ap["wo"], o.reshape(b, s, -1), dtype=dtype)


def _absorb(ap, q_nope, cfg: MoonlightConfig, dtype):
    """q_lat = q_nope W_UK: the key up-projection folded into the query."""
    w_uk, _ = _kv_b_halves(ap, cfg, dtype)
    return jnp.einsum("bshn,chn->bshc", q_nope, w_uk,
                      preferred_element_type=jnp.float32).astype(dtype)


def _unabsorb(ap, o_lat, cfg: MoonlightConfig, dtype):
    """(B, S, H, C) latent outputs -> the block's attention output."""
    b, s = o_lat.shape[:2]
    _, w_uv = _kv_b_halves(ap, cfg, dtype)
    o = jnp.einsum("bshc,chv->bshv", o_lat.astype(dtype), w_uv,
                   preferred_element_type=jnp.float32).astype(dtype)
    return nn.dense(ap["wo"], o.reshape(b, s, -1), dtype=dtype)


def _moe_ffn(mp, x, valid, bank, first_group, cfg: MoonlightConfig, dtype,
             held, max_tokens):
    """x: (B, S, d) normalised; valid: (B, S). Returns (y, rows (E,))."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    experts, weights = sigmoid_topk_route(flat, mp["router"], cfg.top_k,
                                          cfg.routed_scale)
    y, rows = routed_experts(
        flat, valid.reshape(-1), experts, weights, bank,
        first_group=first_group, n_experts=cfg.n_routed, held=held,
        max_tokens=max_tokens, dtype=dtype)
    with step_part("moe/shared"):
        return y.reshape(b, s, d) + _mlp(mp["shared"], x, dtype, cfg), rows


def _whole_bank(params):
    """Every expert layer's bank on ONE leading axis, (L_moe * E, ...): a
    reshape of the stacked leaves, no copy. Layer l's experts are groups
    [l * E, (l + 1) * E)."""
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                        params["moe"]["mlp"]["experts"])


def _without_bank(moe_stack):
    mlp = {k: v for k, v in moe_stack["mlp"].items() if k != "experts"}
    return dict(moe_stack, mlp=mlp)


def _run_layers(params, h, carry, cfg: MoonlightConfig, layer_fn, valid,
                dtype, held, max_tokens):
    """The dense stack, then the expert stack, `layer_fn(bp, x, carry,
    layer) -> (attention output, carry)` with the layer index running
    through both. Returns (h, carry, rows (L_moe, E))."""

    def attend(bp, h, carry, layer):
        with step_part("attn/qkv"):
            x = nn.rmsnorm(bp["ln1"], h, eps=cfg.ln_eps)
        a, carry = layer_fn(bp, x, carry, layer)
        with step_part("attn/out"):
            return (h + a).astype(dtype), carry

    if cfg.n_dense_layers:
        def dense_body(state, xs):
            bp, layer = xs
            h, carry = attend(bp, *state, layer)
            with step_part("mlp"):
                x = nn.rmsnorm(bp["ln2"], h, eps=cfg.ln_eps)
                return ((h + _mlp(bp["mlp"], x, dtype, cfg)).astype(dtype),
                        carry), None

        (h, carry), _ = jax.lax.scan(
            dense_body, (h, carry),
            (params["dense"],
             jnp.arange(cfg.n_dense_layers, dtype=jnp.int32)))
    rows = jnp.zeros((0, cfg.n_routed), jnp.int32)
    if cfg.n_moe_layers:
        bank = _whole_bank(params)

        def moe_body(state, xs):
            bp, k = xs
            h, carry = attend(bp, *state, cfg.n_dense_layers + k)
            with step_part("moe/route"):
                x = nn.rmsnorm(bp["ln2"], h, eps=cfg.ln_eps)
            y, rows = _moe_ffn(bp["mlp"], x, valid, bank, k * cfg.n_routed,
                               cfg, dtype, held, max_tokens)
            with step_part("moe/shared"):
                return ((h + y).astype(dtype), carry), rows

        (h, carry), rows = jax.lax.scan(
            moe_body, (h, carry),
            (_without_bank(params["moe"]),
             jnp.arange(cfg.n_moe_layers, dtype=jnp.int32)))
    return h, carry, rows


# -- the one-shot forward (expanded attention) -----------------------------------

def moonlight_apply(params, tokens, cfg: MoonlightConfig, *,
                    dtype=jnp.bfloat16, absorbed: bool = False):
    """Full-sequence causal forward. tokens: (B, S) int32 -> logits
    (B, S, vocab) float32. `absorbed=True` computes the attention in the
    served step's form over the uncached sequence (tests hold the two
    forms together)."""
    b, s = tokens.shape
    positions = jnp.arange(s)
    h = nn.embedding(params["tok_embed"], tokens).astype(dtype)

    def layer_fn(bp, x, carry, layer):
        del layer
        if not absorbed:
            return _attn_expanded(bp["attn"], x, positions, cfg, dtype), carry
        q_nope, q_pe, c, k_pe = _attn_inputs(bp["attn"], x, positions, cfg,
                                             dtype)
        q_lat = _absorb(bp["attn"], q_nope, cfg, dtype)
        scores = (jnp.einsum("bqhc,bkc->bhqk", q_lat, c,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe,
                               preferred_element_type=jnp.float32))
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(
            jnp.where(causal, scores * cfg.attn_scale, -jnp.inf), axis=-1)
        o_lat = jnp.einsum("bhqk,bkc->bqhc", probs.astype(dtype), c,
                           preferred_element_type=jnp.float32)
        return _unabsorb(bp["attn"], o_lat, cfg, dtype), carry

    h, _, _ = _run_layers(params, h, (), cfg, layer_fn,
                          jnp.ones((b, s), bool), dtype, None, None)
    return lm_head(params, h, cfg.ln_eps, dtype)


# -- the served step: the mixed tick over the latent pool -------------------------

def moonlight_step_rows_ragged(params, tokens, caches: KVCache, tables, pos0,
                               qlen, cfg: MoonlightConfig, *,
                               dtype=jnp.bfloat16, attn_fn=None,
                               sample_slot=None, held=None,
                               max_tokens: Optional[int] = None):
    """This family's step of the mixed tick
    (`models.transformer.transformer_step_rows_ragged`'s arguments), over
    the tick's token list (`models.tick_tokens`) in tiles of
    `ops.latent_attention.slots_per_tile` slots: embedding, projections,
    cache writes, the read, the FFNs and the router all run over
    (n_tiles, S), 68 x 8 tokens where the slots are 32 x 256.

    caches: the latent pool pair, k (L, NB, bs, PE_LANES) rope keys and
    v (L, NB, bs, C) latents, updated in place through both layer loops
    (donate it and a tick copies no layer of it): every token's (c, k_pe)
    is scattered into the row's blocks BEFORE the absorbed read
    (write-before-attend). ``attn_fn`` defaults to
    `ops.latent_attention.default_latent_attention()`.

    ``held`` = (first, count): the experts this shard holds (default
    all; `ops.moe.routed_experts`).

    Returns (logits, caches, rows): logits as the transformer step's
    ((B, vocab) of slot `sample_slot[b]`, or (B, W, vocab), padding
    slots zero-fed); rows (L_moe, E) int32, the rows each expert of each
    expert layer took this tick."""
    from tpu_engine.ops import latent_attention as la

    if attn_fn is None:
        attn_fn = la.default_latent_attention()
    w = tokens.shape[1]
    tt = tick_tokens(pos0, qlen, w, max_tokens,
                     per_tile=la.slots_per_tile(cfg.n_heads, w))
    blk, off = tt.blocks(tables, caches.k.shape[2])
    with step_part("plan"):
        lengths = pos0 + qlen
    h = tt.embed(params, tokens, dtype)

    def layer_fn(bp, x, cache_kv, layer):
        with step_part("attn/qkv"):
            q_nope, q_pe, c, k_pe = _attn_inputs(bp["attn"], x, tt.logical,
                                                 cfg, dtype)
        with step_part("attn/write"):
            cache_kv = _write_pool(cache_kv, layer, blk, off,
                                   pad_rope_lanes(k_pe)[:, :, None, :],
                                   c[:, :, None, :])
        with step_part("attn/qkv"):
            q_lat = _absorb(bp["attn"], q_nope, cfg, dtype)
        with step_part("attn/read"):
            o_lat = attn_fn(q_lat, q_pe, *cache_kv, layer, tables, tt.plan,
                            pos0, lengths, scale=cfg.attn_scale)
        with step_part("attn/out"):
            return _unabsorb(bp["attn"], o_lat, cfg, dtype), cache_kv

    h, cache_kv, rows = _run_layers(params, h, tuple(caches), cfg, layer_fn,
                                    tt.valid, dtype, held, max_tokens)
    return (lm_head(params, tt.head_rows(h, sample_slot), cfg.ln_eps, dtype),
            KVCache(*cache_kv), rows)


# -- registry ----------------------------------------------------------------------

def _lm_spec(name: str, cfg: MoonlightConfig, seq_len: int) -> ModelSpec:
    return causal_lm_spec(name, cfg, seq_len, moonlight_init, moonlight_apply,
                          ragged_step=moonlight_step_rows_ragged)


def _cfg(**kw) -> MoonlightConfig:
    return MoonlightConfig(
        vocab=kw["vocab"], n_layers=kw["n_layers"], d_model=kw["d_model"],
        n_heads=kw["n_heads"], d_ff=kw["d_ff_dense"], max_seq=kw["max_seq"],
        causal=True, norm="rmsnorm", pos="rope", mlp_act="swiglu",
        n_kv_heads=1, rope_theta=kw["rope_theta"], ln_eps=kw["ln_eps"],
        qk_nope=kw["qk_nope"], qk_rope=kw["qk_rope"], v_head=kw["v_head"],
        kv_lora_rank=kw["kv_lora_rank"], d_ff_expert=kw["d_ff_expert"],
        n_routed=kw["n_experts"], top_k=kw["top_k"], n_shared=kw["n_shared"],
        routed_scale=kw["routed_scale"],
        n_dense_layers=kw["n_dense_layers"], kv_norm_eps=kw["kv_norm_eps"],
        param_dtype=kw["param_dtype"])


@register("moonlight")
def make_moonlight(seq_len: int = 128, vocab: int = 163840,
                   n_layers: int = 27, d_model: int = 2048,
                   n_heads: int = 16, qk_nope: int = 128, qk_rope: int = 64,
                   v_head: int = 128, kv_lora_rank: int = 512,
                   d_ff_dense: int = 11264, d_ff_expert: int = 1408,
                   n_experts: int = 64, top_k: int = 6, n_shared: int = 2,
                   routed_scale: float = 2.446, n_dense_layers: int = 1,
                   max_seq: int = 8192, rope_theta: float = 50000.0,
                   ln_eps: float = 1e-5, kv_norm_eps: float = 1e-6,
                   param_dtype: str = "bfloat16") -> ModelSpec:
    """Moonlight-16B-A3B's published geometry; every width a keyword."""
    return _lm_spec("moonlight", _cfg(**{k: v for k, v in locals().items()
                                         if k != "seq_len"}), seq_len)


@register("moonlight-small-test")
def make_moonlight_small(seq_len: int = 16, vocab: int = 256,
                         n_layers: int = 3, d_model: int = 64,
                         n_heads: int = 4, qk_nope: int = 16,
                         qk_rope: int = 8, v_head: int = 16,
                         kv_lora_rank: int = 32, d_ff_dense: int = 128,
                         d_ff_expert: int = 32, n_experts: int = 8,
                         top_k: int = 2, n_shared: int = 1,
                         routed_scale: float = 2.446,
                         n_dense_layers: int = 1, max_seq: int = 128,
                         rope_theta: float = 50000.0, ln_eps: float = 1e-5,
                         kv_norm_eps: float = 1e-6,
                         param_dtype: str = "float32") -> ModelSpec:
    """Tiny config for tests: one dense and two expert layers, float32."""
    return _lm_spec("moonlight-small-test",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)
