"""Laguna family: full-attention and sliding-window layers in one model,
each kind with its own query-head count and rope, a per-head gate on the
attention output, and sigmoid-routed experts of which a chip may hold a
share.

Source of the default geometry: Laguna-S-2.1
(https://huggingface.co/poolside/Laguna-S-2.1, `model_type: laguna`). A
layer l, `x` a token's hidden state, `H_l` = `heads_per_layer[l]` query
heads over `n_kv_heads` KV heads of `head_dim` lanes:

- block     ``h += Attn(RMS(h))``, ``h += FFN(RMS(h))``; final RMSNorm; a
            separate LM head.
- Attn      ``q = x Wq`` (H_l heads), ``k = x Wk``, ``v = x Wv``; q and k
            RMS-normalised a head with a learned scale; rope by the
            layer's kind (below); causal soft-max of ``q.k / sqrt(D)``,
            on a WINDOW layer over the last `window` keys only
            (``i - window < j <= i``); head n's output times
            ``softplus(x Wg)[n]`` (`gating: per-head`), then ``Wo``.
- rope      window layers: plain, `window_rope_theta`, all D lanes. Full
            layers: the first ``partial_rotary * D`` lanes only, YaRN
            frequencies (transformers' `_compute_yarn_parameters`) from
            `rope_theta`, cos and sin times `yarn_attention_factor`.
- FFN       the first `n_dense_layers` layers: SwiGLU of width `d_ff`; the
            others: `ops.moe.sigmoid_topk_route` over `n_routed` experts
            (top `top_k`, weights normalised times `routed_scale`), each a
            SwiGLU of width `d_ff_expert`, plus one ungated shared SwiGLU
            of width `d_ff_shared`. No token is dropped.

**A chip's share.** `held` = (first, count): the routed experts whose
weights THIS tree holds — expert parallelism cut to one chip. The router
keeps `n_routed` outputs and `top_k`; a pair routed to an expert outside
the share forms no row and adds nothing here (another chip's partial sum,
added by the exchange a deployment has and this lane has not).

**Two kinds of K/V block.** A full layer reads a row's whole context; a
window layer reads its last `window` tokens, so the blocks behind them can
go back to the pool. The served step (`laguna_step_rows_ragged`) therefore
takes TWO block pools, each `(layers of its kind, NB, bs, H_kv*D)` with a
table a row of its own (`runtime.scheduler` frees a row's window blocks as
its position passes them); layer l is layer `cfg.pool_layer[l]` of its
kind's pool.

Parameter tree: `tok_embed`, `layers` (a list: the layers differ in
shape), `ln_f`, `head`. A block is `ln1`, `attn` {wq, wk, wv, wo, wg,
q_norm, k_norm}, `ln2`, `mlp`; an expert block's `mlp` is {router {kernel,
bias: float32}, shared {gate, up, proj}, experts {gate_up (count, d, 2f),
down (count, f, d)}}, the HELD experts alone. Weights are made in
`param_dtype` directly, as `models.moonlight` makes them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_engine.models.moonlight import (
    _dense_init,
    _normal,
    _residual_gain,
    _swiglu_init,
)
from tpu_engine.models.registry import ModelSpec, causal_lm_spec, register
from tpu_engine.models.tick_tokens import lm_head, tick_tokens
from tpu_engine.models.transformer import (
    TransformerConfig,
    _mlp,
    _write_pool,
    index_in_kind,
    kv_kind_config,
)
from tpu_engine.ops import nn
from tpu_engine.ops.attention import KVCache, dot_product_attention
from tpu_engine.ops.moe import routed_experts, sigmoid_topk_route
from tpu_engine.utils.tracing import step_part

# Slots a tile of the served step: 8 slots x 9 heads a KV head are 72 query
# rows of the paged kernel's 128; a decode row in a tick that carries a
# chunk pads to one tile, so a smaller tile keeps the token list short.
_SLOTS_PER_TILE = 8


@dataclasses.dataclass(frozen=True)
class LagunaConfig(TransformerConfig):
    """The base fields this family fixes: rmsnorm, rope, swiglu,
    `n_heads` = the full layers' count, `d_ff` = the dense layers' width,
    `rope_theta` = the full layers' base. `sliding_window` stays None: the
    window is a layer's, not the model's."""
    heads_per_layer: Tuple[int, ...] = ()
    windowed: Tuple[bool, ...] = ()         # True: a sliding-window layer
    window: int = 512
    window_rope_theta: float = 10000.0
    partial_rotary: float = 0.5             # full layers: share of D rotated
    yarn_factor: float = 128.0
    yarn_original_max: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4852030263919618
    d_ff_expert: int = 1024
    d_ff_shared: int = 1024
    n_routed: int = 256
    top_k: int = 10
    routed_scale: float = 2.5
    n_dense_layers: int = 1
    held: Tuple[int, int] = (0, 256)        # (first, count) of n_routed
    param_dtype: str = "bfloat16"

    # The registry derives family and TP rule from these two.
    serving_state_family = "kv_windowed"
    tp_partition_rule = ("unshardable: the lane holds one chip's share of "
                         "the experts already and its window layers' "
                         "blocks are freed by a host-side table a shard "
                         "map does not carry")

    def __post_init__(self):
        if not (len(self.heads_per_layer) == len(self.windowed)
                == self.n_layers):
            raise ValueError("heads_per_layer and windowed need one entry "
                             "a layer")
        first, count = self.held
        if not (0 <= first and count > 0
                and first + count <= self.n_routed):
            raise ValueError(f"held={self.held} is no share of "
                             f"{self.n_routed} experts")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def n_window_layers(self) -> int:
        return sum(self.windowed)

    @property
    def n_full_layers(self) -> int:
        return self.n_layers - self.n_window_layers

    @property
    def kv_block_kinds(self) -> Tuple[TransformerConfig, TransformerConfig]:
        """(full, window): what a block pool of each kind is sized by."""
        return tuple(kv_kind_config(self, n)
                     for n in (self.n_full_layers, self.n_window_layers))

    @property
    def pool_layer(self) -> Tuple[int, ...]:
        """Layer l's index in the pool of its kind."""
        return index_in_kind(self.windowed)


# -- rope -----------------------------------------------------------------------

def _yarn_inv_freq(dim: int, base: float, factor: float, original_max: int,
                   beta_fast: float, beta_slow: float):
    """transformers' `_compute_yarn_parameters` over `dim` rotated lanes:
    interpolated (1 / factor) frequencies below the correction range,
    extrapolated (unchanged) ones above it, a linear ramp between."""
    def correction_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                        # the extrapolation's share
    return ((1.0 / (factor * pos_freqs)) * (1.0 - keep)
            + (1.0 / pos_freqs) * keep)


def _rope_of(cfg: LagunaConfig, windowed: bool):
    """(inverse frequencies of the rotated lanes' pairs, what multiplies
    cos and sin) for a layer of that kind."""
    d = cfg.d_head
    if windowed:
        inv = 1.0 / cfg.window_rope_theta ** (
            np.arange(0, d, 2, dtype=np.float64) / d)
        return inv.astype(np.float32), 1.0
    inv = _yarn_inv_freq(int(d * cfg.partial_rotary), cfg.rope_theta,
                         cfg.yarn_factor, cfg.yarn_original_max,
                         cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    return inv.astype(np.float32), cfg.yarn_attention_factor


def _rope(x, positions, inv_freq, factor: float):
    """x: (..., S, H, D) at `positions` (..., S); the first 2 * len(inv_freq)
    lanes rotate (rotate-half pairing inside them), the rest pass."""
    rot = 2 * inv_freq.shape[0]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = (jnp.cos(ang) * factor)[..., None, :]
    sin = (jnp.sin(ang) * factor)[..., None, :]
    x1 = x[..., :rot // 2].astype(jnp.float32)
    x2 = x[..., rot // 2:rot].astype(jnp.float32)
    out = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if rot < x.shape[-1]:
        out.append(x[..., rot:].astype(jnp.float32))
    return jnp.concatenate(out, -1).astype(x.dtype)


# -- parameters -----------------------------------------------------------------

def _bank(key, shape, fan_in, dtype):
    """An expert bank, one expert at a time: the generator's temporaries
    are one expert's, not the bank's."""
    return jax.lax.map(lambda k: _normal(k, shape[1:], fan_in, dtype),
                       jax.random.split(key, shape[0]))


def _block_init(key, cfg: LagunaConfig, layer: int):
    dtype = jnp.dtype(cfg.param_dtype)
    d, dh = cfg.d_model, cfg.d_head
    h, h_kv = cfg.heads_per_layer[layer], cfg.kv_heads
    out_gain = _residual_gain(cfg)
    (kq, kk, kv, ko, kg, kf, kr, kbias, ks, kgu,
     kdn) = jax.random.split(key, 11)
    block = {
        "ln1": nn.rmsnorm_init(d),
        "attn": {
            "wq": _dense_init(kq, d, h * dh, dtype),
            "wk": _dense_init(kk, d, h_kv * dh, dtype),
            "wv": _dense_init(kv, d, h_kv * dh, dtype),
            "wo": _dense_init(ko, h * dh, d, dtype, out_gain),
            "wg": _dense_init(kg, d, h, dtype),
            "q_norm": nn.rmsnorm_init(dh),
            "k_norm": nn.rmsnorm_init(dh),
        },
        "ln2": nn.rmsnorm_init(d),
    }
    if layer < cfg.n_dense_layers:
        block["mlp"] = _swiglu_init(kf, d, cfg.d_ff, dtype, out_gain)
        return block
    e, f, count = cfg.n_routed, cfg.d_ff_expert, cfg.held[1]
    block["mlp"] = {
        # As models.moonlight draws them: unit-variance logits, a
        # selection bias of about a tenth of the scores' spread.
        "router": {"kernel": _normal(kr, (d, e), d, jnp.float32),
                   "bias": 0.02 * jax.random.normal(kbias, (e,),
                                                    jnp.float32)},
        "shared": _swiglu_init(ks, d, cfg.d_ff_shared, dtype, out_gain),
        "experts": {"gate_up": _bank(kgu, (count, d, 2 * f), d, dtype),
                    "down": _bank(kdn, (count, f, d), f / out_gain ** 2,
                                  dtype)},
    }
    return block


def laguna_init(key, cfg: LagunaConfig):
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

def _attn_inputs(ap, x, positions, layer: int, cfg: LagunaConfig, dtype):
    """x: (B, S, d) normalised. Returns q (B, S, H_l, D), k and v
    (B, S, H_kv, D) — q and k normalised a head and rotated as the
    layer's kind says, as the cache holds k — and the heads' gates
    (B, S, H_l) float32."""
    b, s, _ = x.shape
    dh = cfg.d_head

    def heads(name):
        return nn.dense(ap[name], x, dtype=dtype).reshape(b, s, -1, dh)

    rope = _rope_of(cfg, cfg.windowed[layer])
    q = nn.rmsnorm(ap["q_norm"], heads("wq"), eps=cfg.ln_eps).astype(dtype)
    k = nn.rmsnorm(ap["k_norm"], heads("wk"), eps=cfg.ln_eps).astype(dtype)
    gate = jax.nn.softplus(nn.dense(ap["wg"], x, dtype=dtype)
                           .astype(jnp.float32))
    return (_rope(q, positions, *rope), _rope(k, positions, *rope),
            heads("wv").astype(dtype), gate)


def _attn_output(ap, o, gate, dtype):
    """o: (B, S, H_l, D) the heads' outputs, each times its gate, to Wo."""
    b, s = o.shape[:2]
    o = (o.astype(jnp.float32) * gate[..., None]).astype(dtype)
    return nn.dense(ap["wo"], o.reshape(b, s, -1), dtype=dtype)


def _moe_ffn(mp, x, valid, cfg: LagunaConfig, dtype, held, max_tokens):
    """x: (B, S, d) normalised; valid: (B, S); `mp["experts"]` holds the
    `held` experts alone. Returns (y, rows (n_routed,): the rows each
    HELD expert took, zero elsewhere)."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    experts, weights = sigmoid_topk_route(flat, mp["router"], cfg.top_k,
                                          cfg.routed_scale)
    # The bank's group 0 is expert `held[0]`.
    y, rows = routed_experts(
        flat, valid.reshape(-1), experts, weights, mp["experts"],
        first_group=-held[0], n_experts=cfg.n_routed, held=held,
        max_tokens=max_tokens, dtype=dtype)
    with step_part("moe/shared"):
        return y.reshape(b, s, d) + _mlp(mp["shared"], x, dtype, cfg), rows


def _run_layers(params, h, carry, cfg: LagunaConfig, attend, valid, dtype,
                held, max_tokens):
    """`attend(layer, ap, x, carry) -> (heads' outputs, gate, carry)` over
    the layers in order (a Python loop: the layers differ in shape).
    Returns (h, carry, rows (L_moe, n_routed))."""
    rows = []
    for layer, bp in enumerate(params["layers"]):
        with step_part("attn/qkv"):
            x = nn.rmsnorm(bp["ln1"], h, eps=cfg.ln_eps)
        o, gate, carry = attend(layer, bp["attn"], x, carry)
        with step_part("attn/out"):
            h = (h + _attn_output(bp["attn"], o, gate, dtype)).astype(dtype)
        if layer < cfg.n_dense_layers:
            with step_part("mlp"):
                x = nn.rmsnorm(bp["ln2"], h, eps=cfg.ln_eps)
                h = (h + _mlp(bp["mlp"], x, dtype, cfg)).astype(dtype)
        else:
            with step_part("moe/route"):
                x = nn.rmsnorm(bp["ln2"], h, eps=cfg.ln_eps)
            y, taken = _moe_ffn(bp["mlp"], x, valid, cfg, dtype, held,
                                max_tokens)
            rows.append(taken)
            with step_part("moe/shared"):
                h = (h + y).astype(dtype)
    rows = (jnp.stack(rows) if rows
            else jnp.zeros((0, cfg.n_routed), jnp.int32))
    return h, carry, rows


# -- the one-shot forward --------------------------------------------------------

def laguna_apply(params, tokens, cfg: LagunaConfig, *, dtype=jnp.bfloat16):
    """Full-sequence causal forward over the held experts. tokens: (B, S)
    int32 -> logits (B, S, vocab) float32."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = nn.embedding(params["tok_embed"], tokens).astype(dtype)
    at = jnp.arange(s)
    causal = at[None, :] <= at[:, None]
    inside = causal & (at[None, :] > at[:, None] - cfg.window)

    def attend(layer, ap, x, carry):
        q, k, v, gate = _attn_inputs(ap, x, positions, layer, cfg, dtype)
        mask = inside if cfg.windowed[layer] else causal
        o = dot_product_attention(
            q, k, v, mask=jnp.broadcast_to(mask.astype(jnp.int32),
                                           (b, s, s)))
        return o, gate, carry

    h, _, _ = _run_layers(params, h, (), cfg, attend,
                          jnp.ones((b, s), bool), dtype, cfg.held, None)
    return lm_head(params, h, cfg.ln_eps, dtype)


# -- the served step: the mixed tick over the two pools ---------------------------

def laguna_step_rows_ragged(params, tokens, caches, tables, pos0, qlen,
                            cfg: LagunaConfig, *, dtype=jnp.bfloat16,
                            attn_fn=None, sample_slot=None, held=None,
                            max_tokens: Optional[int] = None):
    """This family's step of the mixed tick, over the tick's token list
    (`models.tick_tokens`) in tiles of `_SLOTS_PER_TILE` slots.

    caches: (full, window), a K/V pair each, (layers of the kind, NB, bs,
    H_kv*D), updated in place (donate both); tables: (full, window), each
    (B, nb) by logical column // bs. A window layer's table may hold the
    null block wherever the row's first new token no longer sees (the
    blocks it gave back): the read is `ops.paged_attention`'s ragged read
    with `window`, a TILE of the list a row of the call, so a tile walks
    only the columns its own slots see (~520 at this window of 512; a
    model whose window is thousands of columns reads its window layers by
    class too: `models.smallthinker`). A FULL layer walks a row's whole
    context, so its tiles follow the row's run (`PagedKV.attend`,
    `ops.paged_attention.ragged_read_by_class`): a row with one new token
    is a row of a width-1 call, the 8 KV heads packed, and a longer run is
    cut in tall tiles of up to 64 slots (8 list tiles; at G = 6, 384 query
    rows a KV head, three tiles of the call's grid), each a row of a second
    call, so a 256-token chunk walks its context 12 times and not once a
    list tile, 32 times.

    ``held`` = (first, count): the experts `params` holds (default
    `cfg.held`). Returns (logits, caches, rows (L_moe, n_routed) int32:
    the rows each held expert took)."""
    from tpu_engine.ops import paged_attention as pa

    if attn_fn is None:
        attn_fn = pa.default_ragged_attention()
    held = held or cfg.held
    w = tokens.shape[1]
    per_tile = min(w, _SLOTS_PER_TILE)
    bs = caches[0].k.shape[2]
    tt = tick_tokens(pos0, qlen, w, max_tokens, per_tile=per_tile)
    # The full layers' read takes the list flat, by the class of a row's
    # run; a window layer's a TILE of the list as a row of its call, with
    # its own first column, new tokens and table row.
    full = tt.paged_kv(tables[0], bs, cfg.n_heads // cfg.kv_heads)
    blk, off = tt.blocks(tables[1], bs)
    with step_part("plan"):
        tile_pos0 = pos0[tt.plan.row] + tt.plan.tile * per_tile
        tile_qlen = tt.valid.sum(-1).astype(jnp.int32)
        tile_table = tables[1][tt.plan.row]
    h = tt.embed(params, tokens, dtype)

    def attend(layer, ap, x, pools):
        kind = int(cfg.windowed[layer])
        at = cfg.pool_layer[layer]
        with step_part("attn/qkv"):
            q, k, v, gate = _attn_inputs(ap, x, tt.logical, layer, cfg,
                                         dtype)
        if kind:
            with step_part("attn/write"):
                pool = _write_pool(pools[kind], at, blk, off, k, v)
            with step_part("attn/read"):
                o = attn_fn(q, *pool, at, tile_table, tile_pos0, tile_qlen,
                            window=cfg.window)
        else:
            o, pool = full.attend(attn_fn, q, k, v, pools[kind], at)
        return o, gate, pools[:kind] + (pool,) + pools[kind + 1:]

    h, pools, rows = _run_layers(
        params, h, tuple(tuple(c) for c in caches), cfg, attend, tt.valid,
        dtype, held, max_tokens)
    return (lm_head(params, tt.head_rows(h, sample_slot), cfg.ln_eps, dtype),
            tuple(KVCache(*p) for p in pools), rows)


# -- registry ----------------------------------------------------------------------

def _lm_spec(name: str, cfg: LagunaConfig, seq_len: int) -> ModelSpec:
    return causal_lm_spec(name, cfg, seq_len, laguna_init, laguna_apply,
                          ragged_step=laguna_step_rows_ragged, held=cfg.held)


def _cfg(**kw) -> LagunaConfig:
    pattern = tuple(kw["layer_types"])
    heads = tuple(kw["heads_per_layer"])
    windowed = tuple(t == "sliding_attention" for t in pattern)
    return LagunaConfig(
        vocab=kw["vocab"], n_layers=len(pattern), d_model=kw["d_model"],
        n_heads=next(h for h, w in zip(heads, windowed) if not w),
        d_ff=kw["d_ff_dense"], max_seq=kw["max_seq"], causal=True,
        norm="rmsnorm", pos="rope", mlp_act="swiglu",
        n_kv_heads=kw["n_kv_heads"], head_dim=kw["head_dim"],
        rope_theta=kw["rope_theta"], ln_eps=kw["ln_eps"],
        heads_per_layer=heads, windowed=windowed, window=kw["window"],
        window_rope_theta=kw["window_rope_theta"],
        partial_rotary=kw["partial_rotary"], yarn_factor=kw["yarn_factor"],
        yarn_original_max=kw["yarn_original_max"],
        yarn_beta_fast=kw["yarn_beta_fast"],
        yarn_beta_slow=kw["yarn_beta_slow"],
        yarn_attention_factor=kw["yarn_attention_factor"],
        d_ff_expert=kw["d_ff_expert"], d_ff_shared=kw["d_ff_shared"],
        n_routed=kw["n_experts"], top_k=kw["top_k"],
        routed_scale=kw["routed_scale"],
        n_dense_layers=kw["n_dense_layers"],
        held=(kw["held_first"], kw["held_count"] or kw["n_experts"]),
        param_dtype=kw["param_dtype"])


_PERIOD = ("full_attention",) + ("sliding_attention",) * 3


@register("laguna")
def make_laguna(seq_len: int = 128, vocab: int = 100352,
                layer_types: Tuple[str, ...] = _PERIOD * 12,
                heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72) * 12,
                d_model: int = 3072, n_kv_heads: int = 8,
                head_dim: int = 128, d_ff_dense: int = 12288,
                d_ff_expert: int = 1024, d_ff_shared: int = 1024,
                n_experts: int = 256, top_k: int = 10,
                routed_scale: float = 2.5, n_dense_layers: int = 1,
                held_first: int = 0, held_count: int = 0,
                window: int = 512, window_rope_theta: float = 10000.0,
                rope_theta: float = 500000.0, partial_rotary: float = 0.5,
                yarn_factor: float = 128.0, yarn_original_max: int = 8192,
                yarn_beta_fast: float = 32.0, yarn_beta_slow: float = 1.0,
                yarn_attention_factor: float = 1.4852030263919618,
                max_seq: int = 16384, ln_eps: float = 1e-6,
                param_dtype: str = "bfloat16") -> ModelSpec:
    """Laguna-S-2.1's published geometry; every width a keyword.
    `held_count` 0 holds every expert."""
    return _lm_spec("laguna", _cfg(**{k: v for k, v in locals().items()
                                      if k != "seq_len"}), seq_len)


@register("laguna-small-test")
def make_laguna_small(seq_len: int = 16, vocab: int = 256,
                      layer_types: Tuple[str, ...] = (
                          _PERIOD + ("full_attention",)),
                      heads_per_layer: Tuple[int, ...] = (12, 18, 18, 18,
                                                          12),
                      d_model: int = 64, n_kv_heads: int = 2,
                      head_dim: int = 16, d_ff_dense: int = 128,
                      d_ff_expert: int = 32, d_ff_shared: int = 32,
                      n_experts: int = 16, top_k: int = 4,
                      routed_scale: float = 2.5, n_dense_layers: int = 1,
                      held_first: int = 0, held_count: int = 8,
                      window: int = 8, window_rope_theta: float = 10000.0,
                      rope_theta: float = 500000.0,
                      partial_rotary: float = 0.5,
                      yarn_factor: float = 128.0,
                      yarn_original_max: int = 8192,
                      yarn_beta_fast: float = 32.0,
                      yarn_beta_slow: float = 1.0,
                      yarn_attention_factor: float = 1.4852030263919618,
                      max_seq: int = 128, ln_eps: float = 1e-6,
                      param_dtype: str = "float32") -> ModelSpec:
    """Tiny config for tests: a dense full layer, three window layers and
    a full one (G = 6 and 9 over 2 KV heads), window 8, 8 of 16 experts
    held, float32."""
    return _lm_spec("laguna-small-test",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)
