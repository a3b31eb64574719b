"""Kimi-Linear family: three layers in four keep a fixed-size recurrent
state a row whose decay is a key CHANNEL's (Kimi Delta Attention, KDA:
`ops.gated_delta` with a gate of (T, H, d_k)), the fourth keeps one latent
vector a token (MLA, nothing rotated), and every layer but the first
routes sigmoid-scored experts of which a chip may hold a share.

Source of the default geometry: Kimi-Linear-48B-A3B-Instruct
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct,
`model_type: kimi_linear`; the family's paper, arXiv:2510.26692). Stream h
(T x d), layer l (1-based in the source's `kda_layers` /
`full_attn_layers`, 0-based here):

- block     ``h += Mixer(RMS(h))``, ``h += FFN(RMS(h))``; final RMSNorm; a
            separate LM head.
- KDA       ``q~, k~, v~ = x Wq, x Wk, x Wv`` (`lin_heads` heads of
            `lin_key_dim` / `lin_value_dim` lanes), each through its own
            causal depthwise conv of width `conv_width` (one depthwise
            conv over the three side by side), then SiLU; q and k
            L2-normalised a head, q times 1/sqrt(d_k); the decay a key
            channel's, ``g = -exp(A_log[head]) softplus(x Wf_down Wf_up +
            dt_bias)`` (H x d_k), ``a = exp(g)``; ``b = sigmoid(x Wb)`` a
            head; the state S (d_v x d_k a head, float32, zero at position
            0) follows ``S = S Diag(a) + b (v - S Diag(a) k) k^T``,
            ``o = S q``; ``y = (RMS_head(o) * sigmoid(x Wg_down Wg_up)) Wo``.
- MLA       `models.moonlight`'s, `q_lora_rank` null, with NO rotation
            (`mla_use_nope`): the `qk_rope` lanes of a query head and the
            one shared key of as many lanes stay, as plain key lanes.
- FFN       the first `n_dense_layers` layers: SwiGLU of width `d_ff`; the
            others: `ops.moe.sigmoid_topk_route` over `n_routed` experts
            (top `top_k`, weights normalised times `routed_scale`), each a
            SwiGLU of width `d_ff_expert`, plus one shared SwiGLU of width
            `d_ff_shared`. No token is dropped.

**A chip's share** (`models.laguna`): `held` = (first, count), the routed
experts whose weights THIS tree holds; a pair routed outside the share
forms no row and adds nothing here.

**Two kinds of state in one row, under experts.** An MLA layer's latent
and shared key lanes go to the block pool, which holds the MLA layers
alone and is a LATENT pool (`cfg.kv_lanes` = (128, `kv_lora_rank`)); a KDA
layer's state and conv tail live in one row of a state pool
(`cfg.state_row_shapes`), `cfg.pool_layer[l]` the layer of its kind's
pool: the `kv_and_state` family of `models.olmo_hybrid` over another kind
of block. The served step (`kimi_linear_step_rows_ragged`) takes both,
donated, over the tick's TOKENS in `models.moonlight`'s tiles; a KDA layer
is `models.olmo_hybrid._linear_rows` with this family's projections, whose
gate's rank picks the kernels `kda_step` and `kda_chunk`.

Parameter tree: `tok_embed`, `layers` (a list: the layers are of three
shapes), `ln_f`, `head`. A block is `ln1`, `ln2`, `mlp` (a SwiGLU, or
{router, shared, experts} with the HELD experts alone) and `attn` {wq,
wkv_a, kv_norm, wkv_b, wo} or `lin` {wq, wk, wv, wo, wf_down, wf_up,
wg_down, wg_up, wb, conv (width, lanes), A_log (H,), dt_bias (H * d_k,),
o_norm}. Weights are made in `param_dtype` directly, as `models.moonlight`
makes them and by its rule (what writes into the residual stream 1/sqrt(2
L) smaller). `A_log` is the log of a number drawn evenly from (0.02, 0.25)
a head and `dt_bias` evenly from (-1, 0.5) a channel: with ``x Wf_down
Wf_up`` of unit spread, softplus lies in (0.1, 2.5) but for the normal's
tail and the decay a in (0.55, 1), spread over that range by head, channel
and token.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_engine.models.laguna import _bank, _moe_ffn
from tpu_engine.models.moonlight import (
    _absorb,
    _attn_expanded,
    _attn_inputs,
    _dense_init,
    _normal,
    _residual_gain,
    _swiglu_init,
    _unabsorb,
)
from tpu_engine.models.olmo_hybrid import _conv_heads, _linear_rows, _pad_run
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
from tpu_engine.ops.attention import KVCache
from tpu_engine.ops.gated_delta import gdn_chunk, gdn_chunk_row, gdn_step_rows
from tpu_engine.ops.latent_attention import PE_LANES, pad_rope_lanes
from tpu_engine.utils.tracing import step_part


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(TransformerConfig):
    """The base fields this family fixes: rmsnorm, no positions, swiglu,
    `n_heads` = the MLA layers' heads, `d_ff` = the dense layers' width."""
    linear: Tuple[bool, ...] = ()           # True: a KDA layer
    lin_heads: int = 32
    lin_key_dim: int = 128
    lin_value_dim: int = 128
    conv_width: int = 4
    gate_rank: int = 128                    # Wf and Wg pass through it
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128
    kv_lora_rank: int = 512
    kv_norm_eps: float = 1e-6
    d_ff_expert: int = 1024
    d_ff_shared: int = 1024
    n_routed: int = 256
    top_k: int = 8
    routed_scale: float = 2.446
    n_dense_layers: int = 1
    held: Tuple[int, int] = (0, 256)        # (first, count) of n_routed
    param_dtype: str = "bfloat16"

    # The registry derives family and TP rule from these two; the
    # scheduler names a tick's recurrent work by the third (the kernels'
    # names in a trace).
    serving_state_family = "kv_and_state"
    tp_partition_rule = ("unshardable: a row's recurrent state and conv "
                         "tail are one state row a layer, the latent pool "
                         "has no head axis to split, and the lane holds "
                         "one chip's share of the experts already")
    recurrence = "kda"

    def __post_init__(self):
        if len(self.linear) != self.n_layers:
            raise ValueError("linear needs one entry a layer")
        if not self.n_full_layers or not self.n_linear_layers:
            raise ValueError("this model has layers of both kinds")
        first, count = self.held
        if not (0 <= first and count > 0
                and first + count <= self.n_routed):
            raise ValueError(f"held={self.held} is no share of "
                             f"{self.n_routed} experts")

    @property
    def d_head(self) -> int:
        """A query/key head as the MLA scores see it."""
        return self.qk_nope + self.qk_rope

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_nope + self.qk_rope)

    @property
    def kv_lanes(self) -> Tuple[int, int]:
        """The latent pool: (shared key lanes, latent lanes) a token and
        MLA layer (`ops.latent_attention` states the layout)."""
        return (PE_LANES, self.kv_lora_rank)

    @property
    def n_linear_layers(self) -> int:
        return sum(self.linear)

    @property
    def n_full_layers(self) -> int:
        return self.n_layers - self.n_linear_layers

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def conv_lanes(self) -> int:
        return self.lin_heads * (2 * self.lin_key_dim + self.lin_value_dim)

    @property
    def kv_block_kinds(self) -> Tuple[TransformerConfig]:
        """What the block pool is sized by: the MLA layers alone, a latent
        and its shared key lanes a token."""
        return (kv_kind_config(self, self.n_full_layers),)

    @property
    def pool_layer(self) -> Tuple[int, ...]:
        """Layer l's index in the pool of its kind."""
        return index_in_kind(self.linear)

    @property
    def state_row_shapes(self) -> Tuple[Tuple[int, ...], ...]:
        """A row's state, a KDA layer: S and the conv tail (float32). The
        tail's (width - 1) x lanes are kept as 8 sublanes of whole lane
        tiles: 3 rows tile to 4, and XLA then re-lays the WHOLE array out
        around a decode tick's gather of 128 rows (layers on the
        sublanes), a copy in and a copy out every tick."""
        tail = (self.conv_width - 1) * self.conv_lanes
        return ((self.lin_heads, self.lin_value_dim, self.lin_key_dim),
                (8, tail // 8) if tail % 8 == 0 else (tail,))


# -- parameters -----------------------------------------------------------------

def _block_init(key, cfg: KimiLinearConfig, layer: int):
    dtype = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    out_gain = _residual_gain(cfg)
    k_mix, k_ffn = jax.random.split(key)
    block = {"ln1": nn.rmsnorm_init(d), "ln2": nn.rmsnorm_init(d)}
    if cfg.linear[layer]:
        h, dk, dv, r = (cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim,
                        cfg.gate_rank)
        (kq, kk, kv, ko, kfd, kfu, kgd, kgu, kb, kc, kl,
         kt) = jax.random.split(k_mix, 12)
        block["lin"] = {
            "wq": _dense_init(kq, d, h * dk, dtype),
            "wk": _dense_init(kk, d, h * dk, dtype),
            "wv": _dense_init(kv, d, h * dv, dtype),
            "wo": _dense_init(ko, h * dv, d, dtype, out_gain),
            "wf_down": _dense_init(kfd, d, r, dtype),
            "wf_up": _dense_init(kfu, r, h * dk, dtype),
            "wg_down": _dense_init(kgd, d, r, dtype),
            "wg_up": _dense_init(kgu, r, h * dv, dtype),
            "wb": _dense_init(kb, d, h, dtype),
            "conv": _normal(kc, (cfg.conv_width, cfg.conv_lanes),
                            cfg.conv_width, jnp.float32),
            "A_log": jnp.log(jax.random.uniform(kl, (h,), jnp.float32,
                                                0.02, 0.25)),
            "dt_bias": jax.random.uniform(kt, (h * dk,), jnp.float32,
                                          -1.0, 0.5),
            "o_norm": nn.rmsnorm_init(dv),
        }
    else:
        h = cfg.n_heads
        kq, ka, kb, ko = jax.random.split(k_mix, 4)
        block["attn"] = {
            "wq": _dense_init(kq, d, h * cfg.d_head, dtype),
            "wkv_a": _dense_init(ka, d, cfg.kv_lora_rank + cfg.qk_rope,
                                 dtype),
            "kv_norm": nn.rmsnorm_init(cfg.kv_lora_rank),
            "wkv_b": _dense_init(kb, cfg.kv_lora_rank,
                                 h * (cfg.qk_nope + cfg.v_head), dtype),
            "wo": _dense_init(ko, h * cfg.v_head, d, dtype, out_gain),
        }
    if layer < cfg.n_dense_layers:
        block["mlp"] = _swiglu_init(k_ffn, d, cfg.d_ff, dtype, out_gain)
        return block
    kr, kbias, ks, kgu, kdn = jax.random.split(k_ffn, 5)
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


def kimi_linear_init(key, cfg: KimiLinearConfig):
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

def _kda_inputs(lp, x, cfg: KimiLinearConfig, dtype):
    """`models.olmo_hybrid._lin_inputs` for this family. x: (..., d)
    normalised. Returns (mixed (..., conv lanes): q~, k~, v~ side by side
    BEFORE the conv; the output gate's input (..., H*d_v); g (..., H, d_k)
    a key channel's; beta (..., H))."""
    mixed = jnp.concatenate([nn.dense(lp[w], x, dtype=dtype)
                             for w in ("wq", "wk", "wv")], axis=-1)

    def low_rank(name):
        return nn.dense(lp[name + "_up"],
                        nn.dense(lp[name + "_down"], x, dtype=dtype),
                        dtype=dtype)

    h, dk = cfg.lin_heads, cfg.lin_key_dim
    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(
        low_rank("wf") + lp["dt_bias"]).reshape(x.shape[:-1] + (h, dk))
    beta = jax.nn.sigmoid(nn.dense(lp["wb"], x, dtype=dtype))
    return mixed, low_rank("wg"), g, beta


def _kda_output(lp, o, z, cfg: KimiLinearConfig, dtype):
    """o: (..., H, d_v) the heads' reads; z: (..., H*d_v) the gate's
    input. Normalised a head, gated by a sigmoid, to Wo."""
    y = nn.rmsnorm(lp["o_norm"], o, eps=cfg.ln_eps)
    y = y.reshape(z.shape) * jax.nn.sigmoid(z)
    return nn.dense(lp["wo"], y, dtype=dtype)


def _run_layers(params, h, carry, cfg: KimiLinearConfig, mixer, valid, dtype,
                held, max_tokens):
    """`mixer(layer, block, x, carry) -> (the mixer's output, carry)` over
    the layers in order (a Python loop: the layers differ in shape); h, x:
    (B, S, d). Returns (h, carry, rows (L_moe, n_routed))."""
    rows = []
    for layer, bp in enumerate(params["layers"]):
        enter, leave = (("mixer/in", "mixer/out") if cfg.linear[layer]
                        else ("attn/qkv", "attn/out"))
        with step_part(enter):
            x = nn.rmsnorm(bp["ln1"], h, eps=cfg.ln_eps)
        y, carry = mixer(layer, bp, x, carry)
        with step_part(leave):
            h = (h + y).astype(dtype)
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

def kimi_linear_apply(params, tokens, cfg: KimiLinearConfig, *,
                      dtype=jnp.bfloat16):
    """Full-sequence causal forward from an empty state over the held
    experts, the MLA layers expanded as published. tokens: (B, S) int32 ->
    logits (B, S, vocab) float32."""
    b, s = tokens.shape
    h = nn.embedding(params["tok_embed"], tokens).astype(dtype)
    pad = _pad_run(s) - s

    def whole_row(lp, mixed, g, beta):
        ext = jnp.pad(mixed, ((cfg.conv_width - 1, pad), (0, 0)))
        q, k, v = _conv_heads(lp, ext, cfg)
        o, _ = gdn_chunk(q, k, v, jnp.pad(g, ((0, pad), (0, 0), (0, 0))),
                         jnp.pad(beta, ((0, pad), (0, 0))),
                         jnp.zeros(cfg.state_row_shapes[0], jnp.float32))
        return o[:s]

    def mixer(layer, bp, x, carry):
        if not cfg.linear[layer]:
            return _attn_expanded(bp["attn"], x, None, cfg, dtype), carry
        lp = bp["lin"]
        mixed, z, g, beta = _kda_inputs(lp, x, cfg, dtype)
        o = jax.vmap(lambda m, g, bt: whole_row(lp, m, g, bt))(
            mixed, g, beta)
        return _kda_output(lp, o, z, cfg, dtype), carry

    h, _, _ = _run_layers(params, h, (), cfg, mixer, jnp.ones((b, s), bool),
                          dtype, cfg.held, None)
    return lm_head(params, h, cfg.ln_eps, dtype)


# -- the served step: the mixed tick over the latent pool and the state pool ------

def kimi_linear_step_rows_ragged(params, tokens, caches, tables, pos0, qlen,
                                 cfg: KimiLinearConfig, *,
                                 dtype=jnp.bfloat16, attn_fn=None,
                                 step_fn=gdn_step_rows,
                                 chunk_fn=gdn_chunk_row, sample_slot=None,
                                 held=None,
                                 max_tokens: Optional[int] = None):
    """This family's step of the mixed tick, over the tick's token list
    (`models.tick_tokens`) in tiles of
    `ops.latent_attention.slots_per_tile` slots (32 heads: 4 slots at a
    chunk's width, 1 at a decode tick's). A row's tiles lie side by side
    in the list, so flattened it is the token list a KDA layer takes
    (`models.olmo_hybrid._linear_rows`), a row's new tokens from
    `plan.start[b]` tiles on.

    caches: (the latent pool's pair, k (MLA layers, NB, bs, PE_LANES) the
    shared key lanes and v (MLA layers, NB, bs, C) the latents; the state
    pool's arrays, `_linear_rows`), both updated in place (donate them);
    tables: (the rows' block table (B, nb); the rows' state row (B,), the
    null row 0 for a free slot). An MLA layer scatters every token's
    (c, k_pe) into its row's blocks BEFORE the absorbed read. `step_fn`,
    `chunk_fn`: `ops.gated_delta`'s or stand-ins of their signatures.

    ``held`` = (first, count): the experts `params` holds (default
    `cfg.held`). Returns (logits, caches, rows (L_moe, n_routed) int32:
    the rows each held expert took)."""
    from tpu_engine.ops import latent_attention as la

    if attn_fn is None:
        attn_fn = la.default_latent_attention()
    held = held or cfg.held
    (pool, state), (table, rows) = caches, tables
    w = tokens.shape[1]
    per_tile = la.slots_per_tile(cfg.n_heads, w)
    tt = tick_tokens(pos0, qlen, w, max_tokens, per_tile=per_tile)
    blk, off = tt.blocks(table, pool.k.shape[2])
    with step_part("plan"):
        lengths = pos0 + qlen
    h = tt.embed(params, tokens, dtype)

    def mixer(layer, bp, x, carry):
        pool, state = carry
        at = cfg.pool_layer[layer]
        if cfg.linear[layer]:
            y, state = _linear_rows(
                bp["lin"], x.reshape(tt.n * per_tile, -1), state, at,
                tt.plan.start * per_tile, rows, pos0, qlen, w, cfg, dtype,
                step_fn, chunk_fn, inputs=_kda_inputs, output=_kda_output)
            return y.reshape(x.shape), (pool, state)
        ap = bp["attn"]
        with step_part("attn/qkv"):
            q_nope, q_pe, c, k_pe = _attn_inputs(ap, x, None, cfg, dtype)
        with step_part("attn/write"):
            pool = _write_pool(pool, at, blk, off,
                               pad_rope_lanes(k_pe)[:, :, None, :],
                               c[:, :, None, :])
        with step_part("attn/qkv"):
            q_lat = _absorb(ap, q_nope, cfg, dtype)
        with step_part("attn/read"):
            o_lat = attn_fn(q_lat, q_pe, *pool, at, table, tt.plan, pos0,
                            lengths, scale=cfg.attn_scale)
        with step_part("attn/out"):
            return _unabsorb(ap, o_lat, cfg, dtype), (pool, state)

    h, (pool, state), taken = _run_layers(
        params, h, (tuple(pool), tuple(state)), cfg, mixer, tt.valid, dtype,
        held, max_tokens)
    return (lm_head(params, tt.head_rows(h, sample_slot), cfg.ln_eps, dtype),
            (KVCache(*pool), state), taken)


# -- registry ----------------------------------------------------------------------

def _lm_spec(name: str, cfg: KimiLinearConfig, seq_len: int) -> ModelSpec:
    return causal_lm_spec(name, cfg, seq_len, kimi_linear_init,
                          kimi_linear_apply,
                          ragged_step=kimi_linear_step_rows_ragged,
                          held=cfg.held)


def _cfg(**kw) -> KimiLinearConfig:
    n = kw["n_layers"]
    kda, full = set(kw["kda_layers"]), set(kw["full_attn_layers"])
    # The source counts layers from 1 and lists all of the model's; a cut
    # keeps its first `n_layers`.
    if any((l in kda) == (l in full) for l in range(1, n + 1)):
        raise ValueError("every layer is one of kda_layers and "
                         "full_attn_layers")
    return KimiLinearConfig(
        vocab=kw["vocab"], n_layers=n, d_model=kw["d_model"],
        n_heads=kw["n_heads"], n_kv_heads=1, d_ff=kw["d_ff_dense"],
        max_seq=kw["max_seq"], causal=True, norm="rmsnorm", pos="none",
        mlp_act="swiglu", ln_eps=kw["ln_eps"],
        linear=tuple(l in kda for l in range(1, n + 1)),
        lin_heads=kw["lin_heads"], lin_key_dim=kw["lin_head_dim"],
        lin_value_dim=kw["lin_head_dim"], conv_width=kw["conv_width"],
        gate_rank=kw["gate_rank"], qk_nope=kw["qk_nope"],
        qk_rope=kw["qk_rope"], v_head=kw["v_head"],
        kv_lora_rank=kw["kv_lora_rank"], kv_norm_eps=kw["kv_norm_eps"],
        d_ff_expert=kw["d_ff_expert"],
        d_ff_shared=kw["n_shared"] * kw["d_ff_expert"],
        n_routed=kw["n_experts"], top_k=kw["top_k"],
        routed_scale=kw["routed_scale"],
        n_dense_layers=kw["n_dense_layers"],
        held=(kw["held_first"], kw["held_count"] or kw["n_experts"]),
        param_dtype=kw["param_dtype"])


@register("kimi_linear")
def make_kimi_linear(seq_len: int = 128, vocab: int = 163840,
                     n_layers: int = 27,
                     kda_layers: Tuple[int, ...] = (
                         1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                         19, 21, 22, 23, 25, 26),
                     full_attn_layers: Tuple[int, ...] = (
                         4, 8, 12, 16, 20, 24, 27),
                     d_model: int = 2304, n_heads: int = 32,
                     lin_heads: int = 32, lin_head_dim: int = 128,
                     conv_width: int = 4, gate_rank: int = 128,
                     qk_nope: int = 128, qk_rope: int = 64,
                     v_head: int = 128, kv_lora_rank: int = 512,
                     d_ff_dense: int = 9216, d_ff_expert: int = 1024,
                     n_experts: int = 256, top_k: int = 8,
                     n_shared: int = 1, routed_scale: float = 2.446,
                     n_dense_layers: int = 1, held_first: int = 0,
                     held_count: int = 0, max_seq: int = 16384,
                     ln_eps: float = 1e-5, kv_norm_eps: float = 1e-6,
                     param_dtype: str = "bfloat16") -> ModelSpec:
    """Kimi-Linear-48B-A3B's published geometry; every width a keyword.
    `held_count` 0 holds every expert."""
    return _lm_spec("kimi_linear", _cfg(**{k: v for k, v in locals().items()
                                           if k != "seq_len"}), seq_len)


@register("kimi_linear_small")
def make_kimi_linear_small(seq_len: int = 16, vocab: int = 256,
                           n_layers: int = 5,
                           kda_layers: Tuple[int, ...] = (1, 2, 3, 5),
                           full_attn_layers: Tuple[int, ...] = (4,),
                           d_model: int = 48, n_heads: int = 4,
                           lin_heads: int = 4, lin_head_dim: int = 8,
                           conv_width: int = 4, gate_rank: int = 8,
                           qk_nope: int = 16, qk_rope: int = 8,
                           v_head: int = 16, kv_lora_rank: int = 32,
                           d_ff_dense: int = 96, d_ff_expert: int = 32,
                           n_experts: int = 16, top_k: int = 4,
                           n_shared: int = 1, routed_scale: float = 2.446,
                           n_dense_layers: int = 1, held_first: int = 0,
                           held_count: int = 8, max_seq: int = 128,
                           ln_eps: float = 1e-5, kv_norm_eps: float = 1e-6,
                           param_dtype: str = "float32") -> ModelSpec:
    """Tiny config for tests: the cell's five layers (KDA dense; KDA, KDA,
    MLA, KDA with experts), 4 heads, keys and values of 8 lanes, conv 4, 8
    of 16 experts held, float32."""
    return _lm_spec("kimi_linear_small",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)
