"""Granite-4.0-H family: EVERY layer is a mixer AND an expert block. The
mixer is, by a published list, a Mamba-2 state-space recurrence whose B and
C are ONE group that all the heads read, or causal grouped-query attention
with nothing rotated; the expert block is soft-max-routed SwiGLU experts
beside one shared SwiGLU. Four published scalars sit on the stream.

Source of the default geometry: Granite-4.0-H-Small
(https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json,
`model_type: granitemoehybrid`). Stream h (T x d), eps `ln_eps`, no bias
but the conv's; layer l of kind `layer_types[l]`:

    h0 = `embedding_multiplier` * E[ids]
    u  = RMS_l(h);   h += `residual_multiplier` * mixer_kind(u)
    v  = RMS'_l(h);  h += `residual_multiplier` * (experts(v) + shared(v))
    mamba      [z | x B C | dt] = u W_in (d_ssm | d_ssm + 2 g N | H lanes);
               x, B, C pass ONE causal depthwise conv of width `conv_width`
               with a bias, then SiLU; x: H heads of P lanes, B and C: g
               groups of N lanes (g = 1 as published: every head reads the
               same 128 lanes); dt = softplus(dt + dt_bias), no clamp; A =
               -exp(A_log) a head; the state S (P x N a head, float32, zero
               at position 0) follows S = exp(dt A) S + dt x (outer) B,
               o = S C + D x; y = RMS_grouped(o * SiLU(z)) W_out, the gate
               first, the norm over each group's d_ssm / g lanes (all of
               them at g = 1) (`models.nemotron_h`'s mixer: its
               projections, `models.falcon_h1`'s conv, gated norm and skip
               are imported).
    attention  q = u Wq (`n_heads` heads of `head_dim`), k, v = u Wk, u Wv
               (`n_kv_heads`), NOTHING rotated; causal soft-max of q.k *
               `attention_multiplier` (1/128 as published: NOT 1/sqrt(D));
               y = a Wo. The paged kernel has no scale argument and applies
               D^-1/2 itself, so q is multiplied by `attention_multiplier`
               * sqrt(D) before the read.
    experts    logits = v W_r (`n_routed`, float32); the top `top_k` by
               logit; w = soft-max over the chosen logits, which EQUALS a
               soft-max over all, the top k, renormalised
               (`ops.moe.softmax_topk_route`); expert e: [g | p] = v
               W_in[e] (2 x `d_ff_expert`), r_e = (SiLU(g) * p) W_out[e];
               experts(v) = sum_k w_k r_{e_k}; no selection bias, no
               scaling factor.
    shared     [g | p] = v W_s_in (2 x `d_ff_shared`); shared(v) =
               (SiLU(g) * p) W_s_out.
    logits = RMS_f(h) W_head / `logits_scaling`      (ASSUMED untied here:
             the published head is the embedding; a tie changes bytes and
             no equation, and with RANDOM weights a tied head makes every
             row repeat its last token: the configuration's `assumed`)

**A chip's share** (`models.laguna`): `held` = (first, count), the routed
experts whose weights THIS tree holds; a pair routed outside the share
forms no row and adds nothing here. The mixers, the router and the shared
expert are whole on every chip.

**A layer has TWO indices.** The `kv_and_state` family of
`models.olmo_hybrid`: a mamba layer's state and conv tail live in one row
of the state pool (`cfg.state_row_shapes`), the attention layer's K and V
in the block pool (`cfg.kv_block_kinds[0]`), and `cfg.pool_layer[l]` is
layer l's index among the layers of its MIXER's kind, the layer of that
pool. Beside it every layer routes: layer l's row of the step's per-expert
counts is l itself (`n_moe_layers` = `n_layers`), where
`models.nemotron_h`'s one index a layer named a pool's layer OR a row of
the counts. The served step (`granite_hybrid_step_rows_ragged`) takes both
pools, donated, over the tick's TOKENS: a mamba layer is
`models.olmo_hybrid._linear_rows` (a row that prefills runs its chunk
through `ssd_chunk` FROM the state its last chunk left, a row that decodes
through `ssd_step`, in the same tick), the attention layer the paged read
by the class of a row's run (`ops.paged_attention.ragged_read_by_class`, G
= n_heads / n_kv_heads), every layer's second half
`ops.moe.routed_experts` over the held share and the shared SwiGLU.

Parameter tree: `tok_embed`, `layers` (a list: the layers are of two
shapes), `ln_f`, `head`. A block is `ln1`, `ln2`, ONE of `ssm` {w_in, conv
(width, lanes), conv_bias, A_log, dt_bias, D, norm, w_out} and `attn` {wq,
wk, wv, wo}, and `mlp` {router {kernel}, shared {gate_up, proj}, experts
{gate_up (held, d, 2f), down (held, f, d)}}. Weights are made in
`param_dtype` directly, a layer and an expert at a time
(`models.laguna._bank`): no float32 copy of a bank exists anywhere. **The
draw**: the embedding N(0, 1 / `embedding_multiplier`^2), so the stream
starts at unit spread; every matrix N(0, 1/fan_in); what writes into the
stream (W_out, Wo, the experts' and the shared W_out) is NOT drawn smaller
by depth: `residual_multiplier` 0.22 is 1/sqrt(20.7), the other families'
1/sqrt(2 L) at L = 10, and the published scalar does that work. W_q and
W_k are drawn wider so that the scores q.k * `attention_multiplier` have
`models.falcon_h1`'s spread of 4 (unit draws would give 128^-1/2 = 0.09: a
flat average over the context); the conv's bias, `A_log`, `dt_bias` and
`D` by `models.falcon_h1`'s rule; the router N(0, 1/d), float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_engine.models.falcon_h1 import (
    _CONV_BIAS_MEAN,
    _SCORE_SPREAD,
    Mamba2Shapes,
    _scaled,
    _ssm_conv,
    _ssm_whole_row,
    _with_skip,
)
from tpu_engine.models.laguna import _bank
from tpu_engine.models.moonlight import _dense_init, _normal
from tpu_engine.models.nemotron_h import (
    _attn_inputs,
    _attn_output,
    _ssm_inputs,
    _ssm_output,
)
from tpu_engine.models.olmo_hybrid import _linear_rows
from tpu_engine.models.registry import ModelSpec, causal_lm_spec, register
from tpu_engine.models.tick_tokens import lm_head, tick_tokens
from tpu_engine.models.transformer import (
    TransformerConfig,
    index_in_kind,
    kv_kind_config,
)
from tpu_engine.ops import nn
from tpu_engine.ops.attention import KVCache, dot_product_attention
from tpu_engine.ops.moe import routed_experts, softmax_topk_route
from tpu_engine.ops.ssd import ssd_chunk_row, ssd_step_rows
from tpu_engine.utils.tracing import step_part

# A layer's mixer, as the published `layer_types` writes it.
MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig(Mamba2Shapes, TransformerConfig):
    """The base fields this family fixes: rmsnorm, no positions; `n_heads`,
    `n_kv_heads`, `head_dim` are the attention layers'; `d_ff` is unused
    (every layer's feed-forward is the expert block)."""
    layer_types: Tuple[str, ...] = ()
    lin_heads: int = 128                    # H: the recurrence's heads
    ssm_head_dim: int = 64                  # P
    d_state: int = 128                      # N
    n_groups: int = 1                       # g: B and C ONE group
    conv_width: int = 4
    d_ff_expert: int = 768
    d_ff_shared: int = 1536
    n_routed: int = 72
    top_k: int = 10
    held: Tuple[int, int] = (0, 72)         # (first, count) of n_routed
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    param_dtype: str = "bfloat16"

    # The registry derives family and TP rule from these two; the
    # scheduler names a tick's recurrent work by the third (the kernels'
    # names in a trace).
    serving_state_family = "kv_and_state"
    tp_partition_rule = ("unshardable: a row's SSM state and conv tail "
                         "are one state row a layer, which no shard map "
                         "over heads carries yet, and the lane holds one "
                         "chip's share of the experts already")
    recurrence = "ssd"

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers:
            raise ValueError("layer_types needs one entry a layer")
        for kind in self.layer_types:
            if kind not in (MAMBA, ATTENTION):
                raise ValueError(f"{kind!r} is no mixer: a layer's is "
                                 f"{MAMBA!r} or {ATTENTION!r}")
        if not self.n_full_layers or not self.n_linear_layers:
            raise ValueError("a row owns a chain and a state row: "
                             f"layer_types needs a {MAMBA!r} and an "
                             f"{ATTENTION!r}")
        if self.lin_heads % self.n_groups:
            raise ValueError(f"{self.lin_heads} heads are no whole groups "
                             f"of {self.n_groups}")
        first, count = self.held
        if not (0 <= first and count > 0
                and first + count <= self.n_routed):
            raise ValueError(f"held={self.held} is no share of "
                             f"{self.n_routed} experts")

    @property
    def n_linear_layers(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def n_full_layers(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def n_moe_layers(self) -> int:
        """Every layer routes: layer l's row of the counts is l."""
        return self.n_layers

    @property
    def kv_block_kinds(self) -> Tuple[TransformerConfig]:
        """What the block pool is sized by: the attention layers alone."""
        return (kv_kind_config(self, self.n_full_layers),)

    @property
    def pool_layer(self) -> Tuple[int, ...]:
        """Layer l's index among the layers of its mixer's kind: the layer
        of the state pool (mamba) or of the block pool (attention)."""
        return index_in_kind(self.layer_types)

    @property
    def query_scale(self) -> float:
        """What q is multiplied by before a read that divides the scores
        by sqrt(D) itself: the scores come out q.k *
        `attention_multiplier`."""
        return self.attention_multiplier * math.sqrt(self.d_head)


# -- parameters -----------------------------------------------------------------

def _block_init(key, cfg: GraniteHybridConfig, kind: str):
    dtype = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    k_mix, kr, ksi, kso, kgu, kdn = jax.random.split(key, 6)
    f, fs, count = cfg.d_ff_expert, cfg.d_ff_shared, cfg.held[1]
    block = {
        "ln1": nn.rmsnorm_init(d), "ln2": nn.rmsnorm_init(d),
        "mlp": {
            "router": {"kernel": _normal(kr, (d, cfg.n_routed), d,
                                         jnp.float32)},
            "shared": {"gate_up": _dense_init(ksi, d, 2 * fs, dtype),
                       "proj": _dense_init(kso, fs, d, dtype)},
            "experts": {"gate_up": _bank(kgu, (count, d, 2 * f), d, dtype),
                        "down": _bank(kdn, (count, f, d), f, dtype)},
        },
    }
    if kind == MAMBA:
        ki, kw, kc, kb, kl, kt = jax.random.split(k_mix, 6)
        lanes = cfg.d_ssm + cfg.conv_lanes + cfg.lin_heads
        block["ssm"] = {
            "w_in": _dense_init(ki, d, lanes, dtype),
            "conv": _normal(kc, (cfg.conv_width, cfg.conv_lanes),
                            cfg.conv_width, jnp.float32),
            "conv_bias": _CONV_BIAS_MEAN + 0.1 * jax.random.normal(
                kb, (cfg.conv_lanes,), jnp.float32),
            "A_log": jnp.log(jax.random.uniform(kl, (cfg.lin_heads,),
                                                jnp.float32, 0.02, 0.25)),
            "dt_bias": jax.random.uniform(kt, (cfg.lin_heads,), jnp.float32,
                                          -1.0, 0.5),
            "D": jnp.ones((cfg.lin_heads,), jnp.float32),
            "norm": nn.rmsnorm_init(cfg.d_ssm),
            "w_out": _dense_init(kw, cfg.d_ssm, d, dtype),
        }
    else:
        kq, kk, kv, ko = jax.random.split(k_mix, 4)
        dh = cfg.d_head
        # q.k of unit draws has a spread of sqrt(D); the scores are that
        # times `attention_multiplier` and are to spread by `_SCORE_SPREAD`.
        wider = math.sqrt(_SCORE_SPREAD / cfg.query_scale)
        block["attn"] = {
            "wq": _dense_init(kq, d, cfg.n_heads * dh, dtype, wider),
            "wk": _dense_init(kk, d, cfg.kv_heads * dh, dtype, wider),
            "wv": _dense_init(kv, d, cfg.kv_heads * dh, dtype),
            "wo": _dense_init(ko, cfg.n_heads * dh, d, dtype),
        }
    return block


def granite_hybrid_init(key, cfg: GraniteHybridConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    k_tok, k_head, *k_layers = jax.random.split(key, 2 + cfg.n_layers)
    return {
        "tok_embed": {"table": _normal(
            k_tok, (cfg.vocab, cfg.d_model), cfg.embedding_multiplier ** 2,
            dtype)},
        "layers": [_block_init(k, cfg, kind)
                   for k, kind in zip(k_layers, cfg.layer_types)],
        "ln_f": nn.rmsnorm_init(cfg.d_model),
        "head": _dense_init(k_head, cfg.d_model, cfg.vocab, dtype),
    }


# -- one layer's pieces ----------------------------------------------------------

def _scaled_attn_inputs(ap, u, cfg: GraniteHybridConfig, dtype):
    """`models.nemotron_h._attn_inputs` (nothing rotated) with q times
    `cfg.query_scale`."""
    q, k, v = _attn_inputs(ap, u, cfg, dtype)
    return (q.astype(jnp.float32) * cfg.query_scale).astype(dtype), k, v


def _swiglu(mp, v, dtype):
    """The shared expert: ONE product for gate and up, side by side."""
    gate, up = jnp.split(nn.dense(mp["gate_up"], v, dtype=dtype), 2, axis=-1)
    return nn.dense(mp["proj"], jax.nn.silu(gate) * up, dtype=dtype)


def _expert_block(mp, v, valid, cfg: GraniteHybridConfig, dtype, held,
                  max_tokens):
    """v: (N, d) normalised; valid: (N,); `mp["experts"]` holds the `held`
    experts alone. Returns (experts(v) + shared(v) (N, d) float32, rows
    (n_routed,): the rows each HELD expert took, zero elsewhere)."""
    experts, weights = softmax_topk_route(v, mp["router"], cfg.top_k)
    # The bank's group 0 is expert `held[0]`.
    routed, rows = routed_experts(
        v, valid, experts, weights, mp["experts"], first_group=-held[0],
        n_experts=cfg.n_routed, held=held, max_tokens=max_tokens,
        dtype=dtype)
    with step_part("moe/shared"):
        return routed + _swiglu(mp["shared"], v, dtype), rows


def _run_layers(params, h, carry, cfg: GraniteHybridConfig, mamba, attend,
                valid, dtype, held, max_tokens):
    """The layers in order (a Python loop: they differ in shape), h: (N, d):
    two norms and two scaled writes a layer. `mamba(at, sp, u, carry)` and
    `attend(at, ap, u, carry)` -> (the mixer's output, carry), `at` the
    layer of the mixer's pool. Returns (h, carry, rows (L, n_routed))."""
    def write(h, y):
        return (h + cfg.residual_multiplier * y.astype(jnp.float32)
                ).astype(dtype)

    rows = []
    for kind, at, bp in zip(cfg.layer_types, cfg.pool_layer,
                            params["layers"]):
        enter, leave = (("mixer/in", "mixer/out") if kind == MAMBA
                        else ("attn/qkv", "attn/out"))
        with step_part(enter):
            u = nn.rmsnorm(bp["ln1"], h, eps=cfg.ln_eps)
        if kind == MAMBA:
            y, carry = mamba(at, bp["ssm"], u, carry)
        else:
            y, carry = attend(at, bp["attn"], u, carry)
        with step_part(leave):
            h = write(h, y)
        with step_part("moe/route"):
            v = nn.rmsnorm(bp["ln2"], h, eps=cfg.ln_eps)
        y, taken = _expert_block(bp["mlp"], v, valid, cfg, dtype, held,
                                 max_tokens)
        rows.append(taken)
        with step_part("moe/experts"):
            h = write(h, y)
    return h, carry, jnp.stack(rows)


def _logits(params, h, cfg: GraniteHybridConfig, dtype):
    logits = lm_head(params, h, cfg.ln_eps, dtype)
    with step_part("head"):
        return logits / cfg.logits_scaling


# -- the one-shot forward --------------------------------------------------------

def granite_hybrid_apply(params, tokens, cfg: GraniteHybridConfig, *,
                         dtype=jnp.bfloat16):
    """Full-sequence causal forward from an empty state over the held
    experts. tokens: (B, S) int32 -> logits (B, S, vocab) float32."""
    b, s = tokens.shape
    h = _scaled(nn.embedding(params["tok_embed"], tokens)
                  .astype(jnp.float32), cfg, dtype)

    def by_row(fn):
        """A mixer over one sequence, over the batch's flattened rows."""
        def call(at, p, u, carry):
            y = jax.vmap(lambda row: fn(p, row))(u.reshape(b, s, -1))
            return y.reshape(b * s, -1), carry
        return call

    def attend(ap, u):
        q, k, v = _scaled_attn_inputs(ap, u, cfg, dtype)
        o = dot_product_attention(q[None], k[None], v[None], causal=True)[0]
        return _attn_output(ap, o, dtype)

    def mamba(sp, u):
        return _ssm_whole_row(sp, u, cfg, dtype, _ssm_inputs, _ssm_output)

    h, _, _ = _run_layers(params, h.reshape(b * s, -1), (), cfg,
                          by_row(mamba), by_row(attend),
                          jnp.ones((b * s,), bool), dtype, cfg.held, None)
    return _logits(params, h.reshape(b, s, -1), cfg, dtype)


# -- the served step: the mixed tick over the block pool and the state pool -------

def granite_hybrid_step_rows_ragged(params, tokens, caches, tables, pos0,
                                    qlen, cfg: GraniteHybridConfig, *,
                                    dtype=jnp.bfloat16, attn_fn=None,
                                    step_fn=ssd_step_rows,
                                    chunk_fn=ssd_chunk_row, sample_slot=None,
                                    held=None,
                                    max_tokens: Optional[int] = None):
    """This family's step of the mixed tick, over the tick's token list
    (`models.tick_tokens`, a token an entry).

    caches: (the block pool's K/V pair, (attention layers, NB, bs,
    H_kv*D); the state pool's arrays, `_linear_rows`, mamba layers deep),
    both updated in place (donate them); tables: (the rows' block table
    (B, nb); the rows' state row (B,), the null row 0 for a free slot). An
    attention layer is `PagedKV.attend` at G = n_heads / n_kv_heads; a
    mamba layer runs the recurrence over the same rows (`ssd_step`,
    `ssd_chunk`); EVERY layer then routes its tokens over the held experts.
    `step_fn`, `chunk_fn`: `ops.ssd`'s `ssd_step_rows` and `ssd_chunk_row`
    or stand-ins of their signatures.

    ``held`` = (first, count): the experts `params` holds (default
    `cfg.held`). Returns (logits, caches, rows (n_layers, n_routed) int32:
    the rows each held expert took)."""
    from tpu_engine.ops import paged_attention as pa

    if attn_fn is None:
        attn_fn = pa.default_ragged_attention()
    held = held or cfg.held
    (pool, state), (table, rows) = caches, tables
    w = tokens.shape[1]
    tt = tick_tokens(pos0, qlen, w, max_tokens)
    kv = tt.paged_kv(table, pool.k.shape[2], cfg.n_heads // cfg.kv_heads)
    h = tt.embed(params, tokens, jnp.float32)
    with step_part("embed"):
        h = _scaled(h, cfg, dtype)

    def mamba(at, sp, u, carry):
        pool, state = carry
        with step_part("mixer/in"):
            step, chunk = _with_skip(sp, step_fn), _with_skip(sp, chunk_fn)
        y, state = _linear_rows(
            sp, u, state, at, tt.plan.start, rows, pos0, qlen, w, cfg, dtype,
            step, chunk, inputs=_ssm_inputs, output=_ssm_output,
            conv=_ssm_conv)
        return y, (pool, state)

    def attend(at, ap, u, carry):
        pool, state = carry
        with step_part("attn/qkv"):
            q, k, v = _scaled_attn_inputs(ap, u, cfg, dtype)
        o, pool = kv.attend(attn_fn, q, k, v, pool, at)
        with step_part("attn/out"):
            return _attn_output(ap, o, dtype), (pool, state)

    h, (pool, state), taken = _run_layers(
        params, h, (tuple(pool), tuple(state)), cfg, mamba, attend, tt.valid,
        dtype, held, max_tokens)
    return (_logits(params, tt.head_rows(h, sample_slot), cfg, dtype),
            (KVCache(*pool), state), taken)


# -- registry ----------------------------------------------------------------------

def _lm_spec(name: str, cfg: GraniteHybridConfig, seq_len: int) -> ModelSpec:
    return causal_lm_spec(name, cfg, seq_len, granite_hybrid_init,
                          granite_hybrid_apply,
                          ragged_step=granite_hybrid_step_rows_ragged,
                          held=cfg.held)


def _cfg(**kw) -> GraniteHybridConfig:
    n = kw["n_layers"]
    # The source lists the whole model's layers; a cut keeps the first
    # `n_layers` of what it is given.
    return GraniteHybridConfig(
        vocab=kw["vocab"], n_layers=n, d_model=kw["d_model"],
        n_heads=kw["n_heads"], n_kv_heads=kw["n_kv_heads"],
        head_dim=kw["head_dim"], d_ff=0, max_seq=kw["max_seq"], causal=True,
        norm="rmsnorm", pos="none", ln_eps=kw["ln_eps"],
        layer_types=tuple(kw["layer_types"])[:n],
        lin_heads=kw["ssm_heads"], ssm_head_dim=kw["ssm_head_dim"],
        d_state=kw["d_state"], n_groups=kw["n_groups"],
        conv_width=kw["conv_width"], d_ff_expert=kw["d_ff_expert"],
        d_ff_shared=kw["d_ff_shared"], n_routed=kw["n_experts"],
        top_k=kw["top_k"],
        held=(kw["held_first"], kw["held_count"] or kw["n_experts"]),
        embedding_multiplier=kw["embedding_multiplier"],
        residual_multiplier=kw["residual_multiplier"],
        attention_multiplier=kw["attention_multiplier"],
        logits_scaling=kw["logits_scaling"],
        param_dtype=kw["param_dtype"])


_PUBLISHED_LAYERS = ((MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4) * 4


@register("granite_hybrid")
def make_granite_hybrid(seq_len: int = 128, vocab: int = 100352,
                        n_layers: int = 40,
                        layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS,
                        d_model: int = 4096, n_heads: int = 32,
                        n_kv_heads: int = 8, head_dim: int = 128,
                        ssm_heads: int = 128, ssm_head_dim: int = 64,
                        d_state: int = 128, n_groups: int = 1,
                        conv_width: int = 4, d_ff_expert: int = 768,
                        d_ff_shared: int = 1536, n_experts: int = 72,
                        top_k: int = 10, held_first: int = 0,
                        held_count: int = 0,
                        embedding_multiplier: float = 12.0,
                        residual_multiplier: float = 0.22,
                        attention_multiplier: float = 0.0078125,
                        logits_scaling: float = 16.0, max_seq: int = 16384,
                        ln_eps: float = 1e-5,
                        param_dtype: str = "bfloat16") -> ModelSpec:
    """Granite-4.0-H-Small's published geometry; every width a keyword.
    `held_count` 0 holds every expert."""
    return _lm_spec("granite_hybrid",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)


@register("granite_hybrid-small-test")
def make_granite_hybrid_small(seq_len: int = 16, vocab: int = 256,
                              n_layers: int = 4,
                              layer_types: Tuple[str, ...] = (
                                  MAMBA, MAMBA, ATTENTION, MAMBA),
                              d_model: int = 48, n_heads: int = 4,
                              n_kv_heads: int = 1, head_dim: int = 8,
                              ssm_heads: int = 8, ssm_head_dim: int = 4,
                              d_state: int = 16, n_groups: int = 1,
                              conv_width: int = 4, d_ff_expert: int = 24,
                              d_ff_shared: int = 32, n_experts: int = 12,
                              top_k: int = 4, held_first: int = 0,
                              held_count: int = 6,
                              embedding_multiplier: float = 12.0,
                              residual_multiplier: float = 0.22,
                              attention_multiplier: float = 0.0078125,
                              logits_scaling: float = 16.0,
                              max_seq: int = 128, ln_eps: float = 1e-5,
                              param_dtype: str = "float32") -> ModelSpec:
    """Tiny config for tests: mamba, mamba, attention, mamba, every layer
    with 12 experts top 4 of which 6 are held (one of two chips' share)
    beside a shared one, 4 query heads over 1 KV head of 8 lanes, 8 SSM
    heads of 4 lanes in ONE group, a state of 16 lanes, conv 4, the four
    published scalars, float32."""
    return _lm_spec("granite_hybrid-small-test",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)
