"""Nemotron-H family: a layer is ONE mixer, of three kinds by a published
pattern string, and nothing follows a mixer but the next layer's norm:

    M   a Mamba-2 state-space recurrence over a fixed-size state a row
    *   causal grouped-query attention over every token's keys and values,
        nothing rotated (the recurrent layers carry order)
    E   a LatentMoE: sigmoid-routed experts that read and write a LATENT
        narrower than the model, beside one shared expert at full width

Source of the default geometry: NVIDIA-Nemotron-3-Super-120B-A12B
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
`model_type: nemotron_h`). Stream h (T x d), eps `ln_eps`, no bias but the
conv's; layer l of kind `hybrid_override_pattern[l]`:

    h0 = E[ids]                                  (untied head)
    u  = RMS_l(h);  h += mixer_kind(u)
    M  [z | x B C | dt] = u W_in (d_ssm | d_ssm + 2 g N | H lanes); x, B, C
       pass ONE causal depthwise conv of width `conv_width` with a bias,
       then SiLU; x: H heads of P lanes, B and C: g groups of N lanes, head
       i reads group i // (H / g); dt = softplus(dt + dt_bias), A =
       -exp(A_log) a head; the state S (P x N a head, float32, zero at
       position 0) follows S = exp(dt A) S + dt x (outer) B, o = S C + D x;
       y = RMS_grouped(o * SiLU(z)) W_out, the norm over each group's
       d_ssm / g lanes with a learned scale (`models.falcon_h1`'s mixer
       with no multiplier: its conv, gated norm and skip are imported).
    *  q = u Wq (`n_heads` heads of `head_dim`), k, v = u Wk, u Wv
       (`n_kv_heads`); causal soft-max of q.k / sqrt(D); y = a Wo.
    E  s = sigmoid(u W_r) float32 over `n_routed` experts; the top `top_k`
       of s + bias are chosen, w = s[chosen] / sum * `routed_scale`
       (`ops.moe.sigmoid_topk_route`, one group);
       v = u W_lat_down (d -> `d_latent`);
       r = sum_k w_k relu(v W_up[e_k])^2 W_down[e_k]   (ungated, two
       matrices an expert, `d_latent` -> `d_ff_expert` -> `d_latent`);
       y = r W_lat_up + relu(u W_s_up)^2 W_s_down   (the shared expert reads
       the model's width, `d_ff_shared` wide).
    logits = RMS_f(h) W_head

**A chip's share** (`models.laguna`): `held` = (first, count), the routed
experts whose weights THIS tree holds; a pair routed outside the share
forms no row and adds nothing here. The latent projections, the router and
the shared expert are whole on every chip.

**Three layer shapes, two pools of different depths.** The `kv_and_state`
family of `models.olmo_hybrid`: an M layer's state and conv tail live in
one row of the state pool (`cfg.state_row_shapes`), a * layer's K and V in
the block pool (`cfg.kv_block_kinds[0]`), an E layer owns neither;
`cfg.pool_layer[l]` is layer l's index among the layers of ITS kind: the
layer of the state pool, of the block pool, or the row of the step's
per-expert counts. The served step (`nemotron_h_step_rows_ragged`) takes
both pools, donated, over the tick's TOKENS: an M layer is
`models.olmo_hybrid._linear_rows` with this family's projections (a row
that prefills runs its chunk through `ssd_chunk` FROM the state its last
chunk left, a row that decodes through `ssd_step`, in the same tick), a *
layer the paged read by the class of a row's run
(`ops.paged_attention.ragged_read_by_class`, G = n_heads / n_kv_heads), an
E layer `ops.moe.routed_experts` over the latent rows with the bank's
two-matrix form.

Parameter tree: `tok_embed`, `layers` (a list: the layers are of three
shapes), `ln_f`, `head`. A block is `ln1` and ONE of `ssm` {w_in, conv
(width, lanes), conv_bias, A_log, dt_bias, D, norm, w_out}, `attn` {wq, wk,
wv, wo}, `mlp` {router, latent_down, latent_up, shared {up, proj}, experts
{up (held, d_latent, f), down (held, f, d_latent)}}. Weights are made in
`param_dtype` directly. **The draw** is the other families' (unit-variance
stream, every matrix N(0, 1/fan_in)); what writes into the stream (W_out,
Wo, W_lat_up, W_s_down) is drawn 1/sqrt(L) smaller, not 1/sqrt(2 L): a
layer writes once. relu(z)^2 of a unit normal has a second moment of 3/2,
so the matrices that read it (an expert's W_down, W_s_down) are drawn
sqrt(3/2) smaller and an expert's output has unit spread. The scores
q.k / sqrt(D) are drawn with `models.falcon_h1`'s spread of 4 and the
conv's bias around its -0.5, for its reasons; `A_log` is the log of a number
drawn evenly from (0.02, 0.25) a head and `dt_bias` evenly from (-1, 0.5):
the decay exp(dt A) lies in (0.55, 1), spread over that range by head and
token; `D` is 1; the selection bias about a tenth of the scores' spread.
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
    _gated_norm,
    _ssm_conv,
    _ssm_whole_row,
    _with_skip,
)
from tpu_engine.models.laguna import _bank
from tpu_engine.models.moonlight import _dense_init, _normal
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
from tpu_engine.ops.moe import relu2, routed_experts, sigmoid_topk_route
from tpu_engine.ops.ssd import ssd_chunk_row, ssd_step_rows
from tpu_engine.utils.tracing import step_part

# A layer's kind, as the published pattern writes it.
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# The second moment of relu(z)^2 for a unit normal z, E[z^4] / 2: what a
# matrix that reads the activation is drawn smaller by (module docstring).
_RELU2_MOMENT = 1.5


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(Mamba2Shapes, TransformerConfig):
    """The base fields this family fixes: rmsnorm, no positions; `n_heads`,
    `n_kv_heads`, `head_dim` are the * layers'; `d_ff` is unused (no layer
    has a feed-forward part)."""
    pattern: str = ""                       # one of M, E, * a layer
    lin_heads: int = 128                    # H: the recurrence's heads
    ssm_head_dim: int = 64                  # P
    d_state: int = 128                      # N
    n_groups: int = 8                       # g: B and C a group of heads
    conv_width: int = 4
    d_latent: int = 1024                    # what a routed expert reads
    d_ff_expert: int = 2688
    d_ff_shared: int = 5376
    n_routed: int = 512
    top_k: int = 22
    routed_scale: float = 5.0
    held: Tuple[int, int] = (0, 512)        # (first, count) of n_routed
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
        if len(self.pattern) != self.n_layers:
            raise ValueError("pattern needs one character a layer")
        for kind in self.pattern:
            if kind == "-":
                raise ValueError(
                    "'-' (the family's dense feed-forward layer) is not "
                    "built: no published pattern this model serves has it")
            if kind not in (MAMBA, EXPERTS, ATTENTION):
                raise ValueError(f"{kind!r} is no layer kind: a pattern is "
                                 f"made of {MAMBA}, {EXPERTS} and "
                                 f"{ATTENTION}")
        if not self.n_full_layers or not self.n_linear_layers:
            raise ValueError("a row owns a chain and a state row: the "
                             f"pattern needs a {MAMBA} and a {ATTENTION}")
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
        return self.pattern.count(MAMBA)

    @property
    def n_full_layers(self) -> int:
        return self.pattern.count(ATTENTION)

    @property
    def n_moe_layers(self) -> int:
        return self.pattern.count(EXPERTS)

    @property
    def kv_block_kinds(self) -> Tuple[TransformerConfig]:
        """What the block pool is sized by: the * layers alone."""
        return (kv_kind_config(self, self.n_full_layers),)

    @property
    def pool_layer(self) -> Tuple[int, ...]:
        """Layer l's index among the layers of its kind: the layer of the
        state pool (M), of the block pool (*), or the row of the step's
        per-expert counts (E)."""
        return index_in_kind(self.pattern)


# -- parameters -----------------------------------------------------------------

def _residual_gain(cfg: NemotronHConfig) -> float:
    """`models.moonlight._residual_gain` for a layer that writes ONCE."""
    return 1.0 / math.sqrt(cfg.n_layers)


def _block_init(key, cfg: NemotronHConfig, kind: str):
    dtype = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    out_gain = _residual_gain(cfg)
    block = {"ln1": nn.rmsnorm_init(d)}
    if kind == MAMBA:
        ki, kw, kc, kb, kl, kt = jax.random.split(key, 6)
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
            "w_out": _dense_init(kw, cfg.d_ssm, d, dtype, out_gain),
        }
    elif kind == ATTENTION:
        kq, kk, kv, ko = jax.random.split(key, 4)
        dh = cfg.d_head
        block["attn"] = {
            "wq": _dense_init(kq, d, cfg.n_heads * dh, dtype,
                              math.sqrt(_SCORE_SPREAD)),
            "wk": _dense_init(kk, d, cfg.kv_heads * dh, dtype,
                              math.sqrt(_SCORE_SPREAD)),
            "wv": _dense_init(kv, d, cfg.kv_heads * dh, dtype),
            "wo": _dense_init(ko, cfg.n_heads * dh, d, dtype, out_gain),
        }
    else:
        kr, kbias, kld, klu, ksu, ksd, kup, kdn = jax.random.split(key, 8)
        e, f, lat, count = (cfg.n_routed, cfg.d_ff_expert, cfg.d_latent,
                            cfg.held[1])
        after_relu2 = 1.0 / math.sqrt(_RELU2_MOMENT)
        block["mlp"] = {
            # As models.moonlight draws them: unit-variance logits, a
            # selection bias of about a tenth of the scores' spread.
            "router": {"kernel": _normal(kr, (d, e), d, jnp.float32),
                       "bias": 0.02 * jax.random.normal(kbias, (e,),
                                                        jnp.float32)},
            "latent_down": _dense_init(kld, d, lat, dtype),
            "latent_up": _dense_init(klu, lat, d, dtype, out_gain),
            "shared": {"up": _dense_init(ksu, d, cfg.d_ff_shared, dtype),
                       "proj": _dense_init(ksd, cfg.d_ff_shared, d, dtype,
                                           out_gain * after_relu2)},
            "experts": {"up": _bank(kup, (count, lat, f), lat, dtype),
                        "down": _bank(kdn, (count, f, lat),
                                      f * _RELU2_MOMENT, dtype)},
        }
    return block


def nemotron_h_init(key, cfg: NemotronHConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    k_tok, k_head, *k_layers = jax.random.split(key, 2 + cfg.n_layers)
    return {
        "tok_embed": {"table": jax.random.normal(
            k_tok, (cfg.vocab, cfg.d_model), dtype)},
        "layers": [_block_init(k, cfg, kind)
                   for k, kind in zip(k_layers, cfg.pattern)],
        "ln_f": nn.rmsnorm_init(cfg.d_model),
        "head": _dense_init(k_head, cfg.d_model, cfg.vocab, dtype),
    }


# -- one layer's pieces ----------------------------------------------------------

def _ssm_inputs(sp, u, cfg: NemotronHConfig, dtype):
    """`models.olmo_hybrid._lin_inputs` for this family. u: (..., d)
    normalised. Returns (x, B, C side by side BEFORE the conv (..., conv
    lanes) float32; z (..., d_ssm); dt (..., H) after its softplus, twice:
    `models.falcon_h1._ssm_inputs` says why)."""
    proj = nn.dense(sp["w_in"], u, dtype=dtype).astype(jnp.float32)
    z = proj[..., :cfg.d_ssm]
    mixed = proj[..., cfg.d_ssm:cfg.d_ssm + cfg.conv_lanes]
    dt = jax.nn.softplus(proj[..., -cfg.lin_heads:] + sp["dt_bias"])
    # Written here, once: `models.falcon_h1._ssm_inputs` says why.
    mixed, z, dt = jax.lax.optimization_barrier((mixed, z, dt))
    return mixed, z, dt, dt


def _ssm_output(sp, o, z, cfg: NemotronHConfig, dtype):
    return nn.dense(sp["w_out"], _gated_norm(sp, o, z, cfg), dtype=dtype)


def _attn_inputs(ap, u, cfg: NemotronHConfig, dtype):
    """u: (S, d) normalised. Returns q (S, H, D), k and v (S, H_kv, D), as
    the pool holds k: nothing is rotated."""
    def heads(name):
        y = nn.dense(ap[name], u, dtype=dtype).astype(dtype)
        return y.reshape(y.shape[0], -1, cfg.d_head)

    return heads("wq"), heads("wk"), heads("wv")


def _attn_output(ap, o, dtype):
    return nn.dense(ap["wo"], o.astype(dtype).reshape(o.shape[0], -1),
                    dtype=dtype)


def _latent_moe(mp, u, valid, cfg: NemotronHConfig, dtype, held,
                max_tokens):
    """u: (N, d) normalised; valid: (N,); `mp["experts"]` holds the `held`
    experts alone. The router and the shared expert read u; the pair list
    is gathered from the LATENT rows, so the gather, the two grouped
    products and the scatter-add move `d_latent` lanes. Returns (y (N, d),
    rows (n_routed,): the rows each HELD expert took, zero elsewhere)."""
    experts, weights = sigmoid_topk_route(u, mp["router"], cfg.top_k,
                                          cfg.routed_scale)
    with step_part("moe/experts"):
        latent = nn.dense(mp["latent_down"], u, dtype=dtype)
    # The bank's group 0 is expert `held[0]`.
    routed, rows = routed_experts(
        latent, valid, experts, weights, mp["experts"],
        first_group=-held[0], n_experts=cfg.n_routed, held=held,
        max_tokens=max_tokens, dtype=dtype, activation=relu2)
    with step_part("moe/shared"):
        shared = nn.dense(mp["shared"]["proj"],
                          relu2(nn.dense(mp["shared"]["up"], u, dtype=dtype)),
                          dtype=dtype)
    with step_part("moe/experts"):
        return nn.dense(mp["latent_up"], routed, dtype=dtype) + shared, rows


def _run_layers(params, h, carry, cfg: NemotronHConfig, mamba, attend, valid,
                dtype, held, max_tokens):
    """The layers in order (a Python loop: they differ in shape), h: (N, d).
    `mamba(at, sp, u, carry)` and `attend(at, ap, u, carry)` -> (the
    mixer's output, carry), `at` the layer of the kind's pool. Returns (h,
    carry, rows (L_moe, n_routed))."""
    rows = []
    parts = {MAMBA: ("mixer/in", "mixer/out"),
             ATTENTION: ("attn/qkv", "attn/out")}
    for kind, at, bp in zip(cfg.pattern, cfg.pool_layer, params["layers"]):
        enter, leave = parts.get(kind, ("moe/route", "moe/experts"))
        with step_part(enter):
            u = nn.rmsnorm(bp["ln1"], h, eps=cfg.ln_eps)
        if kind == MAMBA:
            y, carry = mamba(at, bp["ssm"], u, carry)
        elif kind == ATTENTION:
            y, carry = attend(at, bp["attn"], u, carry)
        else:
            y, taken = _latent_moe(bp["mlp"], u, valid, cfg, dtype, held,
                                   max_tokens)
            rows.append(taken)
        with step_part(leave):
            h = (h + y).astype(dtype)
    rows = (jnp.stack(rows) if rows
            else jnp.zeros((0, cfg.n_routed), jnp.int32))
    return h, carry, rows


# -- the one-shot forward --------------------------------------------------------

def nemotron_h_apply(params, tokens, cfg: NemotronHConfig, *,
                     dtype=jnp.bfloat16):
    """Full-sequence causal forward from an empty state over the held
    experts. tokens: (B, S) int32 -> logits (B, S, vocab) float32."""
    b, s = tokens.shape
    h = nn.embedding(params["tok_embed"], tokens).astype(dtype)

    def by_row(fn):
        """A mixer over one sequence, over the batch's flattened rows."""
        def call(at, p, u, carry):
            y = jax.vmap(lambda row: fn(p, row))(u.reshape(b, s, -1))
            return y.reshape(b * s, -1), carry
        return call

    def attend(ap, u):
        q, k, v = _attn_inputs(ap, u, cfg, dtype)
        o = dot_product_attention(q[None], k[None], v[None], causal=True)[0]
        return _attn_output(ap, o, dtype)

    def mamba(sp, u):
        return _ssm_whole_row(sp, u, cfg, dtype, _ssm_inputs, _ssm_output)

    h, _, _ = _run_layers(params, h.reshape(b * s, -1), (), cfg,
                          by_row(mamba), by_row(attend),
                          jnp.ones((b * s,), bool), dtype, cfg.held, None)
    return lm_head(params, h.reshape(b, s, -1), cfg.ln_eps, dtype)


# -- the served step: the mixed tick over the block pool and the state pool -------

def nemotron_h_step_rows_ragged(params, tokens, caches, tables, pos0, qlen,
                                cfg: NemotronHConfig, *, dtype=jnp.bfloat16,
                                attn_fn=None, step_fn=ssd_step_rows,
                                chunk_fn=ssd_chunk_row, sample_slot=None,
                                held=None,
                                max_tokens: Optional[int] = None):
    """This family's step of the mixed tick, over the tick's token list
    (`models.tick_tokens`, a token an entry).

    caches: (the block pool's K/V pair, (* layers, NB, bs, H_kv*D); the
    state pool's arrays, `_linear_rows`, M layers deep), both updated in
    place (donate them); tables: (the rows' block table (B, nb); the rows'
    state row (B,), the null row 0 for a free slot). A * layer is
    `PagedKV.attend` at G = n_heads / n_kv_heads; an M layer runs the
    recurrence over the same rows
    (`ssd_step`, `ssd_chunk`); an E layer touches neither pool. `step_fn`,
    `chunk_fn`: `ops.ssd`'s `ssd_step_rows` and `ssd_chunk_row` or
    stand-ins of their signatures.

    ``held`` = (first, count): the experts `params` holds (default
    `cfg.held`). Returns (logits, caches, rows (L_moe, n_routed) int32:
    the rows each held expert took)."""
    from tpu_engine.ops import paged_attention as pa

    if attn_fn is None:
        attn_fn = pa.default_ragged_attention()
    held = held or cfg.held
    (pool, state), (table, rows) = caches, tables
    w = tokens.shape[1]
    tt = tick_tokens(pos0, qlen, w, max_tokens)
    kv = tt.paged_kv(table, pool.k.shape[2], cfg.n_heads // cfg.kv_heads)
    h = tt.embed(params, tokens, dtype)

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
            q, k, v = _attn_inputs(ap, u, cfg, dtype)
        o, pool = kv.attend(attn_fn, q, k, v, pool, at)
        with step_part("attn/out"):
            return _attn_output(ap, o, dtype), (pool, state)

    h, (pool, state), taken = _run_layers(
        params, h, (tuple(pool), tuple(state)), cfg, mamba, attend, tt.valid,
        dtype, held, max_tokens)
    return (lm_head(params, tt.head_rows(h, sample_slot), cfg.ln_eps, dtype),
            (KVCache(*pool), state), taken)


# -- registry ----------------------------------------------------------------------

def _lm_spec(name: str, cfg: NemotronHConfig, seq_len: int) -> ModelSpec:
    return causal_lm_spec(name, cfg, seq_len, nemotron_h_init,
                          nemotron_h_apply,
                          ragged_step=nemotron_h_step_rows_ragged,
                          held=cfg.held)


def _cfg(**kw) -> NemotronHConfig:
    n = kw["n_layers"]
    # The source writes the whole model's pattern; a cut keeps its first
    # `n_layers` characters.
    return NemotronHConfig(
        vocab=kw["vocab"], n_layers=n, d_model=kw["d_model"],
        n_heads=kw["n_heads"], n_kv_heads=kw["n_kv_heads"],
        head_dim=kw["head_dim"], d_ff=0, max_seq=kw["max_seq"], causal=True,
        norm="rmsnorm", pos="none", ln_eps=kw["ln_eps"],
        pattern=kw["pattern"][:n], lin_heads=kw["ssm_heads"],
        ssm_head_dim=kw["ssm_head_dim"], d_state=kw["d_state"],
        n_groups=kw["n_groups"], conv_width=kw["conv_width"],
        d_latent=kw["d_latent"], d_ff_expert=kw["d_ff_expert"],
        d_ff_shared=kw["d_ff_shared"], n_routed=kw["n_experts"],
        top_k=kw["top_k"], routed_scale=kw["routed_scale"],
        held=(kw["held_first"], kw["held_count"] or kw["n_experts"]),
        param_dtype=kw["param_dtype"])


_PUBLISHED_PATTERN = ("MEMEMEM*E" + "MEMEMEM*E" + "MEMEMEM*E"
                      + "MEMEMEMEM*E" * 4 + "MEMEMEM*E" + "MEMEMEME")


@register("nemotron_h")
def make_nemotron_h(seq_len: int = 128, vocab: int = 131072,
                    n_layers: int = 88, pattern: str = _PUBLISHED_PATTERN,
                    d_model: int = 4096, n_heads: int = 32,
                    n_kv_heads: int = 2, head_dim: int = 128,
                    ssm_heads: int = 128, ssm_head_dim: int = 64,
                    d_state: int = 128, n_groups: int = 8,
                    conv_width: int = 4, d_latent: int = 1024,
                    d_ff_expert: int = 2688, d_ff_shared: int = 5376,
                    n_experts: int = 512, top_k: int = 22,
                    routed_scale: float = 5.0, held_first: int = 0,
                    held_count: int = 0, max_seq: int = 16384,
                    ln_eps: float = 1e-5,
                    param_dtype: str = "bfloat16") -> ModelSpec:
    """Nemotron-3-Super-120B-A12B's published geometry; every width a
    keyword. `held_count` 0 holds every expert."""
    return _lm_spec("nemotron_h", _cfg(**{k: v for k, v in locals().items()
                                          if k != "seq_len"}), seq_len)


@register("nemotron_h_small")
def make_nemotron_h_small(seq_len: int = 16, vocab: int = 256,
                          n_layers: int = 11,
                          pattern: str = _PUBLISHED_PATTERN,
                          d_model: int = 48, n_heads: int = 4,
                          n_kv_heads: int = 1, head_dim: int = 8,
                          ssm_heads: int = 8, ssm_head_dim: int = 4,
                          d_state: int = 16, n_groups: int = 2,
                          conv_width: int = 4, d_latent: int = 16,
                          d_ff_expert: int = 24, d_ff_shared: int = 64,
                          n_experts: int = 16, top_k: int = 6,
                          routed_scale: float = 5.0, held_first: int = 0,
                          held_count: int = 4, max_seq: int = 128,
                          ln_eps: float = 1e-5,
                          param_dtype: str = "float32") -> ModelSpec:
    """Tiny config for tests: the cell's eleven layers (`MEMEMEM*EME`), 4
    query heads over 1 KV head of 8 lanes, 8 SSM heads of 4 lanes in 2
    groups, a state of 16 lanes, conv 4, a latent of 16 lanes, 4 of 16
    experts held (one of four chips' share), top 6, float32."""
    return _lm_spec("nemotron_h_small",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)
