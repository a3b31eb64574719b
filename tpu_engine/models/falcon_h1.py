"""Falcon-H1 family: EVERY layer has two mixers on one normed input, causal
attention (GQA, rotary) over every token's keys and values AND a Mamba-2
state-space recurrence over a fixed-size state, read in parallel and
summed; then a SwiGLU. muP multipliers scale every branch.

Source of the default geometry: Falcon-H1-34B-Instruct
(https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct, `model_type:
falcon_h1`). Stream h (T x d), every layer alike:

    h0 = E[ids] * embedding_multiplier
    u  = RMS_in(h)
    attention   q = (u m_att_in) Wq, k = ((u m_att_in) Wk) * key_multiplier,
                v = (u m_att_in) Wv: `n_heads` query heads over `n_kv_heads`
                KV heads of `head_dim` lanes, rotate-half RoPE (`rope_theta`)
                over all of them, no bias; causal soft-max of q.k / sqrt(D);
                y_att = (a Wo) * attention_out_multiplier.
    Mamba-2     [z | x B C | dt] = ((u ssm_in_multiplier) W_in) * mup_vector
                (`ssm_multipliers` over z, x, B, C, dt: d_ssm | d_ssm +
                2 g N | H lanes); x, B, C pass ONE causal depthwise conv of
                width `conv_width` with a bias, then SiLU; x: H heads of P
                lanes, B and C: g groups of N lanes, head i reads group
                i // (H / g); dt = softplus(dt + dt_bias), A = -exp(A_log)
                a head; the state S (P x N a head, float32, zero at
                position 0) follows S = exp(dt A) S + dt x (outer) B,
                o = S C + D x; y_ssm = (RMS_grouped(o * SiLU(z)) W_out) *
                ssm_out_multiplier, the norm over each group's d_ssm / g
                lanes with a learned scale.
    h += y_att + y_ssm
    v = RMS_ff(h); h += ((v W_up) * SiLU((v W_gate) * mlp_multipliers[0]))
                W_down * mlp_multipliers[1]
    logits = (RMS_f(h) W_head) * lm_head_multiplier        (untied)

The multipliers are applied where they are written, at run time: no matrix
has one folded in.

**Two kinds of state in one row, in EVERY layer.** The `kv_and_state`
family of `models.olmo_hybrid` with both pools `n_layers` deep
(`cfg.pool_layer[l] = l` for both): a layer's K and V go to the block pool
(`cfg.kv_block_kinds[0]`), its SSM state and the last `conv_width - 1`
inputs of its conv to one row of the state pool (`cfg.state_row_shapes`).
The served step (`falcon_h1_step_rows_ragged`) takes both, donated, and
runs both mixers of a layer from the same normed rows: the paged read by
the class of a row's run (`ops.paged_attention.ragged_read_by_class`, G =
5), the recurrence through `models.olmo_hybrid._linear_rows` with this
family's projections and conv: a row that prefills runs its chunk through
`ssd_chunk` FROM the state its last chunk left, a row that decodes through
`ssd_step`, in the same tick.

Parameter tree: `tok_embed`, `layers` (a list), `ln_f`, `head`. A block is
`ln1`, `ln2`, `mlp` {gate, up, proj}, `attn` {wq, wk, wv, wo} and `ssm`
{w_in, conv (width, lanes), conv_bias, A_log, dt_bias, D, norm, w_out}.
Weights are made in `param_dtype` directly. **The draw** is the other
families' (unit-variance stream, every matrix N(0, 1/fan_in), what writes
into the stream 1/sqrt(2 L) smaller) taken AFTER the multipliers: a matrix
whose product is multiplied by m is drawn 1/m wider, so that the
embedding, the conv's inputs, the SwiGLU's gate and the logits have about
unit spread as the published multipliers leave them (under N(0, 1/fan_in)
alone `key_multiplier` 0.011 would make every score 0 and
`attention_out_multiplier` the branch vanish, and no comparison could tell
the attention branch missing). One thing more, so that the two branches
write into the stream within a factor of two of each other at every context
the lane holds: the scores q.k / sqrt(D) are drawn with a spread of
`_SCORE_SPREAD` = 4 and not 1 (`wq` and `wk` 2 wider each). Scores of unit
spread average a row's values over about n / e of its n tokens, so the
branch would write 1/sqrt(n / e) of what the Mamba branch, whose output is
normalised, writes: 1/14 at 512 tokens (measured on the chip, PR 46:
0.02 against 0.29). At a spread of 4 a soft-max over up to 1.5 k tokens
rests on a handful of them, as a trained head's does, and the branch writes
0.15 to 0.23 against the Mamba branch's 0.29 at every context from 16 to
1000 tokens, in every layer. The conv's bias is drawn around
`_CONV_BIAS_MEAN` = -0.5, where SiLU's output has no mean: with a bias
around 0, x, B and C are positive on average, B.C has a mean of 11 over
256 lanes, the state's read is a smooth running sum and by the sixth layer
a third of the logits' variance is one constant vector (PERF.md, PR 46).
`A_log` is the log of a number drawn
evenly from (0.02, 0.25) a head and `dt_bias` evenly from (-1, 0.5): the
decay exp(dt A) lies in (0.55, 1), spread over that range by head and
token, as the other two state families draw theirs; `D` is 1.
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
from tpu_engine.models.olmo_hybrid import _linear_rows
from tpu_engine.models.registry import ModelSpec, causal_lm_spec, register
from tpu_engine.models.tick_tokens import lm_head, tick_tokens
from tpu_engine.models.transformer import (
    TransformerConfig,
    kv_kind_config,
)
from tpu_engine.ops import nn
from tpu_engine.ops.attention import KVCache, dot_product_attention, rope
from tpu_engine.ops.ssd import (
    SUB_CHUNK,
    ssd_chunk_row,
    ssd_chunked,
    ssd_step_rows,
)
from tpu_engine.utils.tracing import step_part


class Mamba2Shapes:
    """What a Mamba-2 mixer's sizes make of a config that states
    `lin_heads`, `ssm_head_dim`, `d_state`, `n_groups` and `conv_width`
    (this family, `models.nemotron_h`)."""

    @property
    def d_ssm(self) -> int:
        return self.lin_heads * self.ssm_head_dim

    @property
    def conv_lanes(self) -> int:
        """x, B and C side by side: what the conv runs over."""
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def state_row_shapes(self) -> Tuple[Tuple[int, ...], ...]:
        """A row's state, a layer: S and the conv tail (float32), the tail
        as 8 sublanes of whole lane tiles where it can be
        (`models.kimi_linear` says what (width - 1, lanes) costs a tick)."""
        tail = (self.conv_width - 1) * self.conv_lanes
        return ((self.lin_heads, self.ssm_head_dim, self.d_state),
                (8, tail // 8) if tail % 8 == 0 else (tail,))


@dataclasses.dataclass(frozen=True)
class FalconH1Config(Mamba2Shapes, TransformerConfig):
    """The base fields this family fixes: rmsnorm, rope, swiglu."""
    lin_heads: int = 32                     # H: the recurrence's heads
    ssm_head_dim: int = 128                 # P
    d_state: int = 256                      # N
    n_groups: int = 2                       # g: B and C a group of heads
    conv_width: int = 4
    embedding_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5   # z, x, B, C, dt
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)  # gate, down
    lm_head_multiplier: float = 1.0
    param_dtype: str = "bfloat16"

    # The registry derives family and TP rule from these two; the
    # scheduler names a tick's recurrent work by the third (the kernels'
    # names in a trace).
    serving_state_family = "kv_and_state"
    tp_partition_rule = ("unshardable: a row's SSM state and conv tail "
                         "are one state row a layer, which no shard map "
                         "over heads carries yet")
    recurrence = "ssd"

    def __post_init__(self):
        if self.lin_heads % self.n_groups:
            raise ValueError(f"{self.lin_heads} heads are no whole groups "
                             f"of {self.n_groups}")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has five entries (z, x, B, C, "
                             "dt) and mlp_multipliers two (gate, down)")

    # Every layer is of both kinds: each pool holds all of them.
    @property
    def n_linear_layers(self) -> int:
        return self.n_layers

    @property
    def n_full_layers(self) -> int:
        return self.n_layers

    @property
    def kv_block_kinds(self) -> Tuple[TransformerConfig]:
        return (kv_kind_config(self, self.n_layers),)

    @property
    def pool_layer(self) -> Tuple[int, ...]:
        """Layer l's index in either pool."""
        return tuple(range(self.n_layers))

    @property
    def mup_segments(self) -> Tuple[Tuple[int, float], ...]:
        """W_in's columns in order, (lanes, multiplier): z, x, B, C, dt."""
        group = self.n_groups * self.d_state
        return tuple(zip((self.d_ssm, self.d_ssm, group, group,
                          self.lin_heads), self.ssm_multipliers))


# -- parameters -----------------------------------------------------------------

# The spread of the scores q.k / sqrt(D) as drawn (module docstring).
_SCORE_SPREAD = 4.0
# The conv's bias is drawn around this: E[SiLU(N(-0.5, 1))] = 0, so x, B and
# C leave the conv with no mean (module docstring).
_CONV_BIAS_MEAN = -0.5


def _block_init(key, cfg: FalconH1Config):
    dtype = jnp.dtype(cfg.param_dtype)
    d, dh = cfg.d_model, cfg.d_head
    out_gain = _residual_gain(cfg)
    (kq, kk, kv, ko, ki, kw, kc, kb, kl, kt, kf,
     kg) = jax.random.split(key, 12)
    gate_m, down_m = cfg.mlp_multipliers
    mlp = _swiglu_init(kf, d, cfg.d_ff, dtype, out_gain / down_m)
    # The gate again, wider by its multiplier, from a key of ITS OWN: one
    # folded from `kf` is one of `_swiglu_init`'s own splits, and a gate
    # that is its `up` makes the SwiGLU x SiLU(x), a constant a layer.
    mlp["gate"] = _dense_init(kg, d, cfg.d_ff, dtype, 1.0 / gate_m)
    w_in = jnp.concatenate(
        [_normal(k, (d, lanes), d * (cfg.ssm_in_multiplier * m) ** 2, dtype)
         for k, (lanes, m) in zip(jax.random.split(ki, 5),
                                  cfg.mup_segments)], axis=1)
    return {
        "ln1": nn.rmsnorm_init(d), "ln2": nn.rmsnorm_init(d), "mlp": mlp,
        "attn": {
            "wq": _dense_init(kq, d, cfg.n_heads * dh, dtype,
                              math.sqrt(_SCORE_SPREAD)
                              / cfg.attention_in_multiplier),
            "wk": _dense_init(kk, d, cfg.kv_heads * dh, dtype,
                              math.sqrt(_SCORE_SPREAD)
                              / (cfg.attention_in_multiplier
                                 * cfg.key_multiplier)),
            "wv": _dense_init(kv, d, cfg.kv_heads * dh, dtype,
                              1.0 / cfg.attention_in_multiplier),
            "wo": _dense_init(ko, cfg.n_heads * dh, d, dtype,
                              out_gain / cfg.attention_out_multiplier),
        },
        "ssm": {
            "w_in": {"kernel": w_in,
                     "bias": jnp.zeros((w_in.shape[1],), jnp.float32)},
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
            "w_out": _dense_init(kw, cfg.d_ssm, d, dtype,
                                 out_gain / cfg.ssm_out_multiplier),
        },
    }


def falcon_h1_init(key, cfg: FalconH1Config):
    dtype = jnp.dtype(cfg.param_dtype)
    k_tok, k_head, *k_layers = jax.random.split(key, 2 + cfg.n_layers)
    table = jax.random.normal(k_tok, (cfg.vocab, cfg.d_model), dtype)
    return {
        "tok_embed": {"table": table * jnp.asarray(
            1.0 / cfg.embedding_multiplier, dtype)},
        "layers": [_block_init(k, cfg) for k in k_layers],
        "ln_f": nn.rmsnorm_init(cfg.d_model),
        "head": _dense_init(k_head, cfg.d_model, cfg.vocab, dtype,
                            1.0 / cfg.lm_head_multiplier),
    }


# -- one layer's pieces ----------------------------------------------------------

def _scaled(h, cfg: FalconH1Config, dtype):
    """h: the embedding's rows in float32."""
    return (h * cfg.embedding_multiplier).astype(dtype)


def _attn_inputs(ap, u, positions, cfg: FalconH1Config, dtype):
    """u: (S, d) normalised, at `positions` (S,). Returns q (S, H, D), k and
    v (S, H_kv, D), q and k rotated and k times its multiplier, as the pool
    holds it."""
    x = u * jnp.asarray(cfg.attention_in_multiplier, u.dtype)

    def heads(name):
        y = nn.dense(ap[name], x, dtype=dtype)
        return y.reshape(y.shape[0], -1, cfg.d_head)

    k = (heads("wk").astype(jnp.float32) * cfg.key_multiplier).astype(dtype)
    q, k = (rope(y[None], positions, cfg.rope_theta)[0]
            for y in (heads("wq").astype(dtype), k))
    return q, k, heads("wv").astype(dtype)


def _attn_output(ap, o, cfg: FalconH1Config, dtype):
    y = nn.dense(ap["wo"], o.reshape(o.shape[0], -1), dtype=dtype)
    return y * jnp.asarray(cfg.attention_out_multiplier, y.dtype)


def _ssm_inputs(sp, u, cfg: FalconH1Config, dtype):
    """`models.olmo_hybrid._lin_inputs` for this family. u: (..., d)
    normalised. Returns (x, B, C side by side BEFORE the conv (...,
    conv lanes) float32; z (..., d_ssm); dt (..., H) after its softplus,
    twice: the decay is exp(dt A) and the write dt x, so the gate's and the
    writing strength's places both hold it, and a token past a run's end,
    whose both are set to 0, neither decays nor writes)."""
    proj = nn.dense(sp["w_in"], u * jnp.asarray(cfg.ssm_in_multiplier,
                                                u.dtype),
                    dtype=dtype).astype(jnp.float32)
    # The mup_vector: a multiplier a lane, by segment; x, B and C lie side
    # by side in W_in's output as the conv takes them.
    proj = proj * np.repeat([m for _, m in cfg.mup_segments],
                            [lanes for lanes, _ in cfg.mup_segments]
                            ).astype(np.float32)
    z = proj[..., :cfg.d_ssm]
    mixed = proj[..., cfg.d_ssm:cfg.d_ssm + cfg.conv_lanes]
    dt = jax.nn.softplus(proj[..., -cfg.lin_heads:] + sp["dt_bias"])
    # A projection that several consumers read is materialised where it is
    # split. `z` is read two parts later (`models.olmo_hybrid._linear_rows`:
    # behind the state step and the chunk loop), `mixed` and dt by both of
    # those: left as slices, XLA fuses each into its consumer and, sooner
    # than keep W_in's product that long, computes the WHOLE product again
    # for a far one, up to three times a layer of a chunk tick. Behind the
    # barrier each part is written once
    # (`tests/test_kernels_tpu_compile.py` `_one_product_a_layer` counts a
    # compiled tick's products with no chip).
    mixed, z, dt = jax.lax.optimization_barrier((mixed, z, dt))
    return mixed, z, dt, dt


def _ssm_conv(sp, ext, cfg):
    """`models.olmo_hybrid._conv_heads` for this family. ext: (..., T +
    width - 1, lanes): a run's inputs behind its conv tail. The causal
    depthwise conv with its bias, SiLU, then C and B (..., T, g, N) and x
    (..., T, H, P): in the places of a delta rule's q, k and v."""
    t = ext.shape[-2] - cfg.conv_width + 1
    out = sum(sp["conv"][j] * ext[..., j:j + t, :]
              for j in range(cfg.conv_width))
    out = jax.nn.silu(out + sp["conv_bias"])
    x, b, c = jnp.split(out, (cfg.d_ssm,
                              cfg.d_ssm + cfg.n_groups * cfg.d_state),
                        axis=-1)

    def grouped(y):
        return y.reshape(y.shape[:-1] + (cfg.n_groups, cfg.d_state))

    return (grouped(c), grouped(b),
            x.reshape(x.shape[:-1] + (cfg.lin_heads, cfg.ssm_head_dim)))


def _gated_norm(sp, o, z, cfg):
    """o: (..., H, P) the heads' reads with the D x skip; z: (..., d_ssm)
    the gate's input. Gated, then normalised a GROUP: what W_out takes."""
    y = o.reshape(z.shape) * jax.nn.silu(z)
    y = y.reshape(z.shape[:-1] + (cfg.n_groups, -1))
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + cfg.ln_eps)
    return y.reshape(z.shape) * sp["norm"]["scale"]


def _ssm_output(sp, o, z, cfg: FalconH1Config, dtype):
    y = nn.dense(sp["w_out"], _gated_norm(sp, o, z, cfg), dtype=dtype)
    return y * jnp.asarray(cfg.ssm_out_multiplier, y.dtype)


def _with_skip(sp, fn):
    """`fn` (a step or a chunk of the recurrence, called as a delta rule's:
    q, k, v, g, beta, then where the state lies) as `ops.ssd` takes it (x,
    dt, A, B, C), its read plus D x."""
    a = -jnp.exp(sp["A_log"])

    def call(c, b, x, dt, _dt, *where):
        o, pool = fn(x, dt, a, b, c, *where)
        return o + sp["D"][:, None] * x, pool

    return call


def _ssm_whole_row(sp, u, cfg, dtype, inputs, output):
    """The mixer over one whole sequence from an empty state, by the
    chunked form. u: (S, d) normalised; `inputs`, `output`: a family's
    `_ssm_inputs` and `_ssm_output`."""
    mixed, z, dt, _ = inputs(sp, u, cfg, dtype)
    c, b, x = _ssm_conv(
        sp, jnp.pad(mixed, ((cfg.conv_width - 1, 0), (0, 0))), cfg)
    y, _ = ssd_chunked(x[None], dt[None], -jnp.exp(sp["A_log"]), b[None],
                       c[None], chunk=SUB_CHUNK)
    return output(sp, y[0] + sp["D"][:, None] * x, z, cfg, dtype)


def _ffn(mp, v, cfg: FalconH1Config, dtype):
    gate_m, down_m = cfg.mlp_multipliers
    gate = nn.dense(mp["gate"], v, dtype=dtype)
    gate = jax.nn.silu(gate * jnp.asarray(gate_m, gate.dtype))
    y = nn.dense(mp["proj"], gate * nn.dense(mp["up"], v, dtype=dtype),
                 dtype=dtype)
    return y * jnp.asarray(down_m, y.dtype)


def _run_layers(params, h, carry, cfg: FalconH1Config, mixers, dtype,
                branches=None):
    """`mixers(layer, block, u, carry) -> (y_att, y_ssm, carry)` over the
    layers in order, both from the same normed rows u. `branches`: a list
    that takes each layer's three writes into the stream (y_att, y_ssm,
    y_ffn)."""
    for layer, bp in enumerate(params["layers"]):
        # One norm feeds both mixers: it is put to the attention's part.
        with step_part("attn/qkv"):
            u = nn.rmsnorm(bp["ln1"], h, eps=cfg.ln_eps)
        y_att, y_ssm, carry = mixers(layer, bp, u, carry)
        with step_part("mixer/out"):
            h = (h + y_att + y_ssm).astype(dtype)
        with step_part("mlp"):
            v = nn.rmsnorm(bp["ln2"], h, eps=cfg.ln_eps)
            y_ffn = _ffn(bp["mlp"], v, cfg, dtype)
            h = (h + y_ffn).astype(dtype)
        if branches is not None:
            branches.append((y_att, y_ssm, y_ffn))
    return h, carry


def _logits(params, h, cfg: FalconH1Config, dtype):
    logits = lm_head(params, h, cfg.ln_eps, dtype)
    with step_part("head"):
        return logits * cfg.lm_head_multiplier


# -- the one-shot forward --------------------------------------------------------

def falcon_h1_apply(params, tokens, cfg: FalconH1Config, *,
                    dtype=jnp.bfloat16, branches=None):
    """Full-sequence causal forward from an empty state. tokens: (B, S)
    int32 -> logits (B, S, vocab) float32. `branches`: a list that takes
    each layer's (y_att, y_ssm, y_ffn), for the test that pins their ratio."""
    b, s = tokens.shape
    h = _scaled(nn.embedding(params["tok_embed"], tokens)
                .astype(jnp.float32), cfg, dtype)
    positions = jnp.arange(s)

    def one_row(bp, u):
        q, k, v = _attn_inputs(bp["attn"], u, positions, cfg, dtype)
        o = dot_product_attention(q[None], k[None], v[None], causal=True)[0]
        return (_attn_output(bp["attn"], o, cfg, dtype),
                _ssm_whole_row(bp["ssm"], u, cfg, dtype, _ssm_inputs,
                               _ssm_output))

    def mixers(layer, bp, u, carry):
        y_att, y_ssm = jax.vmap(lambda row: one_row(bp, row))(u)
        return y_att, y_ssm, carry

    h, _ = _run_layers(params, h, (), cfg, mixers, dtype, branches)
    return _logits(params, h, cfg, dtype)


# -- the served step: the mixed tick over the block pool and the state pool -------

def falcon_h1_step_rows_ragged(params, tokens, caches, tables, pos0, qlen,
                               cfg: FalconH1Config, *, dtype=jnp.bfloat16,
                               attn_fn=None, step_fn=ssd_step_rows,
                               chunk_fn=ssd_chunk_row, sample_slot=None,
                               held=None, max_tokens: Optional[int] = None):
    """This family's step of the mixed tick, over the tick's token list
    (`models.tick_tokens`, a token an entry).

    caches: (the block pool's K/V pair, (layers, NB, bs, H_kv*D); the state
    pool's arrays, `_linear_rows`), both `n_layers` deep and updated in
    place (donate them); tables: (the rows' block table (B, nb); the rows'
    state row (B,), the null row 0 for a free slot). EVERY layer attends
    (`PagedKV.attend` at G = n_heads / n_kv_heads) AND runs the
    recurrence over the same rows (`ssd_step`, `ssd_chunk`), both from
    the same normed input. `step_fn`, `chunk_fn`: `ops.ssd`'s
    `ssd_step_rows` and `ssd_chunk_row` or stand-ins of their signatures.

    Returns (logits, caches, rows (0, 1): the family routes no experts)."""
    from tpu_engine.ops import paged_attention as pa

    del held
    if attn_fn is None:
        attn_fn = pa.default_ragged_attention()
    (pool, state), (table, rows) = caches, tables
    w = tokens.shape[1]
    tt = tick_tokens(pos0, qlen, w, max_tokens)
    kv = tt.paged_kv(table, pool.k.shape[2], cfg.n_heads // cfg.kv_heads)
    h = tt.embed(params, tokens, jnp.float32)
    with step_part("embed"):
        h = _scaled(h, cfg, dtype)

    def mixers(layer, bp, u, carry):
        pool, state = carry
        at = cfg.pool_layer[layer]
        with step_part("attn/qkv"):
            q, k, v = _attn_inputs(bp["attn"], u, tt.logical, cfg, dtype)
        o, pool = kv.attend(attn_fn, q, k, v, pool, at)
        sp = bp["ssm"]
        with step_part("mixer/in"):
            step, chunk = _with_skip(sp, step_fn), _with_skip(sp, chunk_fn)
        y_ssm, state = _linear_rows(
            sp, u, state, at, tt.plan.start, rows, pos0, qlen, w, cfg, dtype,
            step, chunk, inputs=_ssm_inputs, output=_ssm_output,
            conv=_ssm_conv)
        with step_part("attn/out"):
            y_att = _attn_output(bp["attn"], o.astype(dtype), cfg, dtype)
        return y_att, y_ssm, (pool, state)

    h, (pool, state) = _run_layers(params, h, (tuple(pool), tuple(state)),
                                   cfg, mixers, dtype)
    return (_logits(params, tt.head_rows(h, sample_slot), cfg, dtype),
            (KVCache(*pool), state), jnp.zeros((0, 1), jnp.int32))


# -- registry ----------------------------------------------------------------------

def _lm_spec(name: str, cfg: FalconH1Config, seq_len: int) -> ModelSpec:
    return causal_lm_spec(name, cfg, seq_len, falcon_h1_init, falcon_h1_apply,
                          ragged_step=falcon_h1_step_rows_ragged)


def _cfg(**kw) -> FalconH1Config:
    return FalconH1Config(
        vocab=kw["vocab"], n_layers=kw["n_layers"], d_model=kw["d_model"],
        n_heads=kw["n_heads"], n_kv_heads=kw["n_kv_heads"],
        head_dim=kw["head_dim"], d_ff=kw["d_ff"], max_seq=kw["max_seq"],
        causal=True, norm="rmsnorm", pos="rope", mlp_act="swiglu",
        rope_theta=kw["rope_theta"], ln_eps=kw["ln_eps"],
        lin_heads=kw["ssm_heads"], ssm_head_dim=kw["ssm_head_dim"],
        d_state=kw["d_state"], n_groups=kw["n_groups"],
        conv_width=kw["conv_width"],
        embedding_multiplier=kw["embedding_multiplier"],
        attention_in_multiplier=kw["attention_in_multiplier"],
        key_multiplier=kw["key_multiplier"],
        attention_out_multiplier=kw["attention_out_multiplier"],
        ssm_in_multiplier=kw["ssm_in_multiplier"],
        ssm_multipliers=tuple(kw["ssm_multipliers"]),
        ssm_out_multiplier=kw["ssm_out_multiplier"],
        mlp_multipliers=tuple(kw["mlp_multipliers"]),
        lm_head_multiplier=kw["lm_head_multiplier"],
        param_dtype=kw["param_dtype"])


@register("falcon_h1")
def make_falcon_h1(seq_len: int = 128, vocab: int = 261120,
                   n_layers: int = 72, d_model: int = 5120,
                   n_heads: int = 20, n_kv_heads: int = 4,
                   head_dim: int = 128, d_ff: int = 21504,
                   ssm_heads: int = 32, ssm_head_dim: int = 128,
                   d_state: int = 256, n_groups: int = 2,
                   conv_width: int = 4, rope_theta: float = 1e11,
                   embedding_multiplier: float = 5.656854249492381,
                   attention_in_multiplier: float = 1.0,
                   key_multiplier: float = 0.011048543456039804,
                   attention_out_multiplier: float = 0.0375,
                   ssm_in_multiplier: float = 0.25,
                   ssm_multipliers: Tuple[float, ...] = (
                       0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                       0.3535533905932738),
                   ssm_out_multiplier: float = 0.08838834764831845,
                   mlp_multipliers: Tuple[float, float] = (
                       0.1767766952966369, 0.011160714285714284),
                   lm_head_multiplier: float = 0.0078125,
                   max_seq: int = 16384, ln_eps: float = 1e-5,
                   param_dtype: str = "bfloat16") -> ModelSpec:
    """Falcon-H1-34B's published geometry; every width a keyword."""
    return _lm_spec("falcon_h1", _cfg(**{k: v for k, v in locals().items()
                                         if k != "seq_len"}), seq_len)


@register("falcon_h1_small")
def make_falcon_h1_small(seq_len: int = 16, vocab: int = 256,
                         n_layers: int = 3, d_model: int = 40,
                         n_heads: int = 5, n_kv_heads: int = 1,
                         head_dim: int = 8, d_ff: int = 96,
                         ssm_heads: int = 4, ssm_head_dim: int = 8,
                         d_state: int = 16, n_groups: int = 2,
                         conv_width: int = 4, rope_theta: float = 1e11,
                         embedding_multiplier: float = 5.656854249492381,
                         attention_in_multiplier: float = 1.0,
                         key_multiplier: float = 0.011048543456039804,
                         attention_out_multiplier: float = 0.0375,
                         ssm_in_multiplier: float = 0.25,
                         ssm_multipliers: Tuple[float, ...] = (
                             0.3535533905932738, 0.25, 0.1767766952966369,
                             0.5, 0.3535533905932738),
                         ssm_out_multiplier: float = 0.08838834764831845,
                         mlp_multipliers: Tuple[float, float] = (
                             0.1767766952966369, 0.011160714285714284),
                         lm_head_multiplier: float = 0.0078125,
                         max_seq: int = 128, ln_eps: float = 1e-5,
                         param_dtype: str = "float32") -> ModelSpec:
    """Tiny config for tests: three parallel layers, 5 query heads over 1
    KV head of 8 lanes (the odd group), 4 SSM heads of 8 lanes in 2 groups,
    a state of 16 lanes, conv 4, the published multipliers, float32."""
    return _lm_spec("falcon_h1_small",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)
