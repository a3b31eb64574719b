"""Ouro family (LoopLM): ONE stack of layers applied several times a token,
the same weights each time, and a cache that keeps the keys and values of
every application.

Source of the default geometry: Ouro-2.6B
(https://huggingface.co/ByteDance/Ouro-2.6B, `model_type: ouro`; the
looped language model of arXiv:2510.25741). From the config's keys: L =
`num_hidden_layers` layers, all full attention, T = `total_ut_steps`
passes over them, `early_exit_threshold`. `x` a token's hidden state, H
heads = H KV heads of D lanes:

- embed     ``x = E[token]``.
- a pass    t in 0..T-1 over the SAME layers, l = 0..L-1:
            ``a = Attn_l(RMS1_l(x))``, ``x += RMS2_l(a)``;
            ``m = SwiGLU_l(RMS3_l(x))``, ``x += RMS4_l(m)``: four learned
            RMSNorm scales a layer, two before the branches and two ON the
            branches' outputs (**assumed**: the sandwich norm of the
            published model, which no key names; the alternative is the
            llama block with two norms).
- Attn      ``q, k, v = x Wq, x Wk, x Wv`` (H x D each, no bias),
            rotate-half rope over all D lanes by position, scores /
            sqrt(D), causal. **In pass t the keys and values are those of
            pass t**: the query of pass t at position i attends the K and V
            that pass t of layer l made at positions 0..i. The cache so
            has T x L planes, plane ``t * L + l`` (**assumed**: the
            published per-pass cache; the paper also describes sharing the
            last pass's cache at decode, which is not done here).
- between   after EVERY pass ``x = RMS_f(x)``, the model's final norm; the
            normed stream is what pass t + 1 starts from (**assumed**);
            ``h_t = x``; the exit gate ``lam_t = sigmoid(h_t . w_g + b_g)``.
- exit      ``p_t = lam_t prod_{j<t}(1 - lam_j)`` for t < T - 1, ``p_{T-1}``
            the rest; a token exits at the first t whose cumulative
            ``sum_{j<=t} p_j >= early_exit_threshold``, else at T - 1;
            ``logits = h_exit W_head`` (h_exit is normed already). At the
            published threshold 1 every token exits at T - 1 and the gate
            decides nothing. All T passes always run and all T x L planes
            are always written, whatever the threshold: a later token
            attends every plane (**assumed**, as the published forward).

**The exit rule is static where it can be.** `exit_threshold` >= 1 compiles
no gate op at all: the head reads the last pass's stream. Below 1 the step
keeps, a sampled row, the T streams `h_t` (T x d numbers) and the head reads
the one the rule selects.

**A pool deeper than the model.** The block pool is sized by what the model
STATES (`cfg.kv_block_kinds[0]`: T x L planes), apart from the depth of its
weights (L): a block is `block_size` tokens of all T x L planes, and
everything that goes by block id (tables, admission, release, the radix
tree) never learns the word "pass". A block's planes depend on the tokens
up to its end alone, so a prefix hit is sound.

**The step is compiled once a layer.** `ouro_step_rows_ragged` stacks the
L layers on a leading axis and scans them inside a scan over the T passes,
the pool in the carry of both: the lowered program holds ONE layer body
whatever L and T are (a Python loop would hold T x L of them), and no
plane of the pool is sliced out or stacked back
(`models.transformer._scan_layers_paged` does the same for one pass).

Parameter tree: `tok_embed`, `blocks` (every leaf stacked (L, ...): `ln1`,
`attn` {wq, wk, wv, wo}, `ln1_out`, `ln2`, `mlp` {gate, up, proj},
`ln2_out`), `ln_f`, `gate` (d -> 1), `head`. Weights are made in
`param_dtype` directly, as `models.moonlight` makes them.

**The stream is float32.** The residual stream is kept in float32 through
both scans; a branch casts what it multiplies to `dtype` and its norms are
float32 as everywhere. T x 2 L = 384 additions into a bfloat16 stream would
round it 384 times (8 bits each), where another family's 10-70 do; the
stream is (tokens, d) numbers, nothing beside a layer's weights.

**The draw.** The branch-output norms set each branch's size: the SwiGLU's
scale starts at 1 / sqrt(2 L), so the branch writes between two final norms
take the stream from unit size to about sqrt(1.5) (with unit scales the
last layers ARE the stream, and with the same weights four times over a
pass repeats the one before it). **The attention branch's starts four times
smaller**, `_ATTN_WRITE` / sqrt(2 L): T x L = 192 applications of attention
at the SwiGLU's size mix every position's stream into one vector, and every
prompt then decodes ONE token (measured on the chip at the published
widths, PERF.md section 6, PR 58: 1, 1, 3 and 1 distinct tokens of 64 a
row; at a quarter 64 of 64, and a reference whose pass t reads pass 0's
keys still agrees with a quarter of the served tokens only, so attention
still decides). `wq` and `wk` are drawn 2 wider each: scores q.k / sqrt(D)
of spread ~4, at which a soft-max over hundreds of tokens attends a few of
them (`models.falcon_h1` has the lesson: at unit spread attention averages
the context and every prompt decodes alike).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_engine.models.moonlight import _dense_init, _swiglu_init
from tpu_engine.models.registry import ModelSpec, causal_lm_spec, register
from tpu_engine.models.tick_tokens import tick_tokens
from tpu_engine.models.transformer import (
    TransformerConfig,
    _mlp,
    kv_kind_config,
)
from tpu_engine.ops import nn
from tpu_engine.ops.attention import KVCache, dot_product_attention, rope
from tpu_engine.utils.tracing import step_part

# The spread of the scores q.k / sqrt(D) as drawn, and the attention
# branch's size beside the SwiGLU's (module docstring).
_SCORE_SPREAD = 4.0
_ATTN_WRITE = 0.25


@dataclasses.dataclass(frozen=True)
class OuroConfig(TransformerConfig):
    """The base fields this family fixes: rmsnorm, rope, swiglu, MHA
    (`n_kv_heads` = `n_heads`). `n_layers` is the depth of the WEIGHTS;
    the cache is `kv_planes` deep."""
    ut_steps: int = 4
    exit_threshold: float = 1.0
    param_dtype: str = "bfloat16"

    # The registry derives family and TP rule from these two.
    serving_state_family = "kv_looped"
    tp_partition_rule = ("unshardable: the looped step scans one stacked "
                         "set of layers over a pool of ut_steps x n_layers "
                         "planes and its own read is run a chip whole")

    def __post_init__(self):
        if self.ut_steps < 1:
            raise ValueError("ut_steps needs at least one pass")

    @property
    def kv_planes(self) -> int:
        """Planes of the cache: one a (pass, layer)."""
        return self.ut_steps * self.n_layers

    @property
    def kv_block_kinds(self) -> Tuple[TransformerConfig]:
        """What the block pool is sized by: a plane a (pass, layer)."""
        return (kv_kind_config(self, self.kv_planes),)


# -- parameters -----------------------------------------------------------------

def _layer_init(key, cfg: OuroConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    d, lanes = cfg.d_model, cfg.n_heads * cfg.d_head
    kq, kk, kv, ko, kf = jax.random.split(key, 5)
    wide = math.sqrt(_SCORE_SPREAD)
    small = jnp.full((d,), 1.0 / math.sqrt(2.0 * cfg.n_layers), jnp.float32)
    return {
        "ln1": nn.rmsnorm_init(d),
        "attn": {"wq": _dense_init(kq, d, lanes, dtype, wide),
                 "wk": _dense_init(kk, d, lanes, dtype, wide),
                 "wv": _dense_init(kv, d, lanes, dtype),
                 "wo": _dense_init(ko, lanes, d, dtype)},
        "ln1_out": {"scale": _ATTN_WRITE * small},
        "ln2": nn.rmsnorm_init(d),
        "mlp": _swiglu_init(kf, d, cfg.d_ff, dtype, 1.0),
        "ln2_out": {"scale": small},
    }


def ouro_init(key, cfg: OuroConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    k_tok, k_head, k_gate, k_layers = jax.random.split(key, 4)
    return {
        "tok_embed": {"table": jax.random.normal(
            k_tok, (cfg.vocab, cfg.d_model), dtype)},
        # A layer at a time: the generator's temporaries are one layer's.
        "blocks": jax.lax.map(lambda k: _layer_init(k, cfg),
                              jax.random.split(k_layers, cfg.n_layers)),
        "ln_f": nn.rmsnorm_init(cfg.d_model),
        "gate": _dense_init(k_gate, cfg.d_model, 1, jnp.float32),
        "head": _dense_init(k_head, cfg.d_model, cfg.vocab, dtype),
    }


# -- one layer, one pass ---------------------------------------------------------

def _layer(bp, h, carry, plane, attend, cfg: OuroConfig, dtype):
    """One application of one layer. h: (..., S, d) float32;
    `attend(plane, q, k, v, carry) -> (the heads' outputs, carry)`, q, k, v
    (..., S, H, D) before the rope. Returns (h, carry)."""
    dh = cfg.d_head
    with step_part("attn/qkv"):
        x = nn.rmsnorm(bp["ln1"], h, eps=cfg.ln_eps)

        def heads(name):
            y = nn.dense(bp["attn"][name], x, dtype=dtype)
            return y.reshape(y.shape[:-1] + (-1, dh)).astype(dtype)

        q, k, v = heads("wq"), heads("wk"), heads("wv")
    o, carry = attend(plane, q, k, v, carry)
    with step_part("attn/out"):
        a = nn.dense(bp["attn"]["wo"],
                     o.astype(dtype).reshape(o.shape[:-2] + (-1,)),
                     dtype=dtype)
        h = h + nn.rmsnorm(bp["ln1_out"], a, eps=cfg.ln_eps)
    with step_part("mlp"):
        m = _mlp(bp["mlp"], nn.rmsnorm(bp["ln2"], h, eps=cfg.ln_eps), dtype,
                 cfg)
        return h + nn.rmsnorm(bp["ln2_out"], m, eps=cfg.ln_eps), carry


def _passes(params, h, carry, cfg: OuroConfig, attend, dtype, kept=None):
    """The T passes over the stacked layers, as two nested scans with
    `carry` (the pool) in both: ONE layer body in the program. `attend`:
    `_layer`'s, `plane` = t * L + l traced. After each pass the final
    norm. Returns (h after the last pass, carry, the T streams `kept(h)`
    stacked, or None where `kept` is)."""
    n = cfg.n_layers

    def layer(state, xs):
        bp, plane = xs
        return _layer(bp, *state, plane, attend, cfg, dtype), None

    def one_pass(state, t):
        planes = t * n + jnp.arange(n, dtype=jnp.int32)
        (h, carry), _ = jax.lax.scan(layer, state, (params["blocks"], planes))
        with step_part("head"):
            h = nn.rmsnorm(params["ln_f"], h, eps=cfg.ln_eps)
        return (h, carry), (None if kept is None else kept(h))

    (h, carry), streams = jax.lax.scan(
        one_pass, (h, carry), jnp.arange(cfg.ut_steps, dtype=jnp.int32))
    return h, carry, streams


def exit_pass(gate, streams, threshold: float):
    """The pass each token exits at. streams: (T, ..., d) the normed
    streams h_t; returns (...,) int32: the first t whose cumulative exit
    probability reaches `threshold`, else T - 1 (module docstring)."""
    steps = streams.shape[0]
    lam = jax.nn.sigmoid(nn.dense(gate, streams.astype(jnp.float32))[..., 0])
    stay = jnp.cumprod(1.0 - lam, axis=0)                 # prod_{j<=t}
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    p = lam * before
    # The last pass takes the rest: the cumulative sum ends at 1.
    cum = jnp.cumsum(p.at[-1].set(before[-1]), axis=0)
    reached = cum >= threshold
    return jnp.where(reached.any(0), jnp.argmax(reached, axis=0),
                     steps - 1).astype(jnp.int32)


def _logits(params, h, streams, cfg: OuroConfig, dtype):
    """h: (..., d) the last pass's normed stream; streams: (T, ..., d) or
    None (`exit_threshold` >= 1: no gate op is traced). The final norm is
    `_passes`'s, after every pass: `models.tick_tokens.lm_head` would
    norm the last stream twice."""
    if streams is not None:
        at = exit_pass(params["gate"], streams, cfg.exit_threshold)
        h = jnp.take_along_axis(streams, at[None, ..., None], axis=0)[0]
    return nn.dense(params["head"], h, dtype=dtype).astype(jnp.float32)


def _gated(cfg: OuroConfig) -> bool:
    return cfg.exit_threshold < 1.0


# -- the one-shot forward --------------------------------------------------------

def ouro_apply(params, tokens, cfg: OuroConfig, *, dtype=jnp.bfloat16):
    """Full-sequence causal forward, every pass attending its own K and V.
    tokens: (B, S) int32 -> logits (B, S, vocab) float32."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    causal = jnp.broadcast_to(
        (jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]).astype(jnp.int32),
        (b, s, s))
    h = nn.embedding(params["tok_embed"], tokens).astype(jnp.float32)

    def attend(plane, q, k, v, carry):
        del plane
        q, k = (rope(x, positions, cfg.rope_theta) for x in (q, k))
        return dot_product_attention(q, k, v, mask=causal), carry

    h, _, streams = _passes(params, h, (), cfg, attend, dtype,
                            kept=(lambda x: x) if _gated(cfg) else None)
    return _logits(params, h, streams, cfg, dtype)


# -- the served step: the mixed tick over the pool of T x L planes ----------------

def ouro_step_rows_ragged(params, tokens, caches, tables, pos0, qlen,
                          cfg: OuroConfig, *, dtype=jnp.bfloat16,
                          attn_fn=None, sample_slot=None, held=None,
                          max_tokens: Optional[int] = None):
    """This family's step of the mixed tick, over the tick's token list
    (`models.tick_tokens`, a token an entry).

    caches: the block pool's K/V pair, (T x L planes, NB, bs, H*D),
    updated in place (donate it); tables: (B, nb). Pass t of layer l is
    `PagedKV.attend` at plane t * L + l (G = 1: a row with one new token
    a row of a width-1 call, the heads packed, a longer run in tall tiles
    of up to 128 slots). The plane index is TRACED: the layers are
    scanned inside a scan over the passes with the pool in both carries
    (module docstring), so the program holds one layer body.

    Returns (logits, caches, rows (0, 1): the family routes no experts)."""
    from tpu_engine.ops import paged_attention as pa

    del held
    if attn_fn is None:
        attn_fn = pa.default_ragged_attention()
    tt = tick_tokens(pos0, qlen, tokens.shape[1], max_tokens)
    kv = tt.paged_kv(tables, caches.k.shape[2], 1)
    h = tt.embed(params, tokens, jnp.float32)

    def attend(plane, q, k, v, pool):
        with step_part("attn/qkv"):
            q, k = (rope(x[None], tt.logical[None], cfg.rope_theta)[0]
                    for x in (q, k))
        return kv.attend(attn_fn, q, k, v, pool, plane)

    def pick(x):
        return tt.head_rows(x, sample_slot)

    h, pool, streams = _passes(params, h, tuple(caches), cfg, attend, dtype,
                               kept=pick if _gated(cfg) else None)
    h = pick(h)
    with step_part("head"):
        return (_logits(params, h, streams, cfg, dtype), KVCache(*pool),
                jnp.zeros((0, 1), jnp.int32))


# -- registry ----------------------------------------------------------------------

def _lm_spec(name: str, cfg: OuroConfig, seq_len: int) -> ModelSpec:
    return causal_lm_spec(name, cfg, seq_len, ouro_init, ouro_apply,
                          ragged_step=ouro_step_rows_ragged,
                          passes=cfg.ut_steps)


def _cfg(**kw) -> OuroConfig:
    return OuroConfig(
        vocab=kw["vocab"], n_layers=kw["n_layers"], d_model=kw["d_model"],
        n_heads=kw["n_heads"], n_kv_heads=kw["n_heads"],
        head_dim=kw["head_dim"], d_ff=kw["d_ff"], max_seq=kw["max_seq"],
        causal=True, norm="rmsnorm", pos="rope", mlp_act="swiglu",
        rope_theta=kw["rope_theta"], ln_eps=kw["ln_eps"],
        ut_steps=kw["ut_steps"], exit_threshold=kw["exit_threshold"],
        param_dtype=kw["param_dtype"])


@register("ouro")
def make_ouro(seq_len: int = 128, vocab: int = 49152, n_layers: int = 48,
              d_model: int = 2048, n_heads: int = 16, head_dim: int = 128,
              d_ff: int = 5632, ut_steps: int = 4,
              exit_threshold: float = 1.0, rope_theta: float = 1e6,
              max_seq: int = 65536, ln_eps: float = 1e-6,
              param_dtype: str = "bfloat16") -> ModelSpec:
    """Ouro-2.6B's published geometry; every width a keyword."""
    return _lm_spec("ouro", _cfg(**{k: v for k, v in locals().items()
                                    if k != "seq_len"}), seq_len)


@register("ouro-small-test")
def make_ouro_small(seq_len: int = 16, vocab: int = 256, n_layers: int = 3,
                    d_model: int = 64, n_heads: int = 4, head_dim: int = 16,
                    d_ff: int = 128, ut_steps: int = 3,
                    exit_threshold: float = 1.0, rope_theta: float = 1e6,
                    max_seq: int = 128, ln_eps: float = 1e-6,
                    param_dtype: str = "float32") -> ModelSpec:
    """Tiny config for tests: 3 layers x 3 passes (9 planes), 4 heads of
    16 lanes, float32."""
    return _lm_spec("ouro-small-test",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)
