"""Olmo-Hybrid family: three layers in four keep a fixed-size recurrent
state a row (the gated delta rule, `ops.gated_delta`), the fourth keeps
keys and values for every token (full attention, MHA), in one model.

Source of the default geometry: Olmo-Hybrid-7B
(https://huggingface.co/allenai/Olmo-Hybrid-7B, `model_type: olmo_hybrid`).
Stream h (T x d), layer l:

- block     ``h += RMS(Mixer(h))``, ``h += RMS(SwiGLU(h))`` (the Olmo 2/3
            family's reordered norm: the norm follows the branch); final
            RMSNorm; a separate LM head.
- full      (`layer_types[l] == "full_attention"`) ``q, k, v = h Wq, h Wk,
            h Wv``, `n_heads` heads of `head_dim` over as many KV heads; q
            and k RMS-normalised over ALL their lanes with a learned
            scale; NO rotary embedding (`rope_theta: null`: the recurrent
            layers carry order); causal soft-max of ``q.k / sqrt(D)``; Wo.
- linear    ``q~, k~ = h Wq, h Wk`` (`lin_heads` x `lin_key_dim`), ``v~ =
            h Wv`` (`lin_heads` x `lin_value_dim`); the three pass ONE
            causal depthwise conv of width `conv_width` (no bias), then
            SiLU; q and k L2-normalised a head, q times 1/sqrt(d_k);
            ``b = 2 sigmoid(h Wb)`` a head (the 2 is `neg_eigval`),
            ``g = -exp(A_log) softplus(h Wa + dt_bias)``, ``a = exp(g)``;
            the state S (d_v x d_k a head, float32, zero at position 0)
            follows ``S = a S + b (v - a S k) k^T``, ``o = S q``;
            ``y = RMS_head(o) * SiLU(h Wz)``; Wo.

**Two kinds of state in one row.** A full layer's K and V go to the block
pool, which holds the full layers alone (`cfg.kv_block_kinds[0]`); a
linear layer's state and the last `conv_width - 1` inputs of its conv (the
conv tail, which crosses tick boundaries) live in one row of a state pool
(`cfg.state_row_shapes`, a layer: S and the tail), `cfg.pool_layer[l]` the
layer of its kind's pool. The served step (`olmo_hybrid_step_rows_ragged`)
takes both, donated: a row that prefills runs its chunk through
`gdn_chunk` FROM the state its last chunk left, a row that decodes runs
`gdn_step`, in the same tick.

Parameter tree: `tok_embed`, `layers` (a list: the two kinds differ in
shape), `ln_f`, `head`. A block is `ln1`, `ln2` (the branches' norms),
`mlp` and `attn` {wq, wk, wv, wo, q_norm, k_norm} or `lin` {wq, wk, wv,
wz, wo, wa, wb, conv (width, lanes), A_log, dt_bias, o_norm}. Weights are
made in `param_dtype` directly, as `models.moonlight` makes them; the
branches' norms start at 1/sqrt(2 L), the rule by which the other
families draw what writes into the residual stream smaller, so the
stream keeps its size over the layers. `A_log` is the log of a number
drawn evenly from (0.02, 0.25) a head and `dt_bias` evenly from (-1, 0.5):
with ``h Wa`` of about unit spread, softplus lies in (0.1, 2.5) and the
decay a in (0.55, 1), spread over that range by head and by token.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_engine.models.moonlight import _dense_init, _normal, _swiglu_init
from tpu_engine.models.registry import ModelSpec, causal_lm_spec, register
from tpu_engine.models.tick_tokens import lm_head, tick_tokens
from tpu_engine.models.transformer import (
    TransformerConfig,
    _mlp,
    index_in_kind,
    kv_kind_config,
)
from tpu_engine.ops import nn
from tpu_engine.ops.attention import KVCache, dot_product_attention
from tpu_engine.ops.gated_delta import (
    SUB_CHUNK,
    gdn_chunk,
    gdn_chunk_row,
    gdn_step_rows,
)
from tpu_engine.utils.tracing import step_part


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig(TransformerConfig):
    """The base fields this family fixes: rmsnorm, no positions, swiglu,
    `n_heads` = `n_kv_heads` = the full layers' heads."""
    linear: Tuple[bool, ...] = ()           # True: a gated-delta layer
    lin_heads: int = 30
    lin_key_dim: int = 96
    lin_value_dim: int = 192
    conv_width: int = 4
    neg_eigval: bool = True
    param_dtype: str = "bfloat16"

    # The registry derives family and TP rule from these two; the
    # scheduler names a tick's recurrent work by the third (the kernels'
    # names in a trace).
    serving_state_family = "kv_and_state"
    tp_partition_rule = ("unshardable: a row's recurrent state and conv "
                         "tail are one state row a layer, which no shard "
                         "map over heads carries yet")
    recurrence = "gdn"

    def __post_init__(self):
        """THIS model's layers are each of one kind, and it has both (the
        `kv_and_state` family asks only that a row own a chain and a state
        row: `models.falcon_h1` keeps both in every layer)."""
        if len(self.linear) != self.n_layers:
            raise ValueError("linear needs one entry a layer")
        if not self.n_full_layers or not self.n_linear_layers:
            raise ValueError("this model has layers of both kinds")

    @property
    def n_linear_layers(self) -> int:
        return sum(self.linear)

    @property
    def n_full_layers(self) -> int:
        return self.n_layers - self.n_linear_layers

    @property
    def conv_lanes(self) -> int:
        return self.lin_heads * (2 * self.lin_key_dim + self.lin_value_dim)

    @property
    def kv_block_kinds(self) -> Tuple[TransformerConfig]:
        """What the block pool is sized by: the full layers alone."""
        return (kv_kind_config(self, self.n_full_layers),)

    @property
    def pool_layer(self) -> Tuple[int, ...]:
        """Layer l's index in the pool of its kind."""
        return index_in_kind(self.linear)

    @property
    def state_row_shapes(self) -> Tuple[Tuple[int, ...], ...]:
        """A row's state, a linear layer: S and the conv tail (float32)."""
        return ((self.lin_heads, self.lin_value_dim, self.lin_key_dim),
                (self.conv_width - 1, self.conv_lanes))


# -- parameters -----------------------------------------------------------------

def _block_init(key, cfg: OlmoHybridConfig, layer: int):
    dtype = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    branch = {"scale": jnp.full((d,), 1.0 / math.sqrt(2.0 * cfg.n_layers),
                                jnp.float32)}
    kq, kk, kv, kz, ko, ka, kb, kc, kl, kt, kf = jax.random.split(key, 11)
    block = {"ln1": branch, "ln2": dict(branch),
             "mlp": _swiglu_init(kf, d, cfg.d_ff, dtype, 1.0)}
    if not cfg.linear[layer]:
        width = cfg.n_heads * cfg.d_head
        block["attn"] = {
            "wq": _dense_init(kq, d, width, dtype),
            "wk": _dense_init(kk, d, width, dtype),
            "wv": _dense_init(kv, d, width, dtype),
            "wo": _dense_init(ko, width, d, dtype),
            "q_norm": nn.rmsnorm_init(width),
            "k_norm": nn.rmsnorm_init(width),
        }
        return block
    h, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    block["lin"] = {
        "wq": _dense_init(kq, d, h * dk, dtype),
        "wk": _dense_init(kk, d, h * dk, dtype),
        "wv": _dense_init(kv, d, h * dv, dtype),
        "wz": _dense_init(kz, d, h * dv, dtype),
        "wo": _dense_init(ko, h * dv, d, dtype),
        "wa": _dense_init(ka, d, h, dtype),
        "wb": _dense_init(kb, d, h, dtype),
        "conv": _normal(kc, (cfg.conv_width, cfg.conv_lanes),
                        cfg.conv_width, jnp.float32),
        "A_log": jnp.log(jax.random.uniform(kl, (h,), jnp.float32,
                                            0.02, 0.25)),
        "dt_bias": jax.random.uniform(kt, (h,), jnp.float32, -1.0, 0.5),
        "o_norm": nn.rmsnorm_init(dv),
    }
    return block


def olmo_hybrid_init(key, cfg: OlmoHybridConfig):
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

def _attn_inputs(ap, x, cfg: OlmoHybridConfig, dtype):
    """x: (..., d). Returns q, k, v (..., H, D); q and k normalised over
    all their lanes, as the cache holds k."""
    def heads(y):
        return y.astype(dtype).reshape(y.shape[:-1] + (-1, cfg.d_head))

    q = nn.rmsnorm(ap["q_norm"], nn.dense(ap["wq"], x, dtype=dtype),
                   eps=cfg.ln_eps)
    k = nn.rmsnorm(ap["k_norm"], nn.dense(ap["wk"], x, dtype=dtype),
                   eps=cfg.ln_eps)
    return heads(q), heads(k), heads(nn.dense(ap["wv"], x, dtype=dtype))


def _lin_inputs(lp, x, cfg: OlmoHybridConfig, dtype):
    """x: (..., d). Returns (mixed (..., conv lanes) float32: q~, k~, v~
    side by side BEFORE the conv; z (..., H*d_v); g, beta (..., H))."""
    mixed = jnp.concatenate([nn.dense(lp[w], x, dtype=dtype)
                             for w in ("wq", "wk", "wv")], axis=-1)
    z = nn.dense(lp["wz"], x, dtype=dtype)
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(
        nn.dense(lp["wa"], x, dtype=dtype) + lp["dt_bias"])
    beta = jax.nn.sigmoid(nn.dense(lp["wb"], x, dtype=dtype))
    return mixed, z, g, beta * (2.0 if cfg.neg_eigval else 1.0)


def _conv_heads(lp, ext, cfg):
    """ext: (..., T + width - 1, lanes): a run's inputs behind its conv
    tail. The causal depthwise conv, SiLU, then q and k (..., T, H, d_k),
    L2-normalised a head and q scaled, and v (..., T, H, d_v)."""
    t = ext.shape[-2] - cfg.conv_width + 1
    out = sum(lp["conv"][j] * ext[..., j:j + t, :]
              for j in range(cfg.conv_width))
    out = jax.nn.silu(out)
    h, dk = cfg.lin_heads, cfg.lin_key_dim
    q, k, v = (y.reshape(y.shape[:-1] + (h, -1))
               for y in jnp.split(out, (h * dk, 2 * h * dk), axis=-1))

    def unit(y):
        return y * jax.lax.rsqrt((y * y).sum(-1, keepdims=True) + 1e-6)

    return unit(q) / math.sqrt(dk), unit(k), v


def _lin_output(lp, o, z, cfg: OlmoHybridConfig, dtype):
    """o: (..., H, d_v) the heads' reads; z: (..., H*d_v) the gate's
    input. Normalised a head, gated, to Wo."""
    y = nn.rmsnorm(lp["o_norm"], o, eps=cfg.ln_eps)
    y = y.reshape(z.shape) * jax.nn.silu(z)
    return nn.dense(lp["wo"], y, dtype=dtype)


def _run_layers(params, h, carry, cfg: OlmoHybridConfig, mixer, dtype):
    """`mixer(layer, block, h, carry) -> (the mixer's output, carry)` over
    the layers in order (a Python loop: the layers differ in shape)."""
    for layer, bp in enumerate(params["layers"]):
        y, carry = mixer(layer, bp, h, carry)
        with step_part("mixer/out" if cfg.linear[layer] else "attn/out"):
            h = (h + nn.rmsnorm(bp["ln1"], y, eps=cfg.ln_eps)).astype(dtype)
        with step_part("mlp"):
            y = _mlp(bp["mlp"], h, dtype, cfg)
            h = (h + nn.rmsnorm(bp["ln2"], y, eps=cfg.ln_eps)).astype(dtype)
    return h, carry


def _pad_run(n: int) -> int:
    return -(-n // SUB_CHUNK) * SUB_CHUNK


# -- the one-shot forward --------------------------------------------------------

def olmo_hybrid_apply(params, tokens, cfg: OlmoHybridConfig, *,
                      dtype=jnp.bfloat16):
    """Full-sequence causal forward from an empty state. tokens: (B, S)
    int32 -> logits (B, S, vocab) float32."""
    b, s = tokens.shape
    h = nn.embedding(params["tok_embed"], tokens).astype(dtype)
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), jnp.int32)),
                              (b, s, s))
    pad = _pad_run(s) - s

    def whole_row(lp, mixed, g, beta):
        ext = jnp.pad(mixed, ((cfg.conv_width - 1, pad), (0, 0)))
        q, k, v = _conv_heads(lp, ext, cfg)
        o, _ = gdn_chunk(q, k, v, jnp.pad(g, ((0, pad), (0, 0))),
                         jnp.pad(beta, ((0, pad), (0, 0))),
                         jnp.zeros(cfg.state_row_shapes[0], jnp.float32))
        return o[:s]

    def mixer(layer, bp, x, carry):
        if not cfg.linear[layer]:
            q, k, v = _attn_inputs(bp["attn"], x, cfg, dtype)
            o = dot_product_attention(q, k, v, mask=causal)
            return nn.dense(bp["attn"]["wo"], o.reshape(b, s, -1),
                            dtype=dtype), carry
        lp = bp["lin"]
        mixed, z, g, beta = _lin_inputs(lp, x, cfg, dtype)
        o = jax.vmap(lambda m, g, bt: whole_row(lp, m, g, bt))(
            mixed, g, beta)
        return _lin_output(lp, o, z, cfg, dtype), carry

    h, _ = _run_layers(params, h, (), cfg, mixer, dtype)
    return lm_head(params, h, cfg.ln_eps, dtype)


# -- the served step: the mixed tick over the block pool and the state pool -------

def _linear_rows(lp, x, state, at: int, start, rows, pos0, qlen, width: int,
                 cfg, dtype, step_fn, chunk_fn, inputs=None, output=None,
                 conv=None):
    """A recurrent mixer over the tick's token list. x: (M, d), row b's new
    tokens at [start[b], start[b] + qlen[b]); state: the state pool's
    (S (L_lin, R, H, d_v, d_k), conv tails (L_lin, R, width - 1, lanes) or
    as many numbers a row in another shape), row b's at `rows[b]` of layer
    `at`. A row with ONE new token goes through `gdn_step_rows`, all such
    rows at once; a row with more through `gdn_chunk_row`, a row at a time,
    from the state its last tick left (zero where the row starts at
    position 0). Returns (the mixer's output (M, d), state).

    `z` is read two parts after `inputs` made it, behind the state step
    and the chunk loop: an `inputs` that SPLITS one product into the parts
    writes `z` where it splits (`models.falcon_h1._ssm_inputs` says why).

    `inputs`, `output`: this family's `_lin_inputs` and `_lin_output`, or
    another's of their signatures whose `cfg` has the same `lin_*` and
    `conv_width` fields (`models.kimi_linear`: a gate a key channel, g
    (M, H, d_k), which `step_fn` and `chunk_fn` tell by its rank).
    `conv`: this family's `_conv_heads` or another's of its signature,
    whose three results are what `step_fn` and `chunk_fn` take first and
    whose `cfg` need only state `conv_width` (`models.falcon_h1`: a conv
    with a bias over x, B and C, which come out as v, k and q)."""
    conv = conv or _conv_heads
    m = x.shape[0]
    s_pool, c_pool = state
    with step_part("mixer/in"):
        mixed, z, g, beta = (inputs or _lin_inputs)(lp, x, cfg, dtype)
    fresh = pos0 == 0

    with step_part("mixer/step"):
        # Rows that decode (or prefill a single token): one step each.
        first = jnp.minimum(start, m - 1)
        # A pool may keep a row's tail in another shape of as many numbers.
        tail_shape = (cfg.conv_width - 1, mixed.shape[-1])
        tail_old = c_pool[at, rows].reshape((-1,) + tail_shape)
        ext = jnp.concatenate(
            [jnp.where(fresh[:, None, None], 0.0, tail_old),
             mixed[first][:, None]], axis=1)
        q, k, v = conv(lp, ext, cfg)
        steps = qlen == 1
        o, s_pool = step_fn(q[:, 0], k[:, 0], v[:, 0], g[first], beta[first],
                            s_pool, at, rows, steps, fresh)
        c_pool = c_pool.at[at, rows].set(
            jnp.where(steps[:, None, None], ext[:, 1:], tail_old)
            .reshape((-1,) + c_pool.shape[2:]))
        # One padded run behind the list: a run's slice never clamps, and a
        # row that took no step writes there.
        run = _pad_run(width)
        o_all = jnp.zeros((m + run,) + o.shape[1:], jnp.float32)
        o_all = o_all.at[jnp.where(steps, first, m)].set(o)

    with step_part("mixer/chunk"):
        if width > 1:
            mixed, g, beta = (
                jnp.pad(y, ((0, run),) + ((0, 0),) * (y.ndim - 1))
                for y in (mixed, g, beta))
            chunks = qlen > 1
            order = jnp.argsort(~chunks, stable=True)

            def one_row(i, carry):
                s_pool, c_pool, o_all = carry
                b = order[i]
                off, r, n = start[b], rows[b], qlen[b]
                valid = (jnp.arange(run) < n)[:, None]

                def run_of(y):
                    return jax.lax.dynamic_slice_in_dim(y, off, run)

                old = c_pool[at, r].reshape(tail_shape)
                ext = jnp.concatenate(
                    [jnp.where(fresh[b], 0.0, old), run_of(mixed)])
                q, k, v = conv(lp, ext, cfg)
                # Past the row's last new token nothing decays and nothing
                # is written.
                g_run = run_of(g)
                o, s_pool = chunk_fn(
                    q, k, v,
                    jnp.where(valid.reshape((run,) + (1,) * (g_run.ndim - 1)),
                              g_run, 0.0),
                    jnp.where(valid, run_of(beta), 0.0), s_pool, at, r,
                    fresh[b])
                tail = jax.lax.dynamic_slice_in_dim(ext, n, cfg.conv_width - 1)
                o = jnp.where(valid[:, :, None], o, run_of(o_all))
                return (s_pool,
                        c_pool.at[at, r].set(tail.reshape(c_pool.shape[2:])),
                        jax.lax.dynamic_update_slice_in_dim(o_all, o, off, 0))

            s_pool, c_pool, o_all = jax.lax.fori_loop(
                0, chunks.sum(), one_row, (s_pool, c_pool, o_all))
    with step_part("mixer/out"):
        return ((output or _lin_output)(lp, o_all[:m], z, cfg, dtype),
                (s_pool, c_pool))


def olmo_hybrid_step_rows_ragged(params, tokens, caches, tables, pos0, qlen,
                                 cfg: OlmoHybridConfig, *,
                                 dtype=jnp.bfloat16, attn_fn=None,
                                 step_fn=gdn_step_rows,
                                 chunk_fn=gdn_chunk_row, sample_slot=None,
                                 held=None,
                                 max_tokens: Optional[int] = None):
    """This family's step of the mixed tick, over the tick's token list
    (`models.tick_tokens`, a token an entry: the list holds each row's new
    tokens side by side and nothing else).

    caches: (the block pool's K/V pair, (full layers, NB, bs, H*D); the
    state pool's arrays, `_linear_rows`), both updated in place (donate
    them); tables: (the rows' block table (B, nb); the rows' state row
    (B,), the null row 0 for a free slot). A full layer is
    `PagedKV.attend`: a row with one new token a row of a width-1 call,
    the 30 heads packed, a longer run in tall tiles of up to 128 slots,
    each a row of a second call; a step a slot wide makes the first call
    alone. A linear layer is `_linear_rows` over the same list.
    `step_fn`, `chunk_fn`: `ops.gated_delta`'s `gdn_step_rows` and
    `gdn_chunk_row` or stand-ins of their signatures (a test compiling the
    kernels for a chip that is not there).

    Returns (logits, caches, rows (0, 1): the family routes no experts)."""
    from tpu_engine.ops import paged_attention as pa

    del held
    if attn_fn is None:
        attn_fn = pa.default_ragged_attention()
    (pool, state), (table, rows) = caches, tables
    w = tokens.shape[1]
    tt = tick_tokens(pos0, qlen, w, max_tokens)
    kv = tt.paged_kv(table, pool.k.shape[2], cfg.n_heads // cfg.kv_heads)
    h = tt.embed(params, tokens, dtype)

    def mixer(layer, bp, x, carry):
        pool, state = carry
        at = cfg.pool_layer[layer]
        if cfg.linear[layer]:
            y, state = _linear_rows(bp["lin"], x, state, at, tt.plan.start,
                                    rows, pos0, qlen, w, cfg, dtype, step_fn,
                                    chunk_fn)
            return y, (pool, state)
        with step_part("attn/qkv"):
            q, k, v = _attn_inputs(bp["attn"], x, cfg, dtype)
        o, pool = kv.attend(attn_fn, q, k, v, pool, at)
        with step_part("attn/read"):
            o = o.astype(dtype).reshape(tt.n, -1)
        with step_part("attn/out"):
            return nn.dense(bp["attn"]["wo"], o, dtype=dtype), (pool, state)

    h, (pool, state) = _run_layers(params, h, (tuple(pool), tuple(state)),
                                   cfg, mixer, dtype)
    return (lm_head(params, tt.head_rows(h, sample_slot), cfg.ln_eps, dtype),
            (KVCache(*pool), state), jnp.zeros((0, 1), jnp.int32))


# -- registry ----------------------------------------------------------------------

def _lm_spec(name: str, cfg: OlmoHybridConfig, seq_len: int) -> ModelSpec:
    return causal_lm_spec(name, cfg, seq_len, olmo_hybrid_init,
                          olmo_hybrid_apply,
                          ragged_step=olmo_hybrid_step_rows_ragged)


def _cfg(**kw) -> OlmoHybridConfig:
    pattern = tuple(kw["layer_types"])
    return OlmoHybridConfig(
        vocab=kw["vocab"], n_layers=len(pattern), d_model=kw["d_model"],
        n_heads=kw["n_heads"], n_kv_heads=kw["n_heads"],
        head_dim=kw["head_dim"], d_ff=kw["d_ff"], max_seq=kw["max_seq"],
        causal=True, norm="rmsnorm", pos="none", mlp_act="swiglu",
        ln_eps=kw["ln_eps"],
        linear=tuple(t == "linear_attention" for t in pattern),
        lin_heads=kw["lin_heads"], lin_key_dim=kw["lin_key_dim"],
        lin_value_dim=kw["lin_value_dim"], conv_width=kw["conv_width"],
        neg_eigval=kw["neg_eigval"], param_dtype=kw["param_dtype"])


_PERIOD = ("linear_attention",) * 3 + ("full_attention",)


@register("olmo_hybrid")
def make_olmo_hybrid(seq_len: int = 128, vocab: int = 100352,
                     layer_types: Tuple[str, ...] = _PERIOD * 8,
                     d_model: int = 3840, n_heads: int = 30,
                     head_dim: int = 128, d_ff: int = 11008,
                     lin_heads: int = 30, lin_key_dim: int = 96,
                     lin_value_dim: int = 192, conv_width: int = 4,
                     neg_eigval: bool = True, max_seq: int = 16384,
                     ln_eps: float = 1e-6,
                     param_dtype: str = "bfloat16") -> ModelSpec:
    """Olmo-Hybrid-7B's published geometry; every width a keyword."""
    return _lm_spec("olmo_hybrid", _cfg(**{k: v for k, v in locals().items()
                                           if k != "seq_len"}), seq_len)


@register("olmo_hybrid_small")
def make_olmo_hybrid_small(seq_len: int = 16, vocab: int = 256,
                           layer_types: Tuple[str, ...] = _PERIOD * 2,
                           d_model: int = 48, n_heads: int = 3,
                           head_dim: int = 16, d_ff: int = 96,
                           lin_heads: int = 3, lin_key_dim: int = 8,
                           lin_value_dim: int = 16, conv_width: int = 4,
                           neg_eigval: bool = True, max_seq: int = 128,
                           ln_eps: float = 1e-6,
                           param_dtype: str = "float32") -> ModelSpec:
    """Tiny config for tests: two periods (L L L F x 2), 3 heads, keys of
    8 and values of 16 lanes, conv 4, MHA at head size 16, float32."""
    return _lm_spec("olmo_hybrid_small",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)
