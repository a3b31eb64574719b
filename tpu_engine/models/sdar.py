"""SDAR family: a Qwen3-MoE body that generates by diffusion over blocks.

Source of the default geometry: SDAR-30B-A3B-Chat
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat, `model_type: sdar_moe`).
`x` a token's hidden state, H query heads over H_kv KV heads of D lanes,
L = `block_length`, blk(p) = p // L:

- block     ``h = x + Attn(RMS(x))``, ``y = h + MoE(RMS(h))``; final
            RMSNorm; ``logits = y W_head``, a separate (untied) head.
            Every layer is an expert layer (`mlp_only_layers` [],
            `decoder_sparse_step` 1): none dense, no shared expert.
- Attn      ``q = x Wq`` (H heads), ``k = x Wk``, ``v = x Wv`` (H_kv); q
            and k RMS-normalised a head over its D lanes with a learned
            scale (ASSUMED: the Qwen3 convention of the family this
            config's keys come from; no key names it); rotate-half RoPE
            over all D lanes, `rope_theta`, no scaling; scores / sqrt(D);
            **mask: the query at position i sees the key at position j iff
            blk(j) <= blk(i)** — whole earlier blocks and every position
            of its own block, both directions; no bias, no window.
- MoE       ``p = softmax(x Wr)`` over `n_routed` in float32, the top
            `top_k`, weights ``p_e / sum_chosen p`` (`norm_topk_prob`);
            ``sum_e w_e W_down,e (silu(x W_gate,e) * x W_up,e)``. No
            selection bias, no scaling factor, no drops
            (`ops.moe.softmax_topk_route`, `ops.moe.routed_experts`).
- logits    ASSUMED: the logits at position i are the distribution of the
            token AT position i (no shift), as SDAR's published
            `generate.py` reads them (``x0 = argmax(logits)``,
            ``cur_x[transfer] = x0[transfer]``). Alternative: the shifted
            read of a model adapted from an autoregressive one.

**Generation** (SDAR's published `block_diffusion_generate`; L and the
S = `denoising_steps` passes a block are ASSUMED from its README). For a
prompt of P tokens the P // L blocks wholly inside it are prefilled under
the mask above and their K and V stored. Each later block covers positions
[bL, (b + 1)L): it starts as the prompt's tail there (if any), MASK
elsewhere. A DENOISE pass runs the block's L tokens (`mask_token_id` where
masked) against the stored K and V of positions < bL and against each
other; at each still-masked position ``x0 = argmax``, ``c =
softmax(logits)[x0]`` in float32, and n = L / S positions are revealed by
`reveal`: `sequential` (the n leftmost masked), `low_confidence_static`
(the n masked of largest c), `low_confidence_dynamic` (every masked
position with c > `confidence_threshold` if there are at least n, else the
n of largest c). When none is masked ONE commit pass runs the block's final
tokens and stores their K and V. The row ends at the block in which EOS or
a stop token was revealed, or that reaches `max_new_tokens`.

**Served** by `runtime.scheduler`'s mixed tick as rows whose step is a
RUN of L tokens (`ModelSpec.block_decode`): `sdar_step_rows_ragged` below
runs over the tick's tokens in tiles of L slots, writes every pass's K and
V into the row's blocks before the read (a denoise pass's are overwritten
by the next pass, the commit's stay) and reads them back under the block
mask (`ops.paged_attention`, `mask_block`): runs of up to L tokens a row a
tile of a call L slots wide, longer prompt chunks in tall tiles. Which
positions a pass reveals is the scheduler's step (`runtime.generator`).

**The draw** is the other families' (unit-variance embedding, every matrix
N(0, 1/fan_in), what writes into the stream 1/sqrt(2 L) smaller) with one
thing more: the scores q.k / sqrt(D) are drawn with a spread of
`_SCORE_SPREAD` = 4 and not 1 (the learned q and k norm scales are 2 each,
not 1). Every MASKED position enters the model as the same vector, so what
tells two of them apart comes through attention alone (their rotations,
and what they then see). Scores of unit spread average a row's values over
about n / e of its n tokens: the branch writes 1/sqrt(n / e) of a value's
size, every masked position leaves with nearly the same stream, and a
served sample is one token repeated, against which any reference agrees
(`models.falcon_h1` has the same lesson for another reason). At a spread of
4 a soft-max rests on a handful of tokens, as a trained head's does. For
the same reason the MASK token's embedding row is drawn `_MASK_EMBED_GAIN`
= 1/4 the size of the others: at full size it is the one vector every
masked position shares, and the stream at such a position should be what
the layers wrote there, not that constant (measured at the test size:
1-6 distinct tokens in 40 with a full-size row, 20-40 with this one).

Parameter tree: `tok_embed`, `layers` (a list), `ln_f`, `head`. A block is
`ln1`, `attn` {wq, wk, wv, wo, q_norm, k_norm}, `ln2`, `mlp` {router
{kernel: float32}, experts {gate_up (E, d, 2f), down (E, f, d)}}. Weights
are made in `param_dtype` directly, as `models.moonlight` makes them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpu_engine.models.laguna import _bank, _rope
from tpu_engine.models.moonlight import _dense_init, _normal, _residual_gain
from tpu_engine.models.registry import (
    BlockDecode,
    ModelSpec,
    causal_lm_spec,
    register,
)
from tpu_engine.models.tick_tokens import lm_head, tick_tokens
from tpu_engine.models.transformer import TransformerConfig
from tpu_engine.ops import nn
from tpu_engine.ops.attention import KVCache, dot_product_attention
from tpu_engine.ops.moe import routed_experts, softmax_topk_route
from tpu_engine.utils.tracing import step_part

# The spread of the scores q.k / sqrt(D) as drawn (module docstring).
_SCORE_SPREAD = 4.0

# The MASK token's embedding row is drawn this much smaller (module
# docstring).
_MASK_EMBED_GAIN = 0.25

REVEAL_RULES = ("sequential", "low_confidence_static",
                "low_confidence_dynamic")


@dataclasses.dataclass(frozen=True)
class SdarConfig(TransformerConfig):
    """The base fields this family fixes: rmsnorm, rope, swiglu experts.
    `d_ff` is unused (no dense layer)."""
    d_ff_expert: int = 768
    n_routed: int = 128
    top_k: int = 8
    block_length: int = 4
    denoising_steps: int = 4
    mask_token_id: int = 151669
    reveal: str = "sequential"
    confidence_threshold: float = 0.9
    param_dtype: str = "bfloat16"

    # The registry derives family and TP rule from these two.
    serving_state_family = "kv_block_decode"
    tp_partition_rule = ("unshardable: the expert banks and the run's "
                         "block, carried on the device from pass to pass, "
                         "have no shard map yet")

    def __post_init__(self):
        if self.block_length % self.denoising_steps:
            raise ValueError(
                f"denoising_steps={self.denoising_steps} must divide "
                f"block_length={self.block_length}")
        if self.reveal not in REVEAL_RULES:
            raise ValueError(f"reveal={self.reveal!r} is none of "
                             f"{REVEAL_RULES}")
        if not 0 <= self.mask_token_id < self.vocab:
            raise ValueError(f"mask_token_id={self.mask_token_id} is no "
                             f"row of a vocabulary of {self.vocab}")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers

    @property
    def n_dense_layers(self) -> int:
        return 0

    @property
    def block_decode(self) -> BlockDecode:
        return BlockDecode(self.block_length, self.mask_token_id,
                           self.block_length // self.denoising_steps,
                           self.reveal, self.confidence_threshold)


def _inv_freq(cfg: SdarConfig):
    d = cfg.d_head
    return (1.0 / cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64)
                                     / d)).astype(np.float32)


# -- parameters -----------------------------------------------------------------

def _norm_scale(dim: int, value: float):
    return {"scale": jnp.full((dim,), value, jnp.float32)}


def _block_init(key, cfg: SdarConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    d, dh, f = cfg.d_model, cfg.d_head, cfg.d_ff_expert
    h, h_kv, e = cfg.n_heads, cfg.kv_heads, cfg.n_routed
    out_gain = _residual_gain(cfg)
    kq, kk, kv, ko, kr, kgu, kdn = jax.random.split(key, 7)
    return {
        "ln1": nn.rmsnorm_init(d),
        "attn": {
            "wq": _dense_init(kq, d, h * dh, dtype),
            "wk": _dense_init(kk, d, h_kv * dh, dtype),
            "wv": _dense_init(kv, d, h_kv * dh, dtype),
            "wo": _dense_init(ko, h * dh, d, dtype, out_gain),
            "q_norm": _norm_scale(dh, math.sqrt(_SCORE_SPREAD)),
            "k_norm": _norm_scale(dh, math.sqrt(_SCORE_SPREAD)),
        },
        "ln2": nn.rmsnorm_init(d),
        # Unit-variance router logits, as models.moonlight draws them.
        "mlp": {"router": {"kernel": _normal(kr, (d, e), d, jnp.float32)},
                "experts": {"gate_up": _bank(kgu, (e, d, 2 * f), d, dtype),
                            "down": _bank(kdn, (e, f, d),
                                          f / out_gain ** 2, dtype)}},
    }


def sdar_init(key, cfg: SdarConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    k_tok, k_head, *k_layers = jax.random.split(key, 2 + cfg.n_layers)
    table = jax.random.normal(k_tok, (cfg.vocab, cfg.d_model), dtype)
    return {
        "tok_embed": {"table": table.at[cfg.mask_token_id].multiply(
            jnp.asarray(_MASK_EMBED_GAIN, dtype))},
        "layers": [_block_init(k, cfg) for k in k_layers],
        "ln_f": nn.rmsnorm_init(cfg.d_model),
        "head": _dense_init(k_head, cfg.d_model, cfg.vocab, dtype),
    }


# -- one layer's pieces ----------------------------------------------------------

def _attn_inputs(ap, x, positions, cfg: SdarConfig, dtype):
    """x: (B, S, d) normalised. Returns q (B, S, H, D), k and v
    (B, S, H_kv, D): q and k normalised a head and rotated, as the pool
    holds k."""
    b, s, _ = x.shape
    dh = cfg.d_head

    def heads(name):
        return nn.dense(ap[name], x, dtype=dtype).reshape(b, s, -1, dh)

    inv = _inv_freq(cfg)
    q = nn.rmsnorm(ap["q_norm"], heads("wq"), eps=cfg.ln_eps).astype(dtype)
    k = nn.rmsnorm(ap["k_norm"], heads("wk"), eps=cfg.ln_eps).astype(dtype)
    return (_rope(q, positions, inv, 1.0), _rope(k, positions, inv, 1.0),
            heads("wv").astype(dtype))


def _moe_ffn(mp, x, valid, cfg: SdarConfig, dtype, max_tokens):
    """x: (B, S, d) normalised; valid: (B, S). Returns (y, rows
    (n_routed,): the rows each expert took)."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    experts, weights = softmax_topk_route(flat, mp["router"], cfg.top_k)
    y, rows = routed_experts(
        flat, valid.reshape(-1), experts, weights, mp["experts"],
        first_group=0, n_experts=cfg.n_routed, max_tokens=max_tokens,
        dtype=dtype)
    return y.reshape(b, s, d), rows


def _run_layers(params, h, carry, cfg: SdarConfig, attend, valid, dtype,
                max_tokens):
    """`attend(layer, ap, x, carry) -> (heads' outputs, carry)` over the
    layers in order. Returns (h, carry, rows (L, n_routed))."""
    rows = []
    for layer, bp in enumerate(params["layers"]):
        with step_part("attn/qkv"):
            x = nn.rmsnorm(bp["ln1"], h, eps=cfg.ln_eps)
        o, carry = attend(layer, bp["attn"], x, carry)
        with step_part("attn/out"):
            o = nn.dense(bp["attn"]["wo"], o.reshape(o.shape[:2] + (-1,)),
                         dtype=dtype)
            h = (h + o).astype(dtype)
        with step_part("moe/route"):
            x = nn.rmsnorm(bp["ln2"], h, eps=cfg.ln_eps)
        y, taken = _moe_ffn(bp["mlp"], x, valid, cfg, dtype, max_tokens)
        rows.append(taken)
        with step_part("moe/experts"):
            h = (h + y).astype(dtype)
    return h, carry, jnp.stack(rows)


# -- the one-shot forward --------------------------------------------------------

def sdar_apply(params, tokens, cfg: SdarConfig, *, dtype=jnp.bfloat16):
    """Full-sequence forward under the block-causal mask: what the prefill
    and the commit passes must equal. tokens: (B, S) int32 -> logits
    (B, S, vocab) float32."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = nn.embedding(params["tok_embed"], tokens).astype(dtype)
    blk = jnp.arange(s) // cfg.block_length
    mask = jnp.broadcast_to((blk[None, :] <= blk[:, None])
                            .astype(jnp.int32), (b, s, s))

    def attend(layer, ap, x, carry):
        q, k, v = _attn_inputs(ap, x, positions, cfg, dtype)
        return dot_product_attention(q, k, v, mask=mask), carry

    h, _, _ = _run_layers(params, h, (), cfg, attend,
                          jnp.ones((b, s), bool), dtype, None)
    return lm_head(params, h, cfg.ln_eps, dtype)


# -- the served step: runs of L tokens and prompt chunks over the pool ------------

def sdar_step_rows_ragged(params, tokens, caches, tables, pos0, qlen,
                          cfg: SdarConfig, *, dtype=jnp.bfloat16,
                          attn_fn=None, sample_slot=None, held=None,
                          max_tokens: Optional[int] = None):
    """This family's step of the mixed tick: a generating row feeds its
    block of L = `cfg.block_length` tokens (every pass of it), a
    prefilling row a prompt chunk. `qlen` is a multiple of L and `pos0`
    too (the scheduler cuts chunks so): the step runs over the tick's
    token list (`models.tick_tokens`) in tiles of L slots with none part
    full, so the list needs no tile a row beside the budget's.

    caches: the pool's K/V pair, (layers, NB, bs, H_kv*D), updated in
    place (donate it); tables: (B, nb) by logical column // bs. A run
    reads its own block back through the pool under the block mask:
    `PagedKV.attend` with `mask_block` L, a run of up to L tokens a row of
    a call L slots wide (L x G query rows a KV head, the heads packed), a
    longer chunk in tall tiles.

    `sample_slot` (B, n): the head's rows side by side, logits (B * n,
    vocab) (`TickTokens.head_rows`).
    Returns (logits, caches, rows (layers, n_routed) int32: the rows each
    expert took)."""
    from tpu_engine.ops import paged_attention as pa

    del held                    # every expert is held
    if attn_fn is None:
        attn_fn = pa.default_ragged_attention()
    run = cfg.block_length
    b, w = tokens.shape
    if w % run:
        raise ValueError(f"a step {w} slots wide holds no whole blocks of "
                         f"{run}")
    # No tile is part full: the bound needs no tile a row beside.
    n_tiles = b * (w // run)
    if max_tokens is not None:
        n_tiles = min(n_tiles, -(-max_tokens // run))
    tt = tick_tokens(pos0, qlen, w, max_tokens, per_tile=run,
                     n_tiles=n_tiles)
    kv = tt.paged_kv(tables, caches.k.shape[2], cfg.n_heads // cfg.kv_heads,
                     run_slots=run)
    h = tt.embed(params, tokens, dtype)

    def attend(layer, ap, x, pool):
        with step_part("attn/qkv"):
            q, k, v = _attn_inputs(ap, x, tt.logical, cfg, dtype)
        return kv.attend(attn_fn, q, k, v, pool, layer, mask_block=run)

    h, pool, rows = _run_layers(params, h, tuple(caches), cfg, attend,
                                tt.valid, dtype, max_tokens)
    return (lm_head(params, tt.head_rows(h, sample_slot), cfg.ln_eps, dtype),
            KVCache(*pool), rows)


# -- registry ----------------------------------------------------------------------

def _lm_spec(name: str, cfg: SdarConfig, seq_len: int) -> ModelSpec:
    return causal_lm_spec(name, cfg, seq_len, sdar_init, sdar_apply,
                          ragged_step=sdar_step_rows_ragged,
                          block_decode=cfg.block_decode)


def _cfg(**kw) -> SdarConfig:
    return SdarConfig(
        vocab=kw["vocab"], n_layers=kw["n_layers"], d_model=kw["d_model"],
        n_heads=kw["n_heads"], d_ff=0, max_seq=kw["max_seq"], causal=True,
        norm="rmsnorm", pos="rope", mlp_act="swiglu",
        n_kv_heads=kw["n_kv_heads"], head_dim=kw["head_dim"],
        rope_theta=kw["rope_theta"], ln_eps=kw["ln_eps"],
        d_ff_expert=kw["d_ff_expert"], n_routed=kw["n_experts"],
        top_k=kw["top_k"], block_length=kw["block_length"],
        denoising_steps=kw["denoising_steps"],
        mask_token_id=kw["mask_token_id"], reveal=kw["reveal"],
        confidence_threshold=kw["confidence_threshold"],
        param_dtype=kw["param_dtype"])


@register("sdar")
def make_sdar(seq_len: int = 128, vocab: int = 151936, n_layers: int = 48,
              d_model: int = 2048, n_heads: int = 32, n_kv_heads: int = 4,
              head_dim: int = 128, d_ff_expert: int = 768,
              n_experts: int = 128, top_k: int = 8,
              rope_theta: float = 1000000.0, max_seq: int = 32768,
              ln_eps: float = 1e-6, block_length: int = 4,
              denoising_steps: int = 4, mask_token_id: int = 151669,
              reveal: str = "low_confidence_dynamic",
              confidence_threshold: float = 0.9,
              param_dtype: str = "bfloat16") -> ModelSpec:
    """SDAR-30B-A3B-Chat's published geometry; every width a keyword."""
    return _lm_spec("sdar", _cfg(**{k: v for k, v in locals().items()
                                    if k != "seq_len"}), seq_len)


@register("sdar-small-test")
def make_sdar_small(seq_len: int = 16, vocab: int = 256, n_layers: int = 2,
                    d_model: int = 64, n_heads: int = 4, n_kv_heads: int = 2,
                    head_dim: int = 16, d_ff_expert: int = 32,
                    n_experts: int = 8, top_k: int = 2,
                    rope_theta: float = 1000000.0, max_seq: int = 128,
                    ln_eps: float = 1e-6, block_length: int = 4,
                    denoising_steps: int = 4, mask_token_id: int = 255,
                    reveal: str = "sequential",
                    confidence_threshold: float = 0.9,
                    param_dtype: str = "float32") -> ModelSpec:
    """Tiny config for tests: 2 layers, 8 experts top 2 of width 32, 4
    query heads over 2 KV heads of 16 lanes, blocks of 4, float32."""
    return _lm_spec("sdar-small-test",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)
