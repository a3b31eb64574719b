"""SmallThinker family: a layer whose experts are chosen from the layer's
INPUT, before its attention runs; ReGLU experts with no shared one;
sliding-window layers that rotate their queries and keys beside
full-attention layers that rotate nothing.

Source of the default geometry: SmallThinker-21BA3B-Instruct
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct). Layer l,
`h` the residual stream's rows, `H` query heads over `n_kv_heads` KV heads
of `head_dim` lanes (28 over 4: seven query heads a KV head):

- route     ``logits = h Wr`` over all `n_routed` experts, float32, from
            the layer's INPUT, un-normed; the top `top_k` chosen, their
            soft-max probabilities normalised to sum one
            (`ops.moe.softmax_topk_route`). Nothing of it waits for the
            attention: the publication places it there so that the chosen
            experts' weights can be fetched while attention runs.
- Attn      ``x = RMS(h; ln1)``; ``q = x Wq``, ``k = x Wk``, ``v = x Wv``,
            no bias, no q/k norm. `rotated[l]`: q and k rotated over all D
            lanes (`rope_theta`, rotate-half pairing), else nothing is
            (NoPE). `windowed[l]`: causal over the last `window` keys
            (``i - window < j <= i``), else over all. Soft-max of
            ``q.k / sqrt(D)``, ``Wo``; ``h1 = h + Attn``.
- experts   ``z = RMS(h1; ln2)``; ``y = sum_e w_e Wdown_e (relu(Wgate_e z)
            * (Wup_e z))`` over the experts and weights OF THE FIRST LINE,
            width `d_ff_expert`; no shared expert, no dense layer, no
            token dropped. ``h_out = h1 + y``.
- final RMSNorm; a separate LM head.

`windowed` and `rotated` are two lists because the source states two
(`sliding_window_layout`, `rope_layout`); as published they are equal: a
window layer is rotated, a full layer is not. Not modelled: the published
model's secondary experts and its LM-head predictor, which its
`config.json` does not carry.

**A chip's share.** `held` = (first, count): the routed experts whose
weights THIS tree holds, as `models.laguna` states it (default: all).

**Two kinds of K/V block**, as `models.laguna` keeps them: the served
step takes two block pools, (full, window), each with a table a row;
`runtime.scheduler` gives a row's window blocks back as its position
passes them. Both kinds of layer read through `PagedKV.attend`, each row by
the class of its run (`ops.paged_attention.ragged_read_by_class`): a
decode row ONE packed tile of 4 x 7 = 28 query rows, a chunk tall tiles of
128 slots; the window kind's read is handed `window`, so a tile walks only
the columns its own slots see.

What this file shares with `models.laguna` it imports: the two-pool carry's
helpers (`index_in_kind`, `kv_kind_config`), the bank's generator and the
rope. The layer loop is its own: Laguna's normalises q and k a head, gates
the heads' outputs, adds a shared expert, keeps a dense layer and routes
from ``RMS(h1; ln2)`` with a sigmoid and a selection bias; hoisting the
route would leave four more branches in a loop neither model would read
plainly.

Parameter tree: `tok_embed`, `layers` (a list), `ln_f`, `head`. A block is
`ln1`, `attn` {wq, wk, wv, wo}, `ln2`, `mlp` {router {kernel: float32},
experts {gate_up (count, d, 2f), down (count, f, d)}}, the HELD experts
alone; the gate is the first half of `gate_up`. Weights are made in
`param_dtype` directly, as `models.moonlight` makes them.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_engine.models.laguna import _bank, _rope
from tpu_engine.models.moonlight import _dense_init, _normal, _residual_gain
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
from tpu_engine.utils.tracing import step_part


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig(TransformerConfig):
    """The base fields this family fixes: rmsnorm, rope, `d_ff` unused
    (every layer's feed-forward is its experts). `sliding_window` stays
    None: the window is a layer's, not the model's."""
    windowed: Tuple[bool, ...] = ()         # True: a sliding-window layer
    rotated: Tuple[bool, ...] = ()          # True: q and k are rotated
    window: int = 4096
    d_ff_expert: int = 768
    n_routed: int = 64
    top_k: int = 6
    held: Tuple[int, int] = (0, 64)         # (first, count) of n_routed
    param_dtype: str = "bfloat16"

    # The registry derives family and TP rule from these two.
    serving_state_family = "kv_windowed"
    tp_partition_rule = ("unshardable: its window layers' blocks are freed "
                         "by a host-side table a shard map does not carry, "
                         "and the expert banks have no placement rule")

    def __post_init__(self):
        if not (len(self.windowed) == len(self.rotated) == self.n_layers):
            raise ValueError("windowed and rotated need one entry a layer")
        if not self.n_window_layers or not self.n_full_layers:
            raise ValueError("a row holds blocks of two kinds: windowed "
                             "needs a window layer and a full one")
        first, count = self.held
        if not (0 <= first and count > 0
                and first + count <= self.n_routed):
            raise ValueError(f"held={self.held} is no share of "
                             f"{self.n_routed} experts")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers

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


# -- parameters ---------------------------------------------------------------

def _block_init(key, cfg: SmallThinkerConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    d, dh, h, h_kv = cfg.d_model, cfg.d_head, cfg.n_heads, cfg.kv_heads
    out_gain = _residual_gain(cfg)
    kq, kk, kv, ko, kr, kgu, kdn = jax.random.split(key, 7)
    f, count = cfg.d_ff_expert, cfg.held[1]
    return {
        "ln1": nn.rmsnorm_init(d),
        "attn": {"wq": _dense_init(kq, d, h * dh, dtype),
                 "wk": _dense_init(kk, d, h_kv * dh, dtype),
                 "wv": _dense_init(kv, d, h_kv * dh, dtype),
                 "wo": _dense_init(ko, h * dh, d, dtype, out_gain)},
        "ln2": nn.rmsnorm_init(d),
        "mlp": {
            # Unit-variance logits over a unit-variance stream's row would
            # be sqrt(d) wide: N(0, 1/d) a weight, as every router here.
            "router": {"kernel": _normal(kr, (d, cfg.n_routed), d,
                                         jnp.float32)},
            "experts": {"gate_up": _bank(kgu, (count, d, 2 * f), d, dtype),
                        "down": _bank(kdn, (count, f, d), f / out_gain ** 2,
                                      dtype)},
        },
    }


def smallthinker_init(key, cfg: SmallThinkerConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    k_tok, k_head, *k_layers = jax.random.split(key, 2 + cfg.n_layers)
    return {
        "tok_embed": {"table": jax.random.normal(
            k_tok, (cfg.vocab, cfg.d_model), dtype)},
        "layers": [_block_init(k, cfg) for k in k_layers],
        "ln_f": nn.rmsnorm_init(cfg.d_model),
        "head": _dense_init(k_head, cfg.d_model, cfg.vocab, dtype),
    }


# -- one layer's pieces -------------------------------------------------------

def _inv_freq(cfg: SmallThinkerConfig):
    d = cfg.d_head
    return (1.0 / cfg.rope_theta ** (
        np.arange(0, d, 2, dtype=np.float64) / d)).astype(np.float32)


def _attn_inputs(ap, x, positions, layer: int, cfg: SmallThinkerConfig,
                 dtype):
    """x: (..., d) normalised, at `positions` (...). Returns q (..., H, D),
    k and v (..., H_kv, D), q and k rotated where the layer is, as the
    pool holds k."""
    def heads(name):
        y = nn.dense(ap[name], x, dtype=dtype)
        return y.reshape(y.shape[:-1] + (-1, cfg.d_head)).astype(dtype)

    q, k = heads("wq"), heads("wk")
    if cfg.rotated[layer]:
        inv = _inv_freq(cfg)
        q, k = _rope(q, positions, inv, 1.0), _rope(k, positions, inv, 1.0)
    return q, k, heads("wv")


def _run_layers(params, h, carry, cfg: SmallThinkerConfig, attend, valid,
                dtype, held, max_tokens):
    """The layers in order over h (N, d) (a Python loop: the pools they
    read differ). `attend(layer, ap, x, carry) -> (heads' outputs (N, H *
    D), carry)`. Returns (h, carry, rows (L, n_routed))."""
    rows = []
    for layer, bp in enumerate(params["layers"]):
        # The route reads the layer's INPUT: nothing below it in this
        # body is upstream of the choice or of the pair list's sort.
        experts, weights = softmax_topk_route(h, bp["mlp"]["router"],
                                              cfg.top_k)
        with step_part("attn/qkv"):
            x = nn.rmsnorm(bp["ln1"], h, eps=cfg.ln_eps)
        o, carry = attend(layer, bp["attn"], x, carry)
        with step_part("attn/out"):
            h = (h + nn.dense(bp["attn"]["wo"], o,
                              dtype=dtype)).astype(dtype)
        with step_part("moe/experts"):
            z = nn.rmsnorm(bp["ln2"], h, eps=cfg.ln_eps)
        # The bank's group 0 is expert `held[0]`.
        y, taken = routed_experts(
            z, valid, experts, weights, bp["mlp"]["experts"],
            first_group=-held[0], n_experts=cfg.n_routed, held=held,
            max_tokens=max_tokens, dtype=dtype, activation=jax.nn.relu)
        rows.append(taken)
        with step_part("moe/experts"):
            h = (h + y).astype(dtype)
    return h, carry, jnp.stack(rows)


# -- the one-shot forward -----------------------------------------------------

def smallthinker_apply(params, tokens, cfg: SmallThinkerConfig, *,
                       dtype=jnp.bfloat16):
    """Full-sequence causal forward over the held experts. tokens: (B, S)
    int32 -> logits (B, S, vocab) float32."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = nn.embedding(params["tok_embed"], tokens).astype(dtype)
    at = jnp.arange(s)
    causal = at[None, :] <= at[:, None]
    inside = causal & (at[None, :] > at[:, None] - cfg.window)

    def attend(layer, ap, x, carry):
        q, k, v = _attn_inputs(ap, x.reshape(b, s, -1), positions, layer,
                               cfg, dtype)
        mask = inside if cfg.windowed[layer] else causal
        o = dot_product_attention(
            q, k, v, mask=jnp.broadcast_to(mask.astype(jnp.int32),
                                           (b, s, s)))
        return o.reshape(b * s, -1), carry

    h, _, _ = _run_layers(params, h.reshape(b * s, -1), (), cfg, attend,
                          jnp.ones((b * s,), bool), dtype, cfg.held, None)
    return lm_head(params, h.reshape(b, s, -1), cfg.ln_eps, dtype)


# -- the served step: the mixed tick over the two pools -----------------------

def smallthinker_step_rows_ragged(params, tokens, caches, tables, pos0, qlen,
                                  cfg: SmallThinkerConfig, *,
                                  dtype=jnp.bfloat16, attn_fn=None,
                                  sample_slot=None, held=None,
                                  max_tokens: Optional[int] = None):
    """This family's step of the mixed tick, over the tick's token list
    (`models.tick_tokens`, a token an entry).

    caches: (full, window), a K/V pair each, (layers of the kind, NB, bs,
    H_kv*D), updated in place (donate both); tables: (full, window), each
    (B, nb) by logical column // bs. A window layer's table may hold the
    null block wherever the row's first new token no longer sees (the
    blocks it gave back). BOTH kinds read by the class of a row's run
    (`PagedKV.attend`): the window kind's calls are handed `window`, so a
    decode row's packed tile and each of a chunk's tall tiles walk the
    columns their own slots see and no block behind them.

    ``held`` = (first, count): the experts `params` holds (default
    `cfg.held`). Returns (logits, caches, rows (L, n_routed) int32: the
    rows each held expert took)."""
    from tpu_engine.ops import paged_attention as pa

    if attn_fn is None:
        attn_fn = pa.default_ragged_attention()
    held = held or cfg.held
    bs = caches[0].k.shape[2]
    group = cfg.n_heads // cfg.kv_heads
    tt = tick_tokens(pos0, qlen, tokens.shape[1], max_tokens)
    kinds = (tt.paged_kv(tables[0], bs, group),
             tt.paged_kv(tables[1], bs, group))
    reads = ({}, {"window": cfg.window})
    h = tt.embed(params, tokens, dtype)

    def attend(layer, ap, x, pools):
        kind = int(cfg.windowed[layer])
        with step_part("attn/qkv"):
            q, k, v = _attn_inputs(ap, x, tt.logical, layer, cfg, dtype)
        o, pool = kinds[kind].attend(attn_fn, q, k, v, pools[kind],
                                     cfg.pool_layer[layer], **reads[kind])
        return (o.reshape(o.shape[0], -1),
                pools[:kind] + (pool,) + pools[kind + 1:])

    h, pools, rows = _run_layers(
        params, h, tuple(tuple(c) for c in caches), cfg, attend, tt.valid,
        dtype, held, max_tokens)
    return (lm_head(params, tt.head_rows(h, sample_slot), cfg.ln_eps, dtype),
            tuple(KVCache(*p) for p in pools), rows)


# -- registry -----------------------------------------------------------------

FULL, WINDOW = "full_attention", "sliding_attention"


def _lm_spec(name: str, cfg: SmallThinkerConfig, seq_len: int) -> ModelSpec:
    return causal_lm_spec(name, cfg, seq_len, smallthinker_init,
                          smallthinker_apply,
                          ragged_step=smallthinker_step_rows_ragged,
                          held=cfg.held)


def _cfg(**kw) -> SmallThinkerConfig:
    kinds = tuple(kw["layer_types"])
    for kind in kinds:
        if kind not in (FULL, WINDOW):
            raise ValueError(f"{kind!r} is no layer kind: a layer is "
                             f"{FULL!r} or {WINDOW!r}")
    heads = set(kw["heads_per_layer"])
    if len(heads) != 1 or len(kw["heads_per_layer"]) != len(kinds):
        raise ValueError("heads_per_layer needs one entry a layer, all "
                         "equal: both kinds of layer carry the same heads")
    return SmallThinkerConfig(
        vocab=kw["vocab"], n_layers=len(kinds), d_model=kw["d_model"],
        n_heads=heads.pop(), n_kv_heads=kw["n_kv_heads"],
        head_dim=kw["head_dim"], d_ff=kw["d_ff_expert"],
        max_seq=kw["max_seq"], causal=True, norm="rmsnorm", pos="rope",
        mlp_act="swiglu", rope_theta=kw["rope_theta"], ln_eps=kw["ln_eps"],
        windowed=tuple(k == WINDOW for k in kinds),
        rotated=tuple(bool(r) for r in kw["rope_layout"]),
        window=kw["window"], d_ff_expert=kw["d_ff_expert"],
        n_routed=kw["n_experts"], top_k=kw["top_k"],
        held=(kw["held_first"], kw["held_count"] or kw["n_experts"]),
        param_dtype=kw["param_dtype"])


_PERIOD = (FULL,) + (WINDOW,) * 3


@register("smallthinker")
def make_smallthinker(seq_len: int = 128, vocab: int = 151936,
                      layer_types: Tuple[str, ...] = _PERIOD * 13,
                      rope_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13,
                      heads_per_layer: Tuple[int, ...] = (28,) * 52,
                      d_model: int = 2560, n_kv_heads: int = 4,
                      head_dim: int = 128, d_ff_expert: int = 768,
                      n_experts: int = 64, top_k: int = 6,
                      held_first: int = 0, held_count: int = 0,
                      window: int = 4096, rope_theta: float = 1.5e6,
                      max_seq: int = 16384, ln_eps: float = 1e-6,
                      param_dtype: str = "bfloat16") -> ModelSpec:
    """SmallThinker-21BA3B-Instruct's published geometry; every width a
    keyword. `held_count` 0 holds every expert."""
    return _lm_spec("smallthinker",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)


@register("smallthinker-small-test")
def make_smallthinker_small(seq_len: int = 16, vocab: int = 256,
                            layer_types: Tuple[str, ...] = _PERIOD * 2,
                            rope_layout: Tuple[int, ...] = (0, 1, 1, 1) * 2,
                            heads_per_layer: Tuple[int, ...] = (14,) * 8,
                            d_model: int = 64, n_kv_heads: int = 2,
                            head_dim: int = 16, d_ff_expert: int = 32,
                            n_experts: int = 8, top_k: int = 3,
                            held_first: int = 0, held_count: int = 0,
                            window: int = 48, rope_theta: float = 1.5e6,
                            max_seq: int = 256, ln_eps: float = 1e-6,
                            param_dtype: str = "float32") -> ModelSpec:
    """Tiny config for tests: two periods of (full, window, window,
    window), 14 query heads over 2 KV heads (G = 7), a window of three
    blocks of 16, 8 experts top 3 all held, float32."""
    return _lm_spec("smallthinker-small-test",
                    _cfg(**{k: v for k, v in locals().items()
                            if k != "seq_len"}), seq_len)
