"""Transformer core: stacked-layer params + `lax.scan` over layers.

The reference serves transformers only as opaque ONNX graphs (BASELINE.json
configs 3 and 5: BERT-base-squad, GPT-2); here they are JAX programs built
TPU-first:

- **Stacked layer params**: every block's params are stacked on a leading
  layer axis and the forward is one `lax.scan` — the block is traced/compiled
  once regardless of depth (fast XLA compiles), and the layer axis is the
  natural pipeline-parallel shard axis.
- **Static shapes everywhere**: decode uses a preallocated KV cache
  (ops.attention.KVCache) with position masking, so prefill and per-token
  decode are each a single compiled executable.
- **bf16 matmuls, f32 softmax/layernorm** — MXU-native compute with stable
  numerics.

Tensor-parallel sharding (training.shard_params_tp / parallel rules): attn
wq/wk/wv and mlp fc shard the hidden/head output dim over `model`; wo and
mlp proj shard their input dim; embeddings replicate or shard on vocab.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_engine.ops import nn
from tpu_engine.ops.attention import (
    KVCache,
    dot_product_attention,
    mha_init,
    repeat_kv,
    rope,
    _split_heads,
)
from tpu_engine.utils.tracing import step_part


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 50257
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    d_ff: int = 3072
    max_seq: int = 1024
    causal: bool = True  # decoder (GPT) vs encoder (BERT)
    # Architecture dialect knobs — defaults are GPT-2-exact; BERT flips all
    # four (post-LN blocks, LayerNorm'd embeddings, segment embeddings,
    # erf GELU, eps 1e-12). Faithful dialects are what let the HF weight
    # importer (models.import_weights) produce bit-compatible forwards.
    post_ln: bool = False       # BERT: x = LN(x + sub(x));  GPT: x = x + sub(LN(x))
    embed_ln: bool = False      # LayerNorm after (tok + pos + type) embeddings
    type_vocab: int = 0         # token-type (segment) embedding table size
    gelu_tanh: bool = True      # tanh-approx GELU (GPT-2) vs erf GELU (BERT)
    ln_eps: float = 1e-5
    # Llama-family dialect knobs (import_llama produces bit-compatible
    # forwards): RMSNorm blocks, rotary positions (no learned table),
    # SwiGLU FFN, grouped-query attention via n_kv_heads < n_heads.
    norm: str = "layernorm"     # "layernorm" | "rmsnorm"
    pos: str = "learned"        # "learned" | "rope"
    mlp_act: str = "gelu"       # "gelu" | "swiglu"
    n_kv_heads: Optional[int] = None   # None = n_heads (full MHA)
    rope_theta: float = 10000.0  # (bias-free llama projections import as
    #                              zero biases — the graph is unconditional)
    # Sliding-window attention (Mistral family): each position attends at
    # most the last `sliding_window` positions (None = full causal). Mask
    # semantics only — the KV cache stays max_seq-wide (a rolling cache is
    # a memory optimization this knob does not imply).
    sliding_window: Optional[int] = None
    # Mixture-of-Experts FFN (0 = dense). Experts shard over the `expert`
    # mesh axis (ops.moe); top-k routing, static capacity slots.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # A head's width where the model states one (None: d_model / n_heads).
    head_dim: Optional[int] = None

    @property
    def d_head(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def kv_lanes(self) -> Tuple[int, int]:
        """Lanes a token occupies, a layer, in the block pool's two
        tensors: what `runtime.kv_blocks.BlockPool` and its byte
        accounting size a block by. Here every KV head's key and every KV
        head's value; a latent-attention family states its own
        (models.moonlight)."""
        return (self.kv_heads * self.d_head,) * 2

    @property
    def moe(self):
        from tpu_engine.ops.moe import MoEConfig

        return MoEConfig(d_model=self.d_model, d_ff=self.d_ff,
                         n_experts=self.n_experts, top_k=self.moe_top_k,
                         capacity_factor=self.moe_capacity_factor)


def index_in_kind(kinds) -> Tuple[int, ...]:
    """For a model whose layers are of several kinds (`kinds`: one hashable
    value a layer), each kind keeping its rows' state in a pool of its own:
    layer l's index among the layers of ITS kind, the layer of that pool
    it reads and writes (`models.laguna`, `models.olmo_hybrid`)."""
    seen, out = {}, []
    for kind in kinds:
        out.append(seen.get(kind, 0))
        seen[kind] = out[-1] + 1
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class KVKindConfig(TransformerConfig):
    """One kind's layers of a model, as a block pool is sized by them."""
    lanes: Tuple[int, int] = (0, 0)

    @property
    def kv_lanes(self) -> Tuple[int, int]:
        return self.lanes


def kv_kind_config(cfg: TransformerConfig, n_layers: int) -> TransformerConfig:
    """What a block pool that holds `n_layers` of `cfg`'s layers is sized by
    (`runtime.kv_blocks.BlockPool` reads layers, KV heads, head width and
    the lanes a token takes, which are what `cfg` STATES: K and V a head,
    or a latent and its shared key lanes): one kind's layers alone."""
    return KVKindConfig(
        n_layers=n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        lanes=tuple(cfg.kv_lanes))


def _norm_init(cfg: TransformerConfig):
    return (nn.rmsnorm_init(cfg.d_model) if cfg.norm == "rmsnorm"
            else nn.layernorm_init(cfg.d_model))


def _norm(params, x, cfg: TransformerConfig):
    return (nn.rmsnorm(params, x, eps=cfg.ln_eps) if cfg.norm == "rmsnorm"
            else nn.layernorm(params, x, eps=cfg.ln_eps))


def _block_init(key, cfg: TransformerConfig):
    k_attn, k_fc, k_proj = jax.random.split(key, 3)
    out = {
        "ln1": _norm_init(cfg),
        "attn": mha_init(k_attn, cfg.d_model, cfg.n_heads,
                         d_head=cfg.head_dim, n_kv_heads=cfg.n_kv_heads),
        "ln2": _norm_init(cfg),
    }
    if cfg.n_experts > 0:
        from tpu_engine.ops.moe import moe_init

        out["mlp"] = moe_init(k_fc, cfg.moe)
    elif cfg.mlp_act == "swiglu":
        k_gate, k_up = jax.random.split(k_fc)
        out["mlp"] = {
            "gate": nn.dense_init(k_gate, cfg.d_model, cfg.d_ff),
            "up": nn.dense_init(k_up, cfg.d_model, cfg.d_ff),
            "proj": nn.dense_init(k_proj, cfg.d_ff, cfg.d_model),
        }
    else:
        out["mlp"] = {
            "fc": nn.dense_init(k_fc, cfg.d_model, cfg.d_ff),
            "proj": nn.dense_init(k_proj, cfg.d_ff, cfg.d_model),
        }
    return out


def transformer_init(key, cfg: TransformerConfig):
    k_tok, k_pos, k_blocks, k_head, k_type = jax.random.split(key, 5)
    block_keys = jax.random.split(k_blocks, cfg.n_layers)
    # Stack per-layer params on a leading axis: tree of (L, ...) arrays.
    blocks = jax.vmap(lambda k: _block_init(k, cfg))(block_keys)
    params = {
        "tok_embed": nn.embedding_init(k_tok, cfg.vocab, cfg.d_model),
        "blocks": blocks,
        # LM head tied to tok_embed would save params; kept separate so the
        # vocab dim can shard over `model` independently.
        "head": nn.dense_init(k_head, cfg.d_model, cfg.vocab),
    }
    if cfg.pos == "learned":
        params["pos_embed"] = nn.embedding_init(k_pos, cfg.max_seq,
                                                cfg.d_model)
    if not cfg.post_ln:
        # Post-LN dialects (BERT) normalize inside every block and have no
        # final LayerNorm.
        params["ln_f"] = _norm_init(cfg)
    if cfg.embed_ln:
        params["embed_ln"] = nn.layernorm_init(cfg.d_model)
    if cfg.type_vocab > 0:
        params["type_embed"] = nn.embedding_init(k_type, cfg.type_vocab,
                                                 cfg.d_model)
    return params


# The leaves a compiled step hands to `nn.dense(..., dtype=dtype)`: the
# attention projections, the dense MLP's and the LM head ('/'-joined
# paths, as the registry's TP rules name them).
_STEP_KERNELS = re.compile(
    r"^(blocks/attn/w[qkvo]|blocks/mlp/(fc|gate|up|proj)|head)/kernel$")


@functools.partial(jax.jit, static_argnames="dtype")
def _cast_leaves(leaves, dtype):
    return [leaf.astype(dtype) for leaf in leaves]


def step_weights(params, dtype):
    """The tree a lane's compiled steps read: `params` with the kernels
    of `_STEP_KERNELS` cast to the step's dtype ONCE, by the `astype`
    `nn.dense` would apply to them every tick (there it is then the
    identity, and the tick has no cast left to hoist). Every other leaf
    — biases, norm scales, embedding tables, a weight-quantized
    `kernel_q` — IS the array in `params`, and so is a kernel already in
    `dtype`; with nothing to cast, `params` itself comes back. The casts
    run under one jit, so each copy stays where (and sharded as) its
    master is."""
    dtype = jnp.dtype(dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = [leaf for _, leaf in flat]
    cast = [i for i, (path, leaf) in enumerate(flat)
            if leaf.dtype != dtype and _STEP_KERNELS.search(
                jax.tree_util.keystr(path, simple=True, separator="/"))]
    if not cast:
        return params
    for i, leaf in zip(cast, _cast_leaves([leaves[i] for i in cast],
                                          dtype)):
        leaves[i] = leaf
    return treedef.unflatten(leaves)


def _mlp(params, h, dtype, cfg: TransformerConfig = None):
    if cfg is not None and cfg.n_experts > 0:
        from tpu_engine.ops.moe import moe_apply

        return moe_apply(params, h, cfg.moe, dtype=dtype)
    if cfg is not None and cfg.mlp_act == "swiglu":
        gate = jax.nn.silu(nn.dense(params["gate"], h, dtype=dtype))
        return nn.dense(params["proj"],
                        gate * nn.dense(params["up"], h, dtype=dtype),
                        dtype=dtype)
    h = nn.dense(params["fc"], h, dtype=dtype)
    h = jax.nn.gelu(h, approximate=cfg.gelu_tanh if cfg is not None else True)
    return nn.dense(params["proj"], h, dtype=dtype)


_ATTN_CACHE = {}


def default_attention():
    """The serving-path attention implementation, selected once per
    process: the Pallas flash kernel (ops.flash) on a TPU, the XLA
    reference elsewhere. Against the XLA-fused path it was reported on
    an earlier stack at parity through S2048 and ahead beyond; not
    measured on this one (PERF.md). `TPU_ENGINE_FLASH` overrides: "1"
    forces flash (Pallas interpreter off-TPU — slow, for parity tests),
    "0" forces the XLA reference path, unset/"auto" picks by backend.
    """
    import os

    mode = os.environ.get("TPU_ENGINE_FLASH", "auto")
    fn = _ATTN_CACHE.get(mode)
    if fn is None:
        if mode == "0":
            fn = dot_product_attention
        elif mode == "1" or (mode == "auto"
                             and jax.default_backend() == "tpu"):
            from tpu_engine.ops.flash import flash_attention

            fn = flash_attention
        else:
            fn = dot_product_attention
        _ATTN_CACHE[mode] = fn
    return fn


def _project_qkv(bp, x, cfg: TransformerConfig, *, dtype, positions=None):
    """qkv projections + rotary phases — the ONE implementation every path
    (full-seq, prefill, scalar decode, per-row decode) shares, so a dialect
    change can't silently diverge between cached and uncached forwards.
    `positions`: logical positions for rope ((B, S), (B, 1) or None →
    arange over the sequence)."""
    q = _split_heads(nn.dense(bp["attn"]["wq"], x, dtype=dtype), cfg.n_heads)
    k = _split_heads(nn.dense(bp["attn"]["wk"], x, dtype=dtype), cfg.kv_heads)
    v = _split_heads(nn.dense(bp["attn"]["wv"], x, dtype=dtype), cfg.kv_heads)
    if cfg.pos == "rope":
        pos = jnp.arange(x.shape[1]) if positions is None else positions
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    return q, k, v


def _attn(bp, x, cfg: TransformerConfig, *, mask, dtype, attn_fn=None,
          pos_ids=None):
    attn_fn = attn_fn or default_attention()
    q, k, v = _project_qkv(bp, x, cfg, dtype=dtype, positions=pos_ids)
    # Full-sequence attn_fn implementations (flash kernel, ring attention)
    # expect equal head counts — expand grouped KV here (a one-time
    # prompt-pass cost; the decode paths below attend grouped, unexpanded).
    n_rep = cfg.n_heads // cfg.kv_heads
    kw = {}
    if cfg.sliding_window is not None:
        # Only passed when set, so window-less attn_fns (ring attention)
        # keep working; a sliding-window cfg with an attn_fn that can't
        # band-mask fails loudly (TypeError), never silently full-causal.
        kw["window"] = cfg.sliding_window
    a = attn_fn(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                causal=cfg.causal, mask=mask, **kw)
    b, s = a.shape[:2]
    return nn.dense(bp["attn"]["wo"], a.reshape(b, s, -1), dtype=dtype)


def _block_apply(bp, h, cfg: TransformerConfig, *, mask, dtype, attn_fn=None,
                 pos_ids=None):
    if cfg.post_ln:
        # BERT dialect: sublayer → residual add → LayerNorm.
        h = _norm(bp["ln1"], h + _attn(bp, h, cfg, mask=mask, dtype=dtype,
                                       attn_fn=attn_fn, pos_ids=pos_ids),
                  cfg)
        h = _norm(bp["ln2"], h + _mlp(bp["mlp"], h, dtype, cfg), cfg)
    else:
        # GPT/llama dialect: norm → sublayer → residual add.
        h = h + _attn(bp, _norm(bp["ln1"], h, cfg), cfg,
                      mask=mask, dtype=dtype, attn_fn=attn_fn,
                      pos_ids=pos_ids)
        h = h + _mlp(bp["mlp"], _norm(bp["ln2"], h, cfg), dtype, cfg)
    # nn.dense accumulates in f32; keep the residual-stream carry in the
    # compute dtype so the layer scan's carry type is stable.
    return h.astype(dtype)


def transformer_apply(params, tokens, cfg: TransformerConfig, *,
                      mask=None, dtype=jnp.bfloat16, attn_fn=None,
                      token_type_ids=None, remat=False):
    """Full-sequence forward. tokens: (B, S) int32 → logits (B, S, vocab).

    `attn_fn` swaps the attention implementation — e.g. a partial of
    parallel.ring.ring_attention for sequence-parallel long-context runs,
    or ops.flash.flash_attention for the fused Pallas kernel.
    `token_type_ids` (B, S) selects segment embeddings when the config has a
    type vocabulary (BERT); defaults to all-zeros.
    `remat=True` checkpoints each block in the backward pass: activation
    residency drops from O(L·B·S·d) to one layer recomputed at a time —
    the standard FLOPs-for-HBM trade that long-sequence training needs
    (gradients match the unrematerialized pass to float32 tolerance; see
    tests/test_remat.py for the compiled-memory evidence)."""
    b, s = tokens.shape
    h = nn.embedding(params["tok_embed"], tokens)
    if cfg.pos == "learned":
        h = h + params["pos_embed"]["table"][None, :s]
    if cfg.type_vocab > 0:
        if token_type_ids is None:
            h = h + params["type_embed"]["table"][0]
        else:
            h = h + nn.embedding(params["type_embed"], token_type_ids)
    if cfg.embed_ln:
        h = nn.layernorm(params["embed_ln"], h, eps=cfg.ln_eps)
    h = h.astype(dtype)

    def body(carry, bp):
        return _block_apply(bp, carry, cfg, mask=mask, dtype=dtype,
                            attn_fn=attn_fn), None

    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["blocks"])
    if not cfg.post_ln:
        h = _norm(params["ln_f"], h, cfg)
    return nn.dense(params["head"], h, dtype=dtype).astype(jnp.float32)


# -- autoregressive decode ----------------------------------------------------

def init_caches(cfg: TransformerConfig, batch: int, max_seq: Optional[int] = None,
                dtype=jnp.bfloat16) -> KVCache:
    """Stacked (L-leading) KV cache matching the scanned blocks. GQA models
    cache only `kv_heads` heads — the llama-family memory win."""
    max_seq = max_seq or cfg.max_seq
    shape = (cfg.n_layers, batch, max_seq, cfg.kv_heads, cfg.d_head)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _block_decode(bp, h, cache_kv: Tuple[jnp.ndarray, jnp.ndarray],
                  pos, cfg: TransformerConfig, *, dtype, prefill: bool,
                  attn_mask=None, start=None, pos_ids=None):
    """`pos_ids`: LOGICAL positions for rotary phases — (B, S) in prefill,
    (B, 1) in decode. RoPE rotates k BEFORE it enters the cache, so cached
    keys are phase-complete and decode only rotates the new column."""
    ck, cv = cache_kv
    n_rep = cfg.n_heads // cfg.kv_heads
    x = _norm(bp["ln1"], h, cfg)
    q, k, v = _project_qkv(bp, x, cfg, dtype=dtype, positions=pos_ids)
    write_at = 0 if prefill else pos
    ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, write_at, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, write_at, 0, 0))
    if prefill:
        # Prefill is a full-sequence pass — the flash kernel's home turf.
        # Decode (below) keeps the XLA path: a 1-token query block can't
        # feed the MXU enough to win.
        kw = ({"window": cfg.sliding_window}
              if cfg.sliding_window is not None else {})
        a = default_attention()(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                                causal=True, mask=attn_mask, **kw)
    else:
        max_seq = ck.shape[1]
        kpos = jnp.arange(max_seq)[None, :]
        valid = (kpos <= pos) * jnp.ones((h.shape[0], 1), jnp.int32)
        if cfg.sliding_window is not None:
            valid = valid * (kpos > pos - cfg.sliding_window)
        if start is not None:
            # Left-padded batch: positions before each sample's first real
            # token are dead cache slots.
            valid = valid * (kpos >= start[:, None])
        # Grouped attention directly against the un-expanded cache — decode
        # is the bandwidth-bound path GQA exists for.
        a = dot_product_attention(q, ck, cv, mask=valid)
    b, s = a.shape[:2]
    h = h + nn.dense(bp["attn"]["wo"], a.reshape(b, s, -1), dtype=dtype)
    h = h + _mlp(bp["mlp"], _norm(bp["ln2"], h, cfg), dtype, cfg)
    return h.astype(dtype), (ck, cv)


def transformer_prefill(params, tokens, caches: KVCache, cfg: TransformerConfig,
                        *, dtype=jnp.bfloat16, attn_mask=None, pos_ids=None):
    """Causal forward over the prompt, writing all KV entries. Returns
    (last-position logits (B, vocab), caches).

    Mixed-length batches are LEFT-padded: `attn_mask` (B, S) zeroes the pad
    columns, `pos_ids` (B, S) gives each sample positions starting at 0 on
    its first real token — every sample then ends at column S-1, so decode
    continues with one scalar position for the whole batch.
    """
    b, s = tokens.shape
    h = nn.embedding(params["tok_embed"], tokens)
    if cfg.pos == "learned":
        if pos_ids is None:
            h = h + params["pos_embed"]["table"][None, :s]
        else:
            h = h + params["pos_embed"]["table"][pos_ids]
    h = h.astype(dtype)

    def body(carry, layer):
        bp, ck, cv = layer
        h, (ck, cv) = _block_decode(bp, carry, (ck, cv), 0, cfg,
                                    dtype=dtype, prefill=True,
                                    attn_mask=attn_mask, pos_ids=pos_ids)
        return h, (ck, cv)

    h, (k_new, v_new) = jax.lax.scan(body, h, (params["blocks"], caches.k, caches.v))
    h = _norm(params["ln_f"], h[:, -1:], cfg)
    logits = nn.dense(params["head"], h, dtype=dtype).astype(jnp.float32)
    return logits[:, 0], KVCache(k_new, v_new)


def _block_decode_rows(bp, h, cache_kv, pos_vec, cfg: TransformerConfig, *,
                       dtype, start_vec):
    """One decode step with PER-ROW cache positions — the continuous-
    batching primitive (rows admitted at different times sit at different
    depths). pos_vec/start_vec: (B,) int32."""
    ck, cv = cache_kv
    b = h.shape[0]
    x = _norm(bp["ln1"], h, cfg)
    q, k, v = _project_qkv(bp, x, cfg, dtype=dtype,
                           positions=(pos_vec - start_vec)[:, None])
    rows = jnp.arange(b)
    ck = ck.at[rows, pos_vec].set(k[:, 0].astype(ck.dtype))
    cv = cv.at[rows, pos_vec].set(v[:, 0].astype(cv.dtype))
    kpos = jnp.arange(ck.shape[1])[None, :]
    valid = ((kpos <= pos_vec[:, None]) & (kpos >= start_vec[:, None]))
    if cfg.sliding_window is not None:
        valid = valid & (kpos > pos_vec[:, None] - cfg.sliding_window)
    valid = valid.astype(jnp.int32)
    a = dot_product_attention(q, ck, cv, mask=valid)  # grouped, unexpanded
    h = h + nn.dense(bp["attn"]["wo"], a.reshape(b, 1, -1), dtype=dtype)
    h = h + _mlp(bp["mlp"], _norm(bp["ln2"], h, cfg), dtype, cfg)
    return h.astype(dtype), (ck, cv)


def transformer_decode_rows(params, token_t, caches: KVCache, pos_vec,
                            cfg: TransformerConfig, *, dtype=jnp.bfloat16,
                            start_vec=None):
    """One decode step where every row has its own cache position.

    token_t: (B,); pos_vec: (B,) write offsets; start_vec: (B,) first valid
    cache column per row. Returns (logits (B, vocab), caches). The
    continuous scheduler (runtime.scheduler) drives this so rows admitted
    mid-flight decode alongside older rows."""
    if start_vec is None:
        start_vec = jnp.zeros_like(pos_vec)
    h = nn.embedding(params["tok_embed"], token_t[:, None])
    if cfg.pos == "learned":
        logical = jnp.clip(pos_vec - start_vec, 0,
                           params["pos_embed"]["table"].shape[0] - 1)
        h = h + params["pos_embed"]["table"][logical][:, None, :]
    h = h.astype(dtype)

    def body(carry, layer):
        bp, ck, cv = layer
        h, (ck, cv) = _block_decode_rows(bp, carry, (ck, cv), pos_vec, cfg,
                                         dtype=dtype, start_vec=start_vec)
        return h, (ck, cv)

    h, (k_new, v_new) = jax.lax.scan(body, h, (params["blocks"], caches.k, caches.v))
    h = _norm(params["ln_f"], h, cfg)
    logits = nn.dense(params["head"], h, dtype=dtype).astype(jnp.float32)
    return logits[:, 0], KVCache(k_new, v_new)


def _write_pool(cache_kv, layer, blk, off, k, v):
    """Scatter new-token K/V — (..., H_kv, D), one vector per leading
    index — into layer `layer` of the paged pool at (blk, off), in place
    on the WHOLE pool tensors: (L, NB, bs, H_kv*D), head h in lanes
    [h*D, (h+1)*D). A LATENT pool (models.moonlight) is written through
    here too: `k` the rotated shared rope key padded to its lane tile,
    `v` the normalised latent, one "head" each, of different widths (the
    two tensors' last axes are `cfg.kv_lanes`). A QUANTIZED pool
    (cache_kv = (ck, cv, ks, vs), int8
    payloads and (L, NB, bs, H_kv) f32 scales) quantizes HERE, exactly
    once: each (kv-head) vector gets its own scale, so the write never
    touches (or is constrained by) neighbours already in the block."""
    lanes = k.shape[:-2] + (-1,)
    if len(cache_kv) == 2:
        ck, cv = cache_kv
        return (ck.at[layer, blk, off].set(k.reshape(lanes).astype(ck.dtype)),
                cv.at[layer, blk, off].set(v.reshape(lanes).astype(cv.dtype)))
    from tpu_engine.ops.quant import quantize_kv

    ck, cv, ks, vs = cache_kv
    qk, sk = quantize_kv(k)       # (..., H_kv, D) -> int8 + (..., H_kv) f32
    qv, sv = quantize_kv(v)
    return (ck.at[layer, blk, off].set(qk.reshape(lanes)),
            cv.at[layer, blk, off].set(qv.reshape(lanes)),
            ks.at[layer, blk, off].set(sk),
            vs.at[layer, blk, off].set(sv))


def _scan_layers_paged(block_fn, params, h, caches: KVCache,
                       scales: Optional[KVCache]):
    """Run `block_fn(bp, h, cache_kv, layer) -> (h, cache_kv)` over the
    layers with the WHOLE pool (and scales) in the scan's carry beside
    `h`: every layer writes its tokens in place at `[layer, blk, off]`
    and reads through the layer index, so no layer of the pool is ever
    sliced out or stacked back. Returns (h, caches[, scales])."""
    cache_kv = tuple(caches) + (tuple(scales) if scales is not None else ())

    def body(carry, layer):
        bp, l = layer
        h, cache_kv = block_fn(bp, carry[0], carry[1], l)
        return (h, cache_kv), None

    n_layers = caches.k.shape[0]
    (h, cache_kv), _ = jax.lax.scan(
        body, (h, cache_kv),
        (params["blocks"], jnp.arange(n_layers, dtype=jnp.int32)))
    if scales is not None:
        return h, KVCache(*cache_kv[:2]), KVCache(*cache_kv[2:])
    return h, KVCache(*cache_kv)


def pool_write_slots(batch: int, width: int,
                     max_tokens: Optional[int] = None) -> int:
    """The indices ONE layer's pool write of the uniform step scatters
    (`transformer_step_rows_ragged`): the stated bound on a call's valid
    slots where it is under the step's slots, else every slot — a step a
    slot wide has a slot a row and nothing to leave out."""
    slots = batch * width
    if max_tokens is None or width == 1:
        return slots
    return min(max_tokens, slots)


def second_half_slots(cfg: TransformerConfig, batch: int, width: int,
                      max_tokens: Optional[int] = None) -> int:
    """The rows ONE layer's second half of the uniform step computes —
    the output projection's product and its residual add, the norm, the
    feed-forward and its add: the step's token list where it has one
    (`pool_write_slots`) and the feed-forward is dense, so a row's value
    is its own whatever rows sit beside it; every slot, for `wo` too,
    under routed experts, whose capacity counts the rows of the operand
    (`ops.moe.moe_apply`): a listed `wo` there would be a second
    write-back a layer."""
    if cfg.n_experts > 0:
        return batch * width
    return pool_write_slots(batch, width, max_tokens)


def _block_step_rows_ragged(bp, h, cache_kv, layer, tables, pos0, qlen,
                            cfg: TransformerConfig, *, dtype, attn_fn,
                            listed=None,
                            half_at: Optional[jax.Array] = None):
    """One ragged mixed step against the PAGED pool: cache_kv arrays are
    the whole (L, NB, bs, H_kv*D) block pools shared by every row and
    layer (`runtime.kv_blocks.BlockPool` states the layout); ``tables``
    (B, nb) maps row b's logical column c to pool block
    ``tables[b, c // bs]``, offset ``c % bs``. Paged rows are 0-aligned
    (token i at logical column i — the alignment radix sharing needs).
    Row b consumes
    qlen[b] new tokens at logical columns [pos0[b], pos0[b]+qlen[b])
    (decode rows: qlen 1; admitting rows: a prefill chunk). The new
    tokens' K/V scatter into the rows' pool blocks of layer ``layer``
    BEFORE the attention read (write-before-attend; a quantized pool
    quantizes at THIS write, `_write_pool`). What the scatter takes:
    with ``listed`` = (at, blk, off), each a `models.tick_tokens` list
    long (an entry's place row * W + slot among the slots, its pool block
    and its offset there), the tick's valid slots alone, gathered out of
    the (B, W) products into the list's order — an index a token, and XLA's
    scatter on a TPU pays by the index; without it all B x W slots, the
    padding ones (i >= qlen) sent to the null block. The values and the
    places are the same either way, the null block's contents apart;
    q, k, v and the read keep their (B, W) shapes, and the padding
    slots' outputs are garbage the scheduler ignores. With ``half_at``
    (the list's `at`) the layer's second half — `wo`'s product over the
    read's output and its residual add, the norm, the feed-forward and
    its add: all row-wise — runs over the list's rows alone, gathered
    out of the read's output (as it comes, (B x W, H, D): its conversion
    and its laying out for `wo` then pass over the list too) and out of
    the residual, and is written back where they came from, once; a
    padding slot then keeps the layer's INPUT residual, garbage as
    before."""
    bs = cache_kv[0].shape[2]
    b, w = h.shape[:2]
    offs = jnp.arange(w)[None, :]
    logical = pos0[:, None] + offs                           # (B, W)
    with step_part("attn/qkv"):
        x = _norm(bp["ln1"], h, cfg)
        q, k, v = _project_qkv(bp, x, cfg, dtype=dtype, positions=logical)
    with step_part("attn/write"):
        if listed is not None:
            at, blk, off = listed
            # Whole rows of lanes out of the (B x W, H_kv * D) form the
            # projection left: gathered by head, XLA first re-lays all
            # B x W slots out with (H_kv, D) minor, a copy a tensor a
            # layer.
            k_new, v_new = (x.reshape(b * w, -1)[at].reshape(
                (-1,) + x.shape[2:]) for x in (k, v))
        else:
            rows = jnp.arange(b)[:, None]
            max_col = tables.shape[1] * bs - 1
            # padding may run off the table
            cols = jnp.minimum(logical, max_col)
            blk = tables[rows, cols // bs]
            # padding -> null block
            blk = jnp.where(offs < qlen[:, None], blk, 0)
            off = cols % bs
            k_new, v_new = k, v
        cache_kv = _write_pool(cache_kv, layer, blk, off, k_new, v_new)
    with step_part("attn/read"):
        a = attn_fn(q, *cache_kv, layer, tables, pos0, qlen)  # grouped
        if half_at is not None:
            # Whole tokens out of the read's (B, W, H, D) output, before
            # it is converted and laid (B x W, H * D) for `wo`: those
            # passes then cover the list's rows, not every slot.
            a = a.reshape((b * w,) + a.shape[2:])[half_at]
        a = a.astype(dtype)
    with step_part("attn/out"):
        x = h if half_at is None else h.reshape(b * w, -1)[half_at]
        x = x + nn.dense(bp["attn"]["wo"],
                         a.reshape(x.shape[:-1] + (-1,)), dtype=dtype)
    with step_part("mlp"):
        x = x + _mlp(bp["mlp"], _norm(bp["ln2"], x, cfg), dtype, cfg)
        x = x.astype(dtype)
        if half_at is None:
            return x, cache_kv
        # `.set`, not `.add`: the list's tail repeats its last live entry,
        # and every repeat carries the same new row.
        return (h.reshape(b * w, -1).at[half_at].set(x).reshape(h.shape),
                cache_kv)


def transformer_step_rows_ragged(params, tokens, caches: KVCache, tables,
                                 pos0, qlen, cfg: TransformerConfig, *,
                                 dtype=jnp.bfloat16, attn_fn=None,
                                 sample_slot=None, sample_width: int = 1,
                                 scales: Optional[KVCache] = None,
                                 max_tokens: Optional[int] = None):
    """The mixed prefill+decode primitive (runtime.scheduler's ragged
    tick): one ragged batch where each row consumes qlen[b] >= 0
    new tokens, writing their KV straight into the row's pool blocks in
    the SAME dispatch. tokens: (B, W) int32 right-aligned at slot 0;
    caches: (L, NB, bs, H_kv*D) pool pair, updated in place through the
    layer loop (`_scan_layers_paged`: donate it and a tick copies no
    layer of it); tables: (B, nb) block tables; pos0: (B,) logical
    column of each row's first slot; qlen: (B,) valid slots.
    ``attn_fn`` defaults to
    `ops.paged_attention.default_ragged_attention()`.

    ``sample_slot`` (B,) selects ONE slot per row to project through the
    LM head — the scheduler samples exactly one token per row per tick
    (decode rows: slot 0; completing rows: slot L-1-pos0), and gathering
    the hidden state BEFORE ln_f/head turns the (B*W, d)x(d, vocab)
    matmul into (B, d)x(d, vocab) on the per-tick hot path (ln_f and the
    head are per-position, so the selected slot's logits are bit-equal
    either way). ``sample_width`` > 1 widens the gather to the VERIFY
    WINDOW of speculative decoding: slots sample_slot[b]..sample_slot[b]
    + sample_width - 1 (clipped to W-1; slots past qlen are padding the
    caller ignores) project through the head, so one dispatch yields the
    per-position logits that score a whole draft window while rows that
    only sample once still pay a (B*S, d)x(d, vocab) head, not
    (B*W, d)x(d, vocab). Returns (logits (B, vocab), caches) — or
    (B, sample_width, vocab) when sample_width > 1, or (B, W, vocab)
    when ``sample_slot`` is None.

    ``scales`` (KVCache of (L, NB, bs, H_kv) f32) switches to the
    QUANTIZED int8 pool — new-token KV quantizes at its in-dispatch
    write, the default ``attn_fn`` becomes the quantized ragged read
    path, and the caches return grows to (..., caches, scales).

    ``max_tokens``: a static bound on the valid slots of one call,
    sum(qlen) <= max_tokens (the scheduler's token budget plus a token a
    row). Stated, and at a width above 1, each layer's pool write
    scatters that many indices — the tick's tokens, listed once a step
    (`models.tick_tokens`) — where it otherwise scatters all B x W slots,
    most of them padding sent to the null block, and each layer's
    output projection and dense feed-forward run over that many rows
    (`second_half_slots`: three consumers of the list a layer, one
    write-back) where they otherwise run over all B x W. Logits at the
    valid slots and every block but the null one are the same either
    way; tokens past a bound the caller broke would be lost, so the
    caller checks it."""
    if attn_fn is None:
        from tpu_engine.ops.paged_attention import (
            default_quant_ragged_attention,
            default_ragged_attention,
        )

        attn_fn = (default_quant_ragged_attention() if scales is not None
                   else default_ragged_attention())
    if cfg.sliding_window is not None:
        raise NotImplementedError(
            "sliding_window models are not supported by the paged KV "
            "cache (use the dense scheduler)")
    b, w = tokens.shape
    with step_part("embed"):
        h = nn.embedding(params["tok_embed"], tokens)
        if cfg.pos == "learned":
            logical = jnp.clip(pos0[:, None] + jnp.arange(w)[None, :], 0,
                               params["pos_embed"]["table"].shape[0] - 1)
            h = h + params["pos_embed"]["table"][logical]
        h = h.astype(dtype)
    # One list a step, outside the layer loop, where it is shorter than
    # the step's slots.
    n_listed = pool_write_slots(b, w, max_tokens)
    listed = None
    if n_listed < b * w:
        from tpu_engine.models.tick_tokens import tick_tokens

        tt = tick_tokens(pos0, qlen, w, max_tokens, n_tiles=n_listed)
        blk, off = tt.blocks(tables, caches.k.shape[2])
        with step_part("plan"):
            listed = (tt.row * w + tt.slot, blk, off)
    half_at = (listed[0] if second_half_slots(cfg, b, w, max_tokens) < b * w
               else None)

    def block(bp, h, cache_kv, layer):
        return _block_step_rows_ragged(
            bp, h, cache_kv, layer, tables, pos0, qlen, cfg, dtype=dtype,
            attn_fn=attn_fn, listed=listed, half_at=half_at)

    h, *pool = _scan_layers_paged(block, params, h, caches, scales)
    with step_part("head"):
        if sample_slot is not None:
            slots = jnp.minimum(sample_slot[:, None]
                                + jnp.arange(sample_width)[None, :], w - 1)
            h = h[jnp.arange(b)[:, None], slots]          # (B, S, d)
        h = _norm(params["ln_f"], h, cfg)
        logits = nn.dense(params["head"], h,
                          dtype=dtype).astype(jnp.float32)
        if sample_slot is not None and sample_width == 1:
            logits = logits[:, 0]
    return (logits, *pool)


def _block_decode_window(bp, h, cache_kv, pos_vec, cfg: TransformerConfig, *,
                         dtype, start_vec):
    """Width-W decode with PER-ROW cache positions — the speculative-decode
    verify primitive. h: (B, W, d_model); row b writes cache columns
    [pos_vec[b], pos_vec[b]+W) and each window query attends causally to
    its own column and everything before it (>= start_vec[b]).

    The whole window's K/V is scattered into the cache BEFORE the attention
    matmul, so window query w attends fresh values for columns <= its own —
    stale entries from a previous round's rejected speculation are always
    either overwritten first or masked out (kpos <= own column)."""
    ck, cv = cache_kv
    b, w = h.shape[:2]
    x = _norm(bp["ln1"], h, cfg)
    offs = jnp.arange(w)[None, :]                           # (1, W)
    logical = (pos_vec - start_vec)[:, None] + offs          # (B, W)
    q, k, v = _project_qkv(bp, x, cfg, dtype=dtype, positions=logical)
    rows = jnp.arange(b)[:, None]
    cols = pos_vec[:, None] + offs                           # (B, W)
    ck = ck.at[rows, cols].set(k.astype(ck.dtype))
    cv = cv.at[rows, cols].set(v.astype(cv.dtype))
    kpos = jnp.arange(ck.shape[1])[None, None, :]            # (1, 1, S)
    valid = ((kpos <= cols[:, :, None]) &
             (kpos >= start_vec[:, None, None]))
    if cfg.sliding_window is not None:
        valid = valid & (kpos > cols[:, :, None] - cfg.sliding_window)
    valid = valid.astype(jnp.int32)
    a = dot_product_attention(q, ck, cv, mask=valid)  # grouped, unexpanded
    h = h + nn.dense(bp["attn"]["wo"], a.reshape(b, w, -1), dtype=dtype)
    h = h + _mlp(bp["mlp"], _norm(bp["ln2"], h, cfg), dtype, cfg)
    return h.astype(dtype), (ck, cv)


def transformer_decode_window(params, tokens, caches: KVCache, pos_vec,
                              cfg: TransformerConfig, *, dtype=jnp.bfloat16,
                              start_vec=None, head: str = "all"):
    """Consume a W-token window per row against the KV cache in ONE pass.

    tokens: (B, W) int32 — row b's stream tokens at absolute cache columns
    [pos_vec[b], pos_vec[b]+W); start_vec: (B,) first valid column per row
    (left-padded batches). Returns (logits, caches) where logits[:, i]
    predicts the token AFTER tokens[:, i].

    `head` controls the LM-head projection — the (W, vocab) matmul
    dominates a window's FLOPs on small models: "all" projects every slot
    ((B, W, vocab) — speculative verify needs them all), "last" only the
    final slot ((B, 1, vocab) — the final window of a chunked prefill),
    "none" skips it entirely (logits is None — interior prefill windows,
    which only exist to write KV).

    Columns below start_vec may be written with garbage values by window
    slots that precede a short row's prompt — they are never attended
    (mask kpos >= start). Callers must keep pos_vec + W <= max_seq."""
    if start_vec is None:
        start_vec = jnp.zeros_like(pos_vec)
    b, w = tokens.shape
    h = nn.embedding(params["tok_embed"], tokens)
    if cfg.pos == "learned":
        logical = jnp.clip(
            (pos_vec - start_vec)[:, None] + jnp.arange(w)[None, :],
            0, params["pos_embed"]["table"].shape[0] - 1)
        h = h + params["pos_embed"]["table"][logical]
    h = h.astype(dtype)

    def body(carry, layer):
        bp, ck, cv = layer
        h, (ck, cv) = _block_decode_window(bp, carry, (ck, cv), pos_vec, cfg,
                                           dtype=dtype, start_vec=start_vec)
        return h, (ck, cv)

    h, (k_new, v_new) = jax.lax.scan(body, h, (params["blocks"], caches.k, caches.v))
    if head == "none":
        return None, KVCache(k_new, v_new)
    if head == "last":
        h = h[:, -1:]
    h = _norm(params["ln_f"], h, cfg)
    logits = nn.dense(params["head"], h, dtype=dtype).astype(jnp.float32)
    return logits, KVCache(k_new, v_new)


def transformer_decode_step(params, token_t, caches: KVCache, pos,
                            cfg: TransformerConfig, *, dtype=jnp.bfloat16,
                            start=None, pos_ids=None):
    """One decode step. token_t: (B,) int32; pos: traced scalar write offset.
    Returns (logits (B, vocab), caches). Compiles once; shapes are static.

    `start` (B,) marks each sample's first valid cache column (left-padded
    batches); `pos_ids` (B,) overrides the logical position per sample
    (position-embedding index / rotary phase; defaults to `pos` for all)."""
    b = token_t.shape[0]
    h = nn.embedding(params["tok_embed"], token_t[:, None])
    logical = (jnp.full((b,), pos, jnp.int32) if pos_ids is None
               else jnp.asarray(pos_ids))
    if cfg.pos == "learned":
        h = h + params["pos_embed"]["table"][logical][:, None, :]
    h = h.astype(dtype)
    rope_pos = logical[:, None] if cfg.pos == "rope" else None

    def body(carry, layer):
        bp, ck, cv = layer
        h, (ck, cv) = _block_decode(bp, carry, (ck, cv), pos, cfg,
                                    dtype=dtype, prefill=False, start=start,
                                    pos_ids=rope_pos)
        return h, (ck, cv)

    h, (k_new, v_new) = jax.lax.scan(body, h, (params["blocks"], caches.k, caches.v))
    h = _norm(params["ln_f"], h, cfg)
    logits = nn.dense(params["head"], h, dtype=dtype).astype(jnp.float32)
    return logits[:, 0], KVCache(k_new, v_new)
