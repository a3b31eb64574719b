"""SSD/Mamba-style recurrent decoder — the engine's O(1)-state model
family (``state_slab``).

Where the transformer family's autoregressive state is a KV cache that
GROWS linearly with the stream (paged into blocks by
``runtime.kv_blocks.BlockPool``), this family's whole per-stream state
is a FIXED-size slab: per layer, a short-conv tail of the last
``d_conv - 1`` pre-activation inputs plus the selective-SSM state
``(n_heads, head_dim, d_state)`` — constant in sequence length
(``runtime.kv_blocks.StateSlabPool`` holds one ``(n_layers, state_dim)``
row per stream). The Compiler-First State Space Duality paper
(PAPERS.md) is the source; VirtualFlow's model/serving decoupling is the
registry framing (``ModelSpec.state_family`` selects the machinery).

Block = gated SSD mixer (Mamba-2 shape):

  in_proj(d_model) → [z | x | B | C | dt]
  x → depthwise short conv (window d_conv, cached tail) → silu
  dt → softplus(dt + dt_bias);  A = -exp(A_log) per head
  SSD update (ops.ssd.ssd_step) + D·x skip
  RMSNorm(y * silu(z)) → out_proj → residual

Serving uses the O(1) recurrence for BOTH prefill and decode
(`ssd_step_rows` scanned over prompt windows): the recurrence is
partition-invariant, so any chunking of the prompt — budgeted chunks of
any width, a crash-replay (prompt ⧺ emitted) resume —
produces bit-identical state, which is what makes greedy streams
byte-identical across token budgets (tested). The chunked
matmul-form prefill (`ssd_prefill_chunked`, ops.ssd.ssd_chunked) is the
on-chip throughput path, held to the recurrence by
``ops.ssd.ssd_parity_check``.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar, NamedTuple

import jax
import jax.numpy as jnp

from tpu_engine.models.registry import ModelSpec, register
from tpu_engine.ops import nn
from tpu_engine.ops.ssd import ssd_chunked, ssd_step


@dataclasses.dataclass(frozen=True)
class SSDConfig:
    vocab: int = 50257
    n_layers: int = 24
    d_model: int = 768
    d_state: int = 64        # N: SSM state width (shared across heads)
    d_conv: int = 4          # short-conv window (cached tail = d_conv - 1)
    expand: int = 2          # d_inner = expand * d_model
    n_heads: int = 8         # SSD heads over d_inner
    max_seq: int = 1024      # stream-length cap (engine limit, not memory)
    ln_eps: float = 1e-5
    ssd_chunk: int = 16      # matmul-form chunk (prefill fast path)
    # The serving scheduler dispatches by family: this config's streams
    # hold a fixed state slab, never a KV block chain.
    serving_state_family: ClassVar[str] = "state_slab"
    # Tensor parallelism is REFUSED for this family (registry.tp_rule
    # contract): the depthwise short-conv tail mixes channels per
    # position with no heads axis to split, and the fused state slab
    # (conv tail ⧺ SSM state flattened per row) has no per-device
    # partition that survives the flatten/unflatten round trip — a
    # heuristic shard would corrupt the recurrence silently. --tp on a
    # mamba2-family worker is a pinned RuntimeError at startup.
    tp_partition_rule: ClassVar[str] = (
        "unshardable: the mamba2 depthwise conv tail and fused state "
        "slab rows have no heads axis to shard")
    # Autoregressive decoder by construction (registry capability check).
    causal: ClassVar[bool] = True

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def head_dim(self) -> int:
        if self.d_inner % self.n_heads:
            raise ValueError(f"d_inner={self.d_inner} must divide by "
                             f"n_heads={self.n_heads}")
        return self.d_inner // self.n_heads


def ssd_state_dim(cfg: SSDConfig) -> int:
    """Flattened per-layer recurrent state width — the slab pool's row
    geometry: conv tail (d_conv-1, d_inner) ⧺ SSM state (H, P, N)."""
    return ((cfg.d_conv - 1) * cfg.d_inner
            + cfg.n_heads * cfg.head_dim * cfg.d_state)


class SSDState(NamedTuple):
    """Per-layer recurrent state for a batch of rows (leading layer axis
    so `jax.lax.scan` over stacked blocks threads it naturally)."""
    conv: jnp.ndarray   # (L, B, d_conv - 1, d_inner)
    ssm: jnp.ndarray    # (L, B, H, P, N)


def ssd_init_states(cfg: SSDConfig, batch: int) -> SSDState:
    return SSDState(
        jnp.zeros((cfg.n_layers, batch, cfg.d_conv - 1, cfg.d_inner),
                  jnp.float32),
        jnp.zeros((cfg.n_layers, batch, cfg.n_heads, cfg.head_dim,
                   cfg.d_state), jnp.float32))


def flatten_states(states: SSDState) -> jnp.ndarray:
    """SSDState → (L, B, state_dim) — the slab pool's row layout.
    Order (conv ⧺ ssm) is part of the chain wire format: an exported
    slab must unflatten identically on the importing lane."""
    L, B = states.conv.shape[0], states.conv.shape[1]
    return jnp.concatenate([states.conv.reshape(L, B, -1),
                            states.ssm.reshape(L, B, -1)], axis=-1)


def unflatten_states(flat, cfg: SSDConfig) -> SSDState:
    """(L, B, state_dim) → SSDState (inverse of `flatten_states`)."""
    L, B = flat.shape[0], flat.shape[1]
    split = (cfg.d_conv - 1) * cfg.d_inner
    return SSDState(
        flat[..., :split].reshape(L, B, cfg.d_conv - 1, cfg.d_inner),
        flat[..., split:].reshape(L, B, cfg.n_heads, cfg.head_dim,
                                  cfg.d_state))


def _block_init(key, cfg: SSDConfig):
    k_in, k_conv, k_dt, k_out = jax.random.split(key, 4)
    di, N, H = cfg.d_inner, cfg.d_state, cfg.n_heads
    return {
        "ln": nn.rmsnorm_init(cfg.d_model),
        "in_proj": nn.dense_init(k_in, cfg.d_model, 2 * di + 2 * N + H),
        "conv_w": (jax.random.normal(k_conv, (cfg.d_conv, di), jnp.float32)
                   * (1.0 / jnp.sqrt(cfg.d_conv))),
        "conv_b": jnp.zeros((di,), jnp.float32),
        # A_log = log(1..H): the standard Mamba spread of per-head decay
        # rates; dt_bias centers softplus around ~0.7 with a small jitter
        # so random-init test models produce distinguishable streams.
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "dt_bias": 0.1 * jax.random.normal(k_dt, (H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "gate_norm": nn.rmsnorm_init(di),
        "out_proj": nn.dense_init(k_out, di, cfg.d_model),
    }


def ssd_init(key, cfg: SSDConfig):
    k_tok, k_blocks, k_head = jax.random.split(key, 3)
    block_keys = jax.random.split(k_blocks, cfg.n_layers)
    return {
        "tok_embed": nn.embedding_init(k_tok, cfg.vocab, cfg.d_model),
        "blocks": jax.vmap(lambda k: _block_init(k, cfg))(block_keys),
        "ln_f": nn.rmsnorm_init(cfg.d_model),
        "head": nn.dense_init(k_head, cfg.d_model, cfg.vocab),
    }


def _mixer_step(bp, h_norm, conv_s, ssm_s, cfg: SSDConfig):
    """One layer, one token, batch of rows: (B, d_model) normalized
    hidden + per-row state → (mixer output (B, d_model), new conv state,
    new ssm state). All state math in f32 — the recurrence accumulates,
    so the slab stays full precision regardless of the engine dtype."""
    di, N, H, P = cfg.d_inner, cfg.d_state, cfg.n_heads, cfg.head_dim
    proj = nn.dense(bp["in_proj"], h_norm, dtype=jnp.float32)
    z = proj[:, :di]
    xr = proj[:, di:2 * di]
    Bv = proj[:, 2 * di:2 * di + N]
    Cv = proj[:, 2 * di + N:2 * di + 2 * N]
    dt = proj[:, 2 * di + 2 * N:]
    # Depthwise short conv over the cached tail + this token.
    window = jnp.concatenate([conv_s, xr[:, None, :]], axis=1)  # (B, K, di)
    xc = jax.nn.silu(jnp.einsum("bkd,kd->bd", window, bp["conv_w"])
                     + bp["conv_b"])
    new_conv = window[:, 1:]
    dtp = jax.nn.softplus(dt + bp["dt_bias"])                   # (B, H)
    A = -jnp.exp(bp["A_log"])
    xh = xc.reshape(-1, H, P)
    y_h, new_ssm = ssd_step(ssm_s, xh, dtp, A, Bv, Cv)
    y = (y_h + bp["D"][None, :, None] * xh).reshape(-1, di)
    y = nn.rmsnorm(bp["gate_norm"], y * jax.nn.silu(z), eps=cfg.ln_eps)
    return nn.dense(bp["out_proj"], y, dtype=jnp.float32), new_conv, new_ssm


def ssd_step_rows(params, tok, states: SSDState, cfg: SSDConfig):
    """One decode step for a batch of rows — the family's step function
    the continuous scheduler dispatches through. tok (B,) int32 token
    ids (done rows may carry -1: the embedding wrap is harmless, their
    state is masked by the caller) → (logits (B, vocab) f32, new
    states)."""
    h = nn.embedding(params["tok_embed"], tok).astype(jnp.float32)

    def body(h, layer):
        bp, conv_s, ssm_s = layer
        out, new_conv, new_ssm = _mixer_step(
            bp, nn.rmsnorm(bp["ln"], h, eps=cfg.ln_eps), conv_s, ssm_s, cfg)
        return h + out, (new_conv, new_ssm)

    h, (conv2, ssm2) = jax.lax.scan(
        body, h, (params["blocks"], states.conv, states.ssm))
    h = nn.rmsnorm(params["ln_f"], h, eps=cfg.ln_eps)
    return nn.dense(params["head"], h, dtype=jnp.float32), \
        SSDState(conv2, ssm2)


def ssd_step_rows_masked(params, tok, states: SSDState, valid,
                         cfg: SSDConfig):
    """`ssd_step_rows` with per-row state freezing: rows where ``valid``
    is False compute (ride the batch) but keep their old state — the
    primitive that makes window width irrelevant to the state a prompt
    produces (each real token is exactly one step of the same math)."""
    logits, new = ssd_step_rows(params, tok, states, cfg)
    conv = jnp.where(valid[None, :, None, None], new.conv, states.conv)
    ssm = jnp.where(valid[None, :, None, None, None], new.ssm, states.ssm)
    return logits, SSDState(conv, ssm)


def ssd_window_scan(params, tokens, states: SSDState, qlen, sample_slot,
                    cfg: SSDConfig):
    """Consume up to W prompt tokens per row from the rows' current
    states — the budgeted-prefill-chunk form of the mixed tick's
    ragged rows. tokens (B, W); row r advances through its first
    ``qlen[r]`` slots (the rest are padding); the returned logits are
    each row's slot ``sample_slot[r]`` output (garbage for rows whose
    sampled slot lies in another window — callers gate on completion)."""
    B, W = tokens.shape
    kept0 = jnp.zeros((B, cfg.vocab), jnp.float32)

    def body(carry, inp):
        states, kept = carry
        j, tok_j = inp
        logits, states = ssd_step_rows_masked(params, tok_j, states,
                                              j < qlen, cfg)
        kept = jnp.where((j == sample_slot)[:, None], logits, kept)
        return (states, kept), None

    (states, kept), _ = jax.lax.scan(
        body, (states, kept0), (jnp.arange(W), tokens.T))
    return kept, states


def ssd_prefill_chunked(params, tokens, cfg: SSDConfig):
    """One-shot whole-prompt prefill in the chunked MATMUL form — the
    throughput dual of `ssd_window_scan` (ops.ssd.ssd_chunked per
    layer). tokens (B, T) → (last-position logits (B, vocab), final
    states). Equal to the recurrence up to float association; the
    serving path keeps the recurrence for byte-identity, this form is
    the on-chip prefill fast path (tests pin the model-level parity)."""
    B, T = tokens.shape
    di, N, H, P = cfg.d_inner, cfg.d_state, cfg.n_heads, cfg.head_dim
    h = nn.embedding(params["tok_embed"], tokens).astype(jnp.float32)

    def body(h, layer):
        bp, _conv0, _ssm0 = layer
        x = nn.rmsnorm(bp["ln"], h, eps=cfg.ln_eps)       # (B, T, d_model)
        proj = nn.dense(bp["in_proj"], x, dtype=jnp.float32)
        z = proj[..., :di]
        xr = proj[..., di:2 * di]
        Bv = proj[..., 2 * di:2 * di + N]
        Cv = proj[..., 2 * di + N:2 * di + 2 * N]
        dt = proj[..., 2 * di + 2 * N:]
        # Causal depthwise conv from a zero tail (fresh prompt).
        xp = jnp.pad(xr, ((0, 0), (cfg.d_conv - 1, 0), (0, 0)))
        xc = sum(xp[:, k:k + T] * bp["conv_w"][k]
                 for k in range(cfg.d_conv)) + bp["conv_b"]
        xc = jax.nn.silu(xc)
        new_conv = xp[:, T:T + cfg.d_conv - 1]            # last K-1 inputs
        dtp = jax.nn.softplus(dt + bp["dt_bias"])
        A = -jnp.exp(bp["A_log"])
        xh = xc.reshape(B, T, H, P)
        y_h, final = ssd_chunked(xh, dtp, A, Bv, Cv, chunk=cfg.ssd_chunk)
        y = (y_h + bp["D"][None, None, :, None] * xh).reshape(B, T, di)
        y = nn.rmsnorm(bp["gate_norm"], y * jax.nn.silu(z), eps=cfg.ln_eps)
        return h + nn.dense(bp["out_proj"], y, dtype=jnp.float32), \
            (new_conv, final)

    zeros = ssd_init_states(cfg, B)
    h, (conv2, ssm2) = jax.lax.scan(
        body, h, (params["blocks"], zeros.conv, zeros.ssm))
    h = nn.rmsnorm(params["ln_f"], h[:, -1], eps=cfg.ln_eps)
    return nn.dense(params["head"], h, dtype=jnp.float32), \
        SSDState(conv2, ssm2)


# -- registry ----------------------------------------------------------------

def _spec_from_config(name: str, cfg: SSDConfig, seq_len: int) -> ModelSpec:
    def init(rng):
        return ssd_init(rng, cfg)

    def apply(params, x, dtype=jnp.bfloat16):
        # One-shot /infer contract (flat float token ids → last real
        # position's logits), matching the gpt2 family's wire shape.
        tokens = jnp.clip(x.astype(jnp.int32), 0, cfg.vocab - 1)
        positions = jnp.arange(tokens.shape[1])[None, :]
        nonpad = jnp.where(tokens > 0, positions, 0)
        last = jnp.max(nonpad, axis=1)
        states = ssd_init_states(cfg, tokens.shape[0])
        logits, _ = ssd_window_scan(
            params, tokens, states,
            qlen=last + 1, sample_slot=last, cfg=cfg)
        return logits

    return ModelSpec(
        name=name,
        apply=apply,
        init=init,
        input_shape=(seq_len,),
        output_shape=(cfg.vocab,),
        config=cfg,
    )


@register("mamba2")
def make_mamba2(seq_len: int = 128, vocab: int = 50257, n_layers: int = 24,
                d_model: int = 768, d_state: int = 64, n_heads: int = 24,
                max_seq: int = 4096) -> ModelSpec:
    """Mamba-2-shaped SSD decoder: O(1) per-stream serving state —
    max_seq caps stream LENGTH (an engine limit), never state memory."""
    cfg = SSDConfig(vocab=vocab, n_layers=n_layers, d_model=d_model,
                    d_state=d_state, n_heads=n_heads, max_seq=max_seq)
    return _spec_from_config("mamba2", cfg, seq_len)


@register("ssd-small-test")
def make_ssd_small(seq_len: int = 16, vocab: int = 256, n_layers: int = 2,
                   d_model: int = 64, d_state: int = 16, n_heads: int = 4,
                   max_seq: int = 64) -> ModelSpec:
    """Tiny SSD config for tests/CI — same code path, millisecond
    compiles (the state_slab counterpart of gpt2-small-test)."""
    cfg = SSDConfig(vocab=vocab, n_layers=n_layers, d_model=d_model,
                    d_state=d_state, n_heads=n_heads, max_seq=max_seq)
    return _spec_from_config("ssd-small-test", cfg, seq_len)
