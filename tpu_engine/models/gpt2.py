"""GPT-2 family — autoregressive decoder for the generation service.

Reference counterpart: BASELINE.json config 5 ("GPT-2 / distil-Llama ONNX
autoregressive decode"); the reference could only run such a graph one-shot
through ONNX Runtime (`/root/reference/src/inference_engine.cpp:31`) with no
KV cache or decode loop. Here GPT-2 is a JAX program with static-shape
prefill/decode executables (models.transformer) driven by
`tpu_engine.runtime.generator`.

Serving-engine contract (flat float vectors on the wire,
`worker_node.cpp:17`): input = token ids as floats, shape (seq,); output =
next-token logits, shape (vocab,). The generation HTTP surface
(`/generate`) uses the decode loop instead of this one-shot path.
"""

from __future__ import annotations

from tpu_engine.models.registry import ModelSpec, causal_lm_spec, register
from tpu_engine.models.transformer import (
    TransformerConfig,
    step_weights,
    transformer_apply,
    transformer_init,
)


def _spec_from_config(name: str, cfg: TransformerConfig, seq_len: int) -> ModelSpec:
    return causal_lm_spec(
        name, cfg, seq_len, transformer_init, transformer_apply,
        # Megatron-style heads-axis placement (registry.TP_RULES): QKV /
        # MLP-up column-parallel, wo / proj row-parallel, head on vocab,
        # norms + embeddings replicated. Covers every family built on
        # this helper (gpt2, distilgpt2, llama, mistral; MoE expert
        # banks ride replicated under the catch-all).
        tp_rule="transformer",
        # The dense step's kernels are cast to the lane's dtype once, not
        # every tick (transformer.step_weights); an MoE FFN casts its own
        # expert banks (ops.moe), so that family's steps read `params`.
        step_weights=step_weights if cfg.n_experts == 0 else None)


@register("gpt2")
def make_gpt2(seq_len: int = 128, vocab: int = 50257, n_layers: int = 12,
              d_model: int = 768, n_heads: int = 12, d_ff: int = 3072,
              max_seq: int = 1024) -> ModelSpec:
    cfg = TransformerConfig(vocab=vocab, n_layers=n_layers, d_model=d_model,
                            n_heads=n_heads, d_ff=d_ff, max_seq=max_seq,
                            causal=True)
    return _spec_from_config("gpt2", cfg, seq_len)


@register("distilgpt2")
def make_distilgpt2(seq_len: int = 128, vocab: int = 50257, n_layers: int = 6,
                    d_model: int = 768, n_heads: int = 12, d_ff: int = 3072,
                    max_seq: int = 1024) -> ModelSpec:
    """6-layer GPT-2 (HF distilgpt2 architecture) — importable via
    models.import_weights like gpt2, and the natural DRAFT model for
    speculative decoding against a gpt2 target (runtime.speculative)."""
    cfg = TransformerConfig(vocab=vocab, n_layers=n_layers, d_model=d_model,
                            n_heads=n_heads, d_ff=d_ff, max_seq=max_seq,
                            causal=True)
    return _spec_from_config("distilgpt2", cfg, seq_len)


@register("gpt2-small-test")
def make_gpt2_small(seq_len: int = 16, vocab: int = 256, n_layers: int = 2,
                    d_model: int = 64, n_heads: int = 4, d_ff: int = 128,
                    max_seq: int = 64) -> ModelSpec:
    """Tiny config for tests/CI — same code path, millisecond compiles."""
    cfg = TransformerConfig(vocab=vocab, n_layers=n_layers, d_model=d_model,
                            n_heads=n_heads, d_ff=d_ff, max_seq=max_seq,
                            causal=True)
    return _spec_from_config("gpt2-small-test", cfg, seq_len)


@register("gpt2-chaos-test")
def make_gpt2_chaos(seq_len: int = 16, vocab: int = 1024, n_layers: int = 4,
                    d_model: int = 256, n_heads: int = 8, d_ff: int = 1024,
                    max_seq: int = 128) -> ModelSpec:
    """Mid-size config for load/elastic chaos harnesses: big enough that
    CPU decode takes real wall time per token (so slot occupancy is an
    observable, samplable control signal and streams have multi-second
    lifetimes), small enough to compile and serve in CI. gpt2-small-test
    drains a full burst faster than a 4 Hz control loop can sample it —
    useless for autoscaler/overload scenarios; this one is deliberately
    ~100x more compute per token."""
    cfg = TransformerConfig(vocab=vocab, n_layers=n_layers, d_model=d_model,
                            n_heads=n_heads, d_ff=d_ff, max_seq=max_seq,
                            causal=True)
    return _spec_from_config("gpt2-chaos-test", cfg, seq_len)


@register("gpt2-moe")
def make_gpt2_moe(seq_len: int = 128, vocab: int = 50257, n_layers: int = 12,
                  d_model: int = 768, n_heads: int = 12, d_ff: int = 3072,
                  max_seq: int = 1024, n_experts: int = 8, top_k: int = 2,
                  capacity_factor: float = 1.25) -> ModelSpec:
    """GPT-2 with a Mixture-of-Experts FFN in every block — the
    expert-parallel serving family (experts shard over the `expert` mesh
    axis, ops.moe). Same /infer and /generate contracts as gpt2."""
    cfg = TransformerConfig(vocab=vocab, n_layers=n_layers, d_model=d_model,
                            n_heads=n_heads, d_ff=d_ff, max_seq=max_seq,
                            causal=True, n_experts=n_experts,
                            moe_top_k=top_k,
                            moe_capacity_factor=capacity_factor)
    return _spec_from_config("gpt2-moe", cfg, seq_len)


@register("gpt2-moe-test")
def make_gpt2_moe_test(seq_len: int = 16, vocab: int = 256, n_layers: int = 2,
                       d_model: int = 64, n_heads: int = 4, d_ff: int = 128,
                       max_seq: int = 64, n_experts: int = 4) -> ModelSpec:
    """Tiny MoE config; generous capacity so tests are drop-free."""
    cfg = TransformerConfig(vocab=vocab, n_layers=n_layers, d_model=d_model,
                            n_heads=n_heads, d_ff=d_ff, max_seq=max_seq,
                            causal=True, n_experts=n_experts,
                            moe_capacity_factor=4.0)
    return _spec_from_config("gpt2-moe-test", cfg, seq_len)
