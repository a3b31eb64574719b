"""The mistral (llama) dialect: RMSNorm, rotary positions (rotate-half,
theta from the file), SwiGLU MLP, grouped-query attention (query head h
reads KV head h // (n_heads // n_kv_heads)), no sliding window. Departures
from the published model, which the served program shares: the LM head is a
separate matrix, and the projections carry zero biases.

Sizes read from the configuration's `reference` block: n_heads, n_kv_heads,
ln_eps, rope_theta. Parameter tree: tok_embed, blocks{ln1, attn, ln2,
mlp{gate, up, proj}} stacked on a leading layer axis, ln_f, head."""

import jax

from references._plain import attention, dense, rmsnorm


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32."""
    sizes = dict(sizes)
    eps = sizes["ln_eps"]
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][tokens]

        def block(x, p):
            x = x + attention(p["attn"], rmsnorm(p["ln1"], x, eps),
                              sizes["n_heads"], sizes["n_kv_heads"],
                              sizes["rope_theta"])
            h = rmsnorm(p["ln2"], x, eps)
            x = x + dense(p["mlp"]["proj"], jax.nn.silu(
                dense(p["mlp"]["gate"], h)) * dense(p["mlp"]["up"], h))
            return x, None

        x, _ = jax.lax.scan(block, x, params["blocks"])
        return dense(params["head"], rmsnorm(params["ln_f"], x, eps))
