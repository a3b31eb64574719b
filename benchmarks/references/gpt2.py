"""The gpt2 dialect: LayerNorm, learned positions, tanh-GELU MLP,
multi-head attention. Departure from the published model, which the served
program shares: the LM head is a separate matrix (the checkpoint ties it to
the embedding).

Sizes read from the configuration's `reference` block: n_heads, n_kv_heads,
ln_eps. Parameter tree: tok_embed, pos_embed, blocks{ln1, attn, ln2,
mlp{fc, proj}} stacked on a leading layer axis, ln_f, head."""

import jax

from references._plain import attention, dense, layernorm


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32."""
    sizes = dict(sizes)
    eps = sizes["ln_eps"]
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][tokens]
        x = x + params["pos_embed"]["table"][: tokens.shape[0]]

        def block(x, p):
            x = x + attention(p["attn"], layernorm(p["ln1"], x, eps),
                              sizes["n_heads"], sizes["n_kv_heads"])
            h = layernorm(p["ln2"], x, eps)
            x = x + dense(p["mlp"]["proj"], jax.nn.gelu(
                dense(p["mlp"]["fc"], h), approximate=True))
            return x, None

        x, _ = jax.lax.scan(block, x, params["blocks"])
        return dense(params["head"], layernorm(params["ln_f"], x, eps))
