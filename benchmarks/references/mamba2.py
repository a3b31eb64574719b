"""The mamba2 dialect: a stack of gated SSD mixers (Mamba-2), no attention
and no positions. One block, on the RMS-normed residual stream u (T, d):

  in-projection     u -> [z | x | B | C | dt]   (d_inner, d_inner, N, N, H)
  short conv        x <- silu(depthwise causal conv of width d_conv + bias)
  rates             dt <- softplus(dt + dt_bias);  A = -exp(A_log), per head
  SSD recurrence    s_t = exp(dt_t A) s_{t-1} + dt_t x_t (outer) B_t
                    y_t = s_t C_t + D x_t       as a plain scan over time,
                    state (H, P, N) from zero, B and C shared by the heads
  gate, norm, out   out_proj(rmsnorm(y * silu(z)))

Sizes read from the configuration's `reference` block: n_heads, d_state,
ln_eps (d_inner and d_conv are the arrays' own shapes). Parameter tree:
tok_embed, blocks{ln, in_proj, conv_w (d_conv, d_inner), conv_b, A_log,
dt_bias, D, gate_norm, out_proj} stacked on a leading layer axis, ln_f, head.
The recurrence is sequential in T: one scan step a token and layer, so a
sample of some hundred tokens is what this reference is for."""

import jax
import jax.numpy as jnp

from references._plain import dense, rmsnorm


def _mixer(p, u, n_heads, d_state, eps):
    t = u.shape[0]
    d_conv, d_inner = p["conv_w"].shape
    z, x, b, c, dt = jnp.split(
        dense(p["in_proj"], u),
        [d_inner, 2 * d_inner, 2 * d_inner + d_state,
         2 * d_inner + 2 * d_state], axis=-1)
    # Tap j of the window reads the input d_conv - 1 - j tokens back.
    back = jnp.pad(x, ((d_conv - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(back[j:j + t] * p["conv_w"][j]
                        for j in range(d_conv)) + p["conv_b"])
    dt = jax.nn.softplus(dt + p["dt_bias"])                     # (T, H)
    a = -jnp.exp(p["A_log"])                                    # (H,)
    x = x.reshape(t, n_heads, -1)                               # (T, H, P)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (state * jnp.exp(dt_t * a)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t)     # (H, P, N)
        return state, state @ c_t                               # (H, P)

    zero = jnp.zeros((n_heads, x.shape[-1], d_state), jnp.float32)
    _, y = jax.lax.scan(step, zero, (x, dt, b, c))
    y = (y + p["D"][:, None] * x).reshape(t, d_inner)
    return dense(p["out_proj"],
                 rmsnorm(p["gate_norm"], y * jax.nn.silu(z), eps))


def forward(params, tokens, sizes):
    """tokens: (T,) int32 -> logits (T, vocab) float32."""
    sizes = dict(sizes)
    eps = sizes["ln_eps"]
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["table"][tokens]

        def block(x, p):
            return x + _mixer(p, rmsnorm(p["ln"], x, eps), sizes["n_heads"],
                              sizes["d_state"], eps), None

        x, _ = jax.lax.scan(block, x, params["blocks"])
        return dense(params["head"], rmsnorm(params["ln_f"], x, eps))
