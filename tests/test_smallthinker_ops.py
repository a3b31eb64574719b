"""What the SmallThinker family asks of the shared ops, at a small size on
the CPU: a gated expert bank whose gate is ReLU (`ops.moe.routed_experts`,
SiLU the default and every other caller's program as it was), and the
window read by the class of a row's run at seven query heads a KV head in
the Pallas interpreter (`ops.paged_attention.ragged_read_by_class` handed
`window`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.ops import moe
from tpu_engine.ops import paged_attention as pa


def test_a_gated_bank_takes_its_gate_s_activation():
    """`routed_experts(..., activation=relu)` on a `gate_up` bank against a
    loop over experts; SiLU stays the default."""
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    n, d, f, e, top = 13, 32, 24, 8, 3
    x = jax.random.normal(k[0], (n, d))
    bank = {"gate_up": jax.random.normal(k[1], (e, d, 2 * f)) / 6,
            "down": jax.random.normal(k[2], (e, f, d)) / 5}
    experts, weights = moe.softmax_topk_route(
        x, {"kernel": jax.random.normal(k[3], (d, e))}, top)
    valid = jnp.arange(n) != 4

    def looped(act):
        """Every expert over every token, masked by the router's choice."""
        gates = np.zeros((n, e), np.float32)
        np.put_along_axis(gates, np.asarray(experts), np.asarray(weights), 1)
        gates *= np.asarray(valid)[:, None]
        y = np.zeros((n, d), np.float32)
        for e_id in range(e):
            gate, up = np.split(np.asarray(x @ bank["gate_up"][e_id]), 2, -1)
            hidden = np.asarray(act(jnp.asarray(gate))) * up
            y += gates[:, e_id:e_id + 1] * np.asarray(
                hidden @ bank["down"][e_id])
        return y

    def ours(**kw):
        y, rows = moe.routed_experts(
            x, valid, experts, weights, bank, first_group=0, n_experts=e,
            dtype=jnp.float32, **kw)
        assert int(rows.sum()) == (n - 1) * top
        return np.asarray(y)

    relu, silu = ours(activation=jax.nn.relu), ours()
    assert np.abs(relu - looped(jax.nn.relu)).max() < 1e-4
    assert np.abs(silu - looped(jax.nn.silu)).max() < 1e-4
    assert np.abs(relu - silu).max() > 0.05


def test_a_bank_that_states_no_activation_traces_as_it_did():
    """SwiGLU is the default: the program of a caller that states none is
    the one `activation=jax.nn.silu` gives, `silu(gate) * up`, a `logistic`
    and no `max`; ReLU's is the other way round."""
    bank = {"gate_up": jnp.zeros((4, 16, 32)), "down": jnp.zeros((4, 16, 16))}

    def traced(**kw):
        return str(jax.make_jaxpr(lambda x, e, w: moe.routed_experts(
            x, jnp.ones((6,), bool), e, w, bank, first_group=0, n_experts=4,
            dtype=jnp.float32, **kw))(
            jnp.zeros((6, 16)), jnp.zeros((6, 2), jnp.int32),
            jnp.zeros((6, 2))))

    default = traced()
    assert default == traced(activation=jax.nn.silu)
    assert "logistic" in default and " max " not in default
    relu = traced(activation=jax.nn.relu)
    assert " max " in relu and "logistic" not in relu



# -- the window read by class -------------------------------------------------

@pytest.mark.parametrize("case", sorted(pa.WINDOW_CLASS_CASES))
def test_the_window_read_by_class_equals_its_reference_at_seven_heads(case):
    """Interpret mode, float32, G = 7: a tall tile whose window opens inside
    it beside decode rows past the window (null blocks behind them), short
    of it and on its edge. (A window wider than several groups of the walk
    is a `WINDOW_CASES` entry: tests/test_laguna.py runs them all.)"""
    assert pa.window_class_parity_check(case, 7, interpret=True) < 1e-5
