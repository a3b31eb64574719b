"""What the Nemotron-H family (tests/test_nemotron_h.py) asks of shared
code, apart from the model: `ops.moe.routed_experts` with a bank of
two-matrix experts (and the SwiGLU bank bit for bit as before), both served
forms of `ops.ssd` in the Pallas interpreter at heads of (64, 128) in 8
groups, the two tile classes of the paged read at sixteen query heads a KV
head, and the reference's controls, each moving its logits. `nemotron_h_small` is the model
the controls run at."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# The small model, its weights and the reference, as the model's tests
# build them (module-scoped fixtures: built again for this file).
from test_nemotron_h import (  # noqa: F401
    _prompt,
    _sizes,
    params,
    reference,
    spec,
)
from tpu_engine.ops import latent_attention as la
from tpu_engine.ops import moe
from tpu_engine.ops import paged_attention as pa
from tpu_engine.ops import ssd

# What `correct` allows a served token's logit to lie under the largest, in
# standard deviations of the logits (the configuration's
# `tolerance_in_logit_std`): a layer kind zeroed has to move them by more.
TOLERANCE = 0.1


# -- the op: a bank of two-matrix experts beside the SwiGLU bank ---------------------

def _routing(n=40, k=6, e=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    scores = jax.random.uniform(ks[0], (n, e))
    chosen, experts = jax.lax.top_k(scores, k)
    valid = jnp.arange(n) % 7 != 3
    return (valid, experts.astype(jnp.int32),
            chosen / chosen.sum(-1, keepdims=True) * 5.0)


@pytest.mark.parametrize("held", [None, (4, 8), (12, 4)])
def test_the_two_matrix_bank_equals_a_loop_over_pairs(held):
    """`routed_experts` over {"up", "down"} with relu^2 handed in, at an
    input narrower than a model's: each valid token's chosen HELD experts
    one pair at a time, in float32; `y` has the input's width and `rows`
    counts the held experts' rows alone."""
    lanes, hidden, e = 16, 24, 16
    first, count = held or (0, e)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (40, lanes))
    bank = {"up": jax.random.normal(ks[1], (count, lanes, hidden)) / 4.0,
            "down": jax.random.normal(ks[2], (count, hidden, lanes)) / 5.0}
    valid, experts, weights = _routing()
    with jax.default_matmul_precision("highest"):
        y, rows = moe.routed_experts(
            x, valid, experts, weights, bank, first_group=-first,
            n_experts=e, held=held, max_tokens=40, dtype=jnp.float32,
            activation=moe.relu2)
    want, taken = np.zeros((40, lanes), np.float32), np.zeros(e, np.int64)
    for t in range(40):
        for j in range(6):
            expert = int(experts[t, j])
            if bool(valid[t]) and first <= expert < first + count:
                hid = np.maximum(np.asarray(x[t]) @ np.asarray(
                    bank["up"][expert - first]), 0.0) ** 2
                want[t] += float(weights[t, j]) * (
                    hid @ np.asarray(bank["down"][expert - first]))
                taken[expert] += 1
    assert y.shape == (40, lanes) and y.dtype == jnp.float32
    np.testing.assert_allclose(y, want, atol=2e-5)
    np.testing.assert_array_equal(rows, taken)


def _routed_experts_as_before(x, valid, experts, weights, bank, *,
                              first_group, n_experts, held, max_tokens,
                              dtype):
    """`ops.moe.routed_experts` as it stood before it took another form of
    bank (PR 49's tree), kept here to hold the SwiGLU bank's result."""
    n, k = experts.shape
    first, count = held or (0, n_experts)
    mine = (valid[:, None] & (experts >= first) & (experts < first + count))
    eid = jnp.where(mine, experts, n_experts).reshape(-1)
    pairs = min(n, max_tokens or n) * k
    order = jnp.argsort(eid, stable=True)[:pairs]
    eid_sorted = eid[order]
    token = order // k
    rows = jnp.zeros((n_experts + 1,), jnp.int32).at[eid].add(1)[:n_experts]
    groups = bank["gate_up"].shape[0]
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((groups,), jnp.int32), rows[first:first + count],
        (first_group + first,))
    xs = x[token].astype(dtype)
    gate_up = jax.lax.ragged_dot(xs, bank["gate_up"].astype(dtype), sizes,
                                 preferred_element_type=jnp.float32)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    hidden = (jax.nn.silu(gate) * up).astype(dtype)
    out = jax.lax.ragged_dot(hidden, bank["down"].astype(dtype), sizes,
                             preferred_element_type=jnp.float32)
    live = (eid_sorted < n_experts)[:, None]
    out = jnp.where(live, out * weights.reshape(-1)[order][:, None], 0.0)
    y = jnp.zeros((n, x.shape[-1]), jnp.float32).at[token].add(out)
    return y, rows


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("held", [None, (4, 8)])
def test_the_swiglu_bank_s_result_is_bit_for_bit_as_before(held, dtype):
    # 64 of 70 slots x 6 = 384 pairs, three whole row tiles: the list is as
    # long as it was (a longer one moves the CPU's bfloat16 sums by an ulp).
    first, count = held or (0, 16)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (70, 32))
    bank = {"gate_up": jax.random.normal(ks[1], (count, 32, 48)) / 5.0,
            "down": jax.random.normal(ks[2], (count, 24, 32)) / 5.0}
    valid, experts, weights = _routing(n=70)
    kw = dict(first_group=-first, n_experts=16, held=held, max_tokens=64,
              dtype=dtype)
    y, rows = jax.jit(lambda *a: moe.routed_experts(*a, **kw))(
        x, valid, experts, weights, bank)
    y_old, rows_old = jax.jit(
        lambda *a: _routed_experts_as_before(*a, **kw))(
        x, valid, experts, weights, bank)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_old))
    np.testing.assert_array_equal(rows, rows_old)


# -- the recurrence at the cell's head shape, in the Pallas interpreter --------------

def _ssd_inputs(t, h=16, p=64, g=8, n=128, seed=0):
    """Heads of (64, 128) in 8 groups, as the cell's 128 are (16 here: two
    a group); dt over softplus's (0.1, 2.5), A over -(0.02, 0.25)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (t, h, p))
    dt = jax.random.uniform(ks[1], (t, h), minval=0.1, maxval=2.5)
    a = -jax.random.uniform(ks[2], (h,), minval=0.02, maxval=0.25)
    b = jax.random.normal(ks[3], (t, g, n)) / n ** 0.5
    c = jax.random.normal(ks[4], (t, g, n))
    return (x, dt, a, b, c), jax.random.normal(ks[5], (2, 4, h, p, n))


@pytest.mark.parametrize("fresh", [False, True])
def test_the_chunk_kernel_at_64_by_128_in_8_groups_equals_its_reference(
        fresh):
    args, pool = _ssd_inputs(128)
    with jax.default_matmul_precision("highest"):
        y, new = ssd.ssd_chunk_row(*args, pool, 1, 2, fresh, interpret=True)
        y_want, want = ssd.ssd_chunk_row_reference(*args, pool, 1, 2, fresh)
        y_scan, last = ssd.ssd_recurrent(
            *(v[None] for v in args[:2]), args[2], args[3][None],
            args[4][None],
            initial_state=(jnp.zeros_like(pool[1, 2]) if fresh
                           else pool[1, 2])[None])
    np.testing.assert_allclose(y, y_want, atol=2e-4)
    np.testing.assert_allclose(new, want, atol=2e-4)
    np.testing.assert_allclose(y, y_scan[0], atol=2e-4)
    np.testing.assert_allclose(new[1, 2], last[0], atol=2e-4)


def test_the_step_kernel_at_64_by_128_in_8_groups_equals_its_reference():
    (x, dt, a, b, c), pool = _ssd_inputs(4)
    rows = jnp.asarray([3, 0, 1, 2])
    live = jnp.asarray([True, False, True, True])
    fresh = jnp.asarray([False, False, True, False])
    args = (x, dt, a, b, c, pool, 1, rows, live, fresh)
    y, new = ssd.ssd_step_rows(*args, interpret=True)
    y_want, want = ssd.ssd_step_rows_reference(*args)
    np.testing.assert_allclose(y[live], y_want[live], atol=1e-5)
    np.testing.assert_allclose(new, want, atol=1e-5)
    assert float(jnp.abs(new[0] - pool[0]).max()) == 0.0
    assert float(jnp.abs(new[1, 0] - pool[1, 0]).max()) == 0.0


# -- the two tile classes at sixteen query heads a KV head ---------------------------

def test_a_group_of_sixteen_packs_two_heads_and_stands_eight_slots_tall():
    assert la.tall_slots(256, 16) == 8
    assert la.tall_slots(256, 16) * 16 == pa._ROW_TILE
    # A decode row: 2 KV heads x 16 query rows in one score tile.
    assert pa._tile_geometry(16, 2)[:2] == (16, 2)
    # A tall tile's 128 query rows a KV head fill the tile alone.
    assert pa._tile_geometry(8 * 16, 2)[:2] == (128, 1)
    # ~43 decode rows and a ~213-token chunk: 43 short tiles, 27 tall.
    qlen = np.asarray([1] * 43 + [213] + [0] * 20)
    assert la.class_counts(qlen, 256, 16) == (43, 27)


@pytest.mark.parametrize("case", ["a-run-of-129-beside-short-rows",
                                  "max-tokens-reached-exactly"])
def test_the_two_classes_at_sixteen_heads_a_kv_head_read_as_the_reference(
        case):
    q_lens, _, max_tokens = pa.CLASS_CASES[case]
    qlen = np.asarray(q_lens, np.int32)
    classes = la.class_plan(jnp.asarray(qlen), 256, 16, max_tokens)
    assert classes.slot.shape[1] == 8
    seen = np.zeros((len(q_lens), 256), np.int32)
    seen[np.asarray(classes.short), 0] += 1
    rows = np.broadcast_to(np.asarray(classes.tall.row)[:, None],
                           classes.slot.shape)
    valid = np.asarray(classes.valid)
    np.add.at(seen, (rows[valid], np.asarray(classes.slot)[valid]), 1)
    np.testing.assert_array_equal(
        seen, np.arange(256)[None, :] < qlen[:, None])
    assert pa.class_parity_check(case, 16, interpret=True) < 2e-5


# -- the reference's controls ------------------------------------------------------

@pytest.mark.parametrize("kind", ["mamba", "attention", "experts"])
def test_a_kind_of_layer_zeroed_moves_the_logits_past_correct_s_tolerance(
        params, reference, kind):
    """A branch that vanished under the draw could not be checked: with
    every layer of one kind adding nothing, the logits past position 48
    move by more than `correct` allows a served token's to lie under the
    largest (in the logits' standard deviations), at most positions."""
    module, sizes = reference
    tokens = jnp.asarray(_prompt(0, 96), jnp.int32)
    want = module.forward(params, tokens, _sizes(sizes))[48:]
    moved = module.forward(params, tokens, _sizes(sizes, drop=kind))[48:]
    gap = jnp.abs(moved - want).max(-1) / want.std(-1)
    assert float(jnp.median(gap)) > 5 * TOLERANCE, float(jnp.median(gap))


@pytest.mark.parametrize("control", [
    {"drop": "group"}, {"drop": "rotate"}, {"drop": "latent"},
    {"drop": "silu"}, {"drop": "bias"}, {"drop": "other_share"},
    {"drop": "conv_tail"}, {"drop": "state_bf16"},
    {"drop": "state_bf16_step"}, {"top_k": 3},
    {"weights_as": "float8_e4m3fn"}])
def test_each_control_moves_the_reference_s_logits(params, reference,
                                                   control):
    module, sizes = reference
    tokens = jnp.asarray(_prompt(0, 70), jnp.int32)
    want = module.forward(params, tokens, _sizes(sizes))
    moved = module.forward(params, tokens, _sizes(sizes, **control))
    # Rounding a state to bfloat16 moves a logit by thousandths; leaving a
    # term out by far more.
    least = 1e-3 if "bf16" in control.get("drop", "") else 0.05
    assert float(jnp.abs(moved - want)[48:].max()) > least


