"""The Kimi-Linear family (models/kimi_linear.py, ops/gated_delta.py with a
gate a key CHANNEL) on the served path: a row that owns a channel-gated
recurrent state for three layers in four AND a LATENT block chain for the
fourth, under a held share of routed experts. `kimi_linear_small` (the
cell's five layers: KDA dense; KDA, KDA, MLA, KDA with experts; 4 heads,
keys and values of 8 lanes, conv 4, 8 of 16 experts held) against the plain
reference benchmarks/references/kimi_linear.py, on logits; the recurrence's
forms against its scan at the strongest decays; the two pools' bookkeeping
by the code that serves `olmo_hybrid_small`; the start-up fences; the two
shares of the experts adding up to the uncut layer."""

import functools
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models import kimi_linear as kl
from tpu_engine.models.registry import (
    FAMILY_CAPABILITIES,
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.ops import gated_delta as gd
from tpu_engine.ops import latent_attention as la
from tpu_engine.ops.attention import KVCache
from tpu_engine.runtime.scheduler import ContinuousGenerator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16
LANE = dict(n_slots=4, dtype="float32", kv_block_size=BS,
            prefill_chunk=16, prefix_sharing=False)


@pytest.fixture(scope="module")
def spec():
    _ensure_builtin_models_imported()
    return create_model("kimi_linear_small")


@pytest.fixture(scope="module")
def params(spec):
    return jax.jit(spec.init)(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def reference():
    """benchmarks/references/kimi_linear.py and the test configuration's
    `reference` block as the harness hands it over."""
    import sys

    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    path = os.path.join(bench, "references", "kimi_linear.py")
    module_spec = importlib.util.spec_from_file_location(
        "kimi_linear_reference_under_test", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    with open(os.path.join(ROOT, "tests", "benchmarks", "data", "configs",
                           "kimi-linear-small-test.json")) as f:
        sizes = json.load(f)["reference"]
    return module, sizes


def _sizes(sizes, **more):
    return tuple(sorted(dict(sizes, **more).items()))


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, n)]


# -- registry and configuration --------------------------------------------------

def test_family_capabilities_and_stated_widths(spec):
    """The family is Olmo-Hybrid's, over another kind of block: what the
    pool's blocks hold is the model's to state, not the family's."""
    cfg = spec.config
    assert spec.state_family == "kv_and_state"
    assert spec.capabilities == FAMILY_CAPABILITIES["kv_and_state"]
    assert spec.capabilities == create_model("olmo_hybrid_small").capabilities
    for absent in ("prefix_sharing", "kv_host_tier", "kv_quantize",
                   "spec_decode", "tensor_parallel", "migration", "handoff",
                   "two_path"):
        assert not spec.supports(absent)
    assert cfg.linear == (True, True, True, False, True)
    assert cfg.pool_layer == (0, 1, 2, 0, 3)
    (kind,) = cfg.kv_block_kinds
    assert (kind.n_layers, kind.kv_lanes) == (1, (la.PE_LANES, 32))
    assert cfg.kv_lanes == kind.kv_lanes
    assert cfg.state_row_shapes == ((4, 8, 8), (8, 3 * 4 * 24 // 8))
    assert (cfg.recurrence, spec.held, cfg.n_moe_layers) == ("kda", (0, 8), 4)
    # The hybrid that keeps K and V a head states equal lanes.
    olmo = create_model("olmo_hybrid_small").config.kv_block_kinds[0]
    assert olmo.kv_lanes == (48, 48)


def test_the_published_geometry_is_the_default():
    _ensure_builtin_models_imported()
    spec = create_model("kimi_linear")
    cfg = spec.config
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab) == (
        27, 2304, 9216, 163840)
    assert (cfg.n_heads, cfg.qk_nope, cfg.qk_rope, cfg.v_head,
            cfg.kv_lora_rank) == (32, 128, 64, 128, 512)
    assert (cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim,
            cfg.conv_width, cfg.gate_rank) == (32, 128, 128, 4, 128)
    assert (cfg.n_routed, cfg.top_k, cfg.d_ff_expert, cfg.d_ff_shared,
            cfg.routed_scale, cfg.n_dense_layers, cfg.held) == (
        256, 8, 1024, 1024, 2.446, 1, (0, 256))
    assert cfg.n_linear_layers == 20 and cfg.n_full_layers == 7
    assert [l + 1 for l, lin in enumerate(cfg.linear) if not lin] == [
        4, 8, 12, 16, 20, 24, 27]
    shapes = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 48.0 < count / 1e9 < 49.5                # "48B"
    # A row's state a KDA layer: 32 x 128 x 128 and 3 x 12288 float32.
    assert sum(int(np.prod(s)) for s in cfg.state_row_shapes) * 4 == 2244608


def test_a_layer_is_one_of_the_two_kinds():
    with pytest.raises(ValueError, match="every layer is one of"):
        create_model("kimi_linear_small", kda_layers=(1, 2, 3),
                     full_attn_layers=(4,))
    with pytest.raises(ValueError, match="is no share of"):
        create_model("kimi_linear_small", held_first=12, held_count=8)


# -- the op: chunked == one-step == the reference's scan, a gate a channel --------

def _kda_inputs(gate, t=150, h=3, dk=8, dv=16, seed=0):
    """`gate(key, shape) -> g`: the gate a key channel, (t, h, dk)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (t, h, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (t, h, dk)))
    v = jax.random.normal(ks[2], (t, h, dv))
    beta = jax.random.uniform(ks[4], (t, h), minval=0.0, maxval=1.0)
    return ((q, k, v, gate(ks[3], (t, h, dk)), beta),
            jax.random.normal(ks[5], (h, dv, dk)))


def _drawn(key, shape):
    """The draw's range: a in (0.55, 1), by head, channel and token."""
    return jnp.log(jax.random.uniform(key, shape, minval=0.55, maxval=1.0))


GATES = {
    "drawn": _drawn,
    # The draw's edge: exp(A_log) = 0.25 and softplus = 7 (the normal's
    # tail at 1e-9) in EVERY channel and token: a = 0.17.
    "strongest": lambda key, shape: jnp.full(shape, -1.75),
    # A quotient by exp(G) would overflow within 18 tokens at -5, within 3
    # here; and channels that forget at once beside channels that keep.
    "past_float32": lambda key, shape: jnp.where(
        jax.random.bernoulli(key, 0.5, shape), -30.0, -1e-3),
}


@pytest.mark.parametrize("gate", sorted(GATES))
@pytest.mark.parametrize("given", [False, True])
def test_chunked_equals_one_step_equals_the_reference_s_scan(gate, given):
    """A run of 256 tokens (four sub-chunks of 64, sixteen diagonal blocks
    of 16) against the recurrence as the reference writes it, a token at a
    time: the scan, the chunked form and the one-step form."""
    (q, k, v, g, beta), state = _kda_inputs(GATES[gate], t=256)
    if not given:
        state = jnp.zeros_like(state)
    with jax.default_matmul_precision("highest"):
        s, want = state, []
        for t in range(q.shape[0]):
            s = s * jnp.exp(g[t])[:, None, :]
            u = beta[t][:, None] * (v[t] - jnp.einsum("hvk,hk->hv", s, k[t]))
            s = s + u[:, :, None] * k[t][:, None, :]
            want.append(jnp.einsum("hvk,hk->hv", s, q[t]))
        want = jnp.stack(want)
        o_scan, s_scan = gd.gdn_scan(q, k, v, g, beta, state)
        o_chunk, s_chunk = jax.jit(gd.gdn_chunk)(q, k, v, g, beta, state)
        s_step, o_step = state[None], []
        for t in range(q.shape[0]):
            o, s_step = gd.gdn_step(q[t][None], k[t][None], v[t][None],
                                    g[t][None], beta[t][None], s_step)
            o_step.append(o[0])
    assert bool(jnp.isfinite(o_chunk).all() & jnp.isfinite(s_chunk).all())
    # 256 decays of exp(-1e-3) multiplied one by one round 256 times where
    # the chunked form takes one exponential of their sum.
    atol = 1e-4 if gate == "past_float32" else 2e-5
    for o, last in ((o_scan, s_scan), (o_chunk, s_chunk),
                    (jnp.stack(o_step), s_step[0])):
        np.testing.assert_allclose(o, want, atol=atol)
        np.testing.assert_allclose(last, s, atol=atol)


def test_a_run_shorter_than_its_padding_changes_nothing_past_its_end():
    """150 tokens padded to three sub-chunks with tokens of g = 0, b = 0."""
    (q, k, v, g, beta), state = _kda_inputs(_drawn)
    pad = -q.shape[0] % gd.SUB_CHUNK
    padded = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
              for x in (q, k, v, g, beta)]
    with jax.default_matmul_precision("highest"):
        o_want, s_want = gd.gdn_scan(q, k, v, g, beta, state)
        o, s = gd.gdn_chunk(*padded, state)
    np.testing.assert_allclose(o[:q.shape[0]], o_want, atol=2e-5)
    np.testing.assert_allclose(s, s_want, atol=2e-5)
    with pytest.raises(ValueError, match="no multiple of 64"):
        gd.gdn_chunk(q, k, v, g, beta, state)


@pytest.mark.parametrize("form", ["scan", "chunk", "step", "chunk_kernel",
                                  "step_kernel"])
def test_a_gate_constant_over_a_head_s_channels_is_the_scalar_rule(form):
    """With a_t the same in every channel KDA IS the gated delta rule:
    every form under a (T, H, d_k) gate equals itself under the (T, H)
    gate that `olmo_hybrid` serves."""
    (q, k, v, g, beta), state = _kda_inputs(_drawn, t=128)
    scalar = g[..., 0]
    channel = jnp.broadcast_to(scalar[..., None], g.shape)
    pool = jnp.zeros((2, 3) + state.shape).at[1, 2].set(state)

    def run(g):
        if form == "scan":
            return gd.gdn_scan(q, k, v, g, beta, state)
        if form == "chunk":
            return gd.gdn_chunk(q, k, v, g, beta, state)
        if form == "step":
            return gd.gdn_step(q[:5], k[:5], v[:5], g[:5], beta[:5],
                               jnp.stack([state] * 5))
        if form == "chunk_kernel":
            return gd.gdn_chunk_row(q, k, v, g, beta, pool, 1, 2, False,
                                    interpret=True)
        rows = jnp.asarray([2, 0, 1])
        live = jnp.asarray([True, False, True])
        o, new = gd.gdn_step_rows(q[:3], k[:3], v[:3], g[:3], beta[:3],
                                  pool, 1, rows, live, ~live,
                                  interpret=True)
        return o[live], new

    with jax.default_matmul_precision("highest"):
        (o_a, s_a), (o_b, s_b) = run(scalar), run(channel)
    np.testing.assert_allclose(o_a, o_b, atol=1e-5)
    np.testing.assert_allclose(s_a, s_b, atol=1e-5)


def _pool_case(seed=0, h=3, dk=8, dv=16):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, 6, h, dv, dk))


def test_the_step_kernel_changes_the_rows_states_where_they_lie():
    """The Pallas step in the interpreter, a decay row that differs by
    lane, against the gather and scatter: five rows of which three take the
    step (one from a zero state), the other two on the null row."""
    (q, k, v, g, beta), _ = _kda_inputs(GATES["past_float32"], t=5)
    pool = _pool_case()
    rows = jnp.asarray([3, 0, 5, 1, 0])
    live = jnp.asarray([True, False, True, True, False])
    fresh = jnp.asarray([False, False, True, False, False])
    args = (q, k, v, g, beta, pool, 1, rows, live, fresh)
    o, new = gd.gdn_step_rows(*args, interpret=True)
    o_want, want = gd.gdn_step_rows_reference(*args)
    np.testing.assert_allclose(o[live], o_want[live], atol=1e-5)
    np.testing.assert_allclose(new, want, atol=1e-5)
    assert float(jnp.abs(new[0] - pool[0]).max()) == 0.0
    assert float(jnp.abs(new[1, 0] - pool[1, 0]).max()) == 0.0
    assert float(jnp.abs(new[1, 3] - pool[1, 3]).max()) > 0.1


def _chunk_run(run):
    """A run for the chunk kernel and how many of its tokens are live: 128
    tokens under two of `GATES`, or 256 (four sub-chunks, sixteen diagonal
    blocks: every block row of the blocked solve, all four solves before
    the state's pass) with b drawn over (0, 2) and channel 0 of every head
    at -5 a token beside the drawn ones (the module docstring's worst
    case); `shorter_than_its_padding` ends at token 150 and is padded with
    tokens of b = 0, g = 0."""
    if run in GATES:
        return _kda_inputs(GATES[run], t=128)[0], 128
    (q, k, v, g, beta), _ = _kda_inputs(_drawn, t=256, seed=3)
    g = g.at[..., 0].set(-5.0)
    live = 150 if run == "shorter_than_its_padding" else 256
    past = jnp.arange(256)[:, None] >= live
    return (q, k, v, jnp.where(past[..., None], 0.0, g),
            jnp.where(past, 0.0, 2.0 * beta)), live


# Jitted once a shape: `fresh` is an operand, so a run's two cases share
# the interpreter's program.
_chunk_in_the_interpreter = jax.jit(
    functools.partial(gd.gdn_chunk_row, interpret=True))


@pytest.mark.parametrize("run", ["drawn", "past_float32", "four_sub_chunks",
                                 "shorter_than_its_padding"])
@pytest.mark.parametrize("fresh", [False, True])
def test_the_chunk_kernel_equals_the_scan_from_the_row_s_state(fresh, run):
    """The Pallas chunk in the interpreter (the scalar gate's body, exp(G_C)
    a lane vector) against the token-by-token scan of the run's LIVE tokens
    from the pool's row; the other rows and the other layer are left as
    they were."""
    (q, k, v, g, beta), live = _chunk_run(run)
    pool = _pool_case(1)
    with jax.default_matmul_precision("highest"):
        o, new = _chunk_in_the_interpreter(q, k, v, g, beta, pool, 1, 4,
                                           fresh)
        o_want, last = gd.gdn_scan(
            q[:live], k[:live], v[:live], g[:live], beta[:live],
            jnp.zeros_like(pool[1, 4]) if fresh else pool[1, 4])
        o_xla, same = gd.gdn_chunk_row(q, k, v, g, beta, pool, 1, 4, fresh)
    atol = 1e-4 if run == "past_float32" else 2e-5
    np.testing.assert_allclose(o[:live], o_want, atol=atol)
    np.testing.assert_allclose(new, pool.at[1, 4].set(last), atol=atol)
    np.testing.assert_allclose(o_xla[:live], o_want, atol=atol)
    np.testing.assert_allclose(same, new, atol=2e-5)


def test_the_kernels_take_their_names_from_the_gate_s_rank():
    """`kda_step` / `kda_chunk` in a trace where the gate is a channel's;
    the scalar gate keeps `gdn_step` / `gdn_chunk` (digest's readers)."""
    (q, k, v, g, beta), _ = _kda_inputs(_drawn, t=64)
    pool = _pool_case()

    def names(g):
        step = jax.make_jaxpr(lambda: gd.gdn_step_rows(
            q[:2], k[:2], v[:2], g[:2], beta[:2], pool, 1,
            jnp.asarray([1, 2]), jnp.asarray([True, True]),
            jnp.asarray([False, False]), interpret=True))()
        chunk = jax.make_jaxpr(lambda: gd.gdn_chunk_row(
            q, k, v, g, beta, pool, 1, 2, False, interpret=True))()
        return [next(e.params["name"] for e in j.eqns
                     if e.primitive.name == "pallas_call")
                for j in (step.jaxpr, chunk.jaxpr)]

    assert names(g) == ["kda_step", "kda_chunk"]
    assert names(g[..., 0]) == ["gdn_step", "gdn_chunk"]


# -- the latent read at this model's heads ----------------------------------------

@pytest.mark.parametrize("q_lens", [(1, 1, 1, 1), (16, 1, 0, 7)])
def test_latent_kernel_at_thirty_two_heads(q_lens):
    """32 heads over one latent: a decode row's tile is one slot (32 query
    rows), a chunk's tiles hold 4 slots; the kernel in the interpreter
    against the gather."""
    assert la.slots_per_tile(32, 1) == 1 and la.slots_per_tile(32, 256) == 4
    error = la.parity_check(q_lens, n_heads=32, latent=32, rope=8,
                            block_size=BS, n_blocks=33, table_len=8,
                            interpret=True)
    assert error < 2e-5


# -- the model against the plain reference ----------------------------------------

def test_the_forward_equals_the_plain_reference(spec, params, reference):
    module, sizes = reference
    tokens = jnp.asarray(_prompt(0, 70), jnp.int32)
    want = module.forward(params, tokens, _sizes(sizes))
    with jax.default_matmul_precision("highest"):
        got = kl.kimi_linear_apply(params, tokens[None], spec.config,
                                   dtype=jnp.float32)[0]
    assert float(want.std()) > 0.5
    np.testing.assert_allclose(got, want, atol=5e-5)


@pytest.mark.parametrize("control", [
    {"drop": "decay"}, {"drop": "gate_mean"}, {"drop": "rotate"},
    {"drop": "bias"}, {"drop": "shared"}, {"drop": "other_half"},
    {"drop": "conv_tail"}, {"drop": "state"}, {"drop": "state_bf16"},
    {"drop": "state_bf16_step"}, {"weights_as": "float8_e4m3fn"}])
def test_each_control_moves_the_reference_s_logits(params, reference,
                                                   control):
    module, sizes = reference
    tokens = jnp.asarray(_prompt(0, 70), jnp.int32)
    want = module.forward(params, tokens, _sizes(sizes))
    moved = module.forward(params, tokens, _sizes(sizes, **control))
    # Rounding a state to bfloat16 moves a logit by thousandths; leaving a
    # term out by a good part of a standard deviation.
    least = 1e-3 if "bf16" in control.get("drop", "") else 0.3
    assert float(jnp.abs(moved - want)[48:].max()) > least


def _pools(cfg, rows, blocks):
    (kind,) = cfg.kv_block_kinds
    return (KVCache(*(jnp.zeros((kind.n_layers, blocks, BS, lanes))
                      for lanes in kind.kv_lanes)),
            tuple(jnp.zeros((cfg.n_linear_layers, rows) + s)
                  for s in cfg.state_row_shapes))


@pytest.mark.parametrize("chunks", [(16, 16, 16, 2), (7, 16, 16, 11),
                                    (16, 1, 16, 16, 1)])
def test_chunked_prefill_then_decode_equals_the_reference_on_logits(
        spec, params, reference, chunks):
    """Two rows of different lengths in the same ticks: row 0 prefills
    `chunks` (a chunk starts from the state and conv tail the last one
    left) and then decodes; row 2 prefills 23 tokens and decodes beside it,
    so a tick runs the chunked form and the one-step form together and the
    MLA layer reads both rows' latent chains. Row 1 is a free slot on the
    null state row. Float32 within 1e-4: a state kept in bfloat16 moves a
    logit by over 1e-3 (`test_each_control_moves_...`), which the chip's
    limits cannot tell."""
    module, sizes = reference
    cfg = spec.config
    n_prompt, n_new = sum(chunks), 6
    seqs = {0: _prompt(1, n_prompt + n_new), 2: _prompt(2, 23 + 12)}
    plans = {0: list(chunks) + [1] * n_new, 2: [16, 7] + [1] * 12}
    caches = _pools(cfg, rows=4, blocks=17)
    table = np.zeros((3, 8), np.int32)
    table[0], table[2] = np.arange(1, 9), np.arange(9, 17)
    tables = (jnp.asarray(table), jnp.asarray([3, 0, 1], jnp.int32))
    step = jax.jit(lambda tokens, caches, pos0, qlen:
                   kl.kimi_linear_step_rows_ragged(
                       params, tokens, caches, tables, pos0, qlen, cfg,
                       dtype=jnp.float32, max_tokens=36))
    pos, got, taken = {0: 0, 2: 0}, {0: [], 2: []}, 0
    with jax.default_matmul_precision("highest"):
        while any(plans.values()):
            tokens = np.zeros((3, 16), np.int32)
            pos0, qlen = np.zeros(3, np.int32), np.zeros(3, np.int32)
            for r, plan in plans.items():
                if plan:
                    n = plan.pop(0)
                    tokens[r, :n] = seqs[r][pos[r]:pos[r] + n]
                    pos0[r], qlen[r] = pos[r], n
            logits, caches, rows = step(jnp.asarray(tokens), caches,
                                        jnp.asarray(pos0), jnp.asarray(qlen))
            assert rows.shape == (4, 16) and not rows[:, 8:].any()
            taken += int(rows.sum())
            for r in pos:
                got[r].append(np.asarray(logits[r, :qlen[r]]))
                pos[r] += int(qlen[r])
    # About half of the pairs routed formed a row among the 8 held experts.
    routed = sum(len(s) for s in seqs.values()) * 4 * cfg.top_k
    assert 0.35 * routed < taken < 0.65 * routed
    for r, seq in seqs.items():
        want = module.forward(params, jnp.asarray(seq, jnp.int32),
                              _sizes(sizes))
        np.testing.assert_allclose(np.concatenate(got[r]), want, atol=1e-4)
    # The free slot's null row took nothing.
    assert all(float(jnp.abs(x[:, 0]).max()) == 0.0 for x in caches[1])


# -- the two shares of the experts --------------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer(reference):
    """A deployment's two chips each hold half of a layer's experts; both
    compute the shared expert. Their partial sums, the shared expert
    counted ONCE, are the uncut layer of the reference; the program's share
    is the reference's share."""
    module, sizes = reference
    whole = create_model("kimi_linear_small", held_count=16)
    weights = jax.jit(whole.init)(jax.random.PRNGKey(5))
    mp = weights["layers"][2]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(6), (40, 48))

    def share(first):
        bank = jax.tree.map(lambda a: a[first:first + 8], mp["experts"])
        return dict(mp, experts=bank)

    with jax.default_matmul_precision("highest"):
        uncut = module._experts(mp, x, dict(sizes, held_first=0))
        shared = module._swiglu(mp["shared"], x, dict(sizes))
        halves = [module._experts(share(first), x,
                                  dict(sizes, held_first=first))
                  for first in (0, 8)]
        served = [kl._moe_ffn(share(first), x[None], jnp.ones((1, 40), bool),
                              whole.config, jnp.float32, (first, 8), None)
                  for first in (0, 8)]
    assert float(jnp.abs(halves[0] - shared).max()) > 0.05      # both matter
    assert float(jnp.abs(halves[1] - shared).max()) > 0.05
    np.testing.assert_allclose(halves[0] + halves[1] - shared, uncut,
                               atol=2e-5)
    for (y, rows), half, first in zip(served, halves, (0, 8)):
        np.testing.assert_allclose(y[0], half, atol=2e-5)
        assert not rows[:first].any() and not rows[first + 8:].any()
    assert int(served[0][1].sum() + served[1][1].sum()) == 40 * 4


# -- the served path ---------------------------------------------------------------

def test_the_mixed_tick_serves_it_from_both_pools_and_counts(spec, params,
                                                             reference):
    from tpu_engine.utils.tracing import SpanRecorder

    module, sizes = reference
    gen = ContinuousGenerator(spec, params=params, **LANE)
    gen.tracer, gen.trace_node = SpanRecorder(capacity=4096), "lane"
    prompts = [_prompt(5, 50), _prompt(6, 23), _prompt(7, 37)]
    try:
        pools = gen._pool, gen._spool
        assert pools[0].cfg.n_layers == 1 and pools[1].n_layers == 4
        assert pools[0].cfg.kv_lanes == (la.PE_LANES, 32)
        assert [x.shape for x in pools[0].caches] == [
            (1, 33, BS, la.PE_LANES), (1, 33, BS, 32)]
        assert [x.shape for x in pools[1].slab] == [
            (4, 5, 4, 8, 8), (4, 5, 8, 36)]
        futures = [gen.submit(p, max_new_tokens=12) for p in prompts]
        served = [f.result(timeout=300) for f in futures]
        stats = gen.stats()
    finally:
        gen.stop()
    for prompt, tokens in zip(prompts, served):
        want = module.forward(params,
                              jnp.asarray(prompt + tokens[:-1], jnp.int32),
                              _sizes(sizes))[len(prompt) - 1:]
        gap = want.max(-1) - want[jnp.arange(len(tokens)), jnp.asarray(tokens)]
        assert float((gap / want.std(-1)).max()) < 0.05
    state, pool, moe = stats["state_pool"], stats["kv_pool"], stats["moe"]
    assert state["rows_total"] == 4 and state["rows_peak"] == 3
    assert state["rows_held"] == 0 and state["rows_free"] == 4
    assert state["bytes_per_row"] == 4 * (4 * 8 * 8 + 3 * 96) * 4
    assert pool["blocks_free"] == pool["blocks_total"]
    assert pool["kv_bytes_held"] == pool["state_bytes_held"] == 0
    assert pool["block_lanes"] == [la.PE_LANES, 32]
    mixed = stats["mixed"]
    fed = mixed["prefill_tokens"] + mixed["decode_tokens"]
    assert moe["assignments"] == fed * 4 * 4
    assert 0.35 * moe["assignments"] < moe["assignments_held"] \
        < 0.65 * moe["assignments"]
    assert not np.asarray(moe["rows_by_expert"])[:, 8:].any()
    spans = [s["attrs"] for s in gen.tracer.snapshot()
             if s["op"] == "mixed_step"]
    assert sum(s["kda_chunk_tokens"] + s["kda_step_rows"]
               for s in spans) == fed
    assert any(s["kda_chunk_tokens"] and s["kda_step_rows"] for s in spans)
    assert all(s["ctx_tokens_latent"] == s["ctx_tokens"] for s in spans)
    assert max(s["state_rows_held"] for s in spans) == 3
    assert all({"moe_assignments", "moe_assignments_held",
                "moe_experts_touched", "kda_chunk_rows"} <= set(s)
               for s in spans)
    # The scalar rule's names and the K/V read's are another model's.
    assert not any(key.startswith("gdn_") or key == "ctx_tokens_full"
                   for s in spans for key in s)


def test_a_lane_of_many_rows_serves_every_one(spec, params):
    """More rows than any other family's lane runs at this size, all of
    them prefilling and then decoding in the same ticks: every request is
    served whole and equals the same request served alone."""
    prompts = [_prompt(30 + i, 5 + 3 * i) for i in range(12)]
    many = ContinuousGenerator(spec, params=params,
                               **{**LANE, "n_slots": 12})
    try:
        served = many.generate(prompts, max_new_tokens=6)
        assert many.stats()["state_pool"]["rows_peak"] == 12
        assert _drained(many)
    finally:
        many.stop()
    alone = ContinuousGenerator(spec, params=params, **LANE)
    try:
        assert [alone.generate([p], max_new_tokens=6)[0]
                for p in prompts[::5]] == served[::5]
    finally:
        alone.stop()


def _drained(gen):
    stats = gen.stats()
    pool, state = stats["kv_pool"], stats["state_pool"]
    return (pool["blocks_free"] == pool["blocks_total"]
            and state["rows_held"] == 0
            and state["rows_free"] == state["rows_total"]
            and not gen._spool.rows.any())


@pytest.mark.parametrize("how", ["finish", "deadline", "reset"])
def test_a_row_gives_back_its_state_row_and_its_latent_blocks(spec, params,
                                                              how):
    from tpu_engine.utils.deadline import Deadline, DeadlineExceeded

    gen = ContinuousGenerator(spec, params=params, **LANE)
    try:
        gen.generate([_prompt(4, 9)], max_new_tokens=3)      # compiled, warm
        assert _drained(gen)
        if how == "finish":
            gen.generate([_prompt(4, 40), _prompt(8, 20)], max_new_tokens=5)
        elif how == "deadline":
            cut = gen.submit(_prompt(4, 60), max_new_tokens=60,
                             deadline=Deadline.after_ms(150))
            with pytest.raises(DeadlineExceeded):
                cut.result(timeout=120)
            assert gen.stats()["deadline_cancelled"] == 1
        else:
            held = gen.submit(_prompt(4, 60), max_new_tokens=60)
            while not gen._spool.rows.any():
                time.sleep(0.001)
            # A one-shot device failure on the next tick's dispatch: the
            # donated pools are rebuilt, both of them.
            real = gen._mixed_step_exe

            def failing(width, controls):
                gen._mixed_step_exe = real

                def exe(*args, **kwargs):
                    raise RuntimeError("injected device failure")
                return exe

            gen._mixed_step_exe = failing
            with pytest.raises(RuntimeError, match="device-step failure"):
                held.result(timeout=120)
            assert gen.stats()["failures"] == 1
        gen.generate([_prompt(4, 5)], max_new_tokens=2)
        assert _drained(gen)
        assert gen.stats().get("recover_invariant_violations", 0) == 0
    finally:
        gen.stop()


def test_the_tick_runs_one_ahead_with_a_state_row_a_slot(spec, params):
    """As `olmo_hybrid_small`'s lane: a row that meets its EOS is stepped
    once more as a done row, and both pools come back once."""
    from tick_pipeline import check_late_ends

    counters = check_late_ends(
        lambda: ContinuousGenerator(spec, params=params, **LANE),
        [_prompt(21, 40), _prompt(22, 7), _prompt(23, 25)], _drained)
    assert counters["overlapped_ticks"] > counters["ticks"] // 2


@pytest.mark.parametrize("short", [{"n_slots": 1}, {"kv_blocks": 9}])
def test_a_request_waits_when_either_pool_is_short(spec, params, short):
    """One slot and so one state row, or latent blocks for one long row:
    the second request is parked until the first ends, and both are served
    whole, by the admission that parks `olmo_hybrid_small`'s rows."""
    gen = ContinuousGenerator(spec, params=params, **{**LANE, **short})
    prompts = [_prompt(9, 100), _prompt(10, 100)]
    try:
        futures = [gen.submit(p, max_new_tokens=6) for p in prompts]
        served = [f.result(timeout=300) for f in futures]
        stats = gen.stats()
        assert _drained(gen)
    finally:
        gen.stop()
    assert [len(tokens) for tokens in served] == [6, 6]
    assert stats["state_pool"]["rows_peak"] == 1
    assert stats["admitted"] == 2
    alone = ContinuousGenerator(spec, params=params, **LANE)
    try:
        assert alone.generate(prompts, max_new_tokens=6) == served
    finally:
        alone.stop()


def test_one_admission_and_release_path_serves_both_kinds_of_block():
    """The scheduler reads 'a row also owns a state row' off the family and
    'what a block holds' off the pool's own configuration: no branch names
    this model, its latent, or a second hybrid family."""
    import inspect

    from tpu_engine.runtime import scheduler

    source = inspect.getsource(scheduler)
    for name in ("kimi_linear_step_rows_ragged", "models.kimi_linear import",
                 "gated_delta", "kv_latent_and_state", "KimiLinear"):
        assert name not in source
    for method in ("_admit_mixed", "_release_row_blocks"):
        body = inspect.getsource(getattr(scheduler.ContinuousGenerator,
                                         method))
        assert "self._hybrid" in body and "kv_lanes" not in body


@pytest.mark.parametrize("kwargs, error, message", [
    ({"kv_block_size": 0, "kv_blocks": 64}, ValueError,
     r"set kv_block_size > 0 \(the dense per-slot cache has no"),
    ({"kv_block_size": 0}, ValueError,
     "served by the mixed tick over the block pool only"),
    ({"prefix_sharing": True}, ValueError,
     "prefix_sharing needs the 'prefix_sharing' capability.*"
     "not block-addressable, whatever its blocks hold"),
    ({"kv_quantize": "int8"}, ValueError,
     "kv_quantize needs the 'kv_quantize' capability"),
    ({"kv_host_blocks": 8}, ValueError,
     "kv_host_blocks needs the 'kv_host_tier' capability"),
    ({"spec_k": 2}, ValueError,
     "spec_k needs the 'spec_decode' capability.*rolled back"),
    ({"state_rows": 2}, ValueError,
     "state_rows applies to the state_slab family; model "
     "'kimi_linear_small' serves the kv_and_state family"),
    ({"tp": 2}, RuntimeError, "cannot serve tensor-parallel"),
])
def test_what_a_latent_plus_state_lane_cannot_do_is_refused_at_start_up(
        spec, params, kwargs, error, message):
    with pytest.raises(error, match=message):
        ContinuousGenerator(spec, params=params, **{**LANE, **kwargs})


def test_the_chain_wire_format_is_refused_by_name(spec, params):
    gen = ContinuousGenerator(spec, params=params, **LANE)
    try:
        refusal = ("needs the 'migration' capability, which the "
                   "kv_and_state family does not declare")
        assert refusal in gen.export_row("nobody")["reason"]
        assert "no state row" in gen.export_row("nobody")["reason"]
        assert refusal in gen.export_prefix([1] * 32)["reason"]
        with pytest.raises(ValueError, match=refusal):
            gen.submit_import({"prompt": [1], "emitted": [], "pos": 1,
                               "tok": 1, "max_new": 1, "chain": {}})
    finally:
        gen.stop()


# -- the serving layer ---------------------------------------------------------------

_GEN_KW = dict(model="kimi_linear_small", dtype="float32", batch_buckets=(1,),
               gen_max_batch_size=2, gen_kv_block_size=BS,
               gen_prefill_chunk=16,
               gen_prefix_sharing=False)


@pytest.mark.parametrize("role", ["prefill", "decode"])
def test_a_dedicated_role_is_refused_at_start_up(role):
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig

    with pytest.raises(RuntimeError,
                       match=f"--role {role} needs the 'handoff' "
                             f"capability.*kv_and_state family"):
        WorkerNode(WorkerConfig(node_id="w", role=role, **_GEN_KW))


@pytest.mark.parametrize("flag, capability", [
    ("migrate_streams", "migration"), ("disagg", "handoff")])
def test_a_fleet_that_moves_streams_is_refused_at_start_up(flag, capability):
    from tpu_engine.serving.app import serve_combined
    from tpu_engine.utils.config import GatewayConfig, WorkerConfig

    with pytest.raises(RuntimeError,
                       match=f"needs the '{capability}' capability, which "
                             f"model 'kimi_linear_small' \\(kv_and_state"):
        serve_combined(model="kimi_linear_small", lanes=1, port=0,
                       worker_config=WorkerConfig(**_GEN_KW),
                       gateway_config=GatewayConfig(port=0, **{flag: True}),
                       warmup=False, native_front=False)


def test_generate_stream_through_gateway_and_lane():
    """`/generate/stream` through the HTTP front, the gateway and a lane
    of the family, with no flag of its own: the tokens streamed are the
    blocking route's."""
    import http.client

    from tpu_engine.serving.app import serve_combined
    from tpu_engine.utils.config import GatewayConfig, WorkerConfig

    gateway, workers, server = serve_combined(
        model="kimi_linear_small", lanes=1, port=0,
        worker_config=WorkerConfig(**_GEN_KW),
        gateway_config=GatewayConfig(port=0), warmup=False,
        native_front=False)
    try:
        body = json.dumps({"request_id": "s", "prompt_tokens": _prompt(3, 20),
                           "max_new_tokens": 6})
        streamed = []
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=120)
        conn.request("POST", "/generate/stream", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        for line in resp.read().decode().splitlines():
            if line.startswith("data:"):
                event = json.loads(line[5:])
                if event.get("done"):       # the last event repeats them all
                    assert event["tokens"] == streamed
                else:
                    streamed += event.get("tokens", [])
        conn.close()
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=120)
        conn.request("POST", "/generate",
                     body=body.replace('"s"', '"b"'),
                     headers={"Content-Type": "application/json"})
        blocking = json.loads(conn.getresponse().read())["tokens"]
        conn.close()
    finally:
        for part in (server, *workers, gateway):
            part.stop()
    assert len(blocking) == 6 and streamed == blocking
