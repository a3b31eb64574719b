"""The Falcon-H1 family (models/falcon_h1.py, ops/ssd.py) on the served
path: a row that owns, in EVERY layer, a paged K/V chain (GQA at five query
heads a KV head, RoPE) AND a Mamba-2 state, read in parallel from one normed
input and summed. `falcon_h1_small` (three layers, 5 query heads over 1 KV
head of 8 lanes, 4 SSM heads of 8 lanes in 2 groups over a state of 16
lanes, conv 4 with a bias, the published multipliers) against the plain
reference benchmarks/references/falcon_h1.py, on logits; the kernels in the
Pallas interpreter against the scan; the two pools' bookkeeping."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models.falcon_h1 import (
    falcon_h1_apply,
    falcon_h1_step_rows_ragged,
)
from tpu_engine.models.registry import (
    FAMILY_CAPABILITIES,
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.ops import ssd
from tpu_engine.ops.attention import KVCache
from tpu_engine.runtime.scheduler import ContinuousGenerator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16
LANE = dict(n_slots=4, dtype="float32", kv_block_size=BS,
            prefill_chunk=16, prefix_sharing=False)
MULTIPLIERS = ("embedding_multiplier", "attention_in_multiplier",
               "key_multiplier", "attention_out_multiplier",
               "ssm_in_multiplier", "ssm_multipliers", "ssm_out_multiplier",
               "mlp_multipliers", "lm_head_multiplier")


@pytest.fixture(scope="module")
def spec():
    _ensure_builtin_models_imported()
    return create_model("falcon_h1_small")


@pytest.fixture(scope="module")
def params(spec):
    return jax.jit(spec.init)(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def reference():
    """benchmarks/references/falcon_h1.py and the test configuration's
    `reference` block as the harness hands it over."""
    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    path = os.path.join(bench, "references", "falcon_h1.py")
    module_spec = importlib.util.spec_from_file_location(
        "falcon_h1_reference_under_test", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    with open(os.path.join(ROOT, "tests", "benchmarks", "data", "configs",
                           "falcon-h1-small-test.json")) as f:
        sizes = json.load(f)["reference"]
    return module, sizes


def _sizes(sizes, **more):
    return tuple(sorted(dict(sizes, **more).items()))


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, n)]


# -- registry and configuration --------------------------------------------------

def test_family_capabilities_and_stated_widths(spec):
    cfg = spec.config
    assert spec.state_family == "kv_and_state" and cfg.recurrence == "ssd"
    assert spec.capabilities == FAMILY_CAPABILITIES["kv_and_state"]
    for absent in ("prefix_sharing", "kv_host_tier", "kv_quantize",
                   "spec_decode", "tensor_parallel", "migration", "handoff",
                   "two_path"):
        assert not spec.supports(absent)
    # Every layer is in BOTH pools, at its own index.
    assert cfg.pool_layer == (0, 1, 2)
    assert cfg.n_linear_layers == cfg.n_full_layers == cfg.n_layers == 3
    assert [k.n_layers for k in cfg.kv_block_kinds] == [3]
    assert cfg.kv_block_kinds[0].kv_lanes == (8, 8)
    assert cfg.n_heads // cfg.kv_heads == 5
    # S and the conv tail, 3 x (32 + 2 x 2 x 16) = 288 numbers as 8 x 36.
    assert cfg.state_row_shapes == ((4, 8, 16), (8, 36))


def test_the_published_geometry_is_the_default():
    _ensure_builtin_models_imported()
    spec = create_model("falcon_h1")
    cfg = spec.config
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab) == (
        72, 5120, 21504, 261120)
    assert (cfg.n_heads, cfg.kv_heads, cfg.d_head, cfg.rope_theta) == (
        20, 4, 128, 1e11)
    assert (cfg.lin_heads, cfg.ssm_head_dim, cfg.d_state, cfg.n_groups,
            cfg.conv_width, cfg.conv_lanes) == (32, 128, 256, 2, 4, 5120)
    assert [lanes for lanes, _ in cfg.mup_segments] == [4096, 4096, 512, 512,
                                                        32]
    shapes = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert round(count / 1e9, 1) == 33.6
    # A row's state: 32 x 128 x 256 and 3 x 5120 float32 a layer, the tail
    # as 8 sublanes of whole lane tiles.
    assert cfg.state_row_shapes == ((32, 128, 256), (8, 1920))
    assert sum(int(np.prod(s)) for s in cfg.state_row_shapes) * 4 == 4255744


def test_a_head_count_that_is_no_whole_groups_is_refused():
    _ensure_builtin_models_imported()
    with pytest.raises(ValueError, match="no whole groups"):
        create_model("falcon_h1_small", ssm_heads=5)


# -- the op: chunked == one step == the scan, where the state lies -------------------

def _ssd_inputs(t=150, h=4, p=8, g=2, n=16, seed=0, rate=None):
    """The draw's edge: dt over softplus's (0.1, 2.5), A over -(0.02,
    0.25), so a decay of 0.55 to 1 a token; `rate`: A = -rate instead."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (t, h, p))
    dt = jax.random.uniform(ks[1], (t, h), minval=0.1, maxval=2.5)
    a = (-jnp.full((h,), float(rate)) if rate else
         -jax.random.uniform(ks[2], (h,), minval=0.02, maxval=0.25))
    b = jax.random.normal(ks[3], (t, g, n))
    c = jax.random.normal(ks[4], (t, g, n))
    return (x, dt, a, b, c), jax.random.normal(ks[5], (2, 6, h, p, n))


def _scan(x, dt, a, b, c, state):
    """The recurrence as written, token by token, heads reading their
    group: independent of ops/ssd.py."""
    hg = x.shape[1] // b.shape[1]
    s, out = state, []
    for t in range(x.shape[0]):
        bt, ct = (jnp.repeat(v[t], hg, axis=0) for v in (b, c))
        s = (jnp.exp(dt[t] * a)[:, None, None] * s
             + (dt[t][:, None] * x[t])[:, :, None] * bt[:, None, :])
        out.append((s * ct[:, None, :]).sum(-1))
    return jnp.stack(out), s


@pytest.mark.parametrize("rate", [None, 30.0])
@pytest.mark.parametrize("fresh", [False, True])
def test_the_chunk_kernel_equals_the_scan_from_the_row_s_state(fresh, rate):
    """The Pallas chunk in the interpreter (two sub-chunks of 64, the state
    carried in VMEM) and its XLA form against the token-by-token scan from
    the pool's row, at the draw's edge and at A = -30 (a decay of exp(-3)
    to exp(-75) a token: every exponent is a difference that is at most 0,
    so nothing overflows and the result is finite and equal); the other
    rows and the other layer are left as they were."""
    args, pool = _ssd_inputs(t=128, rate=rate)
    with jax.default_matmul_precision("highest"):
        y, new = ssd.ssd_chunk_row(*args, pool, 1, 4, fresh, interpret=True)
        y_want, last = _scan(*args, jnp.zeros_like(pool[1, 4]) if fresh
                             else pool[1, 4])
        y_xla, same = ssd.ssd_chunk_row(*args, pool, 1, 4, fresh)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(new).all())
    for got, state in ((y, new), (y_xla, same)):
        np.testing.assert_allclose(got, y_want, atol=5e-5)
        np.testing.assert_allclose(state, pool.at[1, 4].set(last), atol=5e-5)


@pytest.mark.parametrize("rate", [None, 30.0])
def test_the_step_kernel_changes_the_rows_states_where_they_lie(rate):
    """The Pallas step in the interpreter against the gather and scatter
    and against the scan's one token: five rows of which three take the
    step (one from a zero state), the other two pointed at the null row,
    which is left as it was."""
    (x, dt, a, b, c), pool = _ssd_inputs(t=5, rate=rate)
    rows = jnp.asarray([3, 0, 5, 1, 0])
    live = jnp.asarray([True, False, True, True, False])
    fresh = jnp.asarray([False, False, True, False, False])
    args = (x, dt, a, b, c, pool, 1, rows, live, fresh)
    y, new = ssd.ssd_step_rows(*args, interpret=True)
    y_want, want = ssd.ssd_step_rows_reference(*args)
    np.testing.assert_allclose(y[live], y_want[live], atol=1e-5)
    np.testing.assert_allclose(new, want, atol=1e-5)
    for i, r in ((0, 3), (3, 1)):
        y_one, s_one = _scan(x[i:i + 1], dt[i:i + 1], a, b[i:i + 1],
                             c[i:i + 1], pool[1, r])
        np.testing.assert_allclose(y[i], y_one[0], atol=1e-5)
        np.testing.assert_allclose(new[1, r], s_one, atol=1e-5)
    assert float(jnp.abs(new[0] - pool[0]).max()) == 0.0
    assert float(jnp.abs(new[1, 0] - pool[1, 0]).max()) == 0.0
    assert float(jnp.abs(new[1, 5] - pool[1, 5]).max()) > 0.1


def test_two_groups_are_told_from_one():
    """Heads 2 and 3 read group 1's B and C: with group 0's in their place
    (what one shared group would give) their outputs move, heads 0 and 1
    do not; and one group with no group axis is the slab family's case."""
    (x, dt, a, b, c), pool = _ssd_inputs(t=64)
    state = pool[0, 0]
    y, _ = ssd.ssd_recurrent(x[None], dt[None], a, b[None], c[None],
                             initial_state=state[None])
    shared = [jnp.broadcast_to(v[:, :1], v.shape) for v in (b, c)]
    y_one, _ = ssd.ssd_recurrent(x[None], dt[None], a, shared[0][None],
                                 shared[1][None], initial_state=state[None])
    assert float(jnp.abs(y - y_one)[0, :, :2].max()) == 0.0
    assert float(jnp.abs(y - y_one)[0, :, 2:].max()) > 1.0
    y_flat, _ = ssd.ssd_recurrent(x[None], dt[None], a, b[None, :, 0],
                                  c[None, :, 0], initial_state=state[None])
    np.testing.assert_allclose(y_flat, y_one, atol=1e-5)
    y_chunk, _ = ssd.ssd_chunked(x[None], dt[None], a, b[None], c[None],
                                 chunk=16, initial_state=state[None])
    np.testing.assert_allclose(y_chunk, y, atol=5e-5)


def test_a_run_that_is_no_whole_number_of_sub_chunks_is_refused():
    args, pool = _ssd_inputs(t=65)
    with pytest.raises(ValueError, match="no multiple of 64"):
        ssd.ssd_chunk_row(*args, pool, 0, 1, False, interpret=True)


# -- the model against the plain reference ----------------------------------------

def test_the_forward_equals_the_plain_reference(spec, params, reference):
    module, sizes = reference
    tokens = jnp.asarray(_prompt(0, 70), jnp.int32)
    want = module.forward(params, tokens, _sizes(sizes))
    with jax.default_matmul_precision("highest"):
        got = falcon_h1_apply(params, tokens[None], spec.config,
                              dtype=jnp.float32)[0]
    assert float(want.std()) > 0.5
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_the_two_branches_write_within_a_factor_of_two(spec, params):
    """The draw (models/falcon_h1.py): under the published multipliers,
    with scores of spread 4, the attention branch and the Mamba-2 branch
    write into the stream within a factor of two of each other in every
    layer (read at positions 24-40 of 64), and neither has vanished beside
    the stream (unit variance at the embedding)."""
    tokens = jnp.asarray([_prompt(s, 64) for s in range(4)], jnp.int32)
    branches = []
    with jax.default_matmul_precision("highest"):
        falcon_h1_apply(params, tokens, spec.config, dtype=jnp.float32,
                        branches=branches)
    assert len(branches) == spec.config.n_layers
    for y_att, y_ssm, _ in branches:
        rms = [float(jnp.sqrt(jnp.mean(jnp.square(y[:, 24:40]))))
               for y in (y_att, y_ssm)]
        assert 0.5 < rms[0] / rms[1] < 2.0, rms
        assert min(rms) > 0.2, rms


def test_gate_and_up_do_not_share_a_draw_and_the_logits_are_not_constant(
        params, spec):
    """A SwiGLU whose gate and up matrices came from one key is x SiLU(x),
    positive on average: every layer then writes one constant vector and
    by the sixth 90 % of the logits' variance is the same for every input
    (seen on the chip in PR 46: 15 distinct arg-max tokens over 1024
    positions). The two are uncorrelated, and over random tokens the
    logits' mean over positions is a small part of them."""
    for bp in params["layers"]:
        gate, up = (np.asarray(bp["mlp"][n]["kernel"]).ravel()
                    for n in ("gate", "up"))
        assert abs(np.corrcoef(gate, up)[0, 1]) < 0.1
    tokens = jnp.asarray([_prompt(9, 96)], jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(falcon_h1_apply(params, tokens, spec.config,
                                            dtype=jnp.float32)[0, 32:])
    constant = (logits.mean(0) ** 2).sum() / (logits ** 2).sum(-1).mean()
    assert constant < 0.2, constant
    assert len(set(logits.argmax(-1).tolist())) > 40


@pytest.mark.parametrize("control", [
    {"drop": "ssm"}, {"drop": "attention"}, {"drop": "decay"},
    {"drop": "group"}, {"drop": "skip"}, {"drop": "conv_tail"},
    {"drop": "state"}, {"drop": "state_bf16"},
    {"weights_as": "float8_e4m3fn"}]
    + [{"drop": name} for name in MULTIPLIERS
       if name != "attention_in_multiplier"])
def test_each_control_moves_the_reference_s_logits(params, reference,
                                                   control):
    """Every control of `correct`, and every multiplier that is not 1
    (each dropped: it counts as 1), moves the logits past position 48 by
    more than a float32 served path may differ from the reference."""
    module, sizes = reference
    tokens = jnp.asarray(_prompt(0, 70), jnp.int32)
    want = module.forward(params, tokens, _sizes(sizes))
    moved = module.forward(params, tokens, _sizes(sizes, **control))
    # Rounding a state to bfloat16 moves a logit by thousandths; leaving a
    # term out by far more.
    least = 1e-3 if "bf16" in control.get("drop", "") else 0.05
    assert float(jnp.abs(moved - want)[48:].max()) > least


def test_a_multiplier_of_one_dropped_changes_nothing(params, reference):
    module, sizes = reference
    assert sizes["attention_in_multiplier"] == 1.0
    tokens = jnp.asarray(_prompt(0, 40), jnp.int32)
    want = module.forward(params, tokens, _sizes(sizes))
    same = module.forward(params, tokens,
                          _sizes(sizes, drop="attention_in_multiplier"))
    assert float(jnp.abs(same - want).max()) == 0.0


def test_each_multiplier_moves_the_program_s_logits(spec, params):
    """Every published multiplier reaches the served program's forward: a
    config with one of them doubled gives other logits from the same
    weights."""
    import dataclasses

    tokens = jnp.asarray([_prompt(0, 40)], jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = falcon_h1_apply(params, tokens, spec.config,
                               dtype=jnp.float32)
        for name in MULTIPLIERS:
            value = getattr(spec.config, name)
            doubled = (tuple(2.0 * v for v in value)
                       if isinstance(value, tuple) else 2.0 * value)
            got = falcon_h1_apply(
                params, tokens,
                dataclasses.replace(spec.config, **{name: doubled}),
                dtype=jnp.float32)
            assert float(jnp.abs(got - want).max()) > 0.05, name


def _pools(cfg, rows, blocks, state_dtype=jnp.float32):
    shape = (cfg.n_layers, blocks, BS, cfg.kv_heads * cfg.d_head)
    return (KVCache(jnp.zeros(shape), jnp.zeros(shape)),
            tuple(jnp.zeros((cfg.n_layers, rows) + s, state_dtype)
                  for s in cfg.state_row_shapes))


def _serve_in_chunks(spec, params, chunks, state_dtype=jnp.float32):
    """Two rows of different lengths in the same ticks through
    `falcon_h1_step_rows_ragged`: row 0 prefills `chunks` and then decodes;
    row 2 prefills 23 tokens and decodes beside it, so a tick runs the
    chunked form and the one-step form together. Row 1 is a free slot on
    the null state row. Returns ({row: (tokens, logits)}, caches)."""
    cfg = spec.config
    n_prompt, n_new = sum(chunks), 6
    seqs = {0: _prompt(1, n_prompt + n_new), 2: _prompt(2, 23 + 12)}
    plans = {0: list(chunks) + [1] * n_new, 2: [16, 7] + [1] * 12}
    caches = _pools(cfg, rows=4, blocks=17, state_dtype=state_dtype)
    table = np.zeros((3, 8), np.int32)
    table[0], table[2] = np.arange(1, 9), np.arange(9, 17)
    tables = (jnp.asarray(table), jnp.asarray([3, 0, 1], jnp.int32))

    def through(fn):
        """`fn` on a pool of `state_dtype`: what it reads is what the pool
        holds, what it writes is rounded to it."""
        def call(*args):
            ins, (pool, *where) = args[:5], args[5:]
            y, new = fn(*ins, pool.astype(jnp.float32), *where)
            return y, new.astype(pool.dtype)
        return call

    step = jax.jit(lambda tokens, caches, pos0, qlen:
                   falcon_h1_step_rows_ragged(
                       params, tokens, caches, tables, pos0, qlen, cfg,
                       dtype=jnp.float32, max_tokens=36,
                       step_fn=through(ssd.ssd_step_rows),
                       chunk_fn=through(ssd.ssd_chunk_row)))
    pos, got = {0: 0, 2: 0}, {0: [], 2: []}
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
            for r in pos:
                got[r].append(np.asarray(logits[r, :qlen[r]]))
                pos[r] += int(qlen[r])
    assert rows.shape == (0, 1)
    return {r: (seqs[r], np.concatenate(got[r])) for r in seqs}, caches


@pytest.mark.parametrize("chunks", [(16, 16, 16, 2), (7, 16, 16, 11),
                                    (16, 1, 16, 16, 1)])
def test_chunked_prefill_then_decode_equals_the_reference_on_logits(
        spec, params, reference, chunks):
    """At least three chunks, so a chunk starts from the state and the conv
    tail the last one left and reads K and V the earlier ones wrote, then
    decode steps through both pools, on logits within 1e-4."""
    module, sizes = reference
    served, caches = _serve_in_chunks(spec, params, chunks)
    for seq, got in served.values():
        want = module.forward(params, jnp.asarray(seq, jnp.int32),
                              _sizes(sizes))
        np.testing.assert_allclose(got, want, atol=1e-4)
    # The free slot's null row took nothing.
    assert all(float(jnp.abs(x[:, 0]).max()) == 0.0 for x in caches[1])


def test_a_bfloat16_state_fails_the_float32_comparison(spec, params,
                                                       reference):
    """The same ticks over a state pool kept in bfloat16 (rounded after
    every chunk and every step) miss the reference by far more than the
    1e-4 the float32 pool keeps: what `correct`'s limits on the chip
    cannot tell (bfloat16 weights move a logit by more) is held here."""
    module, sizes = reference
    served, _ = _serve_in_chunks(spec, params, (16, 16, 16, 2),
                                 state_dtype=jnp.bfloat16)
    seq, got = served[0]
    want = module.forward(params, jnp.asarray(seq, jnp.int32), _sizes(sizes))
    assert float(np.abs(got - np.asarray(want)).max()) > 1e-3


def test_both_mixers_of_a_layer_read_the_same_normed_rows(spec, params):
    """The step of three rows in 256 slots, traced: every layer makes the
    short call (3, 1, H, D) and the tall call in tiles of 128 slots (G = 5:
    128 / gcd(128, 5)), a tile a row of the call, and no operand of rows x
    width query slots; a step a slot wide makes one call a layer."""
    from tpu_engine.ops import paged_attention as pa

    cfg = spec.config
    asked = []

    def attn_fn(q, *rest):
        asked.append(q.shape)
        return pa.ragged_paged_attention_reference(q, *rest)

    def step(width):
        tables = (jnp.zeros((3, 32), jnp.int32), jnp.zeros(3, jnp.int32))
        return jax.make_jaxpr(
            lambda tokens, caches, pos0, qlen: falcon_h1_step_rows_ragged(
                params, tokens, caches, tables, pos0, qlen, cfg,
                dtype=jnp.float32, max_tokens=200, attn_fn=attn_fn,
                sample_slot=jnp.zeros(3, jnp.int32)))(
            jnp.zeros((3, width), jnp.int32), _pools(cfg, rows=4, blocks=17),
            jnp.zeros(3, jnp.int32), jnp.ones(3, jnp.int32))

    jaxpr = step(256)
    heads = (cfg.n_heads, cfg.d_head)
    assert asked == [(3, 1) + heads, (3 + 2, 128) + heads] * cfg.n_layers
    shapes = [v.aval.shape for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars]
    assert shapes and not [x for x in shapes
                           if len(x) == 4 and x[:2] == (3, 256)]
    del asked[:]
    step(1)
    assert asked == [(3, 1) + heads] * cfg.n_layers


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
        # Both pools hold EVERY layer.
        assert pools[0].cfg.n_layers == 3 and pools[1].n_layers == 3
        assert [x.shape for x in pools[1].slab] == [
            (3, 5, 4, 8, 16), (3, 5, 8, 36)]
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
    state, pool = stats["state_pool"], stats["kv_pool"]
    assert state["rows_total"] == 4 and state["rows_peak"] == 3
    assert state["rows_held"] == 0 and state["rows_free"] == 4
    assert state["bytes_per_row"] == 3 * (4 * 8 * 16 + 288) * 4
    assert pool["blocks_free"] == pool["blocks_total"]
    assert pool["kv_bytes_held"] == pool["state_bytes_held"] == 0
    assert pool["block_lanes"] == [8, 8]
    spans = [s["attrs"] for s in gen.tracer.snapshot()
             if s["op"] == "mixed_step"]
    mixed = stats["mixed"]
    assert sum(s["ssd_chunk_tokens"] + s["ssd_step_rows"]
               for s in spans) == (mixed["prefill_tokens"]
                                   + mixed["decode_tokens"])
    assert any(s["ssd_chunk_tokens"] and s["ssd_step_rows"] for s in spans)
    assert all(s["ctx_tokens_full"] == s["ctx_tokens"] for s in spans)
    # A chunk of at most 16 tokens is one tall tile; a step, a short one.
    assert all(s["attn_tiles_short"] == s["ssd_step_rows"]
               and s["attn_tiles_tall"] == s["ssd_chunk_rows"] for s in spans)
    assert max(s["state_rows_held"] for s in spans) == 3
    assert not any(k.startswith(("gdn_", "kda_")) for s in spans for k in s)


def test_what_the_family_refuses_at_start_up_stays_refused(spec, params):
    for flag, value in (("prefix_sharing", True), ("kv_quantize", "int8"),
                        ("spec_k", 2)):
        with pytest.raises(ValueError, match="does not declare"):
            ContinuousGenerator(spec, params=params,
                                **{**LANE, flag: value})
    with pytest.raises(ValueError, match="mixed tick over the block pool"):
        ContinuousGenerator(spec, params=params,
                            **{**LANE, "kv_block_size": 0})
