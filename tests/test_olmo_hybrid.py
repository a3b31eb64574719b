"""The Olmo-Hybrid family (models/olmo_hybrid.py, ops/gated_delta.py) on the
served path: a row that owns a recurrent state for three layers in four AND
a paged K/V chain for the fourth. `olmo_hybrid_small` (two periods L L L F,
3 heads, keys of 8 and values of 16 lanes, conv 4, MHA at head size 16)
against the plain reference benchmarks/references/olmo_hybrid.py, on
logits; the two pools' bookkeeping; the start-up fences."""

import functools
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models.olmo_hybrid import (
    olmo_hybrid_apply,
    olmo_hybrid_step_rows_ragged,
)
from tpu_engine.models.registry import (
    FAMILY_CAPABILITIES,
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.ops import gated_delta as gd
from tpu_engine.ops import paged_attention as pa
from tpu_engine.ops.attention import KVCache
from tpu_engine.runtime.scheduler import ContinuousGenerator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16
LANE = dict(n_slots=4, dtype="float32", kv_block_size=BS,
            prefill_chunk=16, prefix_sharing=False)


@pytest.fixture(scope="module")
def spec():
    _ensure_builtin_models_imported()
    return create_model("olmo_hybrid_small")


@pytest.fixture(scope="module")
def params(spec):
    return jax.jit(spec.init)(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def reference():
    """benchmarks/references/olmo_hybrid.py and the test configuration's
    `reference` block as the harness hands it over."""
    import sys

    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    path = os.path.join(bench, "references", "olmo_hybrid.py")
    module_spec = importlib.util.spec_from_file_location(
        "olmo_hybrid_reference_under_test", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    with open(os.path.join(ROOT, "tests", "benchmarks", "data", "configs",
                           "olmo-hybrid-small-test.json")) as f:
        sizes = json.load(f)["reference"]
    return module, sizes


def _sizes(sizes, **more):
    return tuple(sorted(dict(sizes, **more).items()))


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, n)]


# -- registry and configuration --------------------------------------------------

def test_family_capabilities_and_stated_widths(spec):
    cfg = spec.config
    assert spec.state_family == "kv_and_state"
    assert spec.capabilities == FAMILY_CAPABILITIES["kv_and_state"]
    for absent in ("prefix_sharing", "kv_host_tier", "kv_quantize",
                   "spec_decode", "tensor_parallel", "migration", "handoff",
                   "two_path"):
        assert not spec.supports(absent)
    assert cfg.linear == (True, True, True, False) * 2
    assert cfg.pool_layer == (0, 1, 2, 0, 3, 4, 5, 1)
    assert [k.n_layers for k in cfg.kv_block_kinds] == [2]
    assert cfg.state_row_shapes == ((3, 16, 8), (3, 3 * (8 + 8 + 16)))
    assert cfg.lin_key_dim != cfg.lin_value_dim


def test_the_published_geometry_is_the_default():
    _ensure_builtin_models_imported()
    spec = create_model("olmo_hybrid")
    cfg = spec.config
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab) == (
        32, 3840, 11008, 100352)
    assert (cfg.n_heads, cfg.kv_heads, cfg.d_head) == (30, 30, 128)
    assert (cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim,
            cfg.conv_width, cfg.neg_eigval) == (30, 96, 192, 4, True)
    assert cfg.n_linear_layers == 24 and cfg.n_full_layers == 8
    shapes = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert round(count / 1e9, 2) == 7.43
    # A row's state: 30 x 192 x 96 and 3 x 11520 float32 a linear layer.
    assert sum(int(np.prod(s)) for s in cfg.state_row_shapes) * 4 == 2350080


# -- the op: chunked == one-step == the reference's scan --------------------------

def _gdn_inputs(t=150, h=3, dk=8, dv=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (t, h, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (t, h, dk)))
    v = jax.random.normal(ks[2], (t, h, dv))
    g = jnp.log(jax.random.uniform(ks[3], (t, h), minval=0.5, maxval=1.0))
    beta = jax.random.uniform(ks[4], (t, h), minval=1.0, maxval=2.0)
    return (q, k, v, g, beta), jax.random.normal(ks[5], (h, dv, dk))


@pytest.mark.parametrize("given", [False, True])
def test_chunked_equals_one_step_equals_the_reference_s_scan(reference,
                                                             given):
    """b in (1, 2) throughout (negative eigenvalues), a run of 150 tokens
    padded to three sub-chunks of 64 with tokens that change nothing."""
    (q, k, v, g, beta), state = _gdn_inputs()
    if not given:
        state = jnp.zeros_like(state)
    with jax.default_matmul_precision("highest"):
        # The reference's own recurrence, token by token.
        s, want = state, []
        for t in range(q.shape[0]):
            s = jnp.exp(g[t])[:, None, None] * s
            u = beta[t][:, None] * (v[t] - jnp.einsum("hvk,hk->hv", s, k[t]))
            s = s + u[:, :, None] * k[t][:, None, :]
            want.append(jnp.einsum("hvk,hk->hv", s, q[t]))
        want = jnp.stack(want)
        o_scan, s_scan = gd.gdn_scan(q, k, v, g, beta, state)
        pad = -q.shape[0] % gd.SUB_CHUNK
        padded = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
                  for x in (q, k, v, g, beta)]
        o_chunk, s_chunk = jax.jit(gd.gdn_chunk)(*padded, state)
        s_step, o_step = state[None], []
        for t in range(q.shape[0]):
            o, s_step = gd.gdn_step(q[t][None], k[t][None], v[t][None],
                                    g[t][None], beta[t][None], s_step)
            o_step.append(o[0])
    for o, last in ((o_scan, s_scan), (o_chunk[:q.shape[0]], s_chunk),
                    (jnp.stack(o_step), s_step[0])):
        np.testing.assert_allclose(o, want, atol=2e-5)
        np.testing.assert_allclose(last, s, atol=2e-5)


def test_a_run_that_is_no_whole_number_of_sub_chunks_is_refused():
    (q, k, v, g, beta), state = _gdn_inputs(t=65)
    with pytest.raises(ValueError, match="no multiple of 64"):
        gd.gdn_chunk(q, k, v, g, beta, state)


def _pool_case(seed=0, h=3, dk=8, dv=16):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, 6, h, dv, dk))


def test_the_step_kernel_changes_the_rows_states_where_they_lie():
    """The Pallas step in the interpreter against the gather and scatter:
    five rows of which three take the step (one from a zero state), the
    other two pointed at the null row, which is left as it was."""
    (q, k, v, g, beta), _ = _gdn_inputs(t=5)
    beta = beta - 1.0 + jnp.arange(5)[:, None] * 0.4        # b in (0, 2.6)
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
    """A run for the chunk kernel and how many of its tokens are live.
    `two_sub_chunks`: 128 tokens as `_gdn_inputs` draws them. The runs of
    256 (four sub-chunks, sixteen diagonal blocks: every block row of the
    blocked solve and the solves of all four before the state's pass) draw
    b over (0, 2) and let head 0 forget at exp(-5) a token;
    `shorter_than_its_padding` ends at token 150 and is padded with tokens
    of b = 0, g = 0."""
    if run == "two_sub_chunks":
        return _gdn_inputs(t=128)[0], 128
    (q, k, v, g, beta), _ = _gdn_inputs(t=256, seed=3)
    beta = jax.random.uniform(jax.random.PRNGKey(7), beta.shape, maxval=2.0)
    g = g.at[:, 0].set(-5.0)
    live = 150 if run == "shorter_than_its_padding" else 256
    past = jnp.arange(256)[:, None] >= live
    return (q, k, v, jnp.where(past, 0.0, g), jnp.where(past, 0.0, beta)), live


# Jitted once a shape: `fresh` is an operand, so a run's two cases share
# the interpreter's program.
_chunk_in_the_interpreter = jax.jit(
    functools.partial(gd.gdn_chunk_row, interpret=True))


@pytest.mark.parametrize("run", ["two_sub_chunks", "four_sub_chunks",
                                 "shorter_than_its_padding"])
@pytest.mark.parametrize("fresh", [False, True])
def test_the_chunk_kernel_equals_the_scan_from_the_row_s_state(fresh, run):
    """The Pallas chunk in the interpreter (the triangular systems solved
    in blocks of 16 for all the sub-chunks, then the state carried over
    them in VMEM) against the token-by-token scan of the run's LIVE tokens
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
    np.testing.assert_allclose(o[:live], o_want, atol=2e-5)
    np.testing.assert_allclose(new, pool.at[1, 4].set(last), atol=2e-5)
    np.testing.assert_allclose(o_xla[:live], o_want, atol=2e-5)
    np.testing.assert_allclose(same, new, atol=2e-5)


# -- the model against the plain reference ----------------------------------------

def test_the_forward_equals_the_plain_reference(spec, params, reference):
    module, sizes = reference
    tokens = jnp.asarray(_prompt(0, 70), jnp.int32)
    want = module.forward(params, tokens, _sizes(sizes))
    with jax.default_matmul_precision("highest"):
        got = olmo_hybrid_apply(params, tokens[None], spec.config,
                                dtype=jnp.float32)[0]
    assert float(want.std()) > 0.5
    np.testing.assert_allclose(got, want, atol=5e-5)


@pytest.mark.parametrize("control", [
    {"drop": "decay"}, {"drop": "double"}, {"drop": "conv_tail"},
    {"drop": "state"}, {"drop": "state_bf16"}, {"drop": "state_bf16_step"},
    {"weights_as": "float8_e4m3fn"}])
def test_each_control_moves_the_reference_s_logits(params, reference,
                                                   control):
    module, sizes = reference
    tokens = jnp.asarray(_prompt(0, 70), jnp.int32)
    want = module.forward(params, tokens, _sizes(sizes))
    moved = module.forward(params, tokens, _sizes(sizes, **control))
    # Rounding a state to bfloat16 moves a logit by thousandths; leaving a
    # term out by more than a standard deviation.
    least = 1e-3 if "bf16" in control.get("drop", "") else 0.3
    assert float(jnp.abs(moved - want)[48:].max()) > least


def _pools(cfg, rows, blocks):
    shape = (cfg.n_full_layers, blocks, BS, cfg.kv_heads * cfg.d_head)
    return (KVCache(jnp.zeros(shape), jnp.zeros(shape)),
            tuple(jnp.zeros((cfg.n_linear_layers, rows) + s)
                  for s in cfg.state_row_shapes))


@pytest.mark.parametrize("chunks", [(16, 16, 16, 2), (7, 16, 16, 11),
                                    (16, 1, 16, 16, 1)])
def test_chunked_prefill_then_decode_equals_the_reference_on_logits(
        spec, params, reference, chunks):
    """Two rows of different lengths in the same ticks: row 0 prefills
    `chunks` (at least three, so a chunk starts from the state and conv
    tail the last one left) and then decodes; row 2 prefills 23 tokens and
    decodes beside it, so a tick runs the chunked form and the one-step
    form together. Row 1 is a free slot on the null state row."""
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
                   olmo_hybrid_step_rows_ragged(
                       params, tokens, caches, tables, pos0, qlen, cfg,
                       dtype=jnp.float32, max_tokens=36))
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
    for r, seq in seqs.items():
        want = module.forward(params, jnp.asarray(seq, jnp.int32),
                              _sizes(sizes))
        np.testing.assert_allclose(np.concatenate(got[r]), want, atol=1e-4)
    # The free slot's null row took nothing.
    assert all(float(jnp.abs(x[:, 0]).max()) == 0.0 for x in caches[1])


def test_the_chunk_tick_s_full_layers_hold_no_operand_of_rows_x_width(
        spec, params):
    """The step of three rows in 256 slots, traced: each full layer reads
    the rows with one new token as (3, 1, H, D) and the longer runs as
    tall tiles of 128 slots, (3 + ceil(200 / 128), 128, H, D), a tile a
    row of the call; nothing of rows x width query slots is made for a
    full layer (the head's gather of the rows' hidden states is left
    alone: here the step samples a slot a row). A step a slot wide makes
    one call a layer."""
    cfg = spec.config
    asked = []

    def attn_fn(q, *rest):
        asked.append(q.shape)
        return pa.ragged_paged_attention_reference(q, *rest)

    def step(width):
        tables = (jnp.zeros((3, 32), jnp.int32), jnp.zeros(3, jnp.int32))
        return jax.make_jaxpr(
            lambda tokens, caches, pos0, qlen: olmo_hybrid_step_rows_ragged(
                params, tokens, caches, tables, pos0, qlen, cfg,
                dtype=jnp.float32, max_tokens=200, attn_fn=attn_fn,
                sample_slot=jnp.zeros(3, jnp.int32)))(
            jnp.zeros((3, width), jnp.int32), _pools(cfg, rows=4, blocks=17),
            jnp.zeros(3, jnp.int32), jnp.ones(3, jnp.int32))

    jaxpr = step(256)
    heads = (cfg.n_heads, cfg.d_head)
    assert asked == [(3, 1) + heads,
                     (3 + 2, 128) + heads] * cfg.n_full_layers
    shapes = [v.aval.shape for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars]
    assert shapes and not [x for x in shapes
                           if len(x) == 4 and x[:2] == (3, 256)]
    del asked[:]
    step(1)
    assert asked == [(3, 1) + heads] * cfg.n_full_layers


# -- the paged kernel at this model's heads ---------------------------------------

@pytest.mark.parametrize("width", [1, 256])
def test_paged_kernel_at_one_query_head_a_kv_head_of_128_lanes(width):
    """G = 1, head size 128 (six heads here): a width-1 call packs the
    heads into one score tile; a 256-wide chunk is two tiles of 128 rows
    beside decode rows and a dead one."""
    q_lens = (1, 1, 1, 1) if width == 1 else (256, 1, 0, 1)
    pos0 = (37, 128, 300, 5) if width == 1 else (224, 301, 0, 17)
    error = pa._parity("ragged", q_lens, interpret=True, n_heads=6,
                       n_kv_heads=6, d_head=128, block_size=BS,
                       n_blocks=65, table_len=32, dtype=jnp.float32,
                       pos0=pos0)
    assert error < 2e-5


def test_a_pool_of_many_lanes_walks_smaller_groups():
    """30 KV heads of 128 lanes in bfloat16: 16 blocks a group would hold
    7.9 MB of K and V in VMEM; the group halves to fit
    `_KV_SCRATCH_BYTES`, and a pool of 1280 lanes keeps its 16."""
    per_block = lambda lanes: 4 * BS * lanes * 2
    assert 16 * per_block(3840) > pa._KV_SCRATCH_BYTES >= 8 * per_block(3840)
    assert 16 * per_block(2048) <= pa._KV_SCRATCH_BYTES


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
        assert pools[0].cfg.n_layers == 2 and pools[1].n_layers == 6
        assert [x.shape for x in pools[1].slab] == [
            (6, 5, 3, 16, 8), (6, 5, 3, 96)]
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
    assert state["bytes_per_row"] == 6 * (3 * 16 * 8 + 3 * 96) * 4
    assert pool["blocks_free"] == pool["blocks_total"]
    assert pool["kv_bytes_held"] == pool["state_bytes_held"] == 0
    spans = [s["attrs"] for s in gen.tracer.snapshot()
             if s["op"] == "mixed_step"]
    mixed = stats["mixed"]
    assert sum(s["gdn_chunk_tokens"] + s["gdn_step_rows"]
               for s in spans) == (mixed["prefill_tokens"]
                                   + mixed["decode_tokens"])
    assert any(s["gdn_chunk_tokens"] and s["gdn_step_rows"] for s in spans)
    assert all(s["ctx_tokens_full"] == s["ctx_tokens"] for s in spans)
    # A chunk of at most 16 tokens is one tall tile; a step, a short one.
    assert all(s["attn_tiles_short"] == s["gdn_step_rows"]
               and s["attn_tiles_tall"] == s["gdn_chunk_rows"] for s in spans)
    assert max(s["state_rows_held"] for s in spans) == 3


def test_a_context_past_four_thousand_tokens_through_the_whole_step(reference):
    """The benchmark's `correct` samples prompts up to 2000 tokens (its
    reference's logits for more do not fit beside the pools); the traffic
    reaches 8704. Here, at the small widths: a prompt of 4200 tokens in 17
    chunks of 256 (four sub-chunks of 64 each, the state and the conv tail
    carried over 16 chunk boundaries, the full layers' table past 262
    blocks) beside a short row that decodes in the same ticks, then decode
    steps past position 4200, on logits against the reference's scan."""
    module, sizes = reference
    long_spec = create_model("olmo_hybrid_small", max_seq=4608)
    weights = jax.jit(long_spec.init)(jax.random.PRNGKey(3))
    gen = ContinuousGenerator(long_spec, params=weights,
                              **{**LANE, "n_slots": 2, "prefill_chunk": 256})
    prompts = [_prompt(11, 4200), _prompt(12, 70)]
    try:
        futures = [gen.submit(prompts[0], max_new_tokens=6),
                   gen.submit(prompts[1], max_new_tokens=24)]
        served = [f.result(timeout=600) for f in futures]
        stats = gen.stats()
    finally:
        gen.stop()
    assert stats["mixed"]["prefill_tokens"] == 4270
    assert stats["kv_pool"]["blocks_free"] == stats["kv_pool"]["blocks_total"]
    for prompt, tokens in zip(prompts, served):
        want = module.forward(weights,
                              jnp.asarray(prompt + tokens[:-1], jnp.int32),
                              _sizes(sizes))[len(prompt) - 1:]
        gap = want.max(-1) - want[jnp.arange(len(tokens)), jnp.asarray(tokens)]
        assert float((gap / want.std(-1)).max()) < 0.05


def _drained(gen):
    stats = gen.stats()
    pool, state = stats["kv_pool"], stats["state_pool"]
    return (pool["blocks_free"] == pool["blocks_total"]
            and state["rows_held"] == 0
            and state["rows_free"] == state["rows_total"]
            and not gen._spool.rows.any())


@pytest.mark.parametrize("how", ["finish", "deadline", "reset"])
def test_a_row_gives_back_its_state_row_and_its_blocks(spec, params, how):
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
    """A row's recurrent state is stepped where it lies by the tick in
    flight while the next tick is formed; a row that meets its EOS is
    stepped once more as a done row (its state row is its slot's own, and
    the next request starts it from zero at position 0), and both pools
    come back once (tests/tick_pipeline.py)."""
    from tick_pipeline import check_late_ends

    counters = check_late_ends(
        lambda: ContinuousGenerator(spec, params=params, **LANE),
        [_prompt(21, 40), _prompt(22, 7), _prompt(23, 25)], _drained)
    assert counters["overlapped_ticks"] > counters["ticks"] // 2


@pytest.mark.parametrize("short", [{"n_slots": 1}, {"kv_blocks": 9}])
def test_a_request_waits_when_either_pool_is_short(spec, params, short):
    """One slot and so one state row (a state row is its slot's own), or
    blocks for one long row: the second request waits for the first to
    end, and both are served whole."""
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


@pytest.mark.parametrize("kwargs, error, message", [
    ({"kv_block_size": 0, "kv_blocks": 64}, ValueError,
     r"set kv_block_size > 0 \(the dense per-slot cache has no"),
    ({"kv_block_size": 0}, ValueError,
     "served by the mixed tick over the block pool only"),
    ({"prefix_sharing": True}, ValueError,
     "prefix_sharing needs the 'prefix_sharing' capability.*"
     "not block-addressable"),
    ({"kv_quantize": "int8"}, ValueError,
     "kv_quantize needs the 'kv_quantize' capability"),
    ({"kv_host_blocks": 8}, ValueError,
     "kv_host_blocks needs the 'kv_host_tier' capability"),
    ({"spec_k": 2}, ValueError,
     "spec_k needs the 'spec_decode' capability.*rolled back"),
    ({"state_rows": 2}, ValueError,
     "state_rows applies to the state_slab family; model "
     "'olmo_hybrid_small' serves the kv_and_state family"),
    ({"tp": 2}, RuntimeError, "cannot serve tensor-parallel"),
])
def test_what_a_lane_with_both_kinds_of_state_cannot_do_is_refused_at_start_up(
        spec, params, kwargs, error, message):
    with pytest.raises(error, match=message):
        ContinuousGenerator(spec, params=params, **{**LANE, **kwargs})


def test_the_chain_wire_format_is_refused_by_name(spec, params):
    gen = ContinuousGenerator(spec, params=params, **LANE)
    try:
        refusal = ("needs the 'migration' capability, which the "
                   "kv_and_state family does not declare")
        assert refusal in gen.export_row("nobody")["reason"]
        assert refusal in gen.export_prefix([1] * 32)["reason"]
        with pytest.raises(ValueError, match=refusal):
            gen.submit_import({"prompt": [1], "emitted": [], "pos": 1,
                               "tok": 1, "max_new": 1, "chain": {}})
    finally:
        gen.stop()


def test_the_scheduler_imports_no_step_of_this_model_by_name():
    import inspect

    from tpu_engine.runtime import scheduler

    source = inspect.getsource(scheduler)
    for name in ("olmo_hybrid_step_rows_ragged", "models.olmo_hybrid import",
                 "gated_delta"):
        assert name not in source


# -- the serving layer's start-up fences -----------------------------------------

_GEN_KW = dict(model="olmo_hybrid_small", dtype="float32", batch_buckets=(1,),
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
                             f"model 'olmo_hybrid_small' \\(kv_and_state"):
        serve_combined(model="olmo_hybrid_small", lanes=1, port=0,
                       worker_config=WorkerConfig(**_GEN_KW),
                       gateway_config=GatewayConfig(port=0, **{flag: True}),
                       warmup=False, native_front=False)


def test_state_rows_is_refused_at_start_up():
    """A state row is its slot's own: nothing sizes the state pool but
    the slots, and the slab family's knob is refused as on any other."""
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig

    with pytest.raises(RuntimeError,
                       match="--state-rows applies to state_slab-family "
                             "models; model 'olmo_hybrid_small' serves the "
                             "kv_and_state family"):
        WorkerNode(WorkerConfig(node_id="w", gen_state_rows=2, **_GEN_KW))
