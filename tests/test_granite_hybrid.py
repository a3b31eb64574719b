"""The Granite-4.0-H family (models/granite_hybrid.py) on the served path:
EVERY layer is a mixer (a Mamba-2 recurrence whose B and C are ONE group, or
un-rotated GQA with the scores times 1/128) AND an expert block
(soft-max-routed SwiGLU experts beside a shared one), four published scalars
on the stream; a row owns a state row in the mamba layers and a paged K/V
chain in the attention layer, and routes in all of them.
`granite_hybrid-small-test` (mamba, mamba, attention, mamba; 4 query heads
over 1 KV head of 8 lanes, 8 SSM heads of 4 lanes in one group over a state
of 16 lanes, 6 of 12 experts held, top 4) against the plain reference
benchmarks/references/granite_hybrid.py, on logits; the two chips' shares
summed; each control of `correct`; the two pools' bookkeeping and the
spans."""

import dataclasses
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models import granite_hybrid as gh
from tpu_engine.models.granite_hybrid import (
    granite_hybrid_apply,
    granite_hybrid_step_rows_ragged,
)
from tpu_engine.models.registry import (
    FAMILY_CAPABILITIES,
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.ops.attention import KVCache
from tpu_engine.runtime.kv_blocks import BlockPool, StateRowPool
from tpu_engine.runtime.scheduler import ContinuousGenerator

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmarks"))
from bench_paths import BENCH  # noqa: E402,F401  (benchmarks/ on the path)

from lib.xplane_scopes import part_of  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16
LANE = dict(n_slots=4, dtype="float32", kv_block_size=BS,
            prefill_chunk=16, prefix_sharing=False)


@pytest.fixture(scope="module")
def spec():
    _ensure_builtin_models_imported()
    return create_model("granite_hybrid-small-test")


@pytest.fixture(scope="module")
def params(spec):
    return jax.jit(spec.init)(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def reference(params):
    """benchmarks/references/granite_hybrid.py, the test configuration's
    `reference` block as the harness hands it over, and `forward(seq,
    **more)`: the reference's logits over one whole sequence, jitted once a
    padded length and a set of controls."""
    path = os.path.join(ROOT, "benchmarks", "references", "granite_hybrid.py")
    module_spec = importlib.util.spec_from_file_location(
        "granite_hybrid_reference_under_test", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    with open(os.path.join(ROOT, "tests", "benchmarks", "data", "configs",
                           "granite-hybrid-small-test.json")) as f:
        sizes = json.load(f)["reference"]
    jitted = jax.jit(module.forward, static_argnums=(2,))

    def forward(seq, **more):
        tokens = np.zeros((-(-len(seq) // 64) * 64,), np.int32)
        tokens[:len(seq)] = seq
        return np.asarray(jitted(
            params, jnp.asarray(tokens),
            tuple(sorted(dict(sizes, **more).items())))[:len(seq)])

    return module, sizes, forward


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, n)]


# -- registry, configuration ---------------------------------------------------------

def test_family_capabilities_and_a_layer_s_two_indices(spec):
    cfg = spec.config
    assert spec.state_family == "kv_and_state" and cfg.recurrence == "ssd"
    assert spec.capabilities == FAMILY_CAPABILITIES["kv_and_state"]
    for absent in ("prefix_sharing", "kv_host_tier", "kv_quantize",
                   "spec_decode", "tensor_parallel", "migration", "handoff",
                   "two_path"):
        assert not spec.supports(absent)
    assert spec.held == cfg.held == (0, 6) and spec.passes == 1
    # mamba, mamba, attention, mamba: a layer's index in its MIXER's pool;
    # its row of the per-expert counts is the layer itself (all four route).
    assert cfg.pool_layer == (0, 1, 0, 2)
    assert (cfg.n_linear_layers, cfg.n_full_layers, cfg.n_moe_layers) == (
        3, 1, 4)
    assert [k.n_layers for k in cfg.kv_block_kinds] == [1]
    assert cfg.kv_block_kinds[0].kv_lanes == (8, 8)
    # S and the conv tail, 3 x (32 + 2 x 16) = 192 numbers as 8 x 24.
    assert (cfg.n_groups, cfg.conv_lanes) == (1, 64)
    assert cfg.state_row_shapes == ((8, 4, 16), (8, 24))


def test_the_published_geometry_is_the_default_and_the_cut_is_the_issue_s():
    _ensure_builtin_models_imported()
    whole = create_model("granite_hybrid").config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        source = next(row for row in map(json.loads, f)
                      if row["name"] == "granite-4.0-h-small")["config"]
    assert list(whole.layer_types) == source["layer_types"]
    assert (whole.n_layers, whole.d_model, whole.vocab, whole.ln_eps) == (
        40, 4096, 100352, 1e-5)
    assert (whole.n_heads, whole.kv_heads, whole.d_head, whole.pos) == (
        32, 8, 128, "none")
    assert (whole.lin_heads, whole.ssm_head_dim, whole.d_state,
            whole.n_groups, whole.conv_width, whole.d_ssm,
            whole.conv_lanes) == (128, 64, 128, 1, 4, 8192, 8448)
    assert (whole.n_routed, whole.top_k, whole.d_ff_expert,
            whole.d_ff_shared, whole.held) == (72, 10, 768, 1536, (0, 72))
    assert (whole.embedding_multiplier, whole.residual_multiplier,
            whole.attention_multiplier, whole.logits_scaling) == tuple(
        source[k] for k in ("embedding_multiplier", "residual_multiplier",
                            "attention_multiplier", "logits_scaling"))
    assert (whole.n_linear_layers, whole.n_full_layers,
            whole.n_moe_layers) == (36, 4, 40)
    # q times 128^-1/2 before a read that divides by sqrt(128) itself.
    assert abs(whole.query_scale - 128 ** -0.5) < 1e-12
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-small-10l.json")) as f:
        bench = json.load(f)
    for key, value in source.items():
        if key not in bench["reduced"]:
            assert bench[key] == value, key
    assert sorted(bench["reduced"]) == sorted(bench["published"]) == [
        "max_position_embeddings", "num_hidden_layers", "num_local_experts",
        "vocab_size"]
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg = spec.config
    shapes = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    biases = sum(int(np.prod(x.shape)) for path, x in leaves
                 if str(path[-1]) == "['bias']")
    # ISSUE 64's arithmetic, to the parameter, beside the zero biases the
    # program's dense layers carry: 9 x (102.29 + 19.18 + 339.74) M + (41.94
    # + 19.18 + 339.74) M + 2 x 205.5 M and the final norm.
    count = sum(int(np.prod(x.shape)) for _, x in leaves) - biases
    mamba = (4096 * 16768 + 8192 * 4096 + 4 * 8448 + 8448 + 8192 + 3 * 128)
    block = 4096 * 72 + 4096 * 3072 + 1536 * 4096 + 2 * 4096
    expert = 4096 * 1536 + 768 * 4096
    attention = 4096 * 6144 + 4096 * 4096
    assert count == (9 * mamba + attention + 10 * (block + 36 * expert)
                     + 2 * 50176 * 4096 + 4096) == 4962732672
    # Bank dtypes off `eval_shape`: every matrix bfloat16, made so.
    for bp in shapes["layers"]:
        bank = bp["mlp"]["experts"]
        assert bank["gate_up"].shape == (36, 4096, 1536)
        assert bank["down"].shape == (36, 768, 4096)
        assert {x.dtype for x in jax.tree.leaves(bank)} == {
            jnp.dtype(jnp.bfloat16)}
        assert bp["mlp"]["router"]["kernel"].dtype == jnp.float32
    assert (cfg.n_linear_layers, cfg.n_full_layers, cfg.n_moe_layers) == (
        9, 1, 10)
    assert cfg.pool_layer == (0, 1, 2, 3, 4, 0, 5, 6, 7, 8)
    # A row's state: 9 x (128 x 64 x 128 + 3 x 8448) float32; a block of 16
    # tokens: 1 layer x K and V x 8 heads x 128 lanes in bfloat16.
    assert cfg.state_row_shapes == ((128, 64, 128), (8, 3168))
    rows = StateRowPool(cfg.n_linear_layers, cfg.state_row_shapes, 1)
    assert rows.bytes_per_row() == 9 * (128 * 64 * 128 + 3 * 8448) * 4
    pool = BlockPool(cfg.kv_block_kinds[0], 2, 16, jnp.bfloat16)
    assert pool.bytes_per_block() == 16 * 4096


@pytest.mark.parametrize("change, message", [
    (dict(layer_types=("mamba", "attention", "mamba")), "one entry a layer"),
    (dict(layer_types=("mamba", "conv", "attention", "mamba")),
     "'conv' is no mixer"),
    (dict(layer_types=("mamba",) * 4), "needs a 'mamba' and an 'attention'"),
    (dict(layer_types=("attention",) * 4),
     "needs a 'mamba' and an 'attention'"),
    (dict(held=(8, 6)), "is no share of 12 experts"),
    (dict(held=(0, 0)), "is no share of 12 experts"),
    (dict(n_groups=3), "no whole groups"),
])
def test_a_layer_list_or_a_share_that_cannot_be_served_is_refused(
        spec, change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(spec.config, **change)


# -- the model against the plain reference ----------------------------------------

@pytest.fixture(scope="module")
def one_shot(spec, params, reference):
    """(a sequence of 60 tokens, the program's one-shot float32 logits over
    it, the reference's)."""
    _, _, forward = reference
    seq = _prompt(0, 60)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda tokens: granite_hybrid_apply(
            params, tokens, spec.config, dtype=jnp.float32))(
            jnp.asarray([seq], jnp.int32))[0]
    return seq, np.asarray(got), forward(seq)


def test_the_forward_equals_the_plain_reference(one_shot):
    _, got, want = one_shot
    # logits_scaling 16 over a head of unit-variance logits.
    assert 0.03 < float(want.std()) < 0.12
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert len(set(got.argmax(-1).tolist())) > 30


def test_the_two_shares_add_up_to_the_uncut_layer(spec, reference):
    """Two chips share every layer: each holds 6 of the 12 routed experts,
    and the mixer, the router and the shared expert whole. The two shares'
    expert blocks, with the shared expert counted ONCE, sum to the
    reference's block over all 12 experts; through a WHOLE layer (the mixer
    and the stream counted once too) they sum to the uncut reference's
    layer."""
    module, sizes, _ = reference
    cfg = dataclasses.replace(spec.config, held=(0, 12))
    whole = gh.granite_hybrid_init(jax.random.PRNGKey(5), cfg)
    bp = whole["layers"][1]
    mp = bp["mlp"]
    z = jax.random.normal(jax.random.PRNGKey(8), (48, cfg.d_model))
    sizes = dict(sizes)
    with jax.default_matmul_precision("highest"):
        want = module._expert_block(mp, z, sizes)
        shared = module._expert_block(mp, z, dict(sizes, drop="experts"))
        total, taken = -shared, 0                # the shared one, once
        for first in (0, 6):
            share = dict(mp, experts={k: v[first:first + 6]
                                      for k, v in mp["experts"].items()})
            y, rows = gh._expert_block(share, z, jnp.ones(48, bool), cfg,
                                       jnp.float32, (first, 6), None)
            assert int(rows.sum()) == int(rows[first:first + 6].sum()) > 0
            total, taken = total + y, taken + int(rows.sum())
            # One share alone is not the block, and the reference given the
            # same share computes the same part.
            assert float(jnp.abs(y - want).max()) > 0.05
            np.testing.assert_allclose(
                y, module._expert_block(share, z,
                                        dict(sizes, held_first=first)),
                atol=3e-5)
        assert taken == 48 * cfg.top_k           # every pair on some chip
        np.testing.assert_allclose(total, want, atol=3e-5)

        # One whole layer from a stream h: mixer, then the block.
        h = jax.random.normal(jax.random.PRNGKey(9), (48, cfg.d_model))
        into = cfg.residual_multiplier
        u = gh.nn.rmsnorm(bp["ln1"], h, eps=cfg.ln_eps)
        mid = h + into * module._mamba(bp["ssm"], u, sizes)
        v = gh.nn.rmsnorm(bp["ln2"], mid, eps=cfg.ln_eps)
        uncut = mid + into * module._expert_block(mp, v, sizes)

        def shared_of(x):
            return module._expert_block(mp, x, dict(sizes, drop="experts"))

        def mamba(at, sp, x, carry):
            return gh._ssm_whole_row(sp, x, cfg, jnp.float32, gh._ssm_inputs,
                                     gh._ssm_output), carry

        parts = []
        for first in (0, 6):
            share = dict(bp, mlp=dict(mp, experts={
                k: w[first:first + 6] for k, w in mp["experts"].items()}))
            # `_run_layers` stops at the layers it is given: ONE, the
            # config's first kind (a mamba layer).
            out, _, rows = gh._run_layers(
                {"layers": [share]}, h, (), cfg, mamba, None,
                jnp.ones(48, bool), jnp.float32, (first, 6), None)
            assert rows.shape == (1, 12)
            parts.append(out)
        # Each share's layer holds the stream, the mixer and the shared
        # expert: summed, they are counted twice.
        once = mid + into * shared_of(v)
        np.testing.assert_allclose(parts[0] + parts[1] - once, uncut,
                                   atol=5e-5)


# -- the step over both pools ------------------------------------------------------

@pytest.fixture(scope="module")
def step(spec, params):
    """`granite_hybrid_step_rows_ragged` over three slots of 16, jitted
    once: slot 1 is free (the null state row 0), slots 0 and 2 own state
    rows 3 and 1."""
    cfg = spec.config
    table = np.zeros((3, 8), np.int32)
    table[0], table[2] = np.arange(1, 9), np.arange(9, 17)
    tables = (jnp.asarray(table), jnp.asarray([3, 0, 1], jnp.int32))
    return jax.jit(
        lambda tokens, caches, pos0, qlen: granite_hybrid_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            dtype=jnp.float32, max_tokens=36))


def _pools(cfg, rows, blocks, dtype=jnp.float32):
    """Both pools as a lane that has served before leaves them: a row that
    is admitted finds ANOTHER request's state and tail in its state row."""
    shape = (cfg.n_full_layers, blocks, BS, cfg.kv_heads * cfg.d_head)
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    stale = tuple(
        (5.0 * jax.random.normal(k, (cfg.n_linear_layers, rows) + s)
         ).astype(dtype) for k, s in zip(keys, cfg.state_row_shapes))
    return KVCache(jnp.zeros(shape), jnp.zeros(shape)), stale


def _serve_in_chunks(spec, step, chunks, dtype=jnp.float32):
    """Two rows of different lengths in the same ticks: row 0 prefills
    `chunks` (a 0 parks it for a tick: it keeps its position and its state)
    and then decodes; row 2 prefills 23 tokens and decodes beside it, so ONE
    tick holds a run of many tokens and a run of one. Returns ({row:
    (tokens, logits)}, caches, the rows the experts took)."""
    cfg = spec.config
    n_prompt, n_new = sum(chunks), 5
    seqs = {0: _prompt(1, n_prompt + n_new), 2: _prompt(2, 23 + 10)}
    plans = {0: list(chunks) + [1] * n_new, 2: [16, 7] + [1] * 10}
    caches = _pools(cfg, rows=4, blocks=17, dtype=dtype)
    pos, got, taken = {0: 0, 2: 0}, {0: [], 2: []}, 0
    with jax.default_matmul_precision("highest"):
        while any(plans.values()):
            tokens = np.zeros((3, 16), np.int32)
            pos0, qlen = np.zeros(3, np.int32), np.zeros(3, np.int32)
            for r, plan in plans.items():
                pos0[r] = pos[r]     # a row that waits keeps its position
                if plan:
                    n = plan.pop(0)
                    tokens[r, :n] = seqs[r][pos[r]:pos[r] + n]
                    qlen[r] = n
            logits, caches, rows = step(jnp.asarray(tokens), caches,
                                        jnp.asarray(pos0), jnp.asarray(qlen))
            assert rows.shape == (cfg.n_layers, cfg.n_routed)
            taken = taken + np.asarray(rows)
            for r in pos:
                got[r].append(np.asarray(logits[r, :qlen[r]]))
                pos[r] += int(qlen[r])
    return ({r: (seqs[r], np.concatenate(got[r])) for r in seqs}, caches,
            taken)


@pytest.mark.parametrize("chunks", [(16, 16, 16, 2), (7, 16, 16, 11),
                                    (1, 2, 16, 16, 1), (16, 0, 16, 0, 3)])
def test_chunked_prefill_then_decode_equals_the_reference_on_logits(
        spec, step, reference, chunks):
    """Runs of one token, of two, of a whole chunk and of none (a row parked
    for a tick and resumed), a run that crosses at least two chunk
    boundaries (its first tokens read the state and the conv tail the last
    chunk left in each of the three mamba layers, and K and V the earlier
    ones wrote in the attention layer), rows that prefill and rows that
    decode in ONE tick, every row admitted into a state row that holds
    another request's state: logits within 1e-4."""
    _, _, forward = reference
    cfg = spec.config
    served, caches, taken = _serve_in_chunks(spec, step, chunks)
    for seq, got in served.values():
        np.testing.assert_allclose(got, forward(seq), atol=1e-4)
    state, tails = caches[1]
    assert state.shape == (3, 4, 8, 4, 16) and state.dtype == jnp.float32
    assert tails.shape == (3, 4, 8, 24)
    # Every token routes top 4 in each of the four layers; the held half of
    # the experts takes about half, and none of the other half's.
    fed = sum(len(s) for s, _ in served.values())
    assert 0.3 * fed * 16 < taken.sum() < 0.7 * fed * 16
    assert taken[:, cfg.held[1]:].sum() == 0 and (taken.sum(1) > 0).all()


def test_a_bfloat16_state_fails_the_float32_comparison(spec, params,
                                                       reference):
    """That the state is float32 is held HERE (the chip's limits cannot see
    a state rounded to bfloat16 under bfloat16 weights): a bfloat16 state
    pool moves the float32 lane's logits past the 1e-4 the float32 pool
    keeps."""
    _, _, forward = reference
    cfg = spec.config
    table = np.zeros((3, 8), np.int32)
    table[0], table[2] = np.arange(1, 9), np.arange(9, 17)
    tables = (jnp.asarray(table), jnp.asarray([3, 0, 1], jnp.int32))
    rounded = jax.jit(
        lambda tokens, caches, pos0, qlen: granite_hybrid_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            dtype=jnp.float32, max_tokens=36,
            step_fn=_through_bfloat16(gh.ssd_step_rows),
            chunk_fn=_through_bfloat16(gh.ssd_chunk_row)))
    served, _, _ = _serve_in_chunks(spec, rounded, (16, 16, 16, 2))
    seq, got = served[0]
    assert float(np.abs(got - forward(seq)).max()) > 1e-3 * float(
        forward(seq).std())


def _through_bfloat16(fn):
    """`fn` over a state pool that keeps bfloat16's bits."""
    def call(x, dt, a, b, c, pool, *where):
        y, pool = fn(x, dt, a, b, c,
                     pool.astype(jnp.bfloat16).astype(jnp.float32), *where)
        return y, pool.astype(jnp.bfloat16).astype(jnp.float32)
    return call


# The controls of `correct` (benchmarks/configs/granite-4.0-h-small-10l.json
# `correct.why`) that 24 served positions at this size cannot tell (the
# others are refused by `check_served` at the small size:
# tests/benchmarks/test_benchmark_reference_granite_hybrid.py): each moves
# the reference's logits by far more than the float32 lane differs from it,
# so a served path that computed the control's function would fail here
# whatever the chip's 1200 positions can see (the experts alone in float8
# and `logits_scaling` they cannot).
CONTROLS = [
    dict(drop="score_scale"), dict(top_k=2), dict(drop="experts"),
    dict(drop="softmax_all"), dict(experts_as="float8_e4m3fn"),
    dict(weights_as="float8_e4m3fn"), dict(drop="logits"),
]


@pytest.mark.parametrize(
    "control", CONTROLS,
    ids=["=".join(map(str, next(iter(c.items())))) for c in CONTROLS])
def test_each_control_fails_on_logits(one_shot, reference, control):
    _, _, forward = reference
    seq, got, want = one_shot
    changed = forward(seq, **control)
    assert np.abs(got - want).max() < 1e-5
    assert np.abs(got - changed).max() > 30 * 1e-4 * want.std()
    if control == dict(drop="logits"):
        # `logits_scaling` moves no arg-max: held on logits alone.
        assert (changed.argmax(-1) == want.argmax(-1)).all()
        np.testing.assert_allclose(changed, want * 16.0, rtol=1e-5)


# -- the served path ---------------------------------------------------------------

@pytest.fixture(scope="module")
def lane(spec, params):
    gen = ContinuousGenerator(spec, params=params, **LANE)
    yield gen
    gen.stop()


def test_the_mixed_tick_serves_it_from_both_pools_and_counts(spec, lane,
                                                             reference):
    from tpu_engine.utils.tracing import SpanRecorder

    _, _, forward = reference
    gen = lane
    gen.tracer, gen.trace_node = SpanRecorder(capacity=4096), "lane"
    prompts = [_prompt(5, 50), _prompt(6, 23), _prompt(7, 37), _prompt(8, 2)]
    pools = gen._pool, gen._spool
    # Two pools of different depths: one attention layer, three mamba
    # layers; ten... here FOUR rows of per-expert counts, a layer each.
    assert pools[0].cfg.n_layers == 1 and pools[1].n_layers == 3
    assert [x.shape for x in pools[1].slab] == [(3, 5, 8, 4, 16),
                                                (3, 5, 8, 24)]
    for again in range(2):           # the second round re-uses every slot
        futures = [gen.submit(p, max_new_tokens=10) for p in prompts]
        served = [f.result(timeout=300) for f in futures]
        for prompt, tokens in zip(prompts, served):
            want = forward(prompt + tokens[:-1])[len(prompt) - 1:]
            gap = want.max(-1) - want[np.arange(len(tokens)),
                                      np.asarray(tokens)]
            assert float((gap / want.std(-1)).max()) < 0.05
            assert len(set(tokens)) > 2
    stats = gen.stats()
    state, pool, routed = (stats["state_pool"], stats["kv_pool"],
                           stats["moe"])
    assert state["rows_total"] == 4 and state["rows_peak"] == 4
    assert state["rows_held"] == 0 and state["rows_free"] == 4
    assert state["bytes_per_row"] == 3 * (8 * 4 * 16 + 8 * 24) * 4
    assert pool["blocks_free"] == pool["blocks_total"]
    assert pool["kv_bytes_held"] == pool["state_bytes_held"] == 0
    assert pool["block_lanes"] == [8, 8]
    spans = [s["attrs"] for s in gen.tracer.snapshot()
             if s["op"] == "mixed_step"]
    mixed = stats["mixed"]
    fed = mixed["prefill_tokens"] + mixed["decode_tokens"]
    # The state work under the kernels' names, summed over the three state
    # layers' ONE call a kind a layer (the spans count rows, not layers).
    assert sum(s["ssd_chunk_tokens"] + s["ssd_step_rows"]
               for s in spans) == fed
    assert any(s["ssd_chunk_rows"] and s["ssd_step_rows"] for s in spans)
    assert all(s["ssd_step_slots"] == 4 for s in spans)
    assert all(s["ctx_tokens_full"] == s["ctx_tokens"] for s in spans)
    assert max(s["state_rows_held"] for s in spans) == 4
    assert not any(k.startswith(("gdn_", "kda_", "conv_"))
                   for s in spans for k in s)
    # Every fed token routes top_k pairs in EACH of the four layers; half
    # the experts are held, so about half the pairs form rows here.
    assert routed["assignments"] == fed * 4 * 4
    assert routed["assignments"] == sum(s["moe_assignments"] for s in spans)
    assert routed["assignments_held"] == sum(
        s["moe_assignments_held"] for s in spans)
    assert (0.3 * routed["assignments"] < routed["assignments_held"]
            < 0.7 * routed["assignments"])
    assert routed["experts_touched"] == sum(
        s["moe_experts_touched"] for s in spans)
    by_expert = np.asarray(routed["rows_by_expert"])
    assert by_expert.shape == (4, 12)            # a row a LAYER: all route
    assert by_expert.sum() == routed["assignments_held"]
    assert (by_expert[:, :6] > 0).all() and by_expert[:, 6:].sum() == 0


@pytest.mark.parametrize("chunk", [False, True], ids=["narrow", "chunk"])
def test_the_step_opens_the_parts_the_readers_know(lane, chunk):
    """The lane's mixed step LOWERED at both widths: the parts of every
    step, `mixer/in|step|out` (and `mixer/chunk` at a chunk's width),
    `attn/*` of the one attention layer and `moe/route|experts|shared` of
    every layer; no part `lib/xplane_scopes.py` does not know, and no
    `mlp` (no layer has a dense feed-forward)."""
    from tpu_engine.utils import tracing

    gen, width = lane, 16 if chunk else 1
    block = jnp.zeros((gen._tables.shape[0],
                       gen._tick_block(width, False).cols), jnp.int32)
    text = gen._mixed_step_exe(width, False).lower(
        gen._step_params, (gen._pool.caches, gen._spool.slab), block,
        gen._prev_nxt, gen._prev_done).as_text(debug_info=True)
    module, = re.findall(r"module @(\S+)", text)
    assert module == "jit_" + tracing.tick_name(width, 1)
    found = {part_of(p) for p in re.findall(r'loc\("([^"]+)"', text)}
    assert found - {None} == {
        "embed", "plan", "head", "sample", "attn/qkv", "attn/write",
        "attn/read", "attn/out", "mixer/in", "mixer/step", "mixer/out",
        "moe/route", "moe/experts", "moe/shared"} | (
        {"mixer/chunk"} if chunk else set())
    assert found - {None} <= set(tracing.STEP_PARTS)


def test_what_the_family_refuses_at_start_up_stays_refused(spec, params):
    for flag, value in (("prefix_sharing", True), ("kv_quantize", "int8"),
                        ("spec_k", 2)):
        with pytest.raises(ValueError, match="does not declare"):
            ContinuousGenerator(spec, params=params,
                                **{**LANE, flag: value})
    with pytest.raises(ValueError, match="mixed tick over the block pool"):
        ContinuousGenerator(spec, params=params,
                            **{**LANE, "kv_block_size": 0})
