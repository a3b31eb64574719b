"""The LFM2-MoE family (models/lfm2.py) on the served path: a layer's
operator is a gated convolution of three taps or grouped-query attention,
and a row owns ONE array of the state pool (the conv layers' last two
inputs) beside a paged K/V chain. `lfm2-small-test` (a conv layer with the
dense feed-forward, then attention, conv, conv, attention with 16 experts
top 4, all held) against the plain reference benchmarks/references/lfm2.py,
on logits; the conv operator as ONE body over the tick's token list; the
four chips' shares summed; the two pools' bookkeeping."""

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

from tpu_engine.models import lfm2
from tpu_engine.models.lfm2 import lfm2_apply, lfm2_step_rows_ragged
from tpu_engine.models.registry import (
    FAMILY_CAPABILITIES,
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.ops.attention import KVCache
from tpu_engine.runtime.kv_blocks import BlockPool, StateRowPool
from tpu_engine.runtime.scheduler import ContinuousGenerator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmarks"))
from bench_paths import BENCH  # noqa: E402,F401  (benchmarks/ on the path)

from lib.xplane_scopes import part_of  # noqa: E402

BS = 16
LANE = dict(n_slots=4, dtype="float32", kv_block_size=BS,
            prefill_chunk=16, prefix_sharing=False)
PAD = 64
CUT = ["conv", "full_attention", "conv", "conv", "conv", "full_attention",
       "conv", "conv", "conv"]


@pytest.fixture(scope="module")
def spec():
    _ensure_builtin_models_imported()
    return create_model("lfm2-small-test")


@pytest.fixture(scope="module")
def params(spec):
    return jax.jit(spec.init)(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def reference(params):
    """benchmarks/references/lfm2.py over `PAD` right-padded tokens (one
    compiled program a set of sizes) and the test configuration's
    `reference` block as the harness hands it over."""
    path = os.path.join(ROOT, "benchmarks", "references", "lfm2.py")
    module_spec = importlib.util.spec_from_file_location(
        "lfm2_reference_under_test", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    with open(os.path.join(ROOT, "tests", "benchmarks", "data", "configs",
                           "lfm2-small-test.json")) as f:
        sizes = json.load(f)["reference"]
    jitted = jax.jit(module.forward, static_argnums=(2,))

    def forward(seq, **more):
        tokens = np.zeros((PAD,), np.int32)
        tokens[:len(seq)] = seq
        return np.asarray(jitted(
            params, jnp.asarray(tokens),
            tuple(sorted(dict(sizes, **more).items())))[:len(seq)])

    return module, sizes, forward


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, n)]


# -- registry, configuration ---------------------------------------------------------

def test_family_capabilities_and_the_row_of_one_array(spec):
    cfg = spec.config
    assert spec.state_family == "kv_and_state" and cfg.recurrence == "conv"
    assert spec.capabilities == FAMILY_CAPABILITIES["kv_and_state"]
    for absent in ("prefix_sharing", "kv_host_tier", "kv_quantize",
                   "spec_decode", "tensor_parallel", "migration", "handoff",
                   "two_path"):
        assert not spec.supports(absent)
    assert spec.held == cfg.held == (0, 16) and spec.passes == 1
    # conv, attention, conv, conv, attention: a layer's index in ITS pool.
    assert cfg.pool_layer == (0, 0, 1, 2, 1)
    assert (cfg.n_linear_layers, cfg.n_full_layers, cfg.n_moe_layers,
            cfg.n_dense_layers) == (3, 2, 4, 1)
    assert [k.n_layers for k in cfg.kv_block_kinds] == [2]
    assert cfg.kv_block_kinds[0].kv_lanes == (24, 24)
    assert cfg.state_row_shapes == ((2, 48),)


def test_the_published_geometry_is_the_default_and_the_cut_is_the_issue_s():
    _ensure_builtin_models_imported()
    whole = create_model("lfm2").config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        source = next(row for row in map(json.loads, f)
                      if row["name"] == "LFM2-24B-A2B")["config"]
    assert list(whole.layer_types) == source["layer_types"]
    assert (whole.n_layers, whole.d_model, whole.vocab, whole.d_ff,
            whole.n_dense_layers) == (40, 2048, 65536, 11776, 2)
    assert (whole.n_heads, whole.kv_heads, whole.d_head, whole.rope_theta,
            whole.ln_eps) == (32, 8, 64, 1e6, 1e-5)
    assert (whole.conv_width, whole.n_routed, whole.top_k,
            whole.d_ff_expert, whole.routed_scale, whole.held) == (
        3, 64, 4, 1536, 1.0, (0, 64))
    assert (whole.n_linear_layers, whole.n_full_layers) == (30, 10)
    spec = create_model("lfm2", n_layers=9, layer_types=CUT,
                        n_dense_layers=1, max_seq=5120)
    cfg = spec.config
    shapes = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    biases = sum(int(np.prod(x.shape)) for path, x in
                 jax.tree_util.tree_leaves_with_path(shapes)
                 if "bias" in str(path[-1]) and "router" not in str(path))
    # ISSUE 60's arithmetic, to the parameter, beside the zero biases the
    # program's dense layers carry.
    assert sum(int(np.prod(x.shape)) for x in leaves) - biases == 5312168704
    assert all(x.dtype == jnp.bfloat16 for x in leaves if x.ndim >= 2
               and x.shape[-1] != 64 and x.shape[0] != 3)
    assert (cfg.n_linear_layers, cfg.n_full_layers, cfg.n_moe_layers) == (
        7, 2, 8)
    assert cfg.pool_layer == (0, 0, 1, 2, 3, 1, 4, 5, 6)
    # A row's state: 7 x 2 x 2048 float32; a block of 16 tokens: 2 layers
    # x K and V x 8 heads x 64 lanes in bfloat16.
    assert cfg.state_row_shapes == ((2, 2048),)
    rows = StateRowPool(cfg.n_linear_layers, cfg.state_row_shapes, 1)
    assert rows.bytes_per_row() == 114688
    pool = BlockPool(cfg.kv_block_kinds[0], 2, 16, jnp.bfloat16)
    assert pool.bytes_per_block() == 65536


@pytest.mark.parametrize("change, message", [
    (dict(layer_types=("conv", "full_attention", "conv", "conv")),
     "one entry a layer"),
    (dict(layer_types=("conv", "mamba", "conv", "conv", "full_attention")),
     "'mamba' is no layer kind"),
    (dict(layer_types=("conv",) * 5), "needs a 'conv' and a"),
    (dict(layer_types=("full_attention",) * 5), "needs a 'conv' and a"),
    (dict(conv_width=1), "keeps no tail"),
    (dict(held=(14, 4)), "is no share of 16 experts"),
    (dict(held=(0, 0)), "is no share of 16 experts"),
])
def test_a_layer_list_or_a_share_that_cannot_be_served_is_refused(
        spec, change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(spec.config, **change)


# -- the model against the plain reference ----------------------------------------

def test_the_forward_equals_the_plain_reference(spec, params, reference):
    _, _, forward = reference
    seq = _prompt(0, 60)
    want = forward(seq)
    with jax.default_matmul_precision("highest"):
        got = lfm2_apply(params, jnp.asarray([seq], jnp.int32), spec.config,
                         dtype=jnp.float32)[0]
    assert float(want.std()) > 0.5
    np.testing.assert_allclose(got, want, atol=5e-5)
    assert len(set(np.asarray(got).argmax(-1).tolist())) > 30


def test_the_four_shares_add_up_to_the_uncut_layer(spec, params, reference):
    """Four chips share an expert layer: each holds 4 of the 16 routed
    experts and the router whole. Nothing is computed alike on every chip
    (no shared expert, no bias), so the four shares' results sum to the
    reference's layer over all 16 experts."""
    module, sizes, _ = reference
    cfg = spec.config
    mp = params["layers"][1]["mlp"]
    z = jax.random.normal(jax.random.PRNGKey(8), (48, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = module._experts(mp, z, dict(sizes))
        total, taken = 0.0, 0
        for first in (0, 4, 8, 12):
            share = dict(mp, experts={k: v[first:first + 4]
                                      for k, v in mp["experts"].items()})
            y, rows = lfm2._experts_ffn(share, z, jnp.ones(48, bool), cfg,
                                        jnp.float32, (first, 4), None)
            assert int(rows.sum()) == int(rows[first:first + 4].sum()) > 0
            total, taken = total + y, taken + int(rows.sum())
            # One share alone is not the layer, and the reference given the
            # same share computes the same part.
            assert float(jnp.abs(y - want).max()) > 0.05
            np.testing.assert_allclose(
                y, module._experts(share, z, dict(sizes, held_first=first)),
                atol=3e-5)
    assert taken == 48 * cfg.top_k               # every pair on some chip
    np.testing.assert_allclose(total, want, atol=3e-5)


# -- the conv operator over the token list ----------------------------------------

@pytest.fixture(scope="module")
def step(spec, params):
    """`lfm2_step_rows_ragged` over three slots of 16, jitted once: slot 1
    is free (the null state row 0), slots 0 and 2 own state rows 3 and 1."""
    cfg = spec.config
    table = np.zeros((3, 8), np.int32)
    table[0], table[2] = np.arange(1, 9), np.arange(9, 17)
    tables = (jnp.asarray(table), jnp.asarray([3, 0, 1], jnp.int32))
    return jax.jit(lambda tokens, caches, pos0, qlen: lfm2_step_rows_ragged(
        params, tokens, caches, tables, pos0, qlen, cfg, dtype=jnp.float32,
        max_tokens=36))


def _pools(cfg, rows, blocks):
    """Both pools as a lane that has served before leaves them: a row that
    is admitted finds ANOTHER request's tail in its state row."""
    shape = (cfg.n_full_layers, blocks, BS, cfg.kv_heads * cfg.d_head)
    stale = 5.0 * jax.random.normal(
        jax.random.PRNGKey(11),
        (cfg.n_linear_layers, rows) + cfg.state_row_shapes[0])
    return KVCache(jnp.zeros(shape), jnp.zeros(shape)), (stale,)


def _serve_in_chunks(spec, step, chunks):
    """Two rows of different lengths in the same ticks: row 0 prefills
    `chunks` and then decodes; row 2 prefills 23 tokens and decodes beside
    it, so ONE tick holds a run of many tokens and a run of one. Returns
    ({row: (tokens, logits)}, caches, the rows the experts took)."""
    cfg = spec.config
    n_prompt, n_new = sum(chunks), 5
    seqs = {0: _prompt(1, n_prompt + n_new), 2: _prompt(2, 23 + 10)}
    plans = {0: list(chunks) + [1] * n_new, 2: [16, 7] + [1] * 10}
    caches = _pools(cfg, rows=4, blocks=17)
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
            assert rows.shape == (cfg.n_moe_layers, cfg.n_routed)
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
    """Runs of one token, of two, of a whole chunk and of none, a run that
    crosses at least two chunk boundaries (its first tokens read the tail
    the last chunk left in each of the three conv layers, and K and V the
    earlier ones wrote in the two attention layers), rows that prefill and
    rows that decode in ONE tick, every row admitted into a state row that
    holds another request's tail: logits within 1e-4."""
    _, _, forward = reference
    served, caches, taken = _serve_in_chunks(spec, step, chunks)
    for seq, got in served.values():
        np.testing.assert_allclose(got, forward(seq), atol=1e-4)
    (tails,) = caches[1]
    assert tails.shape == (3, 4, 2, 48) and tails.dtype == jnp.float32
    assert taken.sum() == sum(len(s) for s, _ in served.values()) * 4 * 4


def test_the_tail_is_the_last_two_inputs_whatever_the_run(spec, params,
                                                          step):
    """After a run of 16, of 1 and of 2 tokens the state row holds u of the
    row's last two tokens in every conv layer (a run of ONE shifts it by
    one), and the free slot's writes went to the null row alone."""
    cfg = spec.config
    seq = _prompt(4, 19)
    caches = _pools(cfg, rows=4, blocks=17)
    untouched = np.asarray(caches[1][0][:, 2])
    pos = 0
    with jax.default_matmul_precision("highest"):
        for n in (16, 1, 2):
            tokens = np.zeros((3, 16), np.int32)
            tokens[0, :n] = seq[pos:pos + n]
            _, caches, _ = step(
                jnp.asarray(tokens), caches,
                jnp.asarray([pos, 0, 0], jnp.int32),
                jnp.asarray([n, 0, 0], jnp.int32))
            pos += n
        # u of the first conv layer, from the embedding on.
        bp = params["layers"][0]
        h = params["tok_embed"]["table"][jnp.asarray(seq)]
        u, _ = lfm2._conv_inputs(
            bp["conv"], lfm2.nn.rmsnorm(bp["ln1"], h, eps=cfg.ln_eps),
            jnp.float32)
    (tails,) = caches[1]
    np.testing.assert_allclose(tails[0, 3], u[-2:], atol=1e-5)
    # Row 2 of the pool belongs to no slot of this step.
    np.testing.assert_array_equal(np.asarray(tails[:, 2]), untouched)


@pytest.mark.parametrize("width", [1, 16])
def test_the_step_holds_no_loop_and_one_conv_body_a_layer(spec, params,
                                                          width):
    """Traced at a decode tick's width and at a chunk's: no `while`
    anywhere (no loop whose trip count is the chunk rows) and no `scan` but
    the plan's binary searches over the rows' starts (`tile_plan`'s
    `searchsorted`: log2 of the slots, whatever they hold), no kernel, the tails' array
    scattered ONCE a conv layer, and nothing of (rows, width, d) is made:
    the operator runs over the token list."""
    cfg = spec.config
    tables = (jnp.zeros((3, 8), jnp.int32), jnp.zeros(3, jnp.int32))
    jaxpr = jax.make_jaxpr(
        lambda tokens, caches, pos0, qlen: lfm2_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            dtype=jnp.float32, max_tokens=width + 3,
            sample_slot=jnp.zeros(3, jnp.int32)))(
        jnp.zeros((3, width), jnp.int32), _pools(cfg, rows=4, blocks=17),
        jnp.zeros(3, jnp.int32), jnp.ones(3, jnp.int32))

    def walk(eqns):
        for eqn in eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub.eqns)

    eqns = list(walk(jaxpr.jaxpr.eqns))
    names = {e.primitive.name for e in eqns}
    assert not names & {"while", "pallas_call"}
    assert {e.params["length"] for e in eqns
            if e.primitive.name == "scan"} == {(3).bit_length()}
    tail_writes = [e for e in eqns if e.primitive.name.startswith("scatter")
                   and tuple(e.outvars[0].aval.shape) == (3, 4, 2, 48)]
    assert len(tail_writes) == cfg.n_linear_layers
    shapes = {tuple(v.aval.shape) for e in eqns for v in e.outvars}
    assert (3, 16, cfg.d_model) not in shapes
    assert (3, 16, 3 * cfg.d_model) not in shapes


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
    # Two pools of different depths: two attention layers, three conv
    # layers, and a state row of ONE array.
    assert pools[0].cfg.n_layers == 2 and pools[1].n_layers == 3
    assert [x.shape for x in pools[1].slab] == [(3, 5, 2, 48)]
    for again in range(2):           # the second round re-uses every slot
        futures = [gen.submit(p, max_new_tokens=10) for p in prompts]
        served = [f.result(timeout=300) for f in futures]
        for prompt, tokens in zip(prompts, served):
            want = forward(prompt + tokens[:-1])[len(prompt) - 1:]
            gap = want.max(-1) - want[np.arange(len(tokens)),
                                      np.asarray(tokens)]
            assert float((gap / want.std(-1)).max()) < 0.05
            assert len(set(tokens)) > 5
    stats = gen.stats()
    state, pool, routed = (stats["state_pool"], stats["kv_pool"],
                           stats["moe"])
    assert state["rows_total"] == 4 and state["rows_peak"] == 4
    assert state["rows_held"] == 0 and state["rows_free"] == 4
    assert state["bytes_per_row"] == 3 * 2 * 48 * 4
    assert pool["blocks_free"] == pool["blocks_total"]
    assert pool["kv_bytes_held"] == pool["state_bytes_held"] == 0
    assert pool["block_lanes"] == [24, 24]
    spans = [s["attrs"] for s in gen.tracer.snapshot()
             if s["op"] == "mixed_step"]
    mixed = stats["mixed"]
    fed = mixed["prefill_tokens"] + mixed["decode_tokens"]
    # The spans say `conv` where the other state lanes say ssd / kda / gdn.
    assert sum(s["conv_chunk_tokens"] + s["conv_step_rows"]
               for s in spans) == fed
    assert any(s["conv_chunk_tokens"] and s["conv_step_rows"] for s in spans)
    assert all(s["conv_step_slots"] == 4 for s in spans)
    assert all(s["ctx_tokens_full"] == s["ctx_tokens"] for s in spans)
    assert max(s["state_rows_held"] for s in spans) == 4
    assert not any(k.startswith(("gdn_", "kda_", "ssd_"))
                   for s in spans for k in s)
    # Every fed token routes top_k pairs in each of the four expert layers,
    # and every expert is held.
    assert routed["assignments"] == fed * 4 * 4 == routed["assignments_held"]
    assert routed["assignments"] == sum(s["moe_assignments"] for s in spans)
    assert routed["experts_touched"] == sum(
        s["moe_experts_touched"] for s in spans)
    assert np.asarray(routed["rows_by_expert"]).shape == (4, 16)


@pytest.mark.parametrize("chunk", [False, True], ids=["narrow", "chunk"])
def test_the_step_opens_its_parts_and_nothing_under_mixer_chunk(lane, chunk):
    """The lane's mixed step LOWERED at both widths (tests/test_step_parts.py
    holds the other families): the parts of every step, `mlp` (the dense
    layer), `mixer/in|step|out` and the experts' two, and NOT `mixer/chunk`
    (no chunked form exists) nor `moe/shared` (no shared expert)."""
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
        "attn/read", "attn/out", "mlp", "mixer/in", "mixer/step",
        "mixer/out", "moe/route", "moe/experts"}


def test_what_the_family_refuses_at_start_up_stays_refused(spec, params):
    for flag, value in (("prefix_sharing", True), ("kv_quantize", "int8"),
                        ("spec_k", 2)):
        with pytest.raises(ValueError, match="does not declare"):
            ContinuousGenerator(spec, params=params,
                                **{**LANE, flag: value})
    with pytest.raises(ValueError, match="mixed tick over the block pool"):
        ContinuousGenerator(spec, params=params,
                            **{**LANE, "kv_block_size": 0})
