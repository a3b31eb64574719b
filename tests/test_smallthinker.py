"""The SmallThinker family at a small size on the CPU: a router that reads
the layer's input before attention, ReGLU experts all held, three rotated
window layers to one un-rotated full layer at seven query heads a KV head
(14 over 2, a window of three blocks), the forward and the served step
through blocks of two kinds against the plain reference, and what the tick
counts. (The window read by class in interpret mode and the gated bank's
activation: tests/test_smallthinker_ops.py.)"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models import smallthinker
from tpu_engine.models.registry import (
    FAMILY_CAPABILITIES,
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.models.smallthinker import (
    smallthinker_apply,
    smallthinker_step_rows_ragged,
)
from tpu_engine.ops import moe
from tpu_engine.ops import paged_attention as pa
from tpu_engine.ops.attention import KVCache
from tpu_engine.runtime.scheduler import ContinuousGenerator

BS = 16
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LANE = {"n_slots": 4, "dtype": "float32", "kv_block_size": BS,
        "prefill_chunk": 16, "prefix_sharing": False}
# What the served path may differ from the reference by, in float32 on the
# CPU: summation order alone.
SERVED = 2e-4


@pytest.fixture(scope="module")
def spec():
    _ensure_builtin_models_imported()
    return create_model("smallthinker-small-test")


@pytest.fixture(scope="module")
def params(spec):
    return jax.jit(spec.init)(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def reference():
    """benchmarks/references/smallthinker.py and the test configuration's
    `reference` block as the harness hands it over."""
    import sys

    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    path = os.path.join(bench, "references", "smallthinker.py")
    module_spec = importlib.util.spec_from_file_location(
        "smallthinker_reference_under_test", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    with open(os.path.join(ROOT, "tests", "benchmarks", "data", "configs",
                           "smallthinker-small-test.json")) as f:
        block = json.load(f)["reference"]
    block.pop("tail_rows")          # the whole (T, vocab) array here
    return module, tuple(sorted(block.items()))


@pytest.fixture(scope="module")
def as_published(params, reference):
    """(tokens, the reference's logits of them): 70 positions, 22 past the
    window."""
    tokens = jnp.asarray(_tokens(1, 70))
    return tokens, reference[0].forward(params, tokens, reference[1])


def _tokens(seed, n, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, size=n).astype(
        np.int32)


def _pools(cfg, blocks):
    def pair(layers):
        shape = (layers, blocks, BS, cfg.kv_heads * cfg.d_head)
        return KVCache(jnp.zeros(shape), jnp.zeros(shape))

    return pair(cfg.n_full_layers), pair(cfg.n_window_layers)


# -- registry and configuration -----------------------------------------------

def test_family_capabilities_and_stated_widths(spec):
    cfg = spec.config
    assert spec.state_family == "kv_windowed"
    assert spec.capabilities == FAMILY_CAPABILITIES["kv_windowed"]
    assert not spec.supports("prefix_sharing")
    assert spec.ragged_step is smallthinker_step_rows_ragged
    assert cfg.n_heads // cfg.kv_heads == 7
    assert cfg.windowed == cfg.rotated == (False, True, True, True) * 2
    assert (cfg.n_full_layers, cfg.n_window_layers, cfg.n_moe_layers) \
        == (2, 6, 8)
    assert cfg.pool_layer == (0, 0, 1, 2, 1, 3, 4, 5)
    full, window = cfg.kv_block_kinds
    assert (full.n_layers, window.n_layers) == (2, 6)
    assert spec.held == cfg.held == (0, 8)


def test_the_published_geometry_is_the_default():
    cfg = create_model("smallthinker").config
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.d_head,
            cfg.vocab, cfg.max_seq) == (52, 2560, 28, 4, 128, 151936, 16384)
    assert (cfg.n_routed, cfg.top_k, cfg.d_ff_expert, cfg.window,
            cfg.rope_theta, cfg.ln_eps) == (64, 6, 768, 4096, 1.5e6, 1e-6)
    assert cfg.windowed == cfg.rotated == (False, True, True, True) * 13
    assert cfg.held == (0, 64)


@pytest.mark.parametrize("kwargs, message", [
    ({"held_first": 6, "held_count": 4}, "no share"),
    ({"layer_types": ("full_attention",) * 8}, "two kinds"),
    ({"layer_types": ("full_attention", "conv") * 4}, "no layer kind"),
    ({"rope_layout": (0, 1, 1)}, "one entry a layer"),
    ({"heads_per_layer": (14, 7) * 4}, "all equal"),
])
def test_a_configuration_that_is_none_is_refused(kwargs, message):
    with pytest.raises(ValueError, match=message):
        create_model("smallthinker-small-test", **kwargs)


# -- the forward and the served step against the plain reference --------------

def test_the_forward_equals_the_plain_reference(spec, params,
                                                as_published):
    tokens, theirs = as_published
    ours = smallthinker_apply(params, tokens[None], spec.config,
                              dtype=jnp.float32)[0]
    assert np.abs(np.asarray(ours) - np.asarray(theirs)).max() < SERVED


def test_a_late_router_moves_the_reference_s_logits(
        params, reference, as_published, control="late_router"):
    """A model whose router reads RMS(h1; ln2), as every other family here
    routes, differs from the reference by far more than the served
    tolerance: the control can fail. (Every control of `correct` against
    served tokens: tests/benchmarks/test_benchmark_reference_
    smallthinker.py.)"""
    forward, sizes = reference[0].forward, reference[1]
    tokens, whole = as_published
    changed = forward(params, tokens, sizes + (("control", control),))
    assert float(jnp.abs(whole - changed).max()) > 500 * SERVED


def test_both_kinds_of_layer_read_by_the_class_of_a_row_s_run(spec, params):
    """The step of two rows in 256 slots, traced: every layer reads the
    rows with one new token as (2, 1, H, D) and the longer run in tall
    tiles of 128 slots (G = 7), the window layers' calls handed the window
    and the full layers' none; no layer makes rows x width query slots."""
    cfg = spec.config
    asked = []

    def attn_fn(q, *rest, window=None):
        asked.append((window, q.shape))
        return pa.ragged_paged_attention_reference(q, *rest, window=window)

    table = jnp.zeros((2, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda tokens, caches, pos0, qlen: smallthinker_step_rows_ragged(
            params, tokens, caches, (table, table), pos0, qlen, cfg,
            dtype=jnp.float32, max_tokens=200, attn_fn=attn_fn,
            sample_slot=jnp.zeros(2, jnp.int32)))(
        jnp.zeros((2, 256), jnp.int32), _pools(cfg, 9),
        jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.int32))
    heads = (cfg.n_heads, cfg.d_head)
    short, tall = asked[0][1], asked[1][1]
    assert short == (2, 1) + heads
    assert tall[1:] == (128,) + heads and tall[0] <= 2 + 200 // 128 + 1
    assert [shape for _, shape in asked] == [short, tall] * cfg.n_layers
    assert [window for window, _ in asked] == [
        w for layer in range(cfg.n_layers) for w in
        [cfg.window if cfg.windowed[layer] else None] * 2]
    shapes = [v.aval.shape for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars]
    assert shapes and not [x for x in shapes
                           if len(x) == 4 and x[:2] == (2, 256)]


# -- the router is early ------------------------------------------------------

def test_a_layer_s_choice_of_experts_does_not_read_its_attention(spec,
                                                                 params):
    """Perturbing ln1 and the attention's weights of layer 1 leaves THAT
    layer's chosen experts and their weights as they were (they read the
    layer's input), moves the layer's output, and so moves layer 2's."""
    cfg = spec.config
    seen = {}
    route = moe.softmax_topk_route

    def run(tree, tag):
        calls = []

        def watched(x, router, top_k):
            out = route(x, router, top_k)
            calls.append(out)
            return out

        smallthinker.softmax_topk_route = watched
        try:
            logits = smallthinker_apply(
                tree, jnp.asarray(_tokens(5, 40))[None], cfg,
                dtype=jnp.float32)
        finally:
            smallthinker.softmax_topk_route = route
        seen[tag] = [(np.asarray(e), np.asarray(w)) for e, w in calls]
        return np.asarray(logits)

    changed = dict(params["layers"][1])
    changed["ln1"] = jax.tree.map(lambda a: a * 1.7, changed["ln1"])
    changed["attn"] = jax.tree.map(lambda a: a * -0.6, changed["attn"])
    tree = dict(params, layers=[changed if l == 1 else bp
                                for l, bp in enumerate(params["layers"])])
    a, b = run(params, "as made"), run(tree, "changed")
    assert len(seen["as made"]) == cfg.n_layers
    for layer in (0, 1):
        for one, other in zip(seen["as made"][layer], seen["changed"][layer]):
            assert np.array_equal(one, other)
    assert np.abs(seen["as made"][2][1] - seen["changed"][2][1]).max() > 1e-3
    assert np.abs(a - b).max() > 500 * SERVED


def test_the_step_opens_the_route_before_the_layer_s_attention(spec, params):
    """In the traced step the layer's `moe/route` ops (the logits, the
    soft-max, the top k) come before its first `attn/qkv` op."""
    cfg = spec.config
    table = jnp.zeros((2, 8), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda tokens, caches: smallthinker_step_rows_ragged(
            params, tokens, caches, (table, table), jnp.zeros(2, jnp.int32),
            jnp.ones(2, jnp.int32), cfg, dtype=jnp.float32,
            attn_fn=pa.ragged_paged_attention_reference))(
        jnp.zeros((2, 1), jnp.int32), _pools(cfg, 9))
    parts = [str(eqn.source_info.name_stack) for eqn in jaxpr.jaxpr.eqns]
    parts = [p for p in parts if p in ("moe/route", "attn/qkv", "attn/out",
                                       "moe/experts")]
    order = [p for n, p in enumerate(parts) if n == 0 or parts[n - 1] != p]
    assert order[:4] == ["moe/route", "attn/qkv", "attn/out", "moe/experts"]
    assert order.count("attn/qkv") == cfg.n_layers


# -- the experts --------------------------------------------------------------

def test_two_shares_of_the_experts_add_up_to_the_uncut_layer(spec, params,
                                                             reference):
    """The guide's test, though the cell holds every expert: held = (0, 4)
    and (4, 4), each with its half of the banks, give partial sums whose
    total is the uncut layer's, and the plain reference's."""
    module, sizes = reference
    cfg = spec.config
    mp = params["layers"][2]["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(6), (21, cfg.d_model))
    z = jax.random.normal(jax.random.PRNGKey(8), (21, cfg.d_model))
    valid = jnp.ones((21,), bool)
    experts, weights = moe.softmax_topk_route(h, mp["router"], cfg.top_k)

    def share(first, count):
        banks = jax.tree.map(lambda a: a[first:first + count], mp["experts"])
        y, rows = moe.routed_experts(
            z, valid, experts, weights, banks, first_group=-first,
            n_experts=cfg.n_routed, held=(first, count), dtype=jnp.float32,
            activation=jax.nn.relu)
        return np.asarray(y), np.asarray(rows)

    (low, low_rows), (high, high_rows) = share(0, 4), share(4, 4)
    whole, rows = share(0, 8)
    assert np.abs(low + high - whole).max() < 1e-5
    assert not low_rows[4:].any() and not high_rows[:4].any()
    assert np.array_equal(low_rows + high_rows, rows)
    assert rows.sum() == 21 * cfg.top_k
    plain = dict(sizes)
    with jax.default_matmul_precision("highest"):
        theirs = module._experts(mp["experts"], z,
                                 module._route(mp["router"], h, plain), plain)
    assert np.abs(whole - np.asarray(theirs)).max() < 1e-4


# -- `windowed` and `rotated` are two lists -----------------------------------

def test_windowed_and_rotated_are_taken_apart(
        reference, rope_layout=(1, 1, 1, 1, 0, 0, 1, 1),
        name="a rotated full layer, an un-rotated window layer"):
    """The source states two lists: layer 0 a rotated full layer, layer 5
    an un-rotated window layer, one-shot and served (a chunk of 64, then
    decode steps past the window: `test_the_mixed_tick_serves_it...` runs
    the published layout through the scheduler's chunks), against the
    reference told the same."""
    spec = create_model("smallthinker-small-test", rope_layout=rope_layout)
    cfg = spec.config
    params = jax.jit(spec.init)(jax.random.PRNGKey(3))
    forward = reference[0].forward
    sizes = tuple(sorted(dict(
        reference[1], rotated=",".join(map(str, rope_layout))).items()))
    tokens = _tokens(9, 70)
    want = np.asarray(forward(params, jnp.asarray(tokens), sizes))
    ours = smallthinker_apply(params, jnp.asarray(tokens)[None], cfg,
                              dtype=jnp.float32)[0]
    assert np.abs(np.asarray(ours) - want).max() < SERVED, name
    assert np.abs(np.asarray(forward(params, jnp.asarray(tokens),
                                     reference[1])) - want).max() \
        > 500 * SERVED
    # Served: one chunk of 64, then six decode steps past the window.
    step = jax.jit(lambda p, t, c, tb, p0, ql: smallthinker_step_rows_ragged(
        p, t, c, tb, p0, ql, cfg, dtype=jnp.float32))
    caches = _pools(cfg, 6)
    table = jnp.asarray(1 + np.arange(5, dtype=np.int32)[None])
    pos = 0
    for qlen in (64, 1, 1, 1, 1, 1, 1):
        fed = np.zeros((1, 64), np.int32)
        fed[0, :qlen] = tokens[pos:pos + qlen]
        window_table = table.at[0, :max(pos - cfg.window + 1, 0) // BS].set(0)
        logits, caches, _ = step(
            params, jnp.asarray(fed), caches, (table, window_table),
            jnp.asarray([pos], jnp.int32), jnp.asarray([qlen], jnp.int32))
        assert np.abs(np.asarray(logits[0, :qlen])
                      - want[pos:pos + qlen]).max() < SERVED, (name, pos)
        pos += qlen


# -- the scheduler: what runs, what is held, what is counted ------------------

def test_the_mixed_tick_serves_it_frees_window_blocks_and_counts(spec,
                                                                 params,
                                                                 reference):
    """Rows that pass the window (48) and rows that never do share the
    lane's ticks; logits of every served token against the reference's
    full forward, not tokens: a served token must be the reference's
    arg-max to within the served tolerance."""
    from tpu_engine.utils.tracing import SpanRecorder

    forward, sizes = reference[0].forward, reference[1]
    tracer = SpanRecorder(capacity=4096)
    gen = ContinuousGenerator(spec, params=params, **LANE)
    gen.tracer, gen.trace_node = tracer, "lane"
    try:
        rng = np.random.default_rng(0)
        prompts = [[int(t) for t in rng.integers(1, 256, size=n)]
                   for n in (100, 5, 40)]
        outs = gen.generate(prompts, max_new_tokens=20)
        stats = gen.stats()
    finally:
        gen.stop()
    cfg = spec.config
    for prompt, out in zip(prompts, outs):
        seq = np.asarray(list(prompt) + [int(t) for t in out], np.int32)
        padded = np.zeros((128,), np.int32)
        padded[:len(seq)] = seq
        logits = np.asarray(forward(params, jnp.asarray(padded), sizes))
        at = logits[len(prompt) - 1:len(seq) - 1]
        served = at[np.arange(len(out)), np.asarray(out)]
        assert (at.max(-1) - served).max() < SERVED
    pool = stats["kv_pool"]
    bound = -(-(cfg.window + 16) // BS) + 1
    assert pool["window_blocks_total"] == 4 * bound == 20
    assert pool["window_blocks_held"] == pool["full_blocks_held"] == 0
    assert pool["blocks_free"] == pool["blocks_total"]
    assert pool["window_blocks_freed"] > 0
    mixed, counted = stats["mixed"], stats["moe"]
    fed = mixed["prefill_tokens"] + mixed["decode_tokens"]
    assert counted["assignments"] == counted["assignments_held"] \
        == fed * cfg.top_k * cfg.n_moe_layers
    assert np.asarray(counted["rows_by_expert"]).shape == (8, 8)
    spans = [s["attrs"] for s in tracer.snapshot() if s["op"] == "mixed_step"]
    assert len(spans) == mixed["ticks"]
    assert sum(s["window_blocks_freed"] for s in spans) \
        == pool["window_blocks_freed"]
    assert all(0 < s["ctx_tokens_window"] <= s["ctx_tokens_full"]
               for s in spans)
    # Rows the window binds beside rows it does not, in the same ticks.
    rows = [(s["rows_past_window"], s["rows_fed"]) for s in spans]
    assert all(0 <= past <= fed_rows for past, fed_rows in rows)
    assert any(0 < past < fed_rows for past, fed_rows in rows)
    assert any(past == 0 for past, _ in rows)


def test_the_scheduler_names_no_model():
    with open(os.path.join(ROOT, "tpu_engine", "runtime",
                           "scheduler.py")) as f:
        assert "smallthinker" not in f.read().lower()
