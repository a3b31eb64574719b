"""The Laguna family at a small size on the CPU: window and full attention
layers in one model (G = 6 and 9 over 2 KV heads, window 8), the served
step through blocks of two kinds against the forward and against the plain
reference, a share of the experts held, the window layers' blocks given
back and never leaked, the paged kernel's lower bound in interpret mode,
the start-up fences of the kv_windowed family, and what the tick counts."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models import laguna
from tpu_engine.models.laguna import (
    LagunaConfig,
    laguna_apply,
    laguna_step_rows_ragged,
)
from tpu_engine.models.registry import (
    FAMILY_CAPABILITIES,
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.ops import paged_attention as pa
from tpu_engine.ops.attention import KVCache
from tpu_engine.runtime.scheduler import ContinuousGenerator

BS = 16
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LANE = {"n_slots": 4, "dtype": "float32", "kv_block_size": BS,
        "prefill_chunk": 16, "prefix_sharing": False}


@pytest.fixture(scope="module")
def spec():
    _ensure_builtin_models_imported()
    return create_model("laguna-small-test")


@pytest.fixture(scope="module")
def params(spec):
    return jax.jit(spec.init)(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def reference():
    """benchmarks/references/laguna.py `forward` and the test
    configuration's `reference` block as the harness hands it over."""
    import sys

    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    path = os.path.join(bench, "references", "laguna.py")
    module_spec = importlib.util.spec_from_file_location(
        "laguna_reference_under_test", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    with open(os.path.join(ROOT, "tests", "benchmarks", "data", "configs",
                           "laguna-small-test.json")) as f:
        sizes = tuple(sorted(json.load(f)["reference"].items()))
    return module, sizes


def _pools(cfg, blocks):
    def pair(layers):
        shape = (layers, blocks, BS, cfg.kv_heads * cfg.d_head)
        return KVCache(jnp.zeros(shape), jnp.zeros(shape))

    return pair(cfg.n_full_layers), pair(cfg.n_window_layers)


# -- registry and configuration --------------------------------------------------

def test_family_capabilities_and_stated_widths(spec):
    cfg = spec.config
    assert isinstance(cfg, LagunaConfig)
    assert spec.state_family == "kv_windowed"
    assert spec.capabilities == FAMILY_CAPABILITIES["kv_windowed"]
    for absent in ("prefix_sharing", "kv_host_tier", "kv_quantize",
                   "spec_decode", "migration", "handoff",
                   "tensor_parallel", "two_path"):
        assert not spec.supports(absent)
    assert spec.ragged_step is laguna_step_rows_ragged
    assert spec.held == cfg.held == (0, 8)
    assert cfg.windowed == (False, True, True, True, False)
    assert cfg.heads_per_layer == (12, 18, 18, 18, 12)
    assert cfg.pool_layer == (0, 0, 1, 2, 1)
    assert (cfg.n_full_layers, cfg.n_window_layers) == (2, 3)
    full, window = cfg.kv_block_kinds
    assert (full.n_layers, window.n_layers) == (2, 3)
    assert full.kv_lanes == window.kv_lanes == (32, 32)
    assert cfg.sliding_window is None      # the window is a layer's


def test_the_published_geometry_is_the_default():
    cfg = create_model("laguna").config
    assert (cfg.n_layers, cfg.d_model, cfg.kv_heads, cfg.d_head, cfg.d_ff,
            cfg.d_ff_expert, cfg.d_ff_shared, cfg.n_routed, cfg.top_k,
            cfg.routed_scale, cfg.window, cfg.vocab, cfg.held) == (
        48, 3072, 8, 128, 12288, 1024, 1024, 256, 10, 2.5, 512, 100352,
        (0, 256))
    assert cfg.heads_per_layer[:5] == (48, 72, 72, 72, 48)
    assert sum(cfg.windowed) == 36


def test_a_held_share_must_lie_inside_the_experts():
    with pytest.raises(ValueError, match="is no share of 16 experts"):
        create_model("laguna-small-test", held_first=12, held_count=8)


def test_only_the_held_experts_are_made(spec, params):
    banks = params["layers"][1]["mlp"]["experts"]
    assert banks["gate_up"].shape == (8, 64, 64)
    assert banks["down"].shape == (8, 32, 64)
    assert params["layers"][1]["mlp"]["router"]["kernel"].shape == (64, 16)
    assert "experts" not in params["layers"][0]["mlp"]
    assert params["layers"][1]["attn"]["wq"]["kernel"].shape == (64, 18 * 16)
    assert params["layers"][4]["attn"]["wq"]["kernel"].shape == (64, 12 * 16)


def test_yarn_frequencies_blend_as_transformers_does():
    """Laguna-S-2.1's full layers: 64 rotated lanes, base 500000, factor
    128 over 8192 positions, beta 32 / 1. Pairs that turn more than 32
    times in 8192 positions keep their frequency, pairs that turn less
    than once are slowed 128-fold, a linear ramp between."""
    inv = laguna._yarn_inv_freq(64, 500000.0, 128.0, 8192, 32.0, 1.0)
    plain = 1.0 / 500000.0 ** (np.arange(0, 64, 2) / 64)
    turns = 8192 * plain / (2 * np.pi)
    assert np.allclose(inv[turns > 40], plain[turns > 40])
    assert np.allclose(inv[turns < 0.8], plain[turns < 0.8] / 128)
    between = (turns > 1.2) & (turns < 30)
    assert between.any()
    assert np.all(inv[between] < plain[between])
    assert np.all(inv[between] > plain[between] / 128)
    assert np.all(np.diff(inv) < 0)


# -- the forward and the served step against the plain reference -------------------

def test_the_forward_equals_the_plain_reference(spec, params, reference):
    forward, sizes = reference[0].forward, reference[1]
    tokens = np.random.default_rng(0).integers(
        0, spec.config.vocab, size=70).astype(np.int32)
    ours = laguna_apply(params, jnp.asarray(tokens)[None], spec.config,
                        dtype=jnp.float32)[0]
    theirs = forward(params, jnp.asarray(tokens), sizes)
    assert np.abs(np.asarray(ours) - np.asarray(theirs)).max() < 2e-4


@pytest.mark.parametrize("control", ["window", "gate", "partial_rope",
                                     "bias", "shared"])
def test_each_control_moves_the_reference_s_logits(spec, params, reference,
                                                   control):
    forward, sizes = reference[0].forward, reference[1]
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, spec.config.vocab, size=40).astype(np.int32))
    whole = forward(params, tokens, sizes)
    changed = forward(params, tokens, sizes + (("drop", control),))
    assert float(jnp.abs(whole - changed).max()) > 0.1


@pytest.mark.parametrize("chunks", [(16, 16, 8), (7, 16, 16, 1), (40,)])
def test_chunked_prefill_then_decode_equals_the_reference_on_logits(
        spec, params, reference, chunks):
    """A 40-token prompt in chunks that cross the window's edge (window 8)
    and block boundaries, then 12 decode steps, beside a second row that
    decodes from the start: every new token's logits against the plain
    reference over the whole sequence. The window layers' table forgets
    the blocks behind the window before each step, as the scheduler does."""
    cfg = spec.config
    forward, sizes = reference[0].forward, reference[1]
    width = max(chunks)
    step = jax.jit(lambda p, t, c, tb, p0, ql: laguna_step_rows_ragged(
        p, t, c, tb, p0, ql, cfg, dtype=jnp.float32))
    rng = np.random.default_rng(2)
    seqs = [rng.integers(0, cfg.vocab, size=52).astype(np.int32),
            rng.integers(0, cfg.vocab, size=30).astype(np.int32)]
    want = [np.asarray(forward(params, jnp.asarray(s), sizes)) for s in seqs]
    caches = _pools(cfg, 1 + 2 * 4)
    table = 1 + np.arange(8, dtype=np.int32).reshape(2, 4)
    pos = [0, 0]
    feeds = [list(chunks) + [1] * 12, [1] * 30]
    for tick in range(max(len(f) for f in feeds)):
        qlen = np.array([f[tick] if tick < len(f) else 0 for f in feeds],
                        np.int32)
        tokens = np.zeros((2, width), np.int32)
        window_table = table.copy()
        for r in range(2):
            tokens[r, :qlen[r]] = seqs[r][pos[r]:pos[r] + qlen[r]]
            window_table[r, :max(pos[r] - cfg.window + 1, 0) // BS] = 0
        logits, caches, _ = step(
            params, jnp.asarray(tokens), caches,
            (jnp.asarray(table), jnp.asarray(window_table)),
            jnp.asarray(pos, jnp.int32), jnp.asarray(qlen))
        for r in np.flatnonzero(qlen):
            got = np.asarray(logits[r, :qlen[r]])
            assert np.abs(got - want[r][pos[r]:pos[r] + qlen[r]]).max() \
                < 2e-4, (tick, r)
            pos[r] += int(qlen[r])
    assert pos == [52, 30]


def test_the_chunk_tick_s_full_layers_hold_no_operand_of_rows_x_width(
        spec, params):
    """The step of two rows in 256 slots, traced: a full layer (G = 6)
    reads the rows with one new token as (2, 1, H, D) and the longer runs
    as tall tiles of 64 slots, (2 + ceil(100 / 64), 64, H, D), a tile a
    row of the call; a window layer keeps the list's tiles of 8 slots,
    and no layer makes rows x width query slots. A step a slot wide makes
    one call a layer."""
    cfg = spec.config
    asked = []

    def attn_fn(q, *rest, window=None):
        asked.append((window is not None, q.shape))
        return pa.ragged_paged_attention_reference(q, *rest, window=window)

    def step(width):
        table = jnp.zeros((2, 32), jnp.int32)
        return jax.make_jaxpr(
            lambda tokens, caches, pos0, qlen: laguna_step_rows_ragged(
                params, tokens, caches, (table, table), pos0, qlen, cfg,
                dtype=jnp.float32, max_tokens=100, attn_fn=attn_fn,
                sample_slot=jnp.zeros(2, jnp.int32)))(
            jnp.zeros((2, width), jnp.int32), _pools(cfg, 9),
            jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.int32))

    jaxpr = step(256)
    full = [shape for windowed, shape in asked if not windowed]
    heads = (cfg.n_heads, cfg.d_head)
    assert full == [(2, 1) + heads, (2 + 2, 64) + heads] * cfg.n_full_layers
    assert [shape for windowed, shape in asked if windowed] == [
        (2 + -(-100 // 8), 8, 18, cfg.d_head)] * cfg.n_window_layers
    shapes = [v.aval.shape for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars]
    assert shapes and not [x for x in shapes
                           if len(x) == 4 and x[:2] == (2, 256)]
    del asked[:]
    step(1)
    assert [shape for _, shape in asked] == [
        (2, 1, h, cfg.d_head) for h in cfg.heads_per_layer]


def test_two_shares_of_the_experts_add_up_to_the_uncut_layer(reference):
    """A whole expert layer: the shares held=(0, 8) and (8, 8), each with
    its half of the banks, the shared expert counted once, against the
    plain reference holding all 16."""
    module, sizes = reference
    spec = create_model("laguna-small-test", held_count=16)
    cfg = spec.config
    mp = jax.jit(spec.init)(jax.random.PRNGKey(5))["layers"][2]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 7, cfg.d_model))
    valid = jnp.ones((3, 7), bool)

    def share(first):
        banks = jax.tree.map(lambda a: a[first:first + 8], mp["experts"])
        y, rows = laguna._moe_ffn(dict(mp, experts=banks), x, valid, cfg,
                                  jnp.float32, (first, 8), None)
        return np.asarray(y), np.asarray(rows)

    (low, low_rows), (high, high_rows) = share(0), share(8)
    shared = np.asarray(laguna._mlp(mp["shared"], x, jnp.float32, cfg))
    whole, rows = laguna._moe_ffn(mp, x, valid, cfg, jnp.float32, (0, 16),
                                  None)
    assert np.abs(low + high - shared - np.asarray(whole)).max() < 1e-4
    assert not low_rows[8:].any() and not high_rows[:8].any()
    assert np.array_equal(low_rows + high_rows, np.asarray(rows))
    assert rows.sum() == 21 * cfg.top_k
    plain = dict(sizes, held_first=0)
    with jax.default_matmul_precision("highest"):
        theirs = module._experts(mp, x.reshape(21, -1), plain)
    assert np.abs(np.asarray(whole).reshape(21, -1)
                  - np.asarray(theirs)).max() < 1e-4


# -- the kernel's lower bound ------------------------------------------------------

@pytest.mark.parametrize("group", [6, 9])
@pytest.mark.parametrize("case", sorted(pa.WINDOW_CASES))
def test_paged_kernel_with_a_lower_bound_equals_its_reference(case, group):
    """Interpret mode, float32: width 1 (heads packed) and width 256, a
    window that is no whole number of blocks and one wider than a group of
    the walk, the table behind the window nulled as the scheduler leaves
    it."""
    assert pa.window_parity_check(case, group, interpret=True) < 1e-5


def test_the_reference_read_ignores_what_lies_behind_the_window():
    """Garbage in the blocks behind the window changes nothing."""
    operands, qlen = pa.parity_workload(
        "ragged", (8, 1), n_heads=12, n_kv_heads=2, d_head=16, block_size=16,
        n_blocks=17, table_len=8, dtype=jnp.float32, pos0=(70, 100))
    q, k, v, layer, tables, pos0, _ = operands
    out = pa.ragged_paged_attention_reference(*operands, window=20)
    behind = tables[0, :3]                      # columns 0..47 < 70 - 19
    k2 = k.at[:, behind].set(1e3)
    out2 = pa.ragged_paged_attention_reference(q, k2, v, layer, tables, pos0,
                                               qlen, window=20)
    assert np.array_equal(np.asarray(out[0]), np.asarray(out2[0]))


# -- the scheduler: what runs, what is held, what is counted, what is refused ------

def test_the_mixed_tick_serves_it_frees_window_blocks_and_counts(spec,
                                                                 params):
    from tpu_engine.utils.tracing import SpanRecorder

    tracer = SpanRecorder(capacity=4096)
    gen = ContinuousGenerator(spec, params=params, **LANE)
    gen.tracer, gen.trace_node = tracer, "lane"
    peaks = []
    slide = gen._slide_window_blocks

    def watched(pos0, qlen):
        slide(pos0, qlen)
        spans = gen._wspan[:, 1] - gen._wspan[:, 0]
        held = (gen._wtables != 0).sum(1)
        # A contiguous run a row (checked below: this is the tick thread).
        peaks.append((int(held.max()), bool(np.array_equal(spans, held))))

    gen._slide_window_blocks = watched
    try:
        rng = np.random.default_rng(0)
        prompts = [[int(t) for t in rng.integers(1, 256, size=n)]
                   for n in (70, 5, 23, 40, 33)]
        outs = gen.generate(prompts, max_new_tokens=20)
        stats = gen.stats()
    finally:
        gen.stop()
    cfg = spec.config
    # The forward is causal: one program over 96 right-padded columns.
    forward = jax.jit(lambda tokens: laguna_apply(
        params, tokens, cfg, dtype=jnp.float32))
    for prompt, out in zip(prompts, outs):
        seq = list(prompt)
        for _ in range(20):
            padded = np.zeros((1, 96), np.int32)
            padded[0, :len(seq)] = seq
            seq.append(int(jnp.argmax(forward(padded)[0, len(seq) - 1])))
        assert [int(t) for t in out] == seq[len(prompt):]
    # A row's window blocks never exceed the window, a chunk and a block
    # of tokens; the full layers held 6 blocks for the 90-token row.
    bound = -(-(cfg.window + 16) // BS) + 1
    assert all(contiguous for _, contiguous in peaks)
    assert 1 < max(held for held, _ in peaks) <= bound == 3
    pool = stats["kv_pool"]
    assert pool["window_blocks_total"] == 4 * bound
    assert pool["window_blocks_held"] == pool["full_blocks_held"] == 0
    assert pool["blocks_free"] == pool["blocks_total"]
    assert pool["window_blocks_freed"] > 0
    for key in ("evictions", "cow_copies", "radix_hits"):
        assert pool[key] == 0
    mixed, counted = stats["mixed"], stats["moe"]
    fed = mixed["prefill_tokens"] + mixed["decode_tokens"]
    assert counted["assignments"] == fed * cfg.top_k * cfg.n_moe_layers
    by_expert = np.asarray(counted["rows_by_expert"])
    assert by_expert.shape == (cfg.n_moe_layers, cfg.n_routed)
    assert by_expert.sum() == counted["assignments_held"]
    assert not by_expert[:, 8:].any()
    assert 0.3 < counted["assignments_held"] / counted["assignments"] < 0.7
    spans = [s["attrs"] for s in tracer.snapshot() if s["op"] == "mixed_step"]
    assert len(spans) == mixed["ticks"]
    for key, total in (("moe_assignments", counted["assignments"]),
                       ("moe_assignments_held", counted["assignments_held"]),
                       ("moe_experts_touched", counted["experts_touched"]),
                       ("window_blocks_freed", pool["window_blocks_freed"])):
        assert sum(s[key] for s in spans) == total
    assert all(s["ctx_tokens_full"] == s["ctx_tokens"] for s in spans)
    assert all(0 < s["ctx_tokens_window"] <= s["ctx_tokens_full"]
               for s in spans)
    assert any(s["ctx_tokens_window"] < s["ctx_tokens_full"] for s in spans)
    # A tile of one class or the other a row the tick fed (a chunk is at
    # most 16 tokens here, one tall tile), and every fed token in one.
    assert all(0 < s["attn_tiles_short"] + s["attn_tiles_tall"] <= 4
               and s["attn_tiles_short"] + 16 * s["attn_tiles_tall"]
               >= s["decode_rows"] + s["prefill_tokens"] for s in spans)
    assert any(s["attn_tiles_short"] and s["attn_tiles_tall"] for s in spans)


def test_a_row_cut_by_its_deadline_leaks_no_block_of_either_kind(spec,
                                                                 params):
    from tpu_engine.utils.deadline import Deadline, DeadlineExceeded

    gen = ContinuousGenerator(spec, params=params, **LANE)
    try:
        prompt = [int(t) for t in
                  np.random.default_rng(4).integers(1, 256, size=60)]
        gen.generate([prompt[:9]], max_new_tokens=3)     # compiled, warm
        cut = gen.submit(prompt, max_new_tokens=60,
                         deadline=Deadline.after_ms(150))
        with pytest.raises(DeadlineExceeded):
            cut.result(timeout=120)
        gen.generate([prompt[:5]], max_new_tokens=2)
        stats = gen.stats()
    finally:
        gen.stop()
    pool = stats["kv_pool"]
    assert stats["deadline_cancelled"] == 1
    assert pool["window_blocks_held"] == pool["full_blocks_held"] == 0
    assert pool["blocks_free"] == pool["blocks_total"]


def test_the_tick_runs_one_ahead_with_both_kinds_of_block(spec, params):
    """`_slide_window_blocks` works from positions, which the host knows a
    tick early: window blocks are freed and taken when the next tick is
    FORMED, while the tick before still runs. An EOS met mid-stream ends
    the row one tick late on the device and never in the tokens; both
    pools end whole (tests/tick_pipeline.py)."""
    from tick_pipeline import check_late_ends

    def whole(gen):
        pool = gen.stats()["kv_pool"]
        return (pool["window_blocks_held"] == pool["full_blocks_held"] == 0
                and pool["blocks_free"] == pool["blocks_total"])

    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(1, 256, size=n)]
               for n in (70, 9, 33)]
    counters = check_late_ends(
        lambda: ContinuousGenerator(spec, params=params, **LANE),
        prompts, whole, max_new=24)
    assert counters["overlapped_ticks"] > counters["ticks"] // 2


@pytest.mark.parametrize("kwargs, error, message", [
    ({"kv_block_size": 0, "kv_blocks": 64}, ValueError,
     r"set kv_block_size > 0 \(the dense per-slot cache has no"),
    ({"kv_block_size": 0}, ValueError,
     "served by the mixed tick over the block pool only"),
    ({"prefix_sharing": True}, ValueError,
     "prefix_sharing needs the 'prefix_sharing' capability.*"
     "a freed block can serve no prefix hit"),
    ({"kv_quantize": "int8"}, ValueError,
     "kv_quantize needs the 'kv_quantize' capability"),
    ({"kv_host_blocks": 8}, ValueError,
     "kv_host_blocks needs the 'kv_host_tier' capability"),
    ({"spec_k": 2}, ValueError,
     "spec_k needs the 'spec_decode' capability"),
    ({"tp": 2}, RuntimeError, "cannot serve tensor-parallel"),
])
def test_what_a_lane_with_window_layers_cannot_do_is_refused_at_start_up(
        spec, params, kwargs, error, message):
    with pytest.raises(error, match=message):
        ContinuousGenerator(spec, params=params, **{**LANE, **kwargs})


def test_the_chain_wire_format_is_refused_by_name(spec, params):
    gen = ContinuousGenerator(spec, params=params, **LANE)
    try:
        refusal = ("needs the 'migration' capability, which the "
                   "kv_windowed family does not declare")
        assert refusal in gen.export_row("nobody")["reason"]
        assert refusal in gen.export_prefix([1] * 32)["reason"]
        with pytest.raises(ValueError, match=refusal):
            gen.submit_import({"prompt": [1], "emitted": [], "pos": 1,
                               "tok": 1, "max_new": 1, "chain": {}})
    finally:
        gen.stop()


def test_the_scheduler_imports_no_model_s_step_by_name():
    import inspect

    from tpu_engine.runtime import scheduler

    source = inspect.getsource(scheduler)
    for name in ("moonlight_step_rows_ragged", "laguna_step_rows_ragged",
                 "models.moonlight import", "models.laguna import"):
        assert name not in source


# -- the serving layer's start-up fences -----------------------------------------

_GEN_KW = dict(model="laguna-small-test", dtype="float32", batch_buckets=(1,),
               gen_max_batch_size=2, gen_kv_block_size=BS,
               gen_prefill_chunk=16,
               gen_prefix_sharing=False)


@pytest.mark.parametrize("role", ["prefill", "decode"])
def test_a_dedicated_role_is_refused_at_start_up(role):
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig

    with pytest.raises(RuntimeError,
                       match=f"--role {role} needs the 'handoff' "
                             f"capability.*kv_windowed family"):
        WorkerNode(WorkerConfig(node_id="w", role=role, **_GEN_KW))


@pytest.mark.parametrize("flag, capability", [
    ("migrate_streams", "migration"), ("disagg", "handoff")])
def test_a_fleet_that_moves_streams_is_refused_at_start_up(flag, capability):
    from tpu_engine.serving.app import serve_combined
    from tpu_engine.utils.config import GatewayConfig, WorkerConfig

    with pytest.raises(RuntimeError,
                       match=f"needs the '{capability}' capability, which "
                             f"model 'laguna-small-test' \\(kv_windowed"):
        serve_combined(model="laguna-small-test", lanes=1, port=0,
                       worker_config=WorkerConfig(**_GEN_KW),
                       gateway_config=GatewayConfig(port=0, **{flag: True}),
                       warmup=False, native_front=False)
