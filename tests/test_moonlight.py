"""The Moonlight family at a small size on the CPU: the latent pool and its
absorbed read against the full forward, experts routed without drops (a
row never sees its tick-mates, padding reaches no expert), the start-up
fences of the kv_latent family, and what the tick counts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models.moonlight import (
    MoonlightConfig,
    moonlight_apply,
    moonlight_step_rows_ragged,
)
from tpu_engine.models.registry import (
    FAMILY_CAPABILITIES,
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.ops import latent_attention as la
from tpu_engine.ops import moe
from tpu_engine.runtime.kv_blocks import BlockPool, dense_block_bytes
from tpu_engine.runtime.scheduler import ContinuousGenerator

BS = 16


@pytest.fixture(scope="module")
def spec():
    _ensure_builtin_models_imported()
    return create_model("moonlight-small-test")


@pytest.fixture(scope="module")
def params(spec):
    return jax.jit(spec.init)(jax.random.PRNGKey(3))


def _step(spec, rows, width, **kw):
    cfg = spec.config

    def step(params, caches, tables, tokens, pos0, qlen):
        return moonlight_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            dtype=jnp.float32, **kw)

    return jax.jit(step)


def _tables(rows, blocks_each):
    """Row r owns blocks 1 + r * blocks_each ... (block 0 is null)."""
    return jnp.asarray(1 + np.arange(rows * blocks_each, dtype=np.int32)
                       .reshape(rows, blocks_each))


# -- registry, configuration, pool --------------------------------------------

def test_family_capabilities_and_stated_widths(spec):
    cfg = spec.config
    assert isinstance(cfg, MoonlightConfig)
    assert spec.state_family == "kv_latent"
    assert spec.capabilities == FAMILY_CAPABILITIES["kv_latent"]
    for missing in ("kv_quantize", "kv_host_tier", "migration", "handoff",
                    "tensor_parallel", "spec_decode", "two_path"):
        assert not spec.supports(missing)
    assert spec.supports("generate") and spec.supports("prefix_sharing")
    assert spec.tp_rule.startswith("unshardable")
    # A head's width is what the model states, not d_model / n_heads.
    assert cfg.d_model // cfg.n_heads == 16 and cfg.d_head == 16 + 8
    full = create_model("moonlight").config
    assert (full.d_head, full.kv_lanes, full.n_moe_layers) == (
        192, (128, 512), 26)
    assert full.attn_scale == pytest.approx(192 ** -0.5)


def test_the_pool_is_sized_by_the_lanes_the_model_states(spec):
    cfg = spec.config
    pool = BlockPool(cfg, 5, BS, jnp.float32)
    assert pool.caches.k.shape == (3, 5, BS, la.PE_LANES)
    assert pool.caches.v.shape == (3, 5, BS, cfg.kv_lora_rank)
    assert dense_block_bytes(cfg, BS, jnp.float32) == 3 * BS * (128 + 32) * 4
    gpt2 = create_model("gpt2-small-test").config
    assert gpt2.kv_lanes == (64, 64)
    assert dense_block_bytes(gpt2, BS, jnp.bfloat16) == 2 * 2 * BS * 64 * 2
    stated = dataclasses.replace(gpt2, head_dim=24)
    assert stated.d_head == 24 and stated.kv_lanes == (96, 96)


def test_init_makes_the_stated_type_directly():
    _ensure_builtin_models_imported()
    spec = create_model("moonlight-small-test", param_dtype="bfloat16")
    shapes = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)}
    for name, leaf in flat.items():
        plain = (name.endswith(("scale", "bias")) or "router" in name)
        assert leaf.dtype == (jnp.float32 if plain else jnp.bfloat16), name
    assert flat["moe/mlp/experts/gate_up"].shape == (2, 8, 64, 64)
    assert flat["dense/mlp/gate/kernel"].shape == (1, 64, 128)
    # One jit, no float32 intermediate of a bank: nothing wider than
    # bfloat16 of a bank's shape anywhere in the program.
    text = jax.jit(spec.init).lower(jax.random.PRNGKey(0)).as_text()
    assert "tensor<2x8x64x64xf32>" not in text
    assert "tensor<8x64x64xf32>" not in text


# -- attention -------------------------------------------------------------------

def test_absorbed_and_expanded_attention_agree(spec, params):
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, size=(2, 24)), jnp.int32)
    expanded = moonlight_apply(params, tokens, spec.config,
                               dtype=jnp.float32)
    absorbed = moonlight_apply(params, tokens, spec.config,
                               dtype=jnp.float32, absorbed=True)
    assert np.abs(expanded - absorbed).max() < 1e-5 * np.abs(expanded).max()


@pytest.mark.parametrize("q_lens, kw", [
    ((1, 1, 1), {}),
    ((1, 7, 16, 17), {}),
    ((40, 1, 3), {"table_len": 5, "n_blocks": 12, "seed": 3}),
    # 16 heads: tiles of 8 slots; at most 44 valid slots bound the tiles
    ((1, 40, 3, 0, 0, 0), {"n_heads": 16, "table_len": 5, "n_blocks": 12,
                           "max_tokens": 44, "seed": 4}),
])
def test_latent_kernel_in_interpret_mode_equals_its_xla_reference(q_lens,
                                                                  kw):
    assert la.parity_check(q_lens, interpret=True, **kw) < 1e-5


def test_prefill_then_decode_through_the_latent_pool_equals_the_forward(
        spec, params):
    """Two rows: a 37-token prompt in three chunks of 16 beside a 5-token
    one, then six decode steps each, teacher-forced: every logit the step
    returns equals the full forward's at that position."""
    cfg = spec.config
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, 256, size=n).astype(np.int32)
            for n in (37 + 6, 5 + 6)]
    prompt = [37, 5]
    full = [np.asarray(moonlight_apply(params, jnp.asarray(s)[None], cfg,
                                       dtype=jnp.float32)[0]) for s in seqs]
    pool = BlockPool(cfg, 9, BS, jnp.float32)
    caches, tables = pool.caches, _tables(2, 4)
    wide, narrow = _step(spec, 2, 16), _step(spec, 2, 1)
    done = [0, 0]
    while any(d < p for d, p in zip(done, prompt)):
        qlen = [min(16, p - d) for d, p in zip(done, prompt)]
        tokens = np.zeros((2, 16), np.int32)
        for r in range(2):
            tokens[r, :qlen[r]] = seqs[r][done[r]:done[r] + qlen[r]]
        logits, caches, _ = wide(params, caches, tables, jnp.asarray(tokens),
                                 jnp.asarray(done, jnp.int32),
                                 jnp.asarray(qlen, jnp.int32))
        for r in range(2):
            if not qlen[r]:
                continue
            got = np.asarray(logits[r, :qlen[r]])
            want = full[r][done[r]:done[r] + qlen[r]]
            assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()
            done[r] += qlen[r]
    for _ in range(6):
        tokens = np.asarray([[seqs[r][done[r]]] for r in range(2)], np.int32)
        logits, caches, _ = narrow(
            params, caches, tables, jnp.asarray(tokens),
            jnp.asarray(done, jnp.int32), jnp.ones((2,), jnp.int32))
        for r in range(2):
            want = full[r][done[r]]
            assert np.abs(np.asarray(logits[r, 0]) - want).max() \
                < 2e-4 * np.abs(want).max()
            done[r] += 1


# -- the expert layer ----------------------------------------------------------

def _moe_case(n=24, d=32, f=16, e=8, k=2, layers=3):
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (n, d))
    router = {"kernel": jax.random.normal(keys[1], (d, e)),
              "bias": 0.5 * jax.random.normal(keys[2], (e,))}
    bank = {"gate_up": jax.random.normal(keys[3], (layers * e, d, 2 * f)) / 6,
            "down": jax.random.normal(keys[4], (layers * e, f, d)) / 4}
    return x, router, bank, e, k, f


def _dense_experts(x, valid, experts, weights, bank, first, f, held=None):
    out = np.zeros(x.shape, np.float32)
    for n in range(x.shape[0]):
        for j in range(experts.shape[1]):
            e = int(experts[n, j])
            if not valid[n] or (held and not held[0] <= e < sum(held)):
                continue
            gu = np.asarray(x[n]) @ np.asarray(bank["gate_up"][first + e])
            h = np.asarray(jax.nn.silu(gu[:f])) * gu[f:]
            out[n] += float(weights[n, j]) * (
                h @ np.asarray(bank["down"][first + e]))
    return out


@pytest.mark.parametrize("held, max_tokens", [(None, None), (None, 16),
                                              ((2, 4), None)])
def test_routed_experts_equal_every_expert_applied_and_masked(held,
                                                              max_tokens):
    x, router, bank, e, k, f = _moe_case()
    valid = np.arange(x.shape[0]) % 3 != 0              # 16 valid slots
    experts, weights = moe.sigmoid_topk_route(x, router, k, 2.5)
    y, rows = jax.jit(lambda *a: moe.routed_experts(
        *a, first_group=jnp.int32(e), n_experts=e, held=held,
        max_tokens=max_tokens, dtype=jnp.float32))(
        x, jnp.asarray(valid), experts, weights, bank)
    want = _dense_experts(x, valid, np.asarray(experts), np.asarray(weights),
                          bank, e, f, held)
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    assert not np.asarray(y)[~valid].any()
    taken = np.asarray(experts)[valid].reshape(-1)
    if held:
        taken = taken[(taken >= held[0]) & (taken < sum(held))]
    assert np.array_equal(np.asarray(rows), np.bincount(taken, minlength=e))


# The grouped products of the benchmark's four expert cells, (pairs, K, N) in
# bfloat16 with the contraction the rule keeps whole there, and shapes no
# cell has: a short list, wide banks, float32.
_TILED_SHAPES = [
    (256, 2048, 2816, 2, 2048), (1792, 2048, 2816, 2, 2048),
    (1792, 1408, 2048, 2, 1408), (1024, 2304, 2048, 2, 2304),
    (3072, 1024, 2304, 2, 1024), (7040, 1024, 2688, 2, 1024),
    (7040, 2688, 1024, 2, 2688), (2944, 3072, 2048, 2, 3072),
    (384, 1024, 3072, 2, 1024), (48, 128, 256, 2, 128),
    (112, 4096, 14336, 2, 4096), (4096, 14336, 4096, 2, 2048),
    (512, 8192, 8192, 4, 4096), (104, 4096, 14336, 4, 4096),
    (1792, 2048, 2816, 4, 2048)]


@pytest.mark.parametrize("m, k, n, itemsize, tk_wanted", _TILED_SHAPES)
def test_grouped_tiling_states_tiles_the_kernel_can_take(m, k, n, itemsize,
                                                         tk_wanted):
    tm, tk, tn = moe.grouped_tiling(m, k, n, itemsize)
    assert tm == moe.row_tile(m, itemsize) <= 128 and m % tm == 0
    assert tm % (32 // itemsize) == 0
    assert k % tk == 0 and tk % 128 == 0 and tk == tk_wanted
    assert n % tn == 0 and tn % 128 == 0
    blocks = 2 * (tk * tn * itemsize + tm * tk * itemsize + tm * tn * 4)
    blocks += tm * tn * 4 * (tk < k)
    assert blocks == moe._block_bytes(tm, tk, tn, k, itemsize)
    assert blocks <= moe._VMEM_BLOCK_BYTES == 14 * 2 ** 20
    # No wider output tile fits beside this contraction tile.
    wider = [t for t in range(tn + 128, n + 1, 128) if n % t == 0]
    assert all(moe._block_bytes(tm, tk, t, k, itemsize)
               > moe._VMEM_BLOCK_BYTES for t in wider)
    assert tk * tn * itemsize >= 2 ** 20 or (tk, tn) == (k, n)


@pytest.mark.parametrize("m, k, n, itemsize", [
    (1792, 2048, 2800, 2),      # N no multiple of 128
    (1792, 1400, 2048, 2),      # K no multiple of 128
    (48, 32, 32, 4),            # the test models' widths
    (1728, 2048, 2816, 2),      # a list that is no whole number of tiles
    (100, 2048, 2816, 2)])
def test_grouped_tiling_states_none_where_no_tiling_is_legal(m, k, n,
                                                            itemsize):
    assert moe.grouped_tiling(m, k, n, itemsize) is None


@pytest.mark.parametrize("n, max_tokens, pairs", [
    (25, None, 56),             # 50 slots' pairs and 6 that pad the list
    (25, 17, 40),               # 34 pairs rounded up inside the slots' 50
    (70, None, 256)])           # two row tiles of 128 for 140 pairs
def test_the_pair_list_is_whole_row_tiles_and_its_padding_forms_no_row(
        n, max_tokens, pairs):
    x, router, bank, e, k, f = _moe_case(n=n)
    valid = np.arange(n) % 3 != 0
    if max_tokens:
        valid &= np.arange(n) < 3 * max_tokens // 2     # <= max_tokens valid
    experts, weights = moe.sigmoid_topk_route(x, router, k, 2.5)
    y, rows = jax.jit(lambda *a: moe.routed_experts(
        *a, first_group=jnp.int32(e), n_experts=e, max_tokens=max_tokens,
        dtype=jnp.float32))(x, jnp.asarray(valid), experts, weights, bank)
    tilings = moe.traced_tilings()
    assert tilings[f"{pairs}x32x32"] == tilings[f"{pairs}x16x32"] == "xla"
    assert pairs % moe.row_tile(pairs, 4) == 0
    want = _dense_experts(x, valid, np.asarray(experts), np.asarray(weights),
                          bank, e, f)
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    assert not np.asarray(y)[~valid].any()
    taken = np.asarray(experts)[valid].reshape(-1)
    assert np.array_equal(np.asarray(rows), np.bincount(taken, minlength=e))
    assert int(rows.sum()) == valid.sum() * k


def test_stated_tiles_change_no_bit_off_the_tpu(monkeypatch):
    """At widths the rule states tiles for, the attribute rides the op and
    the CPU's product is the one XLA's own pick gives."""
    x, router, bank, e, k, f = _moe_case(n=40, d=128, f=128)
    valid = jnp.arange(40) % 5 != 0
    experts, weights = moe.sigmoid_topk_route(x, router, k, 2.5)

    def run():
        return jax.jit(lambda *a: moe.routed_experts(
            *a, first_group=jnp.int32(0), n_experts=e, dtype=jnp.float32))(
            x, valid, experts, weights, bank)

    stated = run()
    assert moe.traced_tilings()["80x128x256"] == "80,128,256"
    assert moe.traced_tilings()["80x128x128"] == "80,128,128"
    monkeypatch.setattr(moe, "grouped_tiling", lambda *shape: None)
    plain = run()
    assert moe.traced_tilings()["80x128x256"] == "xla"
    for a, b in zip(stated, plain):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_selection_bias_chooses_and_does_not_weigh():
    x, router, _, e, k, _ = _moe_case()
    experts, weights = moe.sigmoid_topk_route(x, router, k, 2.446)
    scores = jax.nn.sigmoid(x @ router["kernel"])
    assert np.array_equal(np.asarray(experts), np.asarray(
        jax.lax.top_k(scores + router["bias"], k)[1]))
    unbiased = jax.lax.top_k(scores, k)[1]
    assert not np.array_equal(np.asarray(experts), np.asarray(unbiased))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(experts), 1)
    assert np.allclose(np.asarray(weights),
                       picked / picked.sum(-1, keepdims=True) * 2.446,
                       rtol=1e-5)


@pytest.mark.parametrize("width", [1, 8])
def test_a_row_s_logits_are_bit_identical_whatever_its_tick_mates(
        spec, params, width):
    """The no-drop contract: row 5 alone in a 32-row tick, and the same
    row among 31 others, through the same compiled step."""
    cfg = spec.config
    rng = np.random.default_rng(2)
    rows = 32
    step = _step(spec, rows, width)
    tables = _tables(rows, 2)
    tokens = rng.integers(0, 256, size=(rows, width)).astype(np.int32)
    pos0 = rng.integers(0, BS, size=rows).astype(np.int32)
    pool = BlockPool(cfg, 1 + rows * 2, BS, jnp.float32)
    filled = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(7), a.shape), pool.caches)
    full_q = np.full((rows,), width, np.int32)
    alone_q = np.where(np.arange(rows) == 5, width, 0).astype(np.int32)
    out = {}
    for name, qlen in (("alone", alone_q), ("full", full_q)):
        logits, _, counts = step(params, filled, tables, jnp.asarray(tokens),
                                 jnp.asarray(pos0), jnp.asarray(qlen))
        out[name] = (np.asarray(logits[5]), np.asarray(counts))
    assert np.array_equal(out["alone"][0], out["full"][0])
    assert out["alone"][1].sum() == width * cfg.top_k * cfg.n_moe_layers
    assert out["full"][1].sum() == rows * width * cfg.top_k * cfg.n_moe_layers


def test_padding_slots_reach_no_expert_and_no_counter(spec, params):
    cfg = spec.config
    rows, width = 4, 8
    step = _step(spec, rows, width, max_tokens=12)
    tables = _tables(rows, 2)
    pool = BlockPool(cfg, 1 + rows * 2, BS, jnp.float32)
    qlen = np.asarray([8, 3, 0, 1], np.int32)
    pos0 = np.asarray([0, 4, 0, 9], np.int32)
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, 256, size=(rows, width)).astype(np.int32)
    other = tokens.copy()
    pad = np.arange(width)[None, :] >= qlen[:, None]
    other[pad] = rng.integers(0, 256, size=int(pad.sum()))
    got = [step(params, pool.caches, tables, jnp.asarray(t),
                jnp.asarray(pos0), jnp.asarray(qlen)) for t in (tokens, other)]
    (logits_a, caches_a, rows_a), (logits_b, caches_b, rows_b) = got
    assert int(rows_a.sum()) == int(qlen.sum()) * cfg.top_k * cfg.n_moe_layers
    assert np.array_equal(np.asarray(rows_a), np.asarray(rows_b))
    valid = ~pad
    assert np.array_equal(np.asarray(logits_a)[valid],
                          np.asarray(logits_b)[valid])
    # Padding wrote into the null block only.
    for a, b in zip(caches_a, caches_b):
        assert np.array_equal(np.asarray(a[:, 1:]), np.asarray(b[:, 1:]))


# -- the scheduler: what runs, what is counted, what is refused --------------

def test_the_mixed_tick_serves_it_and_counts_its_experts(spec, params):
    from tpu_engine.utils.tracing import SpanRecorder

    tracer = SpanRecorder(capacity=4096)
    gen = ContinuousGenerator(spec, params=params, n_slots=4,
                              dtype="float32", kv_block_size=BS,
                              prefill_chunk=16)
    gen.tracer, gen.trace_node = tracer, "lane"
    try:
        rng = np.random.default_rng(0)
        prompts = [[int(t) for t in rng.integers(1, 256, size=n)]
                   for n in (40, 5, 23)]
        outs = gen.generate(prompts, max_new_tokens=6)
        stats = gen.stats()
    finally:
        gen.stop()
    cfg = spec.config
    for prompt, out in zip(prompts, outs):
        seq = list(prompt)
        for _ in range(6):
            logits = moonlight_apply(params, jnp.asarray([seq]), cfg,
                                     dtype=jnp.float32)
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert [int(t) for t in out] == seq[len(prompt):]
    mixed, counted = stats["mixed"], stats["moe"]
    fed = mixed["prefill_tokens"] + mixed["decode_tokens"]
    assert counted["assignments"] == fed * cfg.top_k * cfg.n_moe_layers
    by_expert = np.asarray(counted["rows_by_expert"])
    assert by_expert.shape == (cfg.n_moe_layers, cfg.n_routed)
    assert by_expert.sum() == counted["assignments"]
    assert 0 < counted["experts_touched"] <= (
        mixed["ticks"] * cfg.n_moe_layers * cfg.n_routed)
    spans = [s for s in tracer.snapshot() if s["op"] == "mixed_step"]
    assert len(spans) == mixed["ticks"]
    assert sum(s["attrs"]["moe_assignments"] for s in spans) \
        == counted["assignments"]
    assert sum(s["attrs"]["moe_experts_touched"] for s in spans) \
        == counted["experts_touched"]
    assert all("ctx_tokens" in s["attrs"] for s in spans)
    assert "kv_pool" in stats
    # Both products of every list length the lane traced, by their shapes
    # (the record is the process's: other tests' shapes lie beside them).
    widths = {(cfg.d_model, 2 * cfg.d_ff_expert),
              (cfg.d_ff_expert, cfg.d_model)}
    mine = {}
    for shape, tiles in counted["tilings"].items():
        m, k, n = map(int, shape.split("x"))
        if (k, n) in widths:
            mine.setdefault(m, set()).add((k, n))
            assert m % moe.row_tile(m, 4) == 0
            assert tiles == "xla" and moe.grouped_tiling(m, k, n, 4) is None
    assert mine and all(both == widths for both in mine.values())


@pytest.mark.parametrize("kwargs, error, message", [
    ({"kv_block_size": 0, "kv_blocks": 64}, ValueError,
     r"set kv_block_size > 0 \(the dense per-slot cache has no"),
    ({"kv_block_size": 0}, ValueError,
     "served by the mixed tick over the block pool only"),
    ({"kv_quantize": "int8"}, ValueError,
     "kv_quantize needs the 'kv_quantize' capability"),
    ({"kv_host_blocks": 8}, ValueError,
     "kv_host_blocks needs the 'kv_host_tier' capability"),
    ({"spec_k": 2}, ValueError,
     "spec_k needs the 'spec_decode' capability"),
    ({"tp": 2}, RuntimeError, "cannot serve tensor-parallel"),
])
def test_what_the_latent_pool_cannot_do_is_refused_at_start_up(
        spec, params, kwargs, error, message):
    base = {"n_slots": 2, "dtype": "float32", "kv_block_size": BS,
            "prefill_chunk": 16}
    with pytest.raises(error, match=message):
        ContinuousGenerator(spec, params=params, **{**base, **kwargs})


def test_the_tick_runs_one_ahead_and_late_ends_ride_as_done_rows(spec,
                                                                 params):
    """The pipeline's seam is in the step's shared wrapper, so this
    family's step takes it unedited: an EOS met mid-stream ends the row one
    tick late on the device and never in the tokens (tests/tick_pipeline.py:
    the same lane kept in order is the oracle), the experts' counts come
    back with the tick they belong to, the latent pool ends whole."""
    from tick_pipeline import check_late_ends

    def whole(gen):
        pool = gen.stats()["kv_pool"]
        return pool["blocks_free"] == pool["blocks_total"]

    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(1, 256, size=n)]
               for n in (40, 5, 23)]
    counters = check_late_ends(
        lambda: ContinuousGenerator(spec, params=params, n_slots=4,
                                    dtype="float32", kv_block_size=BS,
                                    prefill_chunk=16,
                                    prefix_sharing=False),
        prompts, whole)
    assert counters["overlapped_ticks"] > counters["ticks"] // 2


def test_the_chain_wire_format_is_refused_by_name(spec, params):
    gen = ContinuousGenerator(spec, params=params, n_slots=2,
                              dtype="float32", kv_block_size=BS,
                              prefill_chunk=16)
    try:
        refusal = "needs the 'migration' capability"
        assert refusal in gen.export_row("nobody")["reason"]
        assert refusal in gen.export_prefix([1] * 32)["reason"]
        with pytest.raises(ValueError, match=refusal):
            gen.submit_import({"prompt": [1], "emitted": [], "pos": 1,
                               "tok": 1, "max_new": 1, "chain": {}})
    finally:
        gen.stop()
