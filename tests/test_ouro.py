"""The Ouro family (models/ouro.py) on the served path: ONE stack of layers
applied several times a token over one set of weights, and a block pool a
plane a (pass, layer) deep. `ouro-small-test` (3 layers x 3 passes = 9
planes, 4 heads of 16 lanes, d 64) against the plain reference
benchmarks/references/ouro.py, on logits and on every plane of the cache;
the exit rule below 1; a prefix hit; the step's one layer body; the
start-up fences."""

import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models.ouro import ouro_apply, ouro_step_rows_ragged
from tpu_engine.models.registry import (
    FAMILY_CAPABILITIES,
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.ops.attention import KVCache
from tpu_engine.runtime.scheduler import ContinuousGenerator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16
LANE = dict(n_slots=4, dtype="float32", kv_block_size=BS, prefill_chunk=16,
            prefix_sharing=False)
L, T = 3, 3


@pytest.fixture(scope="module")
def spec():
    _ensure_builtin_models_imported()
    return create_model("ouro-small-test")


@pytest.fixture(scope="module")
def params(spec):
    return jax.jit(spec.init)(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def reference():
    """benchmarks/references/ouro.py and the test configuration's
    `reference` block as the harness hands it over."""
    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    path = os.path.join(bench, "references", "ouro.py")
    module_spec = importlib.util.spec_from_file_location(
        "ouro_reference_under_test", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    with open(os.path.join(ROOT, "tests", "benchmarks", "data", "configs",
                           "ouro-small-test.json")) as f:
        sizes = json.load(f)["reference"]
    return module, sizes


def _sizes(sizes, **more):
    return tuple(sorted(dict(sizes, **more).items()))


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, n)]


def _pool(cfg, blocks):
    (kind,) = cfg.kv_block_kinds
    shape = (kind.n_layers, blocks, BS, kind.kv_lanes[0])
    return KVCache(jnp.zeros(shape, jnp.float32),
                   jnp.zeros(shape, jnp.float32))


# -- registry and configuration --------------------------------------------------

def test_family_capabilities_and_the_pool_the_model_states(spec):
    cfg = spec.config
    assert spec.state_family == "kv_looped"
    assert spec.capabilities == FAMILY_CAPABILITIES["kv_looped"]
    assert spec.supports("prefix_sharing") and spec.supports("oneshot_rows")
    for absent in ("kv_host_tier", "kv_quantize", "spec_decode",
                   "tensor_parallel", "migration", "handoff", "two_path"):
        assert not spec.supports(absent)
    assert spec.passes == cfg.ut_steps == T and cfg.n_layers == L
    # The pool is as deep as the model STATES, not as its weights are.
    assert [k.n_layers for k in cfg.kv_block_kinds] == [cfg.kv_planes] == [9]
    assert cfg.kv_block_kinds[0].kv_lanes == (64, 64)
    assert spec.block_decode is None and create_model("gpt2").passes == 1


def test_the_published_geometry_is_the_default():
    _ensure_builtin_models_imported()
    spec = create_model("ouro")
    cfg = spec.config
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab) == (
        48, 2048, 5632, 49152)
    assert (cfg.n_heads, cfg.kv_heads, cfg.d_head) == (16, 16, 128)
    assert (cfg.ut_steps, cfg.exit_threshold, cfg.rope_theta) == (4, 1.0, 1e6)
    assert cfg.kv_planes == 192
    shapes = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for path, x
                in jax.tree_util.tree_flatten_with_path(shapes)[0]
                if "bias" not in str(path[-1]))
    assert count + 1 == 2_667_974_657          # the gate's bias is counted


# -- the forward and the step against the reference -------------------------------

@pytest.mark.parametrize("threshold", [1.0, 0.5])
def test_the_forward_equals_the_plain_reference(reference, threshold):
    module, sizes = reference
    spec = create_model("ouro-small-test", exit_threshold=threshold)
    params = jax.jit(spec.init)(jax.random.PRNGKey(3))
    tokens = jnp.asarray(_prompt(0, 48), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = ouro_apply(params, tokens[None], spec.config,
                         dtype=jnp.float32)[0]
    want = module.forward(params, tokens,
                          _sizes(sizes, exit_threshold=threshold))
    np.testing.assert_allclose(got, want, atol=1e-4)
    if threshold < 1:
        streams, _ = module.body(params, tokens, _sizes(sizes))
        exits = np.bincount(np.asarray(module.exit_pass(
            params["gate"], streams, threshold)), minlength=T)
        assert (exits > 0).all(), exits      # the rule decides something


@pytest.mark.parametrize("control", [
    {"drop": "branch_norms"}, {"drop": "pass_norm"}, {"drop": "own_cache"},
    {"weights_as": "float8_e4m3fn"}, {"ut_steps": T - 1}])
def test_each_control_moves_the_reference_s_logits(params, reference,
                                                   control):
    module, sizes = reference
    tokens = jnp.asarray(_prompt(4, 40), jnp.int32)
    plain = module.forward(params, tokens, _sizes(sizes))
    other = module.forward(params, tokens, _sizes(sizes, **control))
    assert float(jnp.abs(plain - other).max()) > 0.1


def _serve_by_hand(spec, params, seqs, plans, table, max_tokens=36):
    """Ticks that mix chunk and decode rows through the step itself.
    Returns ({row: logits of its tokens}, the pool)."""
    cfg = spec.config
    caches = _pool(cfg, blocks=17)
    tables = jnp.asarray(table)
    step = jax.jit(lambda tokens, caches, pos0, qlen:
                   ouro_step_rows_ragged(
                       params, tokens, caches, tables, pos0, qlen, cfg,
                       dtype=jnp.float32, max_tokens=max_tokens))
    pos = {r: 0 for r in seqs}
    got = {r: [] for r in seqs}
    rows = table.shape[0]
    with jax.default_matmul_precision("highest"):
        while any(plans.values()):
            tokens = np.zeros((rows, 16), np.int32)
            pos0, qlen = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
            for r, plan in plans.items():
                if plan:
                    n = plan.pop(0)
                    tokens[r, :n] = seqs[r][pos[r]:pos[r] + n]
                    pos0[r], qlen[r] = pos[r], n
            logits, caches, routed = step(jnp.asarray(tokens), caches,
                                          jnp.asarray(pos0),
                                          jnp.asarray(qlen))
            assert routed.shape == (0, 1)
            for r in pos:
                got[r].append(np.asarray(logits[r, :qlen[r]]))
                pos[r] += int(qlen[r])
    return {r: np.concatenate(x) for r, x in got.items()}, caches


@pytest.mark.parametrize("threshold", [1.0, 0.5])
@pytest.mark.parametrize("chunks", [(16, 16, 16, 2), (7, 16, 16, 11)])
def test_chunked_prefill_then_decode_equals_the_reference_on_logits(
        reference, chunks, threshold):
    """Two rows of different lengths in the same ticks: row 0 prefills
    `chunks` and then decodes; row 2 prefills 23 tokens and decodes beside
    it, so a tick runs tall tiles and short rows together through all nine
    planes. Row 1 is a free slot. Below threshold 1 the head reads the
    stream the exit rule selects."""
    module, sizes = reference
    spec = create_model("ouro-small-test", exit_threshold=threshold)
    params = jax.jit(spec.init)(jax.random.PRNGKey(3))
    n_prompt, n_new = sum(chunks), 6
    seqs = {0: _prompt(1, n_prompt + n_new), 2: _prompt(2, 23 + 12)}
    plans = {0: list(chunks) + [1] * n_new, 2: [16, 7] + [1] * 12}
    table = np.zeros((3, 8), np.int32)
    table[0], table[2] = np.arange(1, 9), np.arange(9, 17)
    got, _ = _serve_by_hand(spec, params, seqs, plans, table)
    for r, seq in seqs.items():
        want = module.forward(params, jnp.asarray(seq, jnp.int32),
                              _sizes(sizes, exit_threshold=threshold))
        np.testing.assert_allclose(got[r], want, atol=1e-4)


def test_every_plane_holds_the_reference_s_k_and_v_of_its_pass_and_layer(
        spec, params, reference):
    """After a served run, plane t * L + l of the row's blocks is what
    pass t of layer l made: the cache's layout tied to the model."""
    module, sizes = reference
    seq = _prompt(8, 16 + 16 + 9 + 5)
    table = np.zeros((2, 8), np.int32)
    table[1] = np.arange(1, 9)
    _, pool = _serve_by_hand(spec, params, {1: seq},
                             {1: [16, 16, 9] + [1] * 5}, table)
    _, (want_k, want_v) = module.body(params, jnp.asarray(seq, jnp.int32),
                                      _sizes(sizes), keep_kv=True)
    assert want_k.shape == (T, L, len(seq), 64)
    for held, want in ((pool.k, want_k), (pool.v, want_v)):
        # (planes, blocks of the row, bs, lanes) -> (T, L, tokens, lanes)
        got = np.asarray(held[:, table[1]]).reshape(T, L, -1, 64)
        np.testing.assert_allclose(got[:, :, :len(seq)], want, atol=1e-4)
        assert float(np.abs(got[:, :, len(seq):]).max()) == 0.0
        flat = got.reshape(T * L, -1, 64)[:, :len(seq)]
        for a in range(T * L):           # no two planes hold the same thing
            for b in range(a):
                assert np.abs(flat[a] - flat[b]).max() > 1e-3


# -- through the scheduler ---------------------------------------------------------

def test_the_mixed_tick_serves_it_and_counts_its_passes(spec, params,
                                                        reference):
    from tpu_engine.utils.tracing import SpanRecorder

    module, sizes = reference
    gen = ContinuousGenerator(spec, params=params, **LANE)
    gen.tracer, gen.trace_node = SpanRecorder(capacity=4096), "lane"
    prompts = [_prompt(5, 50), _prompt(6, 23), _prompt(7, 37)]
    try:
        assert gen._pool.cfg.n_layers == T * L
        assert gen._pool.caches.k.shape == (9, 4 * 8 + 1, BS, 64)
        assert gen._pool.bytes_per_block() == 9 * BS * 2 * 64 * 4
        futures = [gen.submit(p, max_new_tokens=12) for p in prompts]
        served = [f.result(timeout=300) for f in futures]
        stats = gen.stats()
    finally:
        gen.stop()
    for prompt, tokens in zip(prompts, served):
        want = module.forward(params,
                              jnp.asarray(prompt + tokens[:-1], jnp.int32),
                              _sizes(sizes))[len(prompt) - 1:]
        assert tokens == [int(t) for t in want.argmax(-1)]
    mixed, pool = stats["mixed"], stats["kv_pool"]
    assert (mixed["ut_steps"], mixed["kv_planes"]) == (T, T * L)
    assert mixed["kv_bytes_per_token"] == 9 * 2 * 64 * 4
    assert mixed["layer_passes"] == mixed["ticks"] * T * L > 0
    assert pool["blocks_free"] == pool["blocks_total"]
    spans = [s["attrs"] for s in gen.tracer.snapshot()
             if s["op"] == "mixed_step"]
    assert spans and all((s["ut_steps"], s["kv_planes"]) == (T, T * L)
                         for s in spans)
    assert any(s["width"] > 1 and s["decode_rows"] for s in spans)


def test_another_model_s_spans_and_stats_carry_no_pass(params):
    from tpu_engine.utils.tracing import SpanRecorder

    other = create_model("gpt2-small-test")
    gen = ContinuousGenerator(other, n_slots=2, dtype="float32",
                              kv_block_size=BS, prefill_chunk=16)
    gen.tracer, gen.trace_node = SpanRecorder(capacity=256), "lane"
    try:
        assert gen._pool.cfg is gen.cfg       # the model states no kinds
        gen.generate([_prompt(1, 20)], max_new_tokens=3)
        stats = gen.stats()
    finally:
        gen.stop()
    spans = [s["attrs"] for s in gen.tracer.snapshot()
             if s["op"] == "mixed_step"]
    assert spans
    for absent in ("ut_steps", "kv_planes", "layer_passes",
                   "kv_bytes_per_token"):
        assert absent not in stats["mixed"]
        assert all(absent not in s for s in spans)


def test_a_prefix_hit_gives_the_cold_run_s_tokens_and_saves_the_prefill(
        spec, params):
    """A block's nine planes depend on the tokens up to its end alone, so a
    radix hit is sound: the second request shares the first one's 32-token
    prefix, prefills its own tail only and decodes what a lane without
    sharing decodes."""
    shared = _prompt(9, 32)
    first, second = shared + _prompt(10, 7), shared + _prompt(11, 12)
    cold = ContinuousGenerator(spec, params=params, **LANE)
    try:
        want = cold.generate([second], max_new_tokens=10)[0]
    finally:
        cold.stop()
    warm = ContinuousGenerator(spec, params=params,
                               **{**LANE, "prefix_sharing": True})
    try:
        warm.generate([first], max_new_tokens=4)
        got = warm.generate([second], max_new_tokens=10)[0]
        pool = warm.stats()["kv_pool"]
    finally:
        warm.stop()
    assert got == want
    assert pool["radix_hits"] == 1 and pool["prefix_hit_tokens"] == 32


# -- one layer body -----------------------------------------------------------------

def _lowered(n_layers, ut_steps, width):
    spec = create_model("ouro-small-test", n_layers=n_layers,
                        ut_steps=ut_steps)
    cfg = spec.config
    params = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    (kind,) = cfg.kv_block_kinds
    one = jax.ShapeDtypeStruct((kind.n_layers, 9, BS, 64), jnp.float32)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    return jax.jit(
        lambda params, tokens, caches, tables, pos0, qlen:
        ouro_step_rows_ragged(params, tokens, caches, tables, pos0, qlen,
                              cfg, dtype=jnp.float32,
                              sample_slot=jnp.zeros_like(pos0),
                              max_tokens=width + 2)).lower(
        params, ints(2, width), KVCache(one, one), ints(2, 4), ints(2),
        ints(2)).as_text()


@pytest.mark.parametrize("width", [1, 16])
def test_the_lowered_step_holds_one_layer_body_whatever_l_and_t(width):
    """The count of product and scatter sites does not grow with the depth
    of the weights or with the passes: the layers are scanned inside a scan
    over the passes."""
    small, large = _lowered(2, 2, width), _lowered(5, 4, width)
    for site in ("dot_general", "scatter", "while"):
        n = len(re.findall(rf"stablehlo\.{site}\b", small))
        assert n == len(re.findall(rf"stablehlo\.{site}\b", large)), site
        assert n > 0


def test_a_threshold_of_one_compiles_no_gate_op():
    def products(threshold):
        s = create_model("ouro-small-test", exit_threshold=threshold)
        params = jax.eval_shape(s.init, jax.random.PRNGKey(0))
        text = jax.jit(lambda p, t: ouro_apply(
            p, t, s.config, dtype=jnp.float32)).lower(
            params, jax.ShapeDtypeStruct((1, 8), jnp.int32)).as_text()
        return len(re.findall(r"stablehlo\.dot_general\b", text))

    # The gate's d -> 1 product is traced below 1 and not at 1.
    assert products(0.5) == products(1.0) + 1


# -- what the lane refuses ------------------------------------------------------------

@pytest.mark.parametrize("kwargs, error, message", [
    ({"kv_block_size": 0, "kv_blocks": 64}, ValueError,
     r"set kv_block_size > 0 \(the dense per-slot cache has no read of a "
     "plane"),
    ({"kv_block_size": 0}, ValueError,
     "served by the mixed tick over the block pool only"),
    ({"kv_quantize": "int8"}, ValueError,
     "kv_quantize needs the 'kv_quantize' capability.*no int8 scales"),
    ({"kv_host_blocks": 8, "prefix_sharing": True}, ValueError,
     "kv_host_blocks needs the 'kv_host_tier' capability"),
    ({"spec_k": 2}, ValueError,
     "spec_k needs the 'spec_decode' capability.*no verify window"),
    ({"state_rows": 2}, ValueError,
     "state_rows applies to the state_slab family; model "
     "'ouro-small-test' serves the kv_looped family"),
    ({"tp": 2}, RuntimeError, "cannot serve tensor-parallel"),
])
def test_what_a_looped_lane_cannot_do_is_refused_at_start_up(
        spec, params, kwargs, error, message):
    with pytest.raises(error, match=message):
        ContinuousGenerator(spec, params=params, **{**LANE, **kwargs})


def test_the_chain_wire_format_is_refused_by_name(spec, params):
    gen = ContinuousGenerator(spec, params=params, **LANE)
    try:
        refusal = ("needs the 'migration' capability, which the "
                   "kv_looped family does not declare")
        assert refusal in gen.export_row("nobody")["reason"]
        assert refusal in gen.export_prefix([1] * 32)["reason"]
        with pytest.raises(ValueError, match=refusal):
            gen.submit_import({"prompt": [1], "emitted": [], "pos": 1,
                               "tok": 1, "max_new": 1, "chain": {}})
    finally:
        gen.stop()


def test_the_scheduler_names_neither_this_model_nor_a_pass_of_its_pool():
    import inspect

    from tpu_engine.runtime import kv_blocks, scheduler

    source = inspect.getsource(scheduler)
    for name in ("ouro_step_rows_ragged", "models.ouro import", "OuroConfig"):
        assert name not in source
    assert "self._windowed or self._hybrid" not in source
    assert "ut_steps" not in inspect.getsource(kv_blocks)
    assert "passes" not in inspect.getsource(kv_blocks.BlockPool.__init__)


_GEN_KW = dict(model="ouro-small-test", dtype="float32", batch_buckets=(1,),
               gen_max_batch_size=2, gen_kv_block_size=BS,
               gen_prefill_chunk=16, gen_prefix_sharing=False)


@pytest.mark.parametrize("role", ["prefill", "decode"])
def test_a_dedicated_role_is_refused_at_start_up(role):
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig

    with pytest.raises(RuntimeError,
                       match=f"--role {role} needs the 'handoff' "
                             f"capability.*kv_looped family"):
        WorkerNode(WorkerConfig(node_id="w", role=role, **_GEN_KW))


@pytest.mark.parametrize("flag, capability", [
    ("migrate_streams", "migration"), ("disagg", "handoff")])
def test_a_fleet_that_moves_streams_is_refused_at_start_up(flag, capability):
    from tpu_engine.serving.app import serve_combined
    from tpu_engine.utils.config import GatewayConfig, WorkerConfig

    with pytest.raises(RuntimeError,
                       match=f"needs the '{capability}' capability, which "
                             f"model 'ouro-small-test' \\(kv_looped"):
        serve_combined(model="ouro-small-test", lanes=1, port=0,
                       worker_config=WorkerConfig(**_GEN_KW),
                       gateway_config=GatewayConfig(port=0, **{flag: True}),
                       warmup=False, native_front=False)


def test_the_served_surface_generates_scores_and_infers(reference, params):
    """`serve --model ouro-small-test`: /generate and /generate/stream give
    the reference's greedy tokens, /score and /infer answer, and the
    start-up line states the passes and the planes."""
    import http.client

    from tpu_engine.serving.app import serve_combined
    from tpu_engine.utils.config import GatewayConfig, WorkerConfig

    module, sizes = reference
    gateway, workers, server = serve_combined(
        model="ouro-small-test", lanes=1, port=0,
        worker_config=WorkerConfig(**_GEN_KW),
        gateway_config=GatewayConfig(port=0), warmup=False,
        native_front=False)

    def post(path, body):
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=300)
        try:
            conn.request("POST", path, body=json.dumps(body),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    try:
        prompt = _prompt(12, 21)
        served_params = workers[0].engine.params
        status, data = post("/generate", {"request_id": "g",
                                          "prompt_tokens": prompt,
                                          "max_new_tokens": 8})
        assert status == 200, data
        tokens = [int(t) for t in json.loads(data)["tokens"]]
        want = module.forward(served_params,
                              jnp.asarray(prompt + tokens[:-1], jnp.int32),
                              _sizes(sizes))[len(prompt) - 1:]
        assert tokens == [int(t) for t in want.argmax(-1)]
        status, data = post("/generate/stream", {"request_id": "s",
                                                 "prompt_tokens": prompt,
                                                 "max_new_tokens": 8})
        assert status == 200 and b"data:" in data
        streamed = [int(t) for line in data.decode().splitlines()
                    if line.startswith("data:") and "tokens" in line
                    for t in json.loads(line[5:]).get("tokens", [])]
        assert streamed[:8] == tokens
        status, data = post("/score", {"request_id": "c",
                                       "prompt_tokens": prompt[:6],
                                       "completion_tokens": prompt[6:12]})
        assert status == 200, data
        status, data = post("/infer", {"request_id": "i",
                                       "input_data": [float(t) for t
                                                      in prompt[:16]]})
        assert status == 200, data
        assert len(json.loads(data)["output_data"]) == 256
    finally:
        for part in (server, *workers, gateway):
            part.stop()
