"""Unified stateless serving (PR 20): wire compatibility and pool fusion.

The batch lane (runtime.batch_processor) is now a compatibility shim:
stateless /infer and /score requests admit as single-tick rows in the
SAME continuous scheduler that serves decode streams — one scheduler,
one capacity pool, one set of counters. These tests pin the contract:

- /infer answers byte-identically before/after the fold (legacy lane
  via ``unified_stateless=False``), including the LRU result cache's
  reference-exact hit semantics (``inference_time_us == 50``).
- The defaults-off /health schema is UNCHANGED for stateless-family
  lanes: the scheduler's one-shot counters fold into the exact 4-key
  ``batch_processor`` block; no ``generator`` key appears.
- The new ``stateless`` scheduler counters are gated and additive on
  generative lanes (absent until a one-shot row actually dispatched;
  absent entirely with the fold disabled).
- Nonsense knob combos on a stateless-only model refuse LOUDLY
  (RuntimeError), never silently no-op.
"""

import threading

import pytest

from tpu_engine.serving.worker import WorkerNode
from tpu_engine.utils.config import WorkerConfig

HEALTH_KEYS = {"healthy", "node_id", "model", "total_requests",
               "cache_hits", "cache_size", "cache_hit_rate",
               "batch_processor"}
BP_KEYS = {"total_batches", "avg_batch_size", "timeout_batches",
           "full_batches"}


def make_mlp(node_id, unified=True, **kw):
    return WorkerNode(WorkerConfig(
        node_id=node_id, model="mlp", dtype="float32",
        batch_buckets=(1, 2, 4, 8), unified_stateless=unified, **kw))


@pytest.fixture(scope="module")
def unified_worker():
    w = make_mlp("uw1", unified=True)
    yield w
    w.stop()


@pytest.fixture(scope="module")
def legacy_worker():
    w = make_mlp("lw1", unified=False)
    yield w
    w.stop()


# -- wire identity: /infer before/after the fold -----------------------------

def test_infer_byte_identical_unified_vs_legacy(unified_worker,
                                                legacy_worker):
    payload = {"input_data": [1.0, 2.0, 3.0]}
    a = unified_worker.handle_infer(dict(payload, request_id="u1"))
    b = legacy_worker.handle_infer(dict(payload, request_id="l1"))
    assert set(a) == set(b) == {"request_id", "output_data", "node_id",
                                "cached", "inference_time_us"}
    assert a["output_data"] == b["output_data"]
    assert a["cached"] is b["cached"] is False


def test_cache_hit_semantics_unified(unified_worker):
    first = unified_worker.handle_infer(
        {"request_id": "c1", "input_data": [7.0, 7.0]})
    second = unified_worker.handle_infer(
        {"request_id": "c2", "input_data": [7.0, 7.0]})
    assert first["cached"] is False
    assert second["cached"] is True
    assert second["inference_time_us"] == 50  # reference worker_node.cpp:65
    assert second["output_data"] == first["output_data"]


def test_batch_identity_concurrent_infer(unified_worker):
    """Concurrent distinct inputs co-batch into grouped one-shot
    dispatches; every row completes, and the LRU cache replays each
    row's grouped-dispatch output verbatim on the next hit."""
    outs = {}

    def fire(i):
        outs[i] = unified_worker.handle_infer(
            {"request_id": f"b{i}", "input_data": [float(i) + 0.5, 2.0]})

    ts = [threading.Thread(target=fire, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for i in range(4):
        assert outs[i]["cached"] is False
        replay = unified_worker.handle_infer(
            {"request_id": f"r{i}", "input_data": [float(i) + 0.5, 2.0]})
        assert replay["cached"] is True
        assert replay["output_data"] == outs[i]["output_data"]


# -- /health schema: unchanged for stateless-family lanes --------------------

def test_health_schema_exact_unified(unified_worker):
    unified_worker.handle_infer({"request_id": "h1", "input_data": [5.0]})
    h = unified_worker.get_health()
    assert set(h) == HEALTH_KEYS
    assert set(h["batch_processor"]) == BP_KEYS
    assert h["batch_processor"]["total_batches"] >= 1
    assert h["batch_processor"]["avg_batch_size"] >= 1.0


def test_health_schema_matches_legacy(unified_worker, legacy_worker):
    hu = unified_worker.get_health()
    hl = legacy_worker.get_health()
    assert set(hu) == set(hl)
    assert set(hu["batch_processor"]) == set(hl["batch_processor"])


# -- knob fences: loud refusals on a stateless-only model --------------------

def test_spec_k_fenced_on_stateless_model():
    with pytest.raises(RuntimeError, match="spec-k"):
        make_mlp("f1", gen_continuous_spec_k=4)


def test_kv_quantize_fenced_on_stateless_model():
    with pytest.raises(RuntimeError, match="KV cache"):
        make_mlp("f2", gen_kv_quantize="int8")


def test_kv_blocks_fenced_on_stateless_model():
    with pytest.raises(RuntimeError, match="KV cache"):
        make_mlp("f3", gen_kv_block_size=16, gen_kv_blocks=64)


def test_mixed_step_fenced_on_stateless_model():
    with pytest.raises(RuntimeError, match="mixed stepping"):
        make_mlp("f4", gen_mixed_step=True)


# -- scheduler one-shot surface (smoke per new seam) -------------------------

def test_submit_infer_requires_engine(unified_worker):
    """A generator built WITHOUT an infer_engine refuses submit_infer
    loudly instead of wedging a future."""
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    gen = unified_worker.generator
    assert getattr(gen, "accepts_oneshot", False)
    assert isinstance(gen, ContinuousGenerator)
    # The stateless-family lane has no score provider: fenced.
    with pytest.raises(RuntimeError, match="score_provider"):
        gen.submit_score([1, 2], [3])


def test_oneshot_counters_balance(unified_worker):
    unified_worker.handle_infer({"request_id": "cb1",
                                 "input_data": [3.0, 1.0, 4.0]})
    st = unified_worker.generator.stats()["stateless"]
    assert st["admitted"] == st["completed"] + st["failed"]
    assert st["ticks"] == st["dispatches"] >= 1


def test_stateless_block_gated_off_legacy(legacy_worker):
    """With the fold disabled the worker serves /infer through the shim
    and exposes NO scheduler stateless block anywhere."""
    legacy_worker.handle_infer({"request_id": "g1", "input_data": [2.0]})
    gen = getattr(legacy_worker, "generator", None)
    if gen is not None and hasattr(gen, "stats"):
        assert "stateless" not in gen.stats()


# -- generative lane colocation (heavier e2e) --------------------------------

@pytest.mark.slow
def test_score_unified_byte_identical_and_gated():
    """On a generative lane, unified /score answers byte-identically to
    the legacy score batcher, and the stateless counter block appears
    (additive) only on the unified worker."""
    def build(nid, unified):
        return WorkerNode(WorkerConfig(
            node_id=nid, model="gpt2-small-test", dtype="float32",
            max_batch_size=4, unified_stateless=unified))

    req = {"request_id": "sc", "prompt_tokens": [1, 2, 3],
           "completion_tokens": [4, 5, 6]}
    w = build("gu1", True)
    try:
        got = w.handle_score(dict(req))
        st = w.generator.stats()
        assert st["stateless"]["score_rows"] == 1
        assert st["stateless"]["ticks"] == st["stateless"]["dispatches"]
    finally:
        w.stop()
    w2 = build("gl1", False)
    try:
        want = w2.handle_score(dict(req))
        assert "stateless" not in w2.generator.stats()
    finally:
        w2.stop()
    assert got["logprobs"] == want["logprobs"]
    assert got["total_logprob"] == want["total_logprob"]


@pytest.mark.slow
def test_concurrent_generate_and_score_one_pool():
    """Mixed workload on ONE scheduler: a decode stream and co-pending
    scores share the pool; scores group into single-tick dispatches and
    every counter retires (ticks == dispatches with stateless rows)."""
    w = WorkerNode(WorkerConfig(node_id="gm1", model="gpt2-small-test",
                                dtype="float32", max_batch_size=4))
    results = {}
    try:
        def gen():
            results["g"] = w.handle_generate(
                {"request_id": "g", "prompt_tokens": [1, 2, 3, 4],
                 "max_new_tokens": 8})

        def score(i):
            results[f"s{i}"] = w.handle_score(
                {"request_id": f"s{i}",
                 "prompt_tokens": [i + 1, i + 2, i + 3],
                 "completion_tokens": [i + 4, i + 5]})

        ts = ([threading.Thread(target=gen)]
              + [threading.Thread(target=score, args=(i,))
                 for i in range(3)])
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        st = w.generator.stats()["stateless"]
        assert st["failed"] == 0
        assert st["admitted"] == st["completed"] == 3
        assert st["score_rows"] == 3
        assert st["ticks"] == st["dispatches"]
        assert len(results["g"]["tokens"]) == 8
    finally:
        w.stop()
