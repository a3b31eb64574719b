"""Mixed prefill+decode stepping (runtime.scheduler, every lane with a
pool or a slab):
one ragged dispatch per tick serving decode rows and prefill chunks
together.

Contracts under test:
- seeded output streams are identical to the dense cache's —
  greedy AND temperature sampling, short and chunk-crossing prompts,
  radix-shared prefixes, controls (penalty/stop lists).
- token budget: a long prompt's admission cannot stall live decode rows
  — they emit every tick while the prefill spans ceil(L/chunk) ticks.
- the ragged Pallas kernel (interpreter here) matches the XLA gather
  reference at q_len 1 / 7 / block_size / block_size+1 in one batch.
- deadline-cancelled rows mid-prefill return every block.
- one dispatch per tick, counted at separate sites, stays equal.
- serving integration: worker wiring, tpu_engine_mixed_* and
  TTFT/ITL histograms at /metrics, mixed_step spans in the trace ring.

Kept lean per the tier-1 budget: the dense oracle is a module fixture,
prompts are short, and every mixed test shares one compiled scheduler
(chunk widths 1 and 16 only).
"""

import queue as _queue
import threading
import time

import jax
import pytest

from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.runtime.scheduler import ContinuousGenerator
from tpu_engine.utils.deadline import Deadline, DeadlineExceeded

_ensure_builtin_models_imported()


@pytest.fixture(scope="module")
def spec():
    return create_model("gpt2-small-test", max_seq=128)


@pytest.fixture(scope="module")
def params(spec):
    return spec.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def dense(spec, params):
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=4, step_chunk=4, max_seq=128)
    yield s
    s.stop()


@pytest.fixture(scope="module")
def mixed(spec, params):
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=4, step_chunk=4, max_seq=128,
                            kv_block_size=16, prefill_chunk=16,
                            mixed_token_budget=16)
    yield s
    s.stop()


def test_mixed_requires_paged(spec, params):
    with pytest.raises(ValueError, match="mixed_step requires"):
        ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=2, mixed_step=True)


@pytest.mark.parametrize("model, lane", [
    ("gpt2-small-test", dict(kv_block_size=16)),
    ("gpt2-small-test", dict(kv_block_size=16, mixed_step=False)),
    ("ssd-small-test", dict()),
], ids=["pool", "pool-argument-false", "slab"])
def test_a_lane_with_a_pool_steps_by_the_ragged_tick(model, lane):
    """What the lane holds decides, the `mixed_step` argument nothing: a
    block pool and a state slab step by the ragged tick (one dispatch a
    tick, no chunk of the dense loop), asked or not."""
    spec = create_model(model)
    gen = ContinuousGenerator(spec, params=spec.init(jax.random.PRNGKey(0)),
                              dtype="float32", n_slots=2, step_chunk=4,
                              prefill_chunk=16, **lane)
    try:
        assert len(gen.generate([[5, 9, 3]], max_new_tokens=6)[0]) == 6
        st = gen.stats()
        assert st["mixed"]["ticks"] == st["mixed"]["dispatches"] >= 6
        assert st["mixed"]["decode_tokens"] == 5 and st["chunks"] == 0
    finally:
        gen.stop()


def test_greedy_matches_dense_and_paged(dense, mixed):
    prompt = [5, 9, 3]
    d = dense.generate([prompt], max_new_tokens=6)[0]
    assert mixed.generate([prompt], max_new_tokens=6)[0] == d


def test_seeded_sampling_matches_dense(dense, mixed):
    for seed, temp, top_p, top_k in ((7, 0.8, 1.0, 0), (3, 0.7, 0.9, 5)):
        kw = dict(max_new_tokens=8, temperature=temp, seed=seed,
                  top_p=top_p, top_k=top_k)
        assert (mixed.generate([[5, 9, 3, 2]], **kw)[0]
                == dense.generate([[5, 9, 3, 2]], **kw)[0]), (seed, temp)


def test_long_prompt_and_shared_prefix_match_dense(dense, mixed):
    """Chunk-crossing prompts, then a radix-shared pair (mid-prompt
    resume inside the ragged ticks) and a whole-prompt repeat (the COW
    path: the resumed window's block is shared until copied)."""
    lp = [(i * 7) % 90 + 1 for i in range(40)]
    assert (mixed.generate([lp], max_new_tokens=5)[0]
            == dense.generate([lp], max_new_tokens=5)[0])
    shared = [(i * 11) % 90 + 1 for i in range(32)]
    p1, p2 = shared + [91, 92, 93], shared + [81, 82]
    before = mixed.stats()["kv_pool"]["prefix_hit_tokens"]
    a = mixed.generate([p1], max_new_tokens=5)[0]
    b = mixed.generate([p2], max_new_tokens=5)[0]
    assert a == dense.generate([p1], max_new_tokens=5)[0]
    assert b == dense.generate([p2], max_new_tokens=5)[0]
    # The second admission mapped the shared 32-token prefix onto the
    # first's blocks and resumed its prefill mid-prompt.
    assert mixed.stats()["kv_pool"]["prefix_hit_tokens"] >= before + 32
    # Whole-prompt repeat: exact match -> COW the recomputed last block.
    wp = [(i * 5) % 90 + 1 for i in range(32)]
    c1 = mixed.generate([wp], max_new_tokens=4)[0]
    assert mixed.generate([wp], max_new_tokens=4)[0] == c1
    assert c1 == dense.generate([wp], max_new_tokens=4)[0]


def test_controls_match_dense(dense, mixed):
    kw = dict(max_new_tokens=6, repetition_penalty=1.3, seed=5,
              temperature=0.9)
    assert (mixed.generate([[5, 9, 3]], **kw)[0]
            == dense.generate([[5, 9, 3]], **kw)[0])
    kw = dict(max_new_tokens=6, stop_tokens=[89])
    assert (mixed.generate([[5, 9, 3]], **kw)[0]
            == dense.generate([[5, 9, 3]], **kw)[0])


def test_token_budget_no_decode_starvation(mixed):
    """A long prompt's admission must not stall a live decode row: at
    budget 16 a 60-token prefill spans >= 4 ticks, and the decode row
    emits a token EVERY tick — so it collects several tokens before the
    long request's first, and co-scheduled ticks are observed."""
    qa, qb = _queue.Queue(), _queue.Queue()
    ta, tb = [], []

    def consume(q, acc):
        while True:
            item = q.get()
            if item is None:
                return
            acc.append((time.perf_counter(), list(item)))

    th_a = threading.Thread(target=consume, args=(qa, ta), daemon=True)
    th_b = threading.Thread(target=consume, args=(qb, tb), daemon=True)
    th_a.start()
    th_b.start()
    cos0 = mixed.stats()["mixed"]["coscheduled_ticks"]
    fa = mixed.submit([5, 9, 3], max_new_tokens=40, stream=qa)
    while not ta:  # the decode row is live before the long prompt lands
        time.sleep(0.002)
    fb = mixed.submit([(i * 13) % 90 + 1 for i in range(60)],
                      max_new_tokens=3, stream=qb)
    fa.result(60)
    fb.result(60)
    th_a.join(5)
    th_b.join(5)
    b_first = tb[0][0]
    a_before = sum(len(toks) for t, toks in ta if t <= b_first)
    assert a_before >= 3, (a_before, len(ta))
    m = mixed.stats()["mixed"]
    assert m["coscheduled_ticks"] > cos0
    assert m["dispatches"] == m["ticks"]  # counted at separate sites


def test_ragged_kernel_parity():
    from tpu_engine.ops.paged_attention import ragged_parity_check

    # q_len 1 (decode), 7 (partial chunk), block_size, block_size+1
    # (chunk crossing a block boundary) — one ragged batch. bf16 and GQA
    # variants run in diagnostics --mixed-parity and the on-chip
    # campaign's `mixed` stage (tier-1 budget keeps this to one compile).
    assert ragged_parity_check(q_lens=(1, 7, 16, 17)) < 2e-5


@pytest.mark.parametrize("case", [
    "dead-row-between-live-rows", "decode-row-in-wide-tick",
    "ends-on-group-boundary", "ends-on-group-boundary-width-1",
    "table-four-times-wider", "chunk-tiles-straddle-a-group"])
def test_ragged_kernel_walks_each_tiles_own_context(case):
    """The ragged read over the workloads the tile walk can get wrong
    (`ops.paged_attention.WALK_CASES`): a dead row between live ones, a
    decode row inside a wide tick, contexts on and around a group
    boundary, a table four times wider than its longest row, a chunk
    whose tiles straddle a group at G = 4."""
    from tpu_engine.ops.paged_attention import walk_parity_check

    assert walk_parity_check("ragged", case, interpret=True) < 2e-5


def test_cancelled_mid_prefill_returns_blocks(spec, params, mixed):
    """Deadline-expired rows — queued or mid-prefill-chunk — return
    every block; the scheduler keeps serving identical streams after."""
    want = mixed.generate([[5, 9, 3]], max_new_tokens=4)[0]  # warm+oracle
    futs = [mixed.submit([(i * 17 + j) % 90 + 1 for j in range(60)],
                         max_new_tokens=30, deadline=Deadline.after_ms(25))
            for i in range(4)]
    expired = 0
    for f in futs:
        try:
            f.result(60)
        except DeadlineExceeded:
            expired += 1
    deadline = time.time() + 10
    while time.time() < deadline:
        st = mixed.stats()
        pool = st["kv_pool"]
        if (st["active"] == 0 and pool["blocks_free"]
                + pool["radix_nodes"] >= pool["blocks_total"]):
            break
        time.sleep(0.05)
    st = mixed.stats()
    pool = st["kv_pool"]
    assert st["active"] == 0
    assert pool["blocks_free"] + pool["radix_nodes"] \
        >= pool["blocks_total"], pool
    # A later request never sees a cancelled row's ghost.
    assert mixed.generate([[5, 9, 3]], max_new_tokens=4)[0] == want


def test_worker_mixed_serving_and_observability(spec, params):
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig
    from tpu_engine.utils.metrics import render_prometheus

    engine = InferenceEngine(spec, params=params, dtype="float32",
                             batch_buckets=(1, 2))
    w = WorkerNode(WorkerConfig(node_id="mx1", model="gpt2-small-test",
                                dtype="float32",
                                gen_scheduler="continuous",
                                gen_max_batch_size=4,
                                gen_kv_block_size=16,
                                gen_prefill_chunk=16,
                                gen_mixed_token_budget=16),
                   engine=engine)
    try:
        out = w.handle_generate({"request_id": "r1",
                                 "prompt_tokens": [5, 9, 3],
                                 "max_new_tokens": 4})
        assert len(out["tokens"]) == 4
        health = w.get_health()
        m = health["generator"]["mixed"]
        assert m["ticks"] == m["dispatches"] > 0
        body = render_prometheus(
            [health], recorders={w.node_id: w.tracer},
            named_hists=w.latency_histograms()).decode()
        for key in ("tpu_engine_mixed_ticks_total",
                    "tpu_engine_mixed_dispatches_total",
                    "tpu_engine_ttft_seconds_bucket",
                    "tpu_engine_itl_seconds_count"):
            assert key in body, key
        ops = {s["op"] for s in w.tracer.snapshot()}
        assert "mixed_step" in ops and "radix_lookup" in ops
    finally:
        w.stop()
