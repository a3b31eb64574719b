"""Observability plane: SLO burn-rate accounting, the per-tick flight
recorder, cross-lane trace stitching, and the gateway stream ledger.

DESIGN.md "Observability plane": every surface here is additive and
defaults OFF — no objective configured means no SloTracker (and no
/stats "slo" block), no ``--trace-stitch`` means no ledger and no
traceparent injection, no ``--flight-recorder`` means zero per-tick
work and no "flight" stats block. The integration test at the bottom
drives ONE stream through the full mobility gauntlet (disagg handoff →
migrate-mode drain → injected lane fault → replay resume) and asserts
the stitched tree covers every lane with zero orphans and counters
that agree with the hop marker spans.
"""

import json
import os
import threading
import time

import jax.numpy as jnp
import pytest

from tpu_engine.models.transformer import TransformerConfig
from tpu_engine.runtime.kv_blocks import BlockPool
from tpu_engine.serving.gateway import Gateway, _StreamLedger, _parse_sse
from tpu_engine.serving.resilience import HandoffCounters, MigrationCounters
from tpu_engine.serving.slo import SloTracker, violations_over
from tpu_engine.serving.worker import WorkerNode
from tpu_engine.utils.config import GatewayConfig, WorkerConfig
from tpu_engine.utils.tracing import (derive_trace_id, spans_to_chrome,
                                      stitch_trace)


# -- SLO burn-rate math -------------------------------------------------------

def test_violations_over_bucket_math():
    """Violations = samples above the largest bucket boundary ≤ the
    threshold; the effective threshold reported is that boundary
    (quantization explicit, never silent)."""
    snap = {"le": [0.01, 0.1, 1.0], "cumulative": [2, 5, 9], "count": 10}
    assert violations_over(snap, 0.1) == (5, 0.1)
    assert violations_over(snap, 0.5) == (5, 0.1)   # rounds DOWN to 0.1
    assert violations_over(snap, 1.0) == (1, 1.0)
    assert violations_over(snap, 0.005) == (10, 0.0)  # below first bucket


class _Hist:
    """Stand-in histogram: anything with snapshot() works."""

    def __init__(self, snap):
        self.snap = snap

    def snapshot(self):
        return dict(self.snap)


def test_slo_tracker_windowed_burn_rate():
    t = SloTracker({"ttft": 100.0}, target=0.9, window_s=300.0)
    h = _Hist({"le": [0.05, 0.1], "cumulative": [10, 10], "count": 10})
    st = t.status({"ttft": [h]})
    assert st["target"] == 0.9
    assert abs(st["error_budget"] - 0.1) < 1e-9
    obj = st["objectives"]["ttft"]
    assert obj["objective_ms"] == 100.0
    assert obj["effective_threshold_ms"] == 100.0
    assert obj["violations"] == 0 and obj["burn_rate"] == 0.0
    # 10 new samples, every one above the objective: the whole window
    # delta violates, so burn = 1.0 / budget = 10x.
    h.snap = {"le": [0.05, 0.1], "cumulative": [10, 10], "count": 20}
    obj = t.status({"ttft": [h]})["objectives"]["ttft"]
    assert obj["violations"] == 10
    assert obj["window_samples"] == 10 and obj["window_violations"] == 10
    assert obj["burn_rate"] == pytest.approx(10.0)
    assert obj["good_fraction"] == pytest.approx(0.5)


def test_slo_from_config_defaults_off():
    assert SloTracker.from_config(GatewayConfig()) is None
    t = SloTracker.from_config(GatewayConfig(slo_ttft_p99_ms=100.0))
    assert set(t.objectives) == {"ttft"}
    assert t.objectives["ttft"] == pytest.approx(0.1)  # ms -> seconds
    assert t.target == 0.99 and t.window_s == 300.0


def test_slo_pressure_mapping():
    assert SloTracker.pressure({}) == 0.0
    status = {"objectives": {
        "ttft": {"burn_rate": 1.0, "window_samples": 5},
        "itl": {"burn_rate": 9.0, "window_samples": 0},  # empty: ignored
    }}
    assert SloTracker.pressure(status) == pytest.approx(0.5)
    status["objectives"]["ttft"]["burn_rate"] = 5.0
    assert SloTracker.pressure(status) == 1.0  # saturates at burn 2.0


# -- stream ledger ------------------------------------------------------------

def test_stream_ledger_hops_fifo_and_isolation():
    led = _StreamLedger(capacity=2)
    led.hop("a", "w0", "admit", "tid-a")
    led.hop("a", "w1", "migrate")
    led.hop("b", "w0", "admit", "tid-b")
    led.hop("c", "w2", "admit", "tid-c")   # capacity 2: evicts "a"
    assert led.get("a") is None
    ent = led.get("b")
    assert ent["trace_id"] == "tid-b"
    ent["hops"].append({"lane": "x"})      # copies, not live state
    assert len(led.get("b")["hops"]) == 1
    assert led.summary() == {"streams": 2, "capacity": 2, "hops": 2}


def test_stream_ledger_trace_id_backfill():
    led = _StreamLedger()
    led.hop("r", "w0", "admit", None)
    led.hop("r", "w1", "handoff", "tid-late")
    ent = led.get("r")
    assert ent["trace_id"] == "tid-late"
    assert [h["kind"] for h in ent["hops"]] == ["admit", "handoff"]


# -- trace stitching + orphan repair ------------------------------------------

def _span(rid, op, sid, parent=None, ts=100.0, trace=None, **attrs):
    s = {"request_id": rid, "op": op, "node": "n", "duration_us": 10,
         "cached": False, "batch_size": 1, "ts": ts, "start_ts": ts,
         "span_id": sid, "trace_id": trace or derive_trace_id(rid)}
    if parent is not None:
        s["parent_id"] = parent
    if attrs:
        s["attrs"] = attrs
    return s


def test_synthesized_evicted_roots_repair_dangling_parents():
    """Ring eviction can drop a parent while its children survive: the
    chrome export must synthesize ONE labeled root per dangling parent
    id (anchored at the earliest child) so the tree stays connected."""
    spans = [_span("r1", "prefill", "s1", parent="gone", ts=105.0),
             _span("r1", "decode", "s2", parent="gone", ts=101.0),
             _span("r1", "queue_wait", "s3", parent="s2", ts=102.0)]
    events = spans_to_chrome({"w0": spans})["traceEvents"]
    roots = [e for e in events if e["name"] == "evicted_parent"]
    assert len(roots) == 1
    assert roots[0]["args"]["span_id"] == "gone"
    assert roots[0]["ts"] == pytest.approx(101.0 * 1e6)  # earliest child
    # A connected tree synthesizes nothing.
    ok = [_span("r1", "root", "s1"),
          _span("r1", "decode", "s2", parent="s1")]
    events = spans_to_chrome({"w0": ok})["traceEvents"]
    assert not [e for e in events if e["name"] == "evicted_parent"]


def test_stitch_trace_merges_lanes_and_counts_orphans():
    rid = "req-7"
    tid = derive_trace_id(rid)
    frags = {
        "w0": [_span(rid, "route", "a1"),
               _span(rid, "prefill", "a2", parent="a1", ts=101.0)],
        # Matched by trace_id alone (the hop-marker case).
        "w1": [_span("other", "kv_import", "b1", parent="a1",
                     ts=102.0, trace=tid)],
        "w2": [_span("unrelated", "decode", "c1", ts=103.0,
                     trace="ffff00000000000000000000000000ff")],
    }
    out = stitch_trace(frags, rid)
    assert out["trace_id"] == tid
    assert out["lanes"] == ["w0", "w1"]    # w2 contributed nothing
    assert [s["span_id"] for s in out["spans"]] == ["a1", "a2", "b1"]
    assert out["orphans"] == 0
    assert out["chrome"]["traceEvents"]
    # Drop the root: both children orphan (counted BEFORE repair), and
    # the chrome rendering still connects them via the synthetic root.
    frags["w0"] = frags["w0"][1:]
    out = stitch_trace(frags, rid)
    assert out["orphans"] == 2
    assert [e for e in out["chrome"]["traceEvents"]
            if e["name"] == "evicted_parent"]


def test_export_chain_trace_key_gated():
    """The chain wire dict gains a "trace" key ONLY when the exporter
    passes trace context — default exports stay byte-identical."""
    cfg = TransformerConfig(vocab=97, d_model=32, n_layers=2, n_heads=2,
                            d_ff=64, max_seq=64)
    pool = BlockPool(cfg, 8, 4, jnp.bfloat16)
    with pool.lock:
        ids = pool.alloc(2)
        chain = pool.export_chain(ids)
        traced = pool.export_chain(ids, trace={"traceparent": "00-ab-cd-01"})
    assert "trace" not in chain
    assert traced["trace"] == {"traceparent": "00-ab-cd-01"}
    assert {k: v for k, v in traced.items() if k != "trace"} == chain


def test_gateway_defaults_off_no_observability_keys():
    gw = Gateway([], GatewayConfig())
    try:
        st = gw.get_stats()
        assert "slo" not in st and "trace_ledger" not in st
        assert gw._ledger is None
        assert gw.slo_status() is None
        assert gw.slo_pressure() == 0.0
    finally:
        gw.stop()
    gw = Gateway([], GatewayConfig(trace_stitch=True,
                                   slo_completion_p99_ms=500.0))
    try:
        st = gw.get_stats()
        assert st["trace_ledger"]["streams"] == 0
        assert set(st["slo"]["objectives"]) == {"completion"}
    finally:
        gw.stop()


# -- real-model fleet: flight recorder + the twice-moved stream ---------------

GEN_KW = dict(model="gpt2-small-test", dtype="float32",
              gen_scheduler="continuous", gen_step_chunk=2,
              gen_kv_block_size=16, gen_kv_blocks=40,
              gen_prefill_chunk=16, gen_max_batch_size=4)

PROMPT = [5, 9, 3, 17, 4, 22, 8]


@pytest.fixture(scope="module")
def dump_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("flight"))


@pytest.fixture(scope="module")
def fleet(dump_dir):
    """1 prefill + 3 decode lanes, one parameter set, every lane with
    stitching and the flight recorder armed (stream mobility can land a
    row on ANY lane — migration does not respect disagg roles)."""
    roles = ("prefill", "decode", "decode", "decode")
    workers = []
    for i, r in enumerate(roles):
        kw = dict(GEN_KW, trace_stitch=True, flight_recorder=64,
                  flight_dump_dir=dump_dir)
        workers.append(WorkerNode(WorkerConfig(node_id=f"w{i}", role=r,
                                               **kw)))
    p0 = workers[0].engine.params
    for w in workers[1:]:
        w.apply_weights(p0)
    yield workers
    for w in workers:
        w.stop()


@pytest.fixture(autouse=True)
def _heal_fleet(request):
    yield
    if "fleet" in request.fixturenames:
        for w in request.getfixturevalue("fleet"):
            w.heal()
            w.undrain()


def _wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


def pool_leak_free(worker) -> bool:
    st = worker.generator.stats()
    kp = st["kv_pool"]
    return (st["active"] == 0
            and kp["blocks_free"] + kp["radix_nodes"] >= kp["blocks_total"])


def test_flight_recorder_ring_and_stats_gating(fleet):
    decode = fleet[1]
    decode.handle_generate({"request_id": "fr1", "prompt_tokens": PROMPT,
                            "max_new_tokens": 6})
    tl = decode.generator.flight_timeline()
    assert tl["enabled"] is True and tl["capacity"] == 64
    assert tl["ticks"] >= 1
    rec = tl["timeline"][-1]
    for key in ("ts", "tick_wall_ms", "active", "held", "queued", "ready",
                "chunks", "admitted", "completed", "pool"):
        assert key in rec, rec
    assert "flight" in decode.generator.stats()
    # An unarmed lane (the default): no stats block, dump a safe no-op.
    plain = WorkerNode(WorkerConfig(node_id="off0", **GEN_KW))
    try:
        assert "flight" not in plain.generator.stats()
        assert plain.generator.flight_dump("probe") is None
        assert plain.generator.flight_timeline()["enabled"] is False
    finally:
        plain.stop()


def test_flight_dump_forced_names_anomaly(fleet, dump_dir):
    gen = fleet[2].generator
    fleet[2].handle_generate({"request_id": "fd1", "prompt_tokens": PROMPT,
                              "max_new_tokens": 4})
    before = gen.flight_timeline()["dumps"]
    last = gen.flight_dump("operator_probe")
    assert last["anomaly"] == "operator_probe" and last["ticks"] >= 1
    assert last["path"] and os.path.basename(last["path"]).startswith(
        "flight_w2_")
    assert "operator_probe" in last["path"]
    with open(last["path"]) as f:
        dump = json.load(f)
    assert dump["anomaly"] == "operator_probe"
    assert dump["node"] == "w2" and len(dump["timeline"]) == last["ticks"]
    tl = gen.flight_timeline()
    assert tl["dumps"] == before + 1 and tl["last_dump"] == last


def test_twice_moved_stream_stitches_with_zero_orphans(fleet):
    """Satellite (c): ONE stream through disagg handoff → migrate-mode
    drain → injected decode fault → replay resume. Byte-identical to an
    unmoved control; the ledger's hop kinds match the mobility counters;
    the stitched tree covers every serving lane (the DRAINED lane via
    the retired-client stash) with zero orphans; the faulted lane's
    flight recorder auto-dumps a recover postmortem."""
    gw = Gateway(list(fleet), GatewayConfig(
        disagg=True, handoff_timeout_s=20.0, failover_streams=True,
        migrate_streams=True, migrate_timeout_s=20.0, trace_stitch=True))
    armed_gen, armed_real = [None], [None]
    try:
        control = fleet[1].handle_generate(
            {"request_id": "tmctl", "prompt_tokens": PROMPT,
             "max_new_tokens": 48})["tokens"]
        rid = "tm0"
        req = {"request_id": rid, "prompt_tokens": PROMPT,
               "max_new_tokens": 48}
        toks, final = [], [None]
        got_tokens = threading.Event()

        def consume():
            for frame in gw.route_generate_stream(dict(req)):
                evt = _parse_sse(frame)
                if evt is None:
                    continue
                if evt.get("done"):
                    final[0] = evt
                    break
                if "tokens" in evt:
                    toks.extend(evt["tokens"])
                    if len(toks) >= 2:
                        got_tokens.set()

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        assert got_tokens.wait(120), "stream never produced tokens"
        # Move 1: the disagg prefill→decode handoff must have spliced
        # before decode tokens flow.
        assert _wait(lambda: gw.get_stats().get(
            "handoff", {}).get("handoffs_spliced", 0) >= 1, 60)
        rec = gw._streams[rid]
        lane1 = rec.lane
        assert lane1 and gw._roles.get(lane1) == "decode"
        # Move 2: migrate-mode drain of the serving decode lane.
        gw.remove_worker(lane1, drain=True)
        assert _wait(lambda: gw.get_stats().get("migration", {}).get(
            "streams_migrated", 0) >= 1 and rec.lane != lane1, 90), \
            "migration never landed"
        lane2 = rec.lane
        # Move 3: one-shot device fault on the migration destination →
        # retryable terminal → gateway journal resume elsewhere.
        gen = next(w for w in fleet
                   if w.config.node_id == lane2).generator
        # An earlier test may have force-dumped this lane inside the
        # recover dump's 10 s rate-limit window; clear the stamp so the
        # anomaly dump below is observable.
        gen._flight_last_dump_ts = 0.0
        real = gen._mixed_step_exe

        def failing(width, controls):
            gen._mixed_step_exe = real
            armed_gen[0] = None

            def exe(*a, **k):
                raise RuntimeError("injected device failure")
            return exe

        armed_gen[0], armed_real[0] = gen, real
        gen._mixed_step_exe = failing
        t.join(timeout=180)
        assert final[0] is not None, "stream never terminated"
        assert "error" not in final[0], final[0]
        assert toks == control and final[0]["tokens"] == control
        assert final[0].get("resumed") == 1

        st = gw.get_stats()
        assert st["failover"]["resumes_succeeded"] == 1
        assert st["migration"]["streams_migrated"] >= 1
        # Ledger hop kinds agree with the mobility counters.
        entry = gw._ledger.get(rid)
        kinds = [h["kind"] for h in entry["hops"]]
        assert kinds[0] == "admit" and kinds.count("admit") == 1
        assert kinds.count("handoff") >= 1
        assert kinds.count("migrate") >= 1
        assert kinds.count("resume") == st["failover"]["resumes_attempted"]
        # Counters == spans (handoff / migration / resume families).
        spans = gw.tracer.snapshot()
        ho = st["handoff"]
        assert len([s for s in spans if s["op"] == "kv_handoff"]) == sum(
            ho[f] for f in HandoffCounters.SPAN_FIELDS)
        mig = st["migration"]
        assert len([s for s in spans if s["op"] == "migration"]) == sum(
            mig[f] for f in MigrationCounters.SPAN_FIELDS)
        assert len([s for s in spans if s["op"] == "resume"]) \
            == st["failover"]["resumes_attempted"]
        # The stitched tree: every hop lane contributes — the drained
        # lane1 is no longer a ring member and is reached through the
        # retired-client stash — and the tree has ZERO orphans.
        stitched = gw.stitched_trace(rid)
        hop_lanes = {h["lane"] for h in entry["hops"]}
        assert "gateway" in stitched["lanes"]
        assert lane1 in stitched["lanes"]
        assert hop_lanes <= set(stitched["lanes"]), (
            hop_lanes, stitched["lanes"])
        assert stitched["orphans"] == 0, [
            (s["lane"], s["op"], s.get("parent_id"))
            for s in stitched["spans"]]
        assert stitched["hops"] == entry["hops"]
        # The faulted lane's recorder auto-dumped the recover anomaly,
        # and the gateway force-dumped the RESUME lane's black box named
        # for the failover event.
        last = gen.flight_timeline()["last_dump"]
        assert last is not None and last["anomaly"].startswith("recover:")
        resume_lane = next(h["lane"] for h in reversed(entry["hops"])
                           if h["kind"] == "resume")
        resume_gen = next(w for w in fleet
                          if w.config.node_id == resume_lane).generator
        rlast = resume_gen.flight_timeline()["last_dump"]
        assert rlast is not None
        assert rlast["anomaly"] == f"failover_resume:{rid}"
        assert _wait(lambda: all(pool_leak_free(w) for w in fleet), 30)
    finally:
        if armed_gen[0] is not None:       # fault never fired: disarm
            armed_gen[0]._mixed_step_exe = armed_real[0]
        gw.stop()
