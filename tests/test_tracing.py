"""End-to-end distributed tracing (utils.tracing + serving propagation).

Covers the ISSUE 2 acceptance criteria: trace context survives the
gateway→worker hop, hedged requests share a trace_id with distinct
span_ids, no-context requests keep a byte-identical wire schema, the
failover-with-hedge trace exports as valid Chrome trace-event JSON with
parent/child linkage, and nearest-rank percentiles pin their boundaries.
"""

import http.client
import json
import time

import pytest

from tpu_engine.serving.gateway import Gateway
from tpu_engine.serving.worker import WorkerNode
from tpu_engine.utils.config import GatewayConfig, WorkerConfig
from tpu_engine.utils.tracing import (
    SpanRecorder,
    TraceContext,
    derive_trace_id,
    export_chrome,
    percentile,
)


# -- TraceContext wire form ---------------------------------------------------

def test_traceparent_roundtrip():
    ctx = TraceContext.root("req-1")
    parsed = TraceContext.from_request({"traceparent": ctx.to_traceparent()})
    assert parsed.trace_id == ctx.trace_id
    assert parsed.span_id == ctx.span_id


def test_traceparent_malformed_ignored():
    # W3C semantics: an invalid header never fails the request.
    for bad in ("nonsense", "00-zz-yy-01", "01-" + "a" * 32 + "-b" * 16,
                123, None, ""):
        assert TraceContext.from_request({"traceparent": bad}) is None
    assert TraceContext.from_request({}) is None


def test_derived_trace_id_is_deterministic():
    # Anonymous correlation: every hop derives the SAME trace id from the
    # request_id, with no wire field needed.
    assert derive_trace_id("r1") == derive_trace_id("r1")
    assert derive_trace_id("r1") != derive_trace_id("r2")
    assert TraceContext.root("r1").trace_id == TraceContext.root("r1").trace_id


def test_child_spans_share_trace_distinct_span():
    root = TraceContext.root("x")
    a, b = root.child(), root.child()
    assert a.trace_id == b.trace_id == root.trace_id
    assert len({a.span_id, b.span_id, root.span_id}) == 3


# -- nearest-rank percentiles (satellite: int() truncation fix) ---------------

def test_percentile_nearest_rank_boundaries():
    assert percentile([], 50) is None
    assert percentile([7], 99) == 7
    # p50 of an even-length list is the lower middle (nearest rank), not
    # the upper one the old int() indexing returned.
    assert percentile([1, 2, 3, 4], 50) == 2
    assert percentile([1, 2, 3, 4], 100) == 4
    assert percentile([1, 2], 99) == 2
    vals = list(range(1, 101))
    assert percentile(vals, 99) == 99   # old code returned 100 here
    assert percentile(vals, 1) == 1
    assert percentile(vals, 100) == 100
    # p <= 0 clamps to the minimum (rank floor), never a negative index.
    assert percentile(vals, 0) == 1


def test_percentile_sorts_unsorted_input():
    """The helper sorts internally — UNSORTED input used to silently
    return garbage (the known bench footgun: a latency list in arrival
    order produced plausible-looking nonsense percentiles). The input
    list must not be mutated (callers reuse their samples)."""
    unsorted = [9.0, 1.0, 7.0, 3.0, 5.0]
    snapshot = list(unsorted)
    assert percentile(unsorted, 50) == 5.0
    assert percentile(unsorted, 100) == 9.0
    assert percentile(unsorted, 1) == 1.0
    assert unsorted == snapshot  # sorted a COPY, caller's list intact
    # Reverse-sorted worst case agrees with the sorted result.
    rev = list(range(100, 0, -1))
    assert percentile(rev, 99) == percentile(sorted(rev), 99) == 99


def test_summary_uses_nearest_rank():
    rec = SpanRecorder(capacity=16)
    for i, d in enumerate((1, 2, 3, 4)):
        rec.record(f"r{i}", "infer", "w", d)
    s = rec.summary()
    assert s["spans"] == 4
    assert s["duration_us"]["p50"] == 2
    assert s["duration_us"]["p99"] == 4
    assert s["duration_us"]["max"] == 4


def test_recorder_capacity_zero_disables():
    rec = SpanRecorder(capacity=0)
    rec.record("r", "infer", "w", 10)
    assert rec.recent() == []
    assert rec.summary() == {"spans": 0}
    assert rec.histograms() == {}


# -- gateway→worker propagation (in-process hop) ------------------------------

@pytest.fixture()
def lanes():
    w1 = WorkerNode(WorkerConfig(node_id="tr_w1", model="mlp",
                                 batch_timeout_ms=2.0))
    w2 = WorkerNode(WorkerConfig(node_id="tr_w2", model="mlp",
                                 batch_timeout_ms=2.0))
    try:
        yield w1, w2
    finally:
        w1.stop()
        w2.stop()


def _client_ctx():
    return TraceContext("ab" * 16, "cd" * 8)


def _wait_for_ops(recorders, trace_id, needed, timeout_s=3.0):
    """Spans from the batcher observer land on the dispatch thread AFTER
    the request's future resolves — poll briefly instead of racing it."""
    deadline = time.monotonic() + timeout_s
    while True:
        spans = [s for r in recorders for s in r.snapshot()
                 if s.get("trace_id") == trace_id]
        if needed <= {s["op"] for s in spans} \
                or time.monotonic() >= deadline:
            return spans
        time.sleep(0.01)


def test_context_survives_gateway_worker_hop(lanes):
    w1, w2 = lanes
    gw = Gateway([w1, w2])
    client = _client_ctx()
    gw.route_request({"request_id": "hop1", "input_data": [1.0, 2.0],
                      "traceparent": client.to_traceparent()})
    gw_spans = [s for s in gw.tracer.snapshot()
                if s.get("trace_id") == client.trace_id]
    worker_spans = _wait_for_ops(
        [w1.tracer, w2.tracer], client.trace_id,
        {"infer", "admission", "cache_lookup", "queue_wait", "batch_form",
         "device_compute"})
    assert gw_spans and worker_spans
    route = next(s for s in gw_spans if s["op"] == "route")
    attempt = next(s for s in gw_spans if s["op"] == "attempt")
    # Tree shape: client span -> route -> attempt -> worker root -> stages.
    assert route["parent_id"] == client.span_id
    assert attempt["parent_id"] == route["span_id"]
    infer = next(s for s in worker_spans if s["op"] == "infer")
    assert infer["parent_id"] == attempt["span_id"]
    stage_ops = {s["op"] for s in worker_spans
                 if s.get("parent_id") == infer["span_id"]}
    assert {"admission", "cache_lookup", "queue_wait", "batch_form",
            "device_compute"} <= stage_ops


def test_anonymous_requests_correlate_via_request_id(lanes):
    w1, w2 = lanes
    gw = Gateway([w1, w2])
    gw.route_request({"request_id": "anon7", "input_data": [3.0, 4.0]})
    tid = derive_trace_id("anon7")
    gw_ops = {s["op"] for s in gw.tracer.snapshot()
              if s.get("trace_id") == tid}
    worker_ops = {s["op"] for w in (w1, w2) for s in w.tracer.snapshot()
                  if s.get("trace_id") == tid}
    assert "route" in gw_ops and "infer" in worker_ops


# -- wire-schema byte-compatibility (no trace context supplied) ---------------

class _RecordingWorker:
    """Stub lane capturing the exact payload dict the gateway forwards."""

    node_id = "stub_lane"

    def __init__(self):
        self.seen = []

    def handle_infer(self, payload):
        self.seen.append(dict(payload))
        return {"request_id": payload["request_id"], "output_data": [1.0],
                "node_id": self.node_id, "cached": False,
                "inference_time_us": 5}


def test_no_context_wire_schema_byte_identical():
    stub = _RecordingWorker()
    gw = Gateway([stub])
    payload = {"request_id": "plain1", "input_data": [1.0, 2.0]}
    resp = gw.route_request(dict(payload))
    # Forwarded payload: exactly the client's keys/values — no trace
    # field, no rewritten ids. Response schema: reference-exact keys.
    assert stub.seen[0] == payload
    assert "traceparent" not in stub.seen[0]
    assert sorted(resp.keys()) == ["cached", "inference_time_us",
                                   "node_id", "output_data", "request_id"]


def test_traced_request_forwards_reparented_context():
    stub = _RecordingWorker()
    gw = Gateway([stub])
    client = _client_ctx()
    payload = {"request_id": "tp1", "input_data": [1.0],
               "traceparent": client.to_traceparent()}
    gw.route_request(dict(payload))
    fwd = stub.seen[0]
    # Propagation adds/overwrites exactly one field: the traceparent is
    # RE-PARENTED (the gateway's attempt span), same trace, new span id.
    assert set(fwd) == set(payload)
    fwd_ctx = TraceContext.from_request(fwd)
    assert fwd_ctx.trace_id == client.trace_id
    assert fwd_ctx.span_id != client.span_id
    assert fwd["request_id"] == "tp1"


def test_request_id_minted_when_absent():
    stub = _RecordingWorker()
    gw = Gateway([stub])
    resp = gw.route_request({"input_data": [9.0]})
    # Satellite: a stable server-side uuid is minted, forwarded to the
    # lane, and echoed in the response (anonymous requests correlatable).
    rid = resp["request_id"]
    assert isinstance(rid, str) and len(rid) == 32
    assert stub.seen[0]["request_id"] == rid


# -- failover-with-hedge: one trace tree, Chrome-export valid -----------------

def test_hedged_route_trace_tree_and_export(lanes):
    w1, w2 = lanes
    gw = Gateway([w1, w2], GatewayConfig(
        hedge_enabled=True, hedge_min_ms=30.0))
    # A request id whose PRIMARY is a known lane; slow that lane so the
    # hedge fires (slow-not-dead: the breaker never sees it).
    rid = next(f"hedge_{i}" for i in range(200)
               if gw._ring.get_node(f"hedge_{i}") == "tr_w1")
    slow, fast = w1, w2
    # Long enough that the hedge lane wins though its first request
    # compiles the model under six loaded test workers (0.4 s lost once:
    # PR 50's whole run, nothing wrong in the program).
    slow.inject_latency(2.0)
    client = _client_ctx()
    try:
        resp = gw.route_request({
            "request_id": rid, "input_data": [5.0, 6.0],
            "traceparent": client.to_traceparent()})
    finally:
        slow.heal()
    assert resp["node_id"] == fast.node_id  # hedge lane answered
    # The primary attempt span records when its dispatch completes
    # (~2 s after the hedge already won) — wait for both attempts and
    # the dispatch-thread observer spans before asserting on the tree.
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        attempts = [s for s in gw.tracer.snapshot() if s["op"] == "attempt"
                    and s.get("trace_id") == client.trace_id]
        if len(attempts) >= 2:
            break
        time.sleep(0.02)
    _wait_for_ops([w1.tracer, w2.tracer], client.trace_id,
                  {"queue_wait", "device_compute"})
    kinds = {s["attrs"]["kind"]: s for s in attempts}
    assert {"primary", "hedge"} <= set(kinds)
    # Hedged dispatches: same trace_id, distinct span_ids (sibling spans
    # under one route span).
    assert kinds["primary"]["span_id"] != kinds["hedge"]["span_id"]
    route = next(s for s in gw.tracer.snapshot() if s["op"] == "route"
                 and s.get("trace_id") == client.trace_id)
    assert kinds["primary"]["parent_id"] == route["span_id"]
    assert kinds["hedge"]["parent_id"] == route["span_id"]
    # Hedge-win decision marker present for the fault-injection audit.
    decisions = [s["attrs"]["decision"] for s in gw.tracer.snapshot()
                 if s["op"] == "resilience"
                 and s.get("trace_id") == client.trace_id]
    assert "hedges" in decisions and "hedge_wins" in decisions

    # Chrome trace-event export: json-loadable, complete events, and the
    # full parent/child chain resolves inside the export.
    exported = json.loads(json.dumps(export_chrome({
        "gateway": gw.tracer, w1.node_id: w1.tracer,
        w2.node_id: w2.tracer})))
    events = [e for e in exported["traceEvents"] if e["ph"] == "X"
              and e["args"].get("trace_id") == client.trace_id]
    by_span = {e["args"]["span_id"]: e for e in events}
    for e in events:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        parent = e["args"].get("parent_id")
        # Every parent resolves within the export except the client's own
        # (edge) span, which lives outside this process.
        assert parent is None or parent in by_span \
            or parent == client.span_id
    # Worker-stage children hang off BOTH attempts (primary ran to
    # completion on the slow lane; the hedge answered from the fast one).
    worker_roots = [e for e in events if e["name"] == "infer"]
    assert {e["args"]["parent_id"] for e in worker_roots} == {
        kinds["primary"]["span_id"], kinds["hedge"]["span_id"]}
    stage_names = {e["name"] for e in events
                   if e["args"].get("parent_id") in
                   {r["args"]["span_id"] for r in worker_roots}}
    assert {"admission", "cache_lookup", "queue_wait",
            "device_compute"} <= stage_names


# -- HTTP edge: traceparent header + /trace/export endpoint -------------------

def test_trace_header_and_export_over_http():
    from tpu_engine.serving.app import serve_worker

    cfg = WorkerConfig(port=0, node_id="trace_http_w", model="mlp")
    w, server = serve_worker(cfg, background=True)
    try:
        tp = "00-" + "9a" * 16 + "-" + "3b" * 8 + "-01"
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=30)
        conn.request("POST", "/infer",
                     body='{"request_id":"h1","input_data":[1.0,2.0]}',
                     headers={"Content-Type": "application/json",
                              "traceparent": tp})
        resp = conn.getresponse()
        resp.read()
        assert resp.status == 200
        conn.request("GET", "/trace/export")
        resp = conn.getresponse()
        exported = json.loads(resp.read())
        conn.close()
        assert resp.status == 200
        traced = [e for e in exported["traceEvents"] if e.get("ph") == "X"
                  and (e.get("args") or {}).get("trace_id") == "9a" * 16]
        # The W3C header alone (no body field) carried the context.
        assert any(e["name"] == "infer" for e in traced)
        assert any(e["name"] == "device_compute" for e in traced)
    finally:
        server.stop()
        w.stop()


def test_trace_summary_schema_over_http():
    """/trace keeps the original summary schema (additive keys only)."""
    from tpu_engine.serving.app import serve_combined

    gateway, workers, server = serve_combined(model="mlp", lanes=1,
                                              port=0, background=True)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=30)
        conn.request("POST", "/infer",
                     body='{"request_id":"s1","input_data":[1.0]}',
                     headers={"Content-Type": "application/json"})
        conn.getresponse().read()
        # The batch observer records queue_wait/batch_form AFTER the
        # request's future resolves (dispatch thread) — poll briefly so
        # an immediate scrape can't race the stage spans.
        node = workers[0].node_id
        deadline = time.monotonic() + 10.0
        while True:
            conn.request("GET", "/trace")
            trace = json.loads(conn.getresponse().read())
            stages = trace.get("stages", {}).get(node, {})
            if ("queue_wait" in stages and "device_compute" in stages) \
                    or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        conn.close()
        assert set(trace) >= {"summary", "recent"}  # original keys
        node = workers[0].node_id
        s = trace["summary"][node]
        assert set(s) == {"spans", "cached", "duration_us"}
        assert set(s["duration_us"]) == {"p50", "p90", "p99", "max"}
        # Additive: per-stage breakdown for bench.py.
        assert "queue_wait" in trace["stages"][node]
        assert "device_compute" in trace["stages"][node]
    finally:
        server.stop()
        for w in workers:
            w.stop()


# -- continuous-scheduler stage spans -----------------------------------------

def test_continuous_generate_records_scheduler_stages():
    w = WorkerNode(WorkerConfig(node_id="tr_gen", model="gpt2-small-test",
                                gen_scheduler="continuous",
                                batch_timeout_ms=2.0))
    try:
        client = _client_ctx()
        w.handle_generate({"request_id": "g1", "prompt_tokens": [1, 2, 3],
                           "max_new_tokens": 4,
                           "traceparent": client.to_traceparent()})
        spans = [s for s in w.tracer.snapshot()
                 if s.get("trace_id") == client.trace_id]
        ops = {s["op"] for s in spans}
        assert {"generate", "admission", "queue_wait", "prefill",
                "decode"} <= ops
        root = next(s for s in spans if s["op"] == "generate")
        for op in ("queue_wait", "prefill", "decode"):
            child = next(s for s in spans if s["op"] == op)
            assert child["parent_id"] == root["span_id"]
    finally:
        w.stop()
