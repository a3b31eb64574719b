"""Crash-tolerant streaming: journal splice, resume determinism, prober.

DESIGN.md "Crash-tolerant streaming": with ``failover_streams`` on, the
gateway journals every /generate/stream token event it relays and a
retryable mid-stream failure resumes the generation on another ring lane
(prompt ⧺ emitted tokens, budget offset), splicing one seamless stream.
The determinism rule under test: because sampling keys fold per absolute
position and penalty counts / stop ids are replayed from the full prompt
at admission, a resumed stream is byte-identical to an uninterrupted run
— greedy AND seeded-sampled, penalties and stops included.

Also covered: the proactive health prober's eject/restore state machine,
the retryable terminal-error contract (``retryable`` / ``trace_id`` /
``tokens_emitted``), ``_recover``'s per-row retryable events, retry-budget
and deadline interaction, and no-block-leak on the surviving pool.
"""

import json
import time

import pytest

from tpu_engine.serving.gateway import Gateway, _parse_sse
from tpu_engine.serving.resilience import (
    FailoverCounters,
    ProbeStateMachine,
)
from tpu_engine.serving.worker import WorkerNode
from tpu_engine.utils.config import GatewayConfig, WorkerConfig
from tpu_engine.utils.deadline import DeadlineExceeded, Overloaded


def sse(obj) -> bytes:
    from tpu_engine.serving.http import sse_event

    return sse_event(obj)


def consume(it):
    """Drain a stream iterator -> (token list, final event, all events)."""
    events = [_parse_sse(f) for f in it]
    assert events and events[-1] is not None and events[-1].get("done"), events
    toks = [t for e in events[:-1] if e and "tokens" in e for t in e["tokens"]]
    return toks, events[-1], events


# -- policy units -------------------------------------------------------------

def test_probe_state_machine_eject_restore():
    sm = ProbeStateMachine(fail_threshold=3)
    assert sm.record("w1", False) is None
    assert sm.record("w1", False) is None
    assert sm.record("w1", False) == "eject"       # 3rd consecutive failure
    assert sm.record("w1", False) is None          # repeats stay silent
    assert sm.ejected("w1")
    assert sm.record("w1", True) == "restore"      # any success restores
    assert not sm.ejected("w1")
    # A success mid-run zeroes the failure streak.
    assert sm.record("w2", False) is None
    assert sm.record("w2", True) is None
    assert sm.record("w2", False) is None
    assert sm.record("w2", False) is None
    assert sm.record("w2", False) == "eject"
    # forget() drops state so a reused lane name starts clean.
    sm.forget("w2")
    assert not sm.ejected("w2")
    assert sm.record("w2", False) is None


def test_failover_counters_schema():
    c = FailoverCounters()
    assert not c.any_nonzero()
    for f in ("stream_failures", "resumes_attempted", "resumes_succeeded",
              "resumes_failed", "tokens_replayed", "prober_ejections",
              "prober_restores"):
        assert c.get(f) == 0
    c.bump("tokens_replayed", 7)
    assert c.as_dict()["tokens_replayed"] == 7 and c.any_nonzero()


def test_stream_error_event_contract():
    """The terminal error event is no longer opaque: retryable
    classification + trace_id + tokens_emitted (the manual-resume
    offset)."""
    ev = WorkerNode._stream_error(RuntimeError("device"), "r1", "t1", 5)
    assert ev == {"done": True, "error": "device", "retryable": True,
                  "request_id": "r1", "trace_id": "t1", "tokens_emitted": 5}
    # Spent budget: no other lane can help.
    assert WorkerNode._stream_error(
        DeadlineExceeded("late"), "r", "t", 0)["retryable"] is False
    # Overload/drain: healthy lanes elsewhere.
    assert WorkerNode._stream_error(
        Overloaded("full"), "r", "t", 0)["retryable"] is True
    # The request itself is at fault.
    assert WorkerNode._stream_error(
        ValueError("bad"), "r", "t", 0)["retryable"] is False
    # An exception may pre-classify itself (scheduler _recover rows do).
    exc = ValueError("pre-classified")
    exc.retryable = True
    assert WorkerNode._stream_error(exc, "r", "t", 3)["retryable"] is True


# -- scripted lanes -----------------------------------------------------------

def deterministic_tokens(prompt, max_new):
    """Position-dependent function of the full prefix: continuation from
    (prompt ⧺ emitted) equals the uninterrupted run IFF the gateway's
    resume offsets are exact — any duplicated, dropped, or shifted token
    changes every later value."""
    toks = []
    ctx = list(prompt)
    for _ in range(max_new):
        t = (sum(ctx) * 31 + len(ctx)) % 211
        toks.append(t)
        ctx.append(t)
    return toks


class ScriptLane:
    """Stub lane speaking the worker SSE stream contract over
    deterministic_tokens. ``die_after`` kills the Nth+ frame on the first
    call: "truncate" = iterator ends with no terminal event (kill -9
    signature), "raise" = transport exception, "error_event" = worker-side
    terminal error event, "drain" = mid-stream Overloaded shed."""

    def __init__(self, node_id, die_after=None, mode="truncate",
                 retryable=True, admit_fail=False):
        self.node_id = node_id
        self.die_after = die_after
        self.mode = mode
        self.retryable = retryable
        self.admit_fail = admit_fail
        self.calls = 0
        self.payloads = []

    def handle_generate_stream(self, payload):
        self.calls += 1
        self.payloads.append(dict(payload))
        if self.admit_fail:
            raise RuntimeError(f"{self.node_id} down")
        arm = self.calls == 1 and self.die_after is not None
        prompt = payload["prompt_tokens"]
        toks = deterministic_tokens(prompt, payload.get("max_new_tokens", 32))

        def events():
            for i, t in enumerate(toks):
                if arm and i >= self.die_after:
                    if self.mode == "raise":
                        raise ConnectionResetError("lane died")
                    if self.mode == "drain":
                        raise Overloaded("lane draining")
                    if self.mode == "error_event":
                        yield sse(WorkerNode._stream_error(
                            RuntimeError("device-step failure")
                            if self.retryable else ValueError("bad row"),
                            payload["request_id"], "tw", i))
                    return  # "truncate": no terminal event at all
                yield sse({"tokens": [t]})
            yield sse({"done": True, "tokens": toks,
                       "node_id": self.node_id,
                       "request_id": payload["request_id"]})
        return events()

    def get_health(self):
        return {"healthy": True, "node_id": self.node_id}


def make_gw(lanes, **cfg_kw):
    cfg_kw.setdefault("failover_streams", True)
    return Gateway(lanes, GatewayConfig(**cfg_kw))


def primary_rid(gw, lane):
    return next(f"r{i}" for i in range(500)
                if gw._ring.get_node(f"r{i}") == lane)


REQ = {"prompt_tokens": [5, 9, 3], "max_new_tokens": 10}


@pytest.mark.parametrize("mode", ["truncate", "raise", "error_event",
                                  "drain"])
def test_splice_identity_across_failure_modes(mode):
    """Every retryable mid-stream failure signature resumes and splices
    byte-identically: kill -9 truncation, transport exception, a
    worker-side retryable error event, and a drain shed."""
    flaky = ScriptLane("flaky", die_after=4, mode=mode)
    stable = ScriptLane("stable")
    gw = make_gw([flaky, stable])
    control = deterministic_tokens(REQ["prompt_tokens"],
                                   REQ["max_new_tokens"])
    rid = primary_rid(gw, "flaky")
    toks, final, _ = consume(gw.route_generate_stream(
        dict(REQ, request_id=rid)))
    assert toks == control                 # no duplicated or missing token
    assert final["tokens"] == control      # summary covers the FULL stream
    assert final["resumed"] == 1 and final["request_id"] == rid
    assert "error" not in final
    # The resume request: prompt ⧺ emitted, budget offset by the emitted.
    resume = stable.payloads[-1]
    assert resume["prompt_tokens"] == REQ["prompt_tokens"] + control[:4]
    assert resume["max_new_tokens"] == REQ["max_new_tokens"] - 4
    fo = gw.get_stats()["failover"]
    assert fo["stream_failures"] == 1 and fo["resumes_attempted"] == 1
    assert fo["resumes_succeeded"] == 1 and fo["tokens_replayed"] == 4
    # Counters == spans: every resume decision is explainable in a trace.
    spans = [s for s in gw.tracer.snapshot() if s["op"] == "resume"]
    assert len(spans) == fo["resumes_attempted"]
    assert spans[0]["attrs"]["outcome"] == "ok"


def test_non_retryable_error_event_terminates_with_contract():
    """A worker-side NON-retryable terminal error (bad request class) must
    not resume — the terminal event still carries the manual-resume
    contract fields."""
    flaky = ScriptLane("flaky", die_after=4, mode="error_event",
                       retryable=False)
    stable = ScriptLane("stable")
    gw = make_gw([flaky, stable])
    rid = primary_rid(gw, "flaky")
    toks, final, _ = consume(gw.route_generate_stream(
        dict(REQ, request_id=rid)))
    assert len(toks) == 4 and final["retryable"] is False
    assert final["tokens_emitted"] == 4 and final["trace_id"]
    assert final["tokens"] == toks         # partial prefix, for manual resume
    assert stable.calls == 0               # never dispatched
    assert gw.failover.get("resumes_attempted") == 0


def test_budget_fully_delivered_synthesizes_done():
    """Lane dies AFTER emitting the full budget but before its terminal
    frame: nothing is left to resume — the gateway synthesizes the done
    summary instead of replaying a zero-token generation."""
    flaky = ScriptLane("flaky", die_after=10, mode="truncate")
    stable = ScriptLane("stable")
    gw = make_gw([flaky, stable])
    control = deterministic_tokens(REQ["prompt_tokens"], 10)
    rid = primary_rid(gw, "flaky")
    toks, final, _ = consume(gw.route_generate_stream(
        dict(REQ, request_id=rid)))
    assert toks == control and final["tokens"] == control
    assert "error" not in final
    assert stable.calls == 0
    assert gw.failover.get("resumes_attempted") == 0


def test_resume_cap_yields_retryable_terminal_error():
    flaky = ScriptLane("flaky", die_after=2, mode="truncate")
    # The "stable" lane also truncates every call — streams can never end.
    class AlwaysDies(ScriptLane):
        def handle_generate_stream(self, payload):
            self.calls += 1
            self.payloads.append(dict(payload))
            prompt = payload["prompt_tokens"]
            toks = deterministic_tokens(prompt,
                                        payload.get("max_new_tokens", 32))

            def events():
                for t in toks[:2]:
                    yield sse({"tokens": [t]})
            return events()

    gw = make_gw([AlwaysDies("a"), AlwaysDies("b")], failover_max_resumes=2)
    toks, final, _ = consume(gw.route_generate_stream(
        dict(REQ, request_id="rX")))
    assert final["retryable"] is True and "2 resumes" in final["error"]
    assert final["tokens_emitted"] == len(toks) == 6  # 2 per segment
    assert toks == deterministic_tokens(REQ["prompt_tokens"], 10)[:6]
    fo = gw.get_stats()["failover"]
    assert fo["resumes_attempted"] == 2 == fo["resumes_succeeded"]
    assert fo["stream_failures"] == 3


def test_resume_consumes_retry_budget():
    """A resume rides the normal dispatch accounting: the dead lane is
    the rid's ring primary, so the skip-path failover march draws the
    global retry budget — with a zero budget the resume dispatch fails
    and the terminal error says why."""
    flaky = ScriptLane("flaky", die_after=3, mode="truncate")
    stable = ScriptLane("stable")
    gw = make_gw([flaky, stable], retry_budget_ratio=0.0, retry_budget_min=0)
    rid = primary_rid(gw, "flaky")
    toks, final, _ = consume(gw.route_generate_stream(
        dict(REQ, request_id=rid)))
    assert final["retryable"] is True
    assert "retry budget" in final["error"]
    assert final["tokens_emitted"] == 3
    assert stable.calls == 0
    fo = gw.get_stats()["failover"]
    assert fo["resumes_attempted"] == 1 and fo["resumes_failed"] == 1
    assert gw.resilience.get("retry_budget_exhausted") >= 1
    # Exactly ONE budget token was asked for (and refused): no separate
    # pre-draw double-charges the resume.
    spans = [s for s in gw.tracer.snapshot() if s["op"] == "resume"]
    assert len(spans) == 1 and spans[0]["attrs"]["outcome"] == "failed"


def test_resume_budget_single_charge():
    """With a budget of exactly one retry, one resume must succeed — a
    double-charge (pre-draw + march draw) would exhaust it mid-resume."""
    flaky = ScriptLane("flaky", die_after=3, mode="truncate")
    stable = ScriptLane("stable")
    gw = make_gw([flaky, stable], retry_budget_ratio=0.0, retry_budget_min=1)
    rid = primary_rid(gw, "flaky")
    toks, final, _ = consume(gw.route_generate_stream(
        dict(REQ, request_id=rid)))
    control = deterministic_tokens(REQ["prompt_tokens"],
                                   REQ["max_new_tokens"])
    assert toks == control and final["tokens"] == control
    assert final["resumed"] == 1
    assert gw.failover.get("resumes_succeeded") == 1


def test_expired_deadline_blocks_resume():
    """The resume rides the ORIGINAL deadline: a budget that died with the
    lane is terminal (retryable False — retrying elsewhere cannot help)."""
    class SlowDeath(ScriptLane):
        def handle_generate_stream(self, payload):
            inner = super().handle_generate_stream(payload)

            def events():
                for frame in inner:
                    yield frame
                time.sleep(0.2)   # the budget dies with the lane
            return events()

    flaky = SlowDeath("flaky", die_after=3, mode="truncate")
    stable = ScriptLane("stable")
    gw = make_gw([flaky, stable])
    rid = primary_rid(gw, "flaky")
    toks, final, _ = consume(gw.route_generate_stream(
        dict(REQ, request_id=rid, deadline_ms=100)))
    assert final["retryable"] is False
    assert "deadline" in final["error"]
    assert stable.calls == 0


def test_all_lanes_down_on_resume():
    flaky = ScriptLane("flaky", die_after=3, mode="truncate")
    stable = ScriptLane("stable", admit_fail=True)
    gw = make_gw([flaky, stable])
    rid = primary_rid(gw, "flaky")
    toks, final, _ = consume(gw.route_generate_stream(
        dict(REQ, request_id=rid)))
    assert len(toks) == 3 and final["retryable"] is True
    assert final["tokens_emitted"] == 3
    fo = gw.get_stats()["failover"]
    assert fo["resumes_attempted"] == 1 and fo["resumes_failed"] == 1
    spans = [s for s in gw.tracer.snapshot() if s["op"] == "resume"]
    assert len(spans) == 1 and spans[0]["attrs"]["outcome"] == "failed"


def test_resume_forwards_remaining_deadline():
    flaky = ScriptLane("flaky", die_after=3, mode="truncate")
    stable = ScriptLane("stable")
    gw = make_gw([flaky, stable])
    rid = primary_rid(gw, "flaky")
    consume(gw.route_generate_stream(
        dict(REQ, request_id=rid, deadline_ms=60_000)))
    resume = stable.payloads[-1]
    # The clock never restarts: the forwarded budget only shrinks.
    assert 0 < resume["deadline_ms"] <= 60_000


def test_failover_disabled_is_todays_behavior():
    """Defaults: no journal, no resume, no /stats block — a truncated
    stream ends truncated, byte-identical to the pre-failover gateway."""
    flaky = ScriptLane("flaky", die_after=3, mode="truncate")
    stable = ScriptLane("stable")
    gw = Gateway([flaky, stable], GatewayConfig())
    rid = primary_rid(gw, "flaky")
    frames = list(gw.route_generate_stream(dict(REQ, request_id=rid)))
    events = [_parse_sse(f) for f in frames]
    assert len(events) == 3 and not any(e.get("done") for e in events)
    assert stable.calls == 0
    assert "failover" not in gw.get_stats()


def test_stream_transport_error_classification():
    """One classification shared by blocking and streaming HTTP paths: a
    socket timeout under a deadline-clamped read is the CLIENT's budget
    expiring (terminal DeadlineExceeded, lane_suspect feeds the breaker
    the hang signature); everything else is a lane fault."""
    import socket as sock_mod

    from tpu_engine.serving.clients import HttpWorkerClient, WorkerError

    c = HttpWorkerClient("localhost:1")
    exc = c._transport_error(sock_mod.timeout("t"), deadline_clamped=True)
    assert isinstance(exc, DeadlineExceeded) and exc.lane_suspect
    assert isinstance(c._transport_error(sock_mod.timeout("t"), False),
                      WorkerError)
    assert isinstance(c._transport_error(ConnectionResetError(), True),
                      WorkerError)


def _breaker_failures(gw, lane):
    return next(e["failures"] for e in gw.get_stats()["circuit_breakers"]
                if e["node"] == lane)


def test_mid_stream_lane_fault_feeds_breaker():
    """Admission records a breaker SUCCESS at iterator creation; the
    mid-stream fault must record the FAILURE, or a lane that admits
    streams and then dies stays CLOSED forever."""
    flaky = ScriptLane("flaky", die_after=4, mode="raise")
    stable = ScriptLane("stable")
    gw = make_gw([flaky, stable])
    rid = primary_rid(gw, "flaky")
    consume(gw.route_generate_stream(dict(REQ, request_id=rid)))
    assert _breaker_failures(gw, "flaky") == 1
    assert _breaker_failures(gw, "stable") == 0


def test_mid_stream_drain_shed_spares_breaker():
    """A drain shed mid-stream resumes WITHOUT a breaker penalty — the
    healthy-lane rule, same as admission-time sheds."""
    flaky = ScriptLane("flaky", die_after=4, mode="drain")
    stable = ScriptLane("stable")
    gw = make_gw([flaky, stable])
    rid = primary_rid(gw, "flaky")
    toks, final, _ = consume(gw.route_generate_stream(
        dict(REQ, request_id=rid)))
    assert final["resumed"] == 1
    assert _breaker_failures(gw, "flaky") == 0


def test_shed_error_event_spares_breaker():
    """A worker-side terminal error EVENT carrying the shed marker (a
    drain caught after the stream committed) resumes without a breaker
    penalty — same healthy-lane rule as the exception path."""
    from tpu_engine.utils.deadline import Overloaded as _Ov

    class ShedEventLane(ScriptLane):
        def handle_generate_stream(self, payload):
            self.calls += 1
            self.payloads.append(dict(payload))
            prompt = payload["prompt_tokens"]
            toks = deterministic_tokens(prompt,
                                        payload.get("max_new_tokens", 32))
            if self.calls > 1:
                def done_events():
                    for t in toks:
                        yield sse({"tokens": [t]})
                    yield sse({"done": True, "tokens": toks,
                               "node_id": self.node_id,
                               "request_id": payload["request_id"]})
                return done_events()

            def events():
                for t in toks[:4]:
                    yield sse({"tokens": [t]})
                yield sse(WorkerNode._stream_error(
                    _Ov("lane draining"), payload["request_id"], "tw", 4))
            return events()

    flaky = ShedEventLane("flaky")
    stable = ScriptLane("stable")
    gw = make_gw([flaky, stable])
    rid = primary_rid(gw, "flaky")
    toks, final, _ = consume(gw.route_generate_stream(
        dict(REQ, request_id=rid)))
    assert final["resumed"] == 1
    assert toks == deterministic_tokens(REQ["prompt_tokens"], 10)
    assert _breaker_failures(gw, "flaky") == 0  # shed, not a lane fault


def test_default_path_mid_stream_fault_feeds_breaker():
    """failover OFF: the stream still truncates (today's behavior) but
    the dying lane's breaker records the fault — the signal the old
    buffering HTTP shim got at dispatch time."""
    flaky = ScriptLane("flaky", die_after=3, mode="raise")
    stable = ScriptLane("stable")
    gw = Gateway([flaky, stable], GatewayConfig())
    rid = primary_rid(gw, "flaky")
    with pytest.raises(ConnectionResetError):
        list(gw.route_generate_stream(dict(REQ, request_id=rid)))
    assert _breaker_failures(gw, "flaky") == 1
    assert "failover" not in gw.get_stats()


# -- proactive lane health (prober) -------------------------------------------

class HealthLane(ScriptLane):
    def __init__(self, node_id):
        super().__init__(node_id)
        self.healthy = True
        self.reachable = True

    def get_health(self):
        if not self.reachable:
            raise ConnectionRefusedError("probe refused")
        return {"healthy": self.healthy, "node_id": self.node_id}


def _wait(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return pred()


def test_prober_ejects_and_restores_lane():
    lanes = [HealthLane("w1"), HealthLane("w2")]
    gw = Gateway(lanes, GatewayConfig(health_probe_interval_s=0.05,
                                      health_probe_failures=2))
    try:
        lanes[0].reachable = False        # dead-process signature
        assert _wait(lambda: gw.ejected_lanes() == ["w1"])
        # Ejected lanes are skipped by dispatch with no breaker penalty:
        # a request whose ring primary is w1 serves from w2.
        rid = primary_rid(gw, "w1")
        toks, final, _ = consume(gw.route_generate_stream(
            dict(REQ, request_id=rid)))
        assert final["node_id"] == "w2"
        assert toks == deterministic_tokens(REQ["prompt_tokens"], 10)
        breakers = {e["node"]: e for e in gw.get_stats()["circuit_breakers"]}
        assert breakers["w1"]["state"] == "CLOSED"
        # Recovery: the next successful probe restores the lane.
        lanes[0].reachable = True
        assert _wait(lambda: gw.ejected_lanes() == [])
        fo = gw.get_stats()["failover"]
        assert fo["prober_ejections"] == 1 and fo["prober_restores"] == 1
        # Counters == spans, prober included. Settle first: the prober
        # bumps the counter BEFORE recording its span, so one snapshot
        # can land between the two (the same race fault_injection's
        # crash phase settles) — the restore above was observed via
        # _ejected, which clears before either.
        def _actions():
            return sorted(s["attrs"]["action"]
                          for s in gw.tracer.snapshot()
                          if s["op"] == "prober")
        assert _wait(lambda: _actions() == ["eject", "restore"])
    finally:
        gw.stop()


def test_prober_unhealthy_health_counts_as_failure():
    """A lane that ANSWERS but reports unhealthy (e.g. a wedged scheduler
    flagged by last-tick age) ejects exactly like a dead process."""
    lanes = [HealthLane("w1"), HealthLane("w2")]
    gw = Gateway(lanes, GatewayConfig(health_probe_interval_s=0.05,
                                      health_probe_failures=2))
    try:
        lanes[1].healthy = False
        assert _wait(lambda: gw.ejected_lanes() == ["w2"])
    finally:
        gw.stop()


def test_prober_fails_open_when_every_lane_ejected():
    """Probe-only evidence must never turn the gateway into a hard
    outage: with EVERY lane ejected (e.g. a fleet-wide compile stall
    tripping a tight scheduler_stall_s), dispatch ignores ejection and
    the breakers — request evidence — stay the last word."""
    lanes = [HealthLane("w1"), HealthLane("w2")]
    gw = Gateway(lanes, GatewayConfig(health_probe_interval_s=0.05,
                                      health_probe_failures=1))
    try:
        for lane in lanes:
            lane.healthy = False
        assert _wait(lambda: gw.ejected_lanes() == ["w1", "w2"])
        toks, final, _ = consume(gw.route_generate_stream(
            dict(REQ, request_id="r_open")))
        assert toks == deterministic_tokens(REQ["prompt_tokens"], 10)
        # Recovery of ONE lane re-arms normal ejection for the other.
        lanes[0].healthy = True
        assert _wait(lambda: gw.ejected_lanes() == ["w2"])
        toks, final, _ = consume(gw.route_generate_stream(
            dict(REQ, request_id=primary_rid(gw, "w2"))))
        assert final["node_id"] == "w1"
    finally:
        gw.stop()


def test_prober_fail_open_is_per_model_ring():
    """The fail-open guard is evaluated against the REQUEST's ring: one
    model's lanes all ejected must fail open for that model even while
    another model's healthy lanes keep the fleet-wide count low."""
    import types

    class TypedLane(HealthLane):
        def __init__(self, node_id, model):
            super().__init__(node_id)
            self.engine = types.SimpleNamespace(
                spec=types.SimpleNamespace(name=model))

    lanes = [TypedLane("a1", "mA"), TypedLane("a2", "mA"),
             TypedLane("b1", "mB"), TypedLane("b2", "mB")]
    gw = Gateway(lanes, GatewayConfig(health_probe_interval_s=0.05,
                                      health_probe_failures=1))
    try:
        lanes[0].healthy = lanes[1].healthy = False
        assert _wait(lambda: set(gw.ejected_lanes()) == {"a1", "a2"})
        toks, final, _ = consume(gw.route_generate_stream(
            dict(REQ, request_id="rA", model="mA")))
        assert final["node_id"] in ("a1", "a2")   # served despite ejection
        assert toks == deterministic_tokens(REQ["prompt_tokens"], 10)
        # mB routing honors ejection state normally (none ejected there).
        toks, final, _ = consume(gw.route_generate_stream(
            dict(REQ, request_id="rB", model="mB")))
        assert final["node_id"] in ("b1", "b2")
    finally:
        gw.stop()


def test_probe_health_bypasses_exhausted_pool():
    """The prober's dedicated connection must answer even when every
    pooled data connection is held by long-lived streams — a saturated
    lane is busy, not dead."""
    from queue import LifoQueue

    from tpu_engine.serving.app import serve_worker
    from tpu_engine.serving.clients import HttpWorkerClient, WorkerError

    w, s = serve_worker(WorkerConfig(port=0, node_id="ph1", model="mlp",
                                     dtype="float32", batch_buckets=(1, 2)))
    try:
        client = HttpWorkerClient(f"localhost:{s.port}", timeout_s=0.3)
        client._pool = LifoQueue()      # every slot held by live streams
        with pytest.raises(WorkerError, match="pool"):
            client.health()             # pooled path starves...
        assert client.probe_health()["healthy"] is True  # ...probe doesn't
    finally:
        s.stop()
        w.stop()


def test_removed_lane_forgets_probe_state():
    lanes = [HealthLane("w1"), HealthLane("w2")]
    gw = Gateway(lanes, GatewayConfig(health_probe_interval_s=0.05,
                                      health_probe_failures=1))
    try:
        lanes[0].reachable = False
        assert _wait(lambda: gw.ejected_lanes() == ["w1"])
        gw.remove_worker("w1")
        assert gw.ejected_lanes() == []
        assert not gw._probe_state.ejected("w1")
    finally:
        gw.stop()


# -- real model: resume determinism e2e ---------------------------------------

class RealLane:
    """A named lane delegating to a SHARED real WorkerNode — two lanes,
    one scheduler, so the splice-identity e2e pays one model compile.
    ``die_after`` raises a transport error after N relayed frames (first
    call only), closing the worker-side iterator like a dead socket."""

    def __init__(self, worker, node_id, die_after=None):
        self.worker = worker
        self.node_id = node_id
        self.die_after = die_after
        self.calls = 0

    def handle_generate_stream(self, payload):
        self.calls += 1
        inner = self.worker.handle_generate_stream(payload)
        if self.die_after is None or self.calls > 1:
            return inner
        die_after = self.die_after

        def gen():
            n = 0
            for frame in inner:
                if n >= die_after:
                    inner.close()
                    raise ConnectionResetError("lane killed mid-stream")
                yield frame
                n += 1
        return gen()

    def get_health(self):
        return {"healthy": True, "node_id": self.node_id}


@pytest.fixture(scope="module")
def shared_worker():
    w = WorkerNode(WorkerConfig(
        node_id="shared", model="gpt2-small-test", dtype="float32",
        gen_step_chunk=2, gen_kv_block_size=16, gen_prefill_chunk=16))
    yield w
    w.stop()


def pool_leak_free(worker) -> bool:
    st = worker.generator.stats()
    kp = st["kv_pool"]
    return (st["active"] == 0
            and kp["blocks_free"] + kp["radix_nodes"] >= kp["blocks_total"])


@pytest.mark.parametrize("params", [
    {},                                                      # greedy
    {"temperature": 0.9, "seed": 11},                        # seeded sampled
    {"temperature": 0.8, "seed": 4, "repetition_penalty": 1.3,
     "stop_tokens": [7], "top_p": 0.9},                      # controls
])
def test_real_model_splice_identity(shared_worker, params):
    """The determinism rule, live: a resumed stream over (prompt ⧺
    emitted) is byte-identical to the blocking result — fold_in(seed,
    absolute position) sampling, penalty counts rebuilt from the full
    prompt at admission, stop ids position-independent."""
    flaky = RealLane(shared_worker, "flaky", die_after=3)
    stable = RealLane(shared_worker, "stable")
    gw = make_gw([flaky, stable])
    req = {"prompt_tokens": [5, 9, 3, 17, 4, 8], "max_new_tokens": 14,
           **params}
    control = shared_worker.handle_generate(
        dict(req, request_id="ctl"))["tokens"]
    rid = primary_rid(gw, "flaky")
    toks, final, _ = consume(gw.route_generate_stream(
        dict(req, request_id=rid)))
    assert flaky.calls == 1 and stable.calls == 1   # resume really happened
    assert toks == control and final["tokens"] == control
    assert final["resumed"] == 1
    assert _wait(lambda: pool_leak_free(shared_worker))


def test_recover_emits_per_row_retryable_events(shared_worker):
    """A device-step failure fails each in-flight row with a RETRYABLE
    event carrying its emitted count — the journal's resume hook — and
    the rebuilt pool passes its post-recover invariants."""
    gen = shared_worker.generator
    worker_stream = shared_worker.handle_generate_stream(
        {"request_id": "rec1", "prompt_tokens": [2, 4, 6],
         "max_new_tokens": 30})
    frames = []
    it = iter(worker_stream)
    frames.append(next(it))               # at least one token is out
    # Arm a one-shot device failure on the next decode dispatch.
    real = gen._mixed_step_exe

    def failing(width, controls):
        gen._mixed_step_exe = real

        def exe(*a, **k):
            raise RuntimeError("injected device failure")
        return exe

    gen._mixed_step_exe = failing
    events = [_parse_sse(frames[0])] + [_parse_sse(f) for f in it]
    final = events[-1]
    assert final["done"] and final["retryable"] is True
    assert "device-step failure" in final["error"]
    emitted = sum(len(e["tokens"]) for e in events[:-1] if e and "tokens" in e)
    assert final["tokens_emitted"] == emitted >= 1
    # Post-recover: invariants held, pool clean, lane still serves.
    st = gen.stats()
    assert st.get("recover_invariant_violations", 0) == 0
    assert st["failures"] >= 1
    assert _wait(lambda: pool_leak_free(shared_worker))
    again = shared_worker.handle_generate(
        {"request_id": "rec2", "prompt_tokens": [2, 4, 6],
         "max_new_tokens": 5})
    assert len(again["tokens"]) == 5


def test_gateway_resumes_past_recover_event(shared_worker):
    """End to end: scheduler _recover row event -> worker terminal error
    (retryable) -> gateway journal resume -> byte-identical splice."""
    gen = shared_worker.generator

    class KillLane(RealLane):
        def handle_generate_stream(self, payload):
            self.calls += 1
            inner = self.worker.handle_generate_stream(payload)
            if self.calls > 1:
                return inner

            def gen_frames():
                it = iter(inner)
                yield next(it)            # first token is out
                real = gen._mixed_step_exe

                def failing(width, controls):
                    gen._mixed_step_exe = real

                    def exe(*a, **k):
                        raise RuntimeError("injected device failure")
                    return exe

                gen._mixed_step_exe = failing
                yield from it
            return gen_frames()

    flaky = KillLane(shared_worker, "flaky")
    stable = RealLane(shared_worker, "stable")
    gw = make_gw([flaky, stable])
    # Long enough that the lane, which steps ahead of the reader, cannot
    # finish the stream before the failure is installed (12 tokens did,
    # once in a loaded run: the stream ended whole, nothing to resume).
    req = {"prompt_tokens": [3, 1, 4, 1, 5], "max_new_tokens": 48,
           "temperature": 0.7, "seed": 23}
    control = shared_worker.handle_generate(
        dict(req, request_id="ctl2"))["tokens"]
    rid = primary_rid(gw, "flaky")
    toks, final, _ = consume(gw.route_generate_stream(
        dict(req, request_id=rid)))
    assert toks == control and final["tokens"] == control
    assert final.get("resumed") == 1
    assert gw.failover.get("resumes_succeeded") == 1
    assert _wait(lambda: pool_leak_free(shared_worker))


def test_prefill_busy_age_feeds_liveness(shared_worker):
    """A device dispatch hung inside the PREFILL thread must age the
    liveness signal too — the decode loop keeps idle-ticking, so the
    busy-age is the only thing that sees a wedged admission path."""
    gen = shared_worker.generator
    assert gen.stats()["last_tick_age_s"] < 5.0
    gen._prefill_busy_since = time.monotonic() - 123.0  # wedged prefill
    try:
        assert gen.stats()["last_tick_age_s"] >= 123.0
    finally:
        gen._prefill_busy_since = None
    assert gen.stats()["last_tick_age_s"] < 5.0


def test_scheduler_liveness_flips_health(shared_worker):
    """/health gains last-tick age; with scheduler_stall_s set, a wedged
    decode loop reads unhealthy (process-alive is not serving)."""
    h = shared_worker.get_health()
    assert h["generator"]["last_tick_age_s"] >= 0.0
    assert h["healthy"] is True
    shared_worker.config.scheduler_stall_s = 3600.0
    assert shared_worker.get_health()["healthy"] is True
    try:
        shared_worker.config.scheduler_stall_s = 1e-9
        time.sleep(0.01)
        h = shared_worker.get_health()
        # The loop ticks continuously; age may race under 1e-9 only if a
        # tick landed this instant — retry once to de-flake.
        if h["healthy"]:
            time.sleep(0.05)
            h = shared_worker.get_health()
        assert h["healthy"] is False and h["scheduler_stalled"] is True
    finally:
        shared_worker.config.scheduler_stall_s = 0.0
    assert shared_worker.get_health()["healthy"] is True
