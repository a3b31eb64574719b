"""Adaptive overload control (serving/overload.py; DESIGN.md "Overload
control"): priority-tiered admission, per-tenant rate limiting, AIMD
adaptive concurrency, load-derived Retry-After, and the staged brownout
ladder.

Contracts under test:
- tier ordering at admission: under depth pressure the lowest tier
  sheds first, at the gateway and at the worker's AdmissionController;
- token-bucket fairness: one tenant's burst never consumes another's
  tokens, and refusals carry the bucket's actual refill time;
- the AIMD limit grows under good latency, shrinks (bounded, with a
  decrease cooldown) when latency blows past the baseline;
- the brownout ladder escalates and restores one stage at a time with
  hysteresis — pressure oscillating inside the band can never flap it —
  and its degradations leave greedy streams byte-identical;
- defaults-off wire-compat: no new /stats//health keys, admission shed
  totals remain the sum of their causes;
- load-derived Retry-After is monotone in measured pressure.

Kept lean per the tier-1 budget: one compiled scheduler (module
fixture) covers every brownout-application test; everything else is
pure logic or stub-lane gateways.
"""

import queue as _queue
import time

import pytest

from tpu_engine.serving.gateway import Gateway
from tpu_engine.serving.overload import (
    AIMDLimit,
    BROWNOUT_STAGES,
    BrownoutController,
    OverloadCounters,
    TIER_ADMIT_FRAC,
    TOP_TIER,
    TenantRateLimiter,
    load_retry_after,
    parse_priority,
)
from tpu_engine.serving.resilience import AdmissionController
from tpu_engine.utils.config import GatewayConfig, WorkerConfig
from tpu_engine.utils.deadline import Overloaded

# -- priority tiers -----------------------------------------------------------


def test_parse_priority_ordering_default_and_invalid():
    assert (parse_priority({"priority": "background"})
            < parse_priority({"priority": "batch"})
            < parse_priority({"priority": "interactive"}))
    # Absent field = top tier: old clients are never deprioritized.
    assert parse_priority({}) == TOP_TIER
    with pytest.raises(ValueError, match="priority"):
        parse_priority({"priority": "urgent"})


def test_tier_admission_sheds_lowest_first():
    a = AdmissionController(max_depth=10, node_id="t",
                            tier_fracs=TIER_ADMIT_FRAC)
    for _ in range(7):          # fill to background's 70% fraction
        a.admit(tier=TOP_TIER)
    with pytest.raises(Overloaded) as exc:
        a.admit(tier=0)         # background sheds first
    assert exc.value.cause == "tier"
    a.admit(tier=1)             # batch still admits (85% = 8) -> depth 8
    with pytest.raises(Overloaded):
        a.admit(tier=1)         # batch sheds at 8 >= 8
    a.admit(tier=TOP_TIER)      # interactive admits to the full limit
    a.admit(tier=TOP_TIER)      # depth 10
    with pytest.raises(Overloaded) as exc:
        a.admit(tier=TOP_TIER)  # full: even top tier sheds now
    assert exc.value.cause == "depth"
    d = a.as_dict()
    # Old total stays the sum of the causes (wire-compat guarantee).
    assert d["shed_overloaded"] == d["shed_depth"] + d["shed_tier"] \
        + d["shed_adaptive"] == 3
    assert d["shed_tier"] == 2 and d["shed_depth"] == 1


def test_admission_schema_unchanged_without_overload_features():
    # A plain max_queue_depth lane (PR 1 behavior) keeps its exact
    # pre-overload-control key set — no cause keys, no adaptive block.
    a = AdmissionController(max_depth=1, node_id="t")
    a.admit()
    with pytest.raises(Overloaded):
        a.admit()
    assert set(a.as_dict()) == {"draining", "queue_depth",
                                "max_queue_depth", "shed_overloaded",
                                "shed_deadline", "shed_draining"}
    # Untiered admit with a tier argument stays untiered (tier needs
    # tier_fracs): depth 1 lane already full either way.
    assert a.as_dict()["shed_overloaded"] == 1


# -- per-tenant token bucket --------------------------------------------------


def test_token_bucket_fairness_and_refill():
    b = TenantRateLimiter(rate=50.0, burst=3.0)
    got = [b.allow("A")[0] for _ in range(6)]
    assert got[:3] == [True] * 3 and got[3:] == [False] * 3
    ok, wait = b.allow("A")
    assert not ok and wait > 0          # refusal says when to come back
    # Fairness: A's exhaustion never touched B's bucket.
    assert b.allow("B")[0]
    # Refill: at 50/s a token exists within ~20 ms.
    time.sleep(0.05)
    assert b.allow("A")[0]
    assert b.tenants() == 2


# -- AIMD adaptive concurrency ------------------------------------------------


def test_aimd_limit_grows_on_good_latency():
    a = AIMDLimit(min_limit=1, max_limit=32, start=4, min_samples=4,
                  cooldown_s=0.0)
    for _ in range(200):
        a.observe(0.01)
    assert a.limit > 4
    assert a.limit <= 32


def test_aimd_limit_shrinks_bounded_with_cooldown():
    a = AIMDLimit(min_limit=2, max_limit=32, start=16, min_samples=4,
                  tolerance=2.0, decrease=0.5, cooldown_s=3600.0)
    for _ in range(8):
        a.observe(0.01)         # establish the baseline
    for _ in range(20):
        a.observe(1.0)          # 100x the baseline
    # Cooldown: one congested burst costs ONE multiplicative decrease,
    # not a collapse to min_limit.
    assert a.limit == 8
    assert a.as_dict()["decreases"] == 1
    fast = AIMDLimit(min_limit=2, max_limit=32, start=4, min_samples=4,
                     decrease=0.1, cooldown_s=0.0)
    for _ in range(8):
        fast.observe(0.01)
    for _ in range(12):         # few enough not to poison the baseline
        fast.observe(5.0)
    assert fast.limit == 2      # floored at min_limit, never below


# -- brownout ladder ----------------------------------------------------------


def test_brownout_escalates_and_restores_in_order():
    c = BrownoutController(up_hold=1, down_hold=1)
    seen = []
    for _ in range(6):
        c.evaluate({"queue_depth": 1.5})
        seen.append(c.stage)
    # One stage per evaluation, capped at the ladder's end.
    assert seen == [1, 2, 3, 4, 4, 4]
    down = []
    for _ in range(6):
        c.evaluate({"queue_depth": 0.0})
        down.append(c.stage)
    assert down == [3, 2, 1, 0, 0, 0]   # restores in reverse
    d = c.as_dict()
    assert d["escalations"] == 4 and d["restores"] == 4
    assert d["stage_name"] == BROWNOUT_STAGES[0]


def test_brownout_hysteresis_holds_stage_no_flapping():
    c = BrownoutController(high=0.85, low=0.5, up_hold=2, down_hold=2)
    c.evaluate({"x": 1.0})
    c.evaluate({"x": 1.0})
    assert c.stage == 1
    # Pressure oscillating INSIDE the (low, high) band: stage holds.
    for p in (0.6, 0.8, 0.55, 0.84, 0.7, 0.6):
        c.evaluate({"x": p})
        assert c.stage == 1
    # Non-consecutive excursions never accumulate: high, band, high ...
    for p in (0.9, 0.7, 0.9, 0.7, 0.9, 0.7):
        c.evaluate({"x": p})
    assert c.stage == 1
    # Same for the restore run.
    for p in (0.4, 0.7, 0.4, 0.7):
        c.evaluate({"x": p})
    assert c.stage == 1
    assert c.as_dict()["escalations"] == 1
    assert c.as_dict()["restores"] == 0


def test_brownout_binding_signal_reported():
    c = BrownoutController(up_hold=1)
    c.evaluate({"queue_depth": 0.2, "tick_age": 1.4})
    assert c.as_dict()["binding_signal"] == "tick_age"
    assert c.as_dict()["pressure"] == pytest.approx(1.4)


# -- load-derived Retry-After -------------------------------------------------


def test_load_retry_after_monotone_and_clamped():
    base = 1.0
    vals = [load_retry_after(base, p) for p in (0.0, 0.5, 1.0, 2.0, 5.0)]
    assert vals == sorted(vals)         # monotone in pressure
    assert vals[0] == base              # never below the configured base
    assert load_retry_after(base, 1e9) == 30.0   # clamped
    assert load_retry_after(base, -5.0) == base  # negative pressure = idle


# -- gateway ------------------------------------------------------------------


class StubWorker:
    def __init__(self, node_id):
        self.node_id = node_id

    def handle_infer(self, payload):
        return {"request_id": payload["request_id"], "output_data": [1.0],
                "node_id": self.node_id, "cached": False,
                "inference_time_us": 10}

    def get_health(self):
        return {"healthy": True, "node_id": self.node_id}


def test_gateway_stats_schema_unchanged_at_defaults():
    gw = Gateway([StubWorker("w1")], GatewayConfig())
    gw.route_request({"request_id": "r", "input_data": [1.0],
                      "priority": "background", "tenant": "A"})
    # Overload features off: the priority/tenant fields are ignored and
    # /stats carries no overload block — byte-compat with PR 8.
    assert set(gw.get_stats()) == {"total_workers", "total_requests",
                                   "failovers", "circuit_breakers"}


def test_gateway_tier_admission_lowest_first_counters_match_spans():
    gw = Gateway([StubWorker("w1")],
                 GatewayConfig(overload_control=True,
                               overload_max_inflight=10))
    gw._inflight = 8  # simulate 8 concurrent residents
    with pytest.raises(Overloaded) as exc:
        gw.route_request({"request_id": "r1", "input_data": [1.0],
                          "priority": "background"})
    assert exc.value.cause == "tier"
    assert exc.value.retry_after_s > gw.config.shed_retry_after_s
    gw._inflight = 8
    # Top tier rides through the same pressure.
    assert gw.route_request({"request_id": "r2", "input_data": [1.0],
                             "priority": "interactive"})["node_id"] == "w1"
    gw._inflight = 10
    with pytest.raises(Overloaded) as exc:
        gw.route_request({"request_id": "r3", "input_data": [1.0],
                          "priority": "interactive"})
    assert exc.value.cause == "depth"   # full gauge sheds even top tier
    gw._inflight = 0
    ov = gw.get_stats()["overload"]
    assert ov["shed_tier"] == 1 and ov["shed_depth"] == 1
    # counters == spans: every decision has an `overload` marker span.
    spans = [s for s in gw.tracer.recent(100) if s.get("op") == "overload"]
    assert len(spans) == ov["shed_tier"] + ov["shed_depth"] \
        + ov["rate_limited"] == 2


def test_gateway_unknown_priority_is_client_error():
    # Validation rides the master switch alone — no gauge configured
    # (MIGRATION.md: a typo'd priority must never silently ride).
    gw = Gateway([StubWorker("w1")],
                 GatewayConfig(overload_control=True))
    with pytest.raises(ValueError, match="priority"):
        gw.route_request({"request_id": "r", "input_data": [1.0],
                          "priority": "asap"})
    # Known values still route fine without a gauge.
    assert gw.route_request({"request_id": "r2", "input_data": [1.0],
                             "priority": "background"})["node_id"] == "w1"


def test_gateway_stream_holds_inflight_gauge():
    # A stream occupies the gauge until its iterator finishes — not
    # just the admission leg (stream-heavy fleets must fill the gauge).
    gw = Gateway([StubWorker("w1")],
                 GatewayConfig(overload_control=True,
                               overload_max_inflight=10))

    def frames():
        yield b"data: {}\n\n"
        yield b"data: {}\n\n"

    with gw._lock:
        gw._inflight += 1  # what _route does before handing off
    it = gw._inflight_watched(frames())
    next(it)
    assert gw.get_stats()["overload"]["inflight"] == 1  # held mid-stream
    list(it)
    assert gw.get_stats()["overload"]["inflight"] == 0  # settled


def test_aimd_starts_at_operator_cap():
    from tpu_engine.serving.worker import WorkerNode

    w = WorkerNode(WorkerConfig(node_id="ov4", model="mlp",
                                dtype="float32", batch_buckets=(1, 2),
                                adaptive_depth=True, max_queue_depth=4))
    try:
        # The adaptive limit replaces the static cap, so it begins at
        # the operator's configured value and adapts from there.
        assert w._aimd.limit == 4
        assert w._admission.effective_limit() == 4
    finally:
        w.stop()


def test_gateway_tenant_bucket_fairness_and_retry_after():
    gw = Gateway([StubWorker("w1")],
                 GatewayConfig(tenant_rate=1.0, tenant_burst=2.0))
    ok = shed = 0
    for i in range(6):
        try:
            gw.route_request({"request_id": f"a{i}", "input_data": [1.0],
                              "tenant": "A"})
            ok += 1
        except Overloaded as exc:
            assert exc.cause == "rate_limit"
            # Never told to retry sooner than a token can exist.
            assert exc.retry_after_s >= 0.5
            shed += 1
    assert ok == 2 and shed == 4
    # Fairness: tenant B admits regardless of A's exhaustion.
    assert gw.route_request({"request_id": "b0", "input_data": [1.0],
                             "tenant": "B"})
    ov = gw.get_stats()["overload"]
    assert ov["rate_limited"] == 4 and ov["tenants"] == 2


def test_gateway_retry_after_monotone_in_pressure():
    gw = Gateway([StubWorker("w1")],
                 GatewayConfig(overload_control=True,
                               overload_max_inflight=10))
    hints = []
    for inflight in (11, 15, 20):
        gw._inflight = inflight - 1  # _route adds this request
        with pytest.raises(Overloaded) as exc:
            gw.route_request({"request_id": "r", "input_data": [1.0]})
        hints.append(exc.value.retry_after_s)
    gw._inflight = 0
    assert hints == sorted(hints) and hints[0] < hints[-1]


def test_overload_counters_family():
    c = OverloadCounters()
    assert set(c.as_dict()) == {"rate_limited", "shed_tier", "shed_depth"}
    assert not c.any_nonzero()


# -- worker -------------------------------------------------------------------


def test_worker_tiered_admission_health_breakdown():
    from tpu_engine.serving.worker import WorkerNode

    w = WorkerNode(WorkerConfig(node_id="ov1", model="mlp",
                                dtype="float32", batch_buckets=(1, 2),
                                max_queue_depth=4,
                                priority_admission=True))
    try:
        for _ in range(3):      # hold 3 of 4 slots (past 70% = 2.8)
            w._admission.admit()
        with pytest.raises(Overloaded):
            w.handle_infer({"request_id": "x", "input_data": [1.0],
                            "priority": "background"})
        # Top tier (and the implicit default) still admits.
        assert w.handle_infer({"request_id": "y",
                               "input_data": [1.0]})["node_id"] == "ov1"
        adm = w.get_health()["admission"]
        assert adm["shed_tier"] == 1
        assert adm["shed_overloaded"] == adm["shed_depth"] \
            + adm["shed_tier"] + adm["shed_adaptive"] == 1
        # Unknown priority with the feature ON is a client error.
        with pytest.raises(ValueError, match="priority"):
            w.handle_infer({"request_id": "z", "input_data": [1.0],
                            "priority": "now"})
    finally:
        for _ in range(3):
            w._admission.release()
        w.stop()


def test_worker_adaptive_depth_exposes_limit_and_feeds_latency():
    from tpu_engine.serving.worker import WorkerNode

    w = WorkerNode(WorkerConfig(node_id="ov2", model="mlp",
                                dtype="float32", batch_buckets=(1, 2),
                                adaptive_depth=True,
                                adaptive_depth_max=16))
    try:
        for i in range(3):
            w.handle_infer({"request_id": f"r{i}", "input_data": [1.0]})
        adm = w.get_health()["admission"]
        assert adm["adaptive"]["max"] == 16
        assert 1 <= adm["adaptive"]["limit"] <= 16
        # Completed requests fed the limiter's latency window.
        assert len(w._aimd._tracker) == 3
    finally:
        w.stop()


def test_worker_brownout_clamps_low_tiers_only():
    from tpu_engine.serving.worker import WorkerNode

    w = WorkerNode(WorkerConfig(node_id="ov3", model="mlp",
                                dtype="float32", batch_buckets=(1, 2),
                                brownout=True, brownout_clamp_tokens=8))
    try:
        clamp_stage = BROWNOUT_STAGES.index("clamp")
        # Below the clamp stage nothing is touched.
        assert w._brownout_clamp(100, 0) == 100
        w._brownout._stage = clamp_stage
        assert w._brownout_clamp(100, 0) == 8           # background
        assert w._brownout_clamp(100, 1) == 8           # batch
        assert w._brownout_clamp(100, TOP_TIER) == 100  # never the top
        assert w._brownout_clamp(4, 0) == 4             # already under
        assert w.get_health()["brownout"]["clamped_requests"] == 2
        assert w.get_health()["brownout"]["stage"] == clamp_stage
    finally:
        w.stop()


# -- scheduler brownout application (one compiled scheduler) ------------------


def test_fleet_stream_under_the_knee_is_identical_with_control_on():
    """The control plane decides WHETHER a request runs, never what it
    says: on an idle fleet of real lanes a background-tier stream
    through a gateway with every overload feature on (bounded depth,
    tiered admission, the brownout ladder, the in-flight gauge) carries
    the tokens the plain fleet's does, and nothing is shed."""
    import jax

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.gateway import _parse_sse
    from tpu_engine.serving.worker import WorkerNode

    _ensure_builtin_models_imported()
    spec = create_model("gpt2-small-test")
    params = spec.init(jax.random.PRNGKey(0))
    req = {"request_id": "ident", "prompt_tokens": [5, 9, 3, 7],
           "max_new_tokens": 12, "priority": "background",
           "temperature": 0.9, "seed": 11}
    streams = {}
    for control in (False, True):
        lanes = [WorkerNode(WorkerConfig(
            node_id=f"ov_lane_{i}", model="gpt2-small-test",
            gen_max_batch_size=2, gen_prefix_cache_mb=0,
            gen_kv_block_size=16,
            gen_mixed_token_budget=16, gen_prefill_chunk=16,
            max_queue_depth=2 if control else 0,
            priority_admission=control, brownout=control,
            brownout_interval_s=0.15),
            engine=InferenceEngine(spec, params=params, dtype="float32"))
            for i in range(2)]
        gw = Gateway(lanes, GatewayConfig(
            overload_control=control,
            overload_max_inflight=8 if control else 0))
        try:
            toks = []
            for frame in gw.route_generate_stream(dict(req)):
                evt = _parse_sse(frame)
                if evt and not evt.get("done"):
                    toks.extend(evt.get("tokens", ()))
            streams[control] = toks
            stats = gw.get_stats()
            if control:
                assert stats["overload"]["shed_tier"] == 0
            else:
                assert "overload" not in stats
        finally:
            gw.stop()
            for w in lanes:
                w.stop()
    assert len(streams[False]) == 12
    assert streams[True] == streams[False]


@pytest.fixture(scope="module")
def bo_sched():
    import jax

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    _ensure_builtin_models_imported()
    spec = create_model("gpt2-small-test", max_seq=128)
    s = ContinuousGenerator(spec, params=spec.init(jax.random.PRNGKey(0)),
                            dtype="float32", n_slots=2, max_seq=128,
                            kv_block_size=16, prefill_chunk=16,
                            mixed_token_budget=16,
                            spec_k=2)
    yield s
    s.stop()


def test_brownout_stream_identity_and_spec_suspension(bo_sched):
    prompt = [5, 9, 3, 5, 9, 3, 5, 9]    # loopy: the drafter proposes
    base = bo_sched.generate([prompt], max_new_tokens=12)[0]
    assert bo_sched.stats()["spec"]["proposed_tokens"] > 0
    assert "brownout" not in bo_sched.stats()
    bo_sched.set_brownout(budget_frac=0.5, suspend_spec=True,
                          defer_swap_in=True)
    try:
        p0 = bo_sched.stats()["spec"]["proposed_tokens"]
        degraded = bo_sched.generate([prompt], max_new_tokens=12)[0]
        # Every stage degrades work SHAPE, never stream content.
        assert degraded == base
        # Suspended drafting: no new proposals.
        assert bo_sched.stats()["spec"]["proposed_tokens"] == p0
        st = bo_sched.stats()["brownout"]
        assert st == {"budget_frac": 0.5, "spec_suspended": True,
                      "swap_in_deferred": True}
        # Budget shrink is visible to the tick loop; the compiled chunk
        # cap (the executable width) is untouched.
        assert bo_sched._effective_mixed_budget() == 8
        assert bo_sched._chunk_cap == 16
        # Swap-in deferral: the lookup reserve becomes unsatisfiable.
        assert bo_sched._swap_reserve() == bo_sched._pool.num_blocks
    finally:
        bo_sched.set_brownout()
    assert "brownout" not in bo_sched.stats()
    assert bo_sched._effective_mixed_budget() == 16


def test_brownout_budget_floor_allows_admission(bo_sched):
    # Even a brutal budget fraction leaves >= 1 token per tick so
    # admission can never deadlock behind the degradation.
    bo_sched.set_brownout(budget_frac=0.0001)
    try:
        assert bo_sched._effective_mixed_budget() >= 1
        out = bo_sched.generate([[7, 2]], max_new_tokens=4)[0]
        assert len(out) == 4
    finally:
        bo_sched.set_brownout()
