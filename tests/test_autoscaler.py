"""Elastic fleet: the closed-loop autoscaler, /admin/fleet actuators,
named degraded states, and the defaults-off wire guarantee.

DESIGN.md "Elastic fleet": the controller reads per-lane overload
pressure and actuates through the existing ladders — scale-down via the
PR 11 drain + live-stream-migration removal (zero tokens lost),
scale-up via probe-then-register (a lane joins the ring only after a
passing /health probe), role rebalancing via the /admin/role
drain+migrate+undrain flip. A wedged actuator is bounded by timeouts
and latches a NAMED degraded-but-serving state; every decision bumps a
FleetCounters field with a matching ``fleet`` marker span.
"""

import threading
import time

import pytest

from tpu_engine.serving.autoscaler import (DEGRADED_DRAIN_WEDGED,
                                           DEGRADED_SPAWN_WEDGED,
                                           FleetAutoscaler,
                                           InProcessLaneProvider,
                                           StandbyLaneProvider,
                                           lane_pressure)
from tpu_engine.serving.gateway import Gateway, _parse_sse
from tpu_engine.serving.resilience import FleetCounters
from tpu_engine.serving.worker import WorkerNode
from tpu_engine.utils.config import GatewayConfig, WorkerConfig

MLP_KW = dict(model="mlp", dtype="float32", batch_buckets=(1, 2))
GEN_KW = dict(model="gpt2-small-test", dtype="float32",
              gen_scheduler="continuous", gen_step_chunk=2,
              gen_kv_block_size=16, gen_kv_blocks=40,
              gen_prefill_chunk=16, gen_max_batch_size=4)
PROMPT = [5, 9, 3, 17, 4, 22, 8]


def _mlp(node_id):
    return WorkerNode(WorkerConfig(node_id=node_id, **MLP_KW))


def _fleet_spans(gw):
    return [s for s in gw.tracer.snapshot() if s["op"] == "fleet"]


def assert_counters_match_spans(gw):
    fl = gw.fleet.as_dict()
    expect = sum(fl[f] for f in FleetCounters.SPAN_FIELDS)
    spans = _fleet_spans(gw)
    assert len(spans) == expect, (fl, [s["attrs"] for s in spans])


@pytest.fixture(scope="module")
def gen_fleet():
    """Two continuous-scheduler lanes sharing one parameter set (the
    lane-uniformity deployments migration assumes)."""
    workers = [WorkerNode(WorkerConfig(node_id=f"g{i}", **GEN_KW))
               for i in range(2)]
    p0 = workers[0].engine.params
    for w in workers[1:]:
        w.apply_weights(p0)
    yield workers
    for w in workers:
        w.stop()


# -- counters + defaults-off ---------------------------------------------------

def test_fleet_counters_schema():
    c = FleetCounters()
    assert not c.any_nonzero()
    for f in FleetCounters.FIELDS:
        assert c.get(f) == 0
    c.bump("scale_up_attempted")
    assert c.as_dict()["scale_up_attempted"] == 1 and c.any_nonzero()
    # Every fleet decision is span-paired (counters == spans).
    assert FleetCounters.SPAN_FIELDS == FleetCounters.FIELDS


def test_defaults_off_stats_schema_and_no_controller():
    """With --autoscale absent and no /admin/fleet actuation, /stats
    keeps the reference-exact key set (no "fleet" key) and no
    controller thread exists."""
    gw = Gateway([_mlp("w1")], GatewayConfig())
    try:
        assert set(gw.get_stats()) == {"total_workers", "total_requests",
                                       "failovers", "circuit_breakers"}
        assert gw._autoscaler is None
        st = gw.fleet_admin({"action": "status"})
        assert st["ok"] and st["state"] == "steady"
        assert st["autoscale"] is False
        # The status read itself must not create a fleet stats block.
        assert "fleet" not in gw.get_stats()
    finally:
        gw.stop()


def test_stats_fleet_block_appears_with_flag_or_activity():
    gw = Gateway([_mlp("w1")], GatewayConfig(autoscale=True))
    try:
        fl = gw.get_stats()["fleet"]
        assert fl["lanes"] == 1 and fl["degraded"] == {}
        for f in FleetCounters.FIELDS:
            assert fl[f] == 0
    finally:
        gw.stop()


# -- pressure folding ----------------------------------------------------------

def test_lane_pressure_folds_health_signals():
    # AIMD adaptive limit wins over the static depth bound.
    assert lane_pressure({"admission": {
        "queue_depth": 3, "max_queue_depth": 12,
        "adaptive": {"limit": 6}}}) == pytest.approx(0.5)
    assert lane_pressure({"admission": {
        "queue_depth": 3, "max_queue_depth": 12}}) == pytest.approx(0.25)
    # Decode-slot occupancy is the fallback signal.
    assert lane_pressure({"generator": {"active": 2, "n_slots": 4}}) \
        == pytest.approx(0.5)
    # An engaged brownout stage clamps the lane to saturated.
    assert lane_pressure({"generator": {"active": 0, "n_slots": 4},
                          "brownout": {"stage": 2}}) == pytest.approx(1.0)
    # No load signal at all -> None (dropped from the mean, not "idle").
    assert lane_pressure({"healthy": True}) is None
    assert lane_pressure(None) is None


# -- manual actuators (the /admin/fleet surface) -------------------------------

def test_scale_up_probe_gate_and_idempotency():
    gw = Gateway([_mlp("w1")], GatewayConfig())
    w2 = _mlp("w2")
    try:
        ctl = gw._fleet_controller()
        res = ctl.scale_up(worker=w2)
        assert res == {"ok": True, "status": "registered", "worker": "w2"}
        assert "w2" in gw.worker_names()
        # Idempotent: a second add of a member is a named no-op that
        # does not touch the counters.
        before = gw.fleet.as_dict()
        assert ctl.scale_up(worker=w2)["status"] == "already-member"
        assert gw.fleet.as_dict() == before
        assert_counters_match_spans(gw)
    finally:
        gw.stop()
        w2.stop()


def test_scale_up_spawn_wedged_named_state_still_serving():
    """A spawn that never probes healthy (dead address) is bounded by
    autoscale_spawn_timeout_s and lands in the NAMED spawn-wedged
    degraded state — with the fleet still serving."""
    gw = Gateway([_mlp("w1")],
                 GatewayConfig(autoscale_spawn_timeout_s=0.6))
    try:
        res = gw.fleet_admin({"action": "add", "worker": "localhost:9"})
        assert res["ok"] is False
        assert res["status"] == DEGRADED_SPAWN_WEDGED
        st = gw.fleet_status()
        assert st["state"] == "degraded:spawn-wedged"
        assert st["degraded"] == {"localhost:9": DEGRADED_SPAWN_WEDGED}
        # The wedge never reached the ring, and the fleet still serves.
        assert gw.worker_names() == ["w1"]
        assert gw.route_request({"request_id": "r1",
                                 "input_data": [1.0]})["node_id"]
        fl = gw.get_stats()["fleet"]
        assert fl["scale_up_failed"] == 1 and fl["degraded_entered"] == 1
        assert_counters_match_spans(gw)
        # Operator clear answers named statuses both ways.
        assert gw.fleet_admin({"action": "clear",
                               "worker": "localhost:9"})["status"] \
            == "cleared"
        assert gw.fleet_admin({"action": "clear",
                               "worker": "localhost:9"})["status"] \
            == "not-degraded"
        assert gw.fleet_status()["state"] == "steady"
    finally:
        gw.stop()


def test_scale_down_unknown_lane_and_missing_args():
    gw = Gateway([_mlp("w1")], GatewayConfig())
    try:
        assert gw.fleet_admin({"action": "remove",
                               "worker": "ghost"})["status"] \
            == "unknown-lane"
        assert gw.fleet_admin({"action": "remove"})["status"] \
            == "missing-worker"
        assert gw.fleet_admin({"action": "add"})["status"] \
            == "missing-worker"
        assert gw.fleet_admin({"action": "rebalance",
                               "worker": "w1"})["status"] \
            == "missing-worker-or-role"
        assert gw.fleet_admin({"action": "bogus"})["status"] \
            == "unknown-action:bogus"
    finally:
        gw.stop()


def test_scale_down_drain_wedged_named_state_lane_still_removed():
    """The kill -9 mid-drain shape: the drain call errors, removal
    proceeds (a wedged lane must never hang membership), and the fleet
    latches the NAMED drain-wedged state while still serving."""
    w1, w2 = _mlp("w1"), _mlp("w2")
    gw = Gateway([w1, w2], GatewayConfig(drain_timeout_s=1.0))
    try:
        def boom():
            raise ConnectionError("lane killed mid-drain")

        gw.lane_clients()["w2"].drain = boom
        res = gw._fleet_controller().scale_down(name="w2", manual=True)
        assert res["ok"] is True and res["status"] == "removed-degraded"
        assert gw.worker_names() == ["w1"]
        st = gw.fleet_status()
        assert st["degraded"] == {"w2": DEGRADED_DRAIN_WEDGED}
        assert st["state"] == "degraded:drain-wedged"
        # Still serving on the survivor.
        assert gw.route_request({"request_id": "r1",
                                 "input_data": [1.0]})["node_id"]
        fl = gw.get_stats()["fleet"]
        assert fl["scale_down_completed"] == 1
        assert fl["degraded_entered"] == 1
        assert gw.migration.get("drain_failures") == 1
        assert_counters_match_spans(gw)
    finally:
        gw.stop()
        w1.stop()
        w2.stop()


def test_scale_down_rides_live_stream_migration(gen_fleet):
    """Scale-down ALWAYS drains via the PR 11 ladder: a live stream on
    the retiring lane migrates mid-stream and finishes byte-identically
    to an uninterrupted control run — zero tokens lost."""
    gw = Gateway(gen_fleet, GatewayConfig(migrate_streams=True,
                                          migrate_timeout_s=20.0))
    try:
        lane = gw._ring.get_node("el-0")
        control = gen_fleet[0].generator.generate(
            [PROMPT], max_new_tokens=16)[0]
        toks, final = [], [None]
        armed = threading.Event()
        # The export must find the row mid-stream, however slow this
        # thread is to be woken: the lane holds once, after the third
        # streamed token, until the export command waits in its queue
        # (13 more tokens of this model take a few milliseconds).
        gen = next(w for w in gen_fleet if w.node_id == lane).generator
        push, held = gen._push_stream, []

        def push_then_hold(row, req):
            push(row, req)
            if req.streamed >= 3 and not held:
                held.append(row)
                limit = time.monotonic() + 60
                while gen._migrate_q.empty() and time.monotonic() < limit:
                    time.sleep(0.001)

        gen._push_stream = push_then_hold

        def consume():
            for frame in gw.route_generate_stream(
                    {"request_id": "el-0", "prompt_tokens": PROMPT,
                     "max_new_tokens": 16}):
                evt = _parse_sse(frame)
                if evt is None:
                    continue
                if evt.get("done"):
                    final[0] = evt
                    break
                if "tokens" in evt:
                    toks.extend(evt["tokens"])
                    if len(toks) >= 3:
                        armed.set()

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        assert armed.wait(120), "stream never reached the drain point"
        res = gw._fleet_controller().scale_down(name=lane, manual=True)
        assert res["ok"] and res["status"] == "removed", res
        t.join(timeout=120)
        assert final[0] is not None and toks == control
        assert lane not in gw.worker_names()
        assert gw.fleet_status()["state"] == "steady"
        assert gw.migration.get("streams_migrated") == 1
        assert_counters_match_spans(gw)
    finally:
        gw.stop()
        # Re-register both lanes for other tests sharing the fixture.
        for w in gen_fleet:
            w.generator.__dict__.pop("_push_stream", None)
            w.undrain()


def test_rebalance_through_admin_role(gen_fleet):
    gw = Gateway(gen_fleet, GatewayConfig(disagg=True))
    try:
        res = gw.fleet_admin({"action": "rebalance", "worker": "g0",
                              "role": "prefill"})
        assert res["ok"] and res["status"] == "rebalanced"
        assert gw.worker_roles()["g0"] == "prefill"
        bad = gw.fleet_admin({"action": "rebalance", "worker": "g0",
                              "role": "sideways"})
        assert bad["ok"] is False and bad["status"] == "rebalance-failed"
        fl = gw.get_stats()["fleet"]
        assert fl["rebalance_completed"] == 1
        assert fl["rebalance_failed"] == 1
        assert_counters_match_spans(gw)
    finally:
        gw.fleet_admin({"action": "rebalance", "worker": "g0",
                        "role": "both"})
        gw.stop()


# -- the closed loop (synchronous ticks) ---------------------------------------

class _TickHarness:
    """A controller with observation stubbed: ticks run synchronously
    against scripted per-lane pressures."""

    def __init__(self, gw, provider, pressures, **cfg_over):
        cfg = GatewayConfig(autoscale=True, autoscale_cooldown_s=0.0,
                            autoscale_min_lanes=1, **cfg_over)
        self.ctl = FleetAutoscaler(gw, provider=provider, config=cfg)
        self.pressures = pressures
        self.ctl.observe = lambda: {
            lane: self.pressures.get(lane, 0.0)
            for lane in gw.lane_clients()}


def test_tick_scales_up_then_down_with_clamps_and_cooldown():
    gw = Gateway([_mlp("w1")], GatewayConfig())
    extra = []

    def factory(idx):
        w = _mlp(f"spawn_{idx+1}")
        extra.append(w)
        return w

    provider = InProcessLaneProvider(factory, max_lanes=4)
    try:
        h = _TickHarness(gw, provider, {}, autoscale_max_lanes=2,
                         autoscale_spawn_timeout_s=5.0)
        ctl = h.ctl
        # Saturated fleet -> spawn exactly one lane per tick.
        h.pressures = {"w1": 1.0, "spawn_1": 1.0}
        ctl._tick()
        assert sorted(gw.worker_names()) == ["spawn_1", "w1"]
        # At the max-lanes clamp the decision is HELD, not actuated.
        ctl._tick()
        assert sorted(gw.worker_names()) == ["spawn_1", "w1"]
        assert gw.fleet.get("decisions_held") == 1
        # Cooldown suppression: an idle fleet wants to retire, but the
        # cooldown window holds the decision first.
        ctl.config.autoscale_cooldown_s = 60.0
        ctl._last_action_ts = time.monotonic()
        h.pressures = {"w1": 0.0, "spawn_1": 0.0}
        ctl._tick()
        assert sorted(gw.worker_names()) == ["spawn_1", "w1"]
        assert gw.fleet.get("decisions_held") == 2
        # Cooldown expired -> retire one lane (lowest weight/streams).
        ctl.config.autoscale_cooldown_s = 0.0
        ctl._last_action_ts = 0.0
        ctl._tick()
        assert len(gw.worker_names()) == 1
        # At min_lanes the retire decision is held.
        ctl._tick()
        assert len(gw.worker_names()) == 1
        assert gw.fleet.get("decisions_held") == 3
        fl = gw.get_stats()["fleet"]
        assert fl["scale_up_completed"] == 1
        assert fl["scale_down_completed"] == 1
        assert_counters_match_spans(gw)
    finally:
        gw.stop()
        for w in extra:
            w.stop()


def test_tick_publishes_pressure_and_clears_spawn_wedge():
    gw = Gateway([_mlp("w1")], GatewayConfig())
    try:
        h = _TickHarness(gw, None, {"w1": 0.5})
        gw.fleet_enter_degraded("w1", DEGRADED_SPAWN_WEDGED)
        h.ctl._tick()
        # Mid-band: no actuation, pressure published, and the wedge on
        # a lane that IS serving auto-clears.
        assert gw.get_stats()["fleet"]["pressure"] == pytest.approx(0.5)
        assert gw.fleet_status()["state"] == "steady"
        assert gw.fleet.get("degraded_cleared") == 1
        assert_counters_match_spans(gw)
    finally:
        gw.stop()


def test_tick_blind_hold_never_retires_unobserved_fleet():
    """The blind-hold rule: zero observable lanes -> no decision at all;
    a PARTIALLY blind fleet may scale up (adding capacity is the safe
    direction) but never down — the unobservable lane (health blocked
    behind a compile, a stalled box) might be the loaded one."""
    w1, w2 = _mlp("b1"), _mlp("b2")
    gw = Gateway([w1, w2], GatewayConfig())
    extra = []

    def factory(idx):
        w = _mlp(f"bspawn_{idx + 1}")
        extra.append(w)
        return w

    provider = InProcessLaneProvider(factory, max_lanes=4)
    try:
        h = _TickHarness(gw, provider, {}, autoscale_max_lanes=4,
                         autoscale_spawn_timeout_s=5.0)
        ctl = h.ctl
        # Every lane blind: hold, never actuate.
        h.pressures = {"b1": None, "b2": None}
        ctl._tick()
        assert len(gw.worker_names()) == 2
        assert gw.fleet.get("decisions_held") == 1
        # One lane blind, observed mean idle: retirement is HELD.
        h.pressures = {"b1": 0.0, "b2": None}
        ctl._tick()
        assert len(gw.worker_names()) == 2
        assert gw.fleet.get("decisions_held") == 2
        assert not gw.fleet.get("scale_down_attempted")
        # One lane blind, observed mean saturated: scale-UP proceeds.
        h.pressures = {"b1": 1.0, "b2": None}
        ctl._tick()
        assert len(gw.worker_names()) == 3
        assert gw.get_stats()["fleet"]["scale_up_completed"] == 1
        assert_counters_match_spans(gw)
    finally:
        gw.stop()
        for w in extra:
            w.stop()


def test_rebalance_arm_hysteresis():
    w = [_mlp(f"w{i}") for i in range(4)]
    gw = Gateway(w, GatewayConfig(disagg=True))
    try:
        gw._roles.update({"w0": "prefill", "w1": "prefill",
                          "w2": "decode", "w3": "decode"})
        flips = []
        h = _TickHarness(gw, None, {}, disagg=True,
                         autoscale_rebalance_band=2.0)
        ctl = h.ctl
        ctl.rebalance = lambda lane, role: (
            flips.append((lane, role)) or {"ok": True})
        # Prefill side 4x hotter than decode -> flip a decode lane.
        samples = {"w0": 0.8, "w1": 0.8, "w2": 0.2, "w3": 0.2}
        assert ctl._maybe_rebalance(samples) is True
        assert flips == [("w2", "prefill")]
        # Still outside the band, but the arm is DISARMED until the
        # ratio returns inside band/2 — no flip storm.
        assert ctl._maybe_rebalance(samples) is False
        assert len(flips) == 1
        # Back inside band/2 re-arms; the next excursion flips again.
        assert ctl._maybe_rebalance(
            {"w0": 0.5, "w1": 0.5, "w2": 0.5, "w3": 0.5}) is False
        ctl._last_action_ts = 0.0
        assert ctl._maybe_rebalance(samples) is True
        assert len(flips) == 2
    finally:
        gw.stop()
        for x in w:
            x.stop()


def test_run_loop_starts_and_stops_cleanly():
    gw = Gateway([_mlp("w1")],
                 GatewayConfig(autoscale=True,
                               autoscale_interval_s=0.05))
    try:
        ctl = gw.engage_autoscaler(provider=StandbyLaneProvider())
        assert ctl.running and gw.fleet_status()["autoscale"] is True
        time.sleep(0.3)  # a few live ticks against the real observe()
        assert gw.get_stats()["fleet"].get("pressure") is not None
        ctl.stop()
        assert not ctl.running
        assert_counters_match_spans(gw)
    finally:
        gw.stop()


def test_manual_surface_survives_loop_stop():
    """Regression: /admin/fleet actuations on a STOPPED controller must
    run the same ladders — the actuator pool is re-created on demand
    and the probe gate's wait is re-armed (a stopped loop once left the
    pool shut down, so a manual remove raised instead of draining)."""
    w1, w2 = _mlp("m1"), _mlp("m2")
    gw = Gateway([w1, w2], GatewayConfig(autoscale=True,
                                         autoscale_interval_s=0.05))
    try:
        ctl = gw.engage_autoscaler(provider=StandbyLaneProvider())
        ctl.stop()
        assert not ctl.running
        res = gw.fleet_admin({"action": "remove", "worker": "m2"})
        assert res["status"] == "removed"
        assert gw.worker_names() == ["m1"]
        assert_counters_match_spans(gw)
    finally:
        gw.stop()
        w1.stop()
        w2.stop()


# -- providers -----------------------------------------------------------------

def test_standby_provider_lease_cycle():
    p = StandbyLaneProvider(["a:1", "b:2"])
    assert p.capacity() == 2
    first = p.spawn()
    assert first == "a:1" and p.capacity() == 1
    p.retire("a:1")
    assert p.capacity() == 2
    assert p.spawn() and p.spawn()
    assert p.spawn() is None and p.capacity() == 0


def test_inprocess_provider_stops_retired_lanes():
    stopped = []

    class FakeLane:
        def __init__(self, idx):
            self.node_id = f"lane{idx}"

        def stop(self):
            stopped.append(self.node_id)

    dropped = []
    p = InProcessLaneProvider(lambda i: FakeLane(i), max_lanes=1,
                              on_retire=dropped.append)
    lane = p.spawn()
    assert lane.node_id == "lane0" and p.capacity() == 0
    assert p.spawn() is None
    p.retire("lane0")  # by NAME, the controller's handle
    assert stopped == ["lane0"] and len(dropped) == 1
    assert p.capacity() == 1


# -- scheduler drain-pressure stat ---------------------------------------------

def test_drain_pressure_stat_gated_on_draining(gen_fleet):
    w = gen_fleet[0]
    assert "drain_pressure" not in w.generator.stats()
    assert w.drain() == "draining"
    try:
        st = w.generator.stats()
        assert st["drain_pressure"] == pytest.approx(
            st["active"] / max(1, w.generator.n_slots))
    finally:
        assert w.undrain() == "undrained"
    assert "drain_pressure" not in w.generator.stats()
