"""Elastic fleet: closed-loop autoscaling over the serving gateway.

DESIGN.md "Elastic fleet". Every primitive this controller composes
already exists in the serving stack — it closes the loop ROADMAP item 4
left open:

- **Control signal** (PR 9): per-lane overload pressure — AIMD adaptive
  depth limit, admission queue fill, brownout stage — read off each
  lane's ``/health``, folded into one mean fleet pressure in [0, ~1+].
- **Scale-down actuator** (PR 11): ``Gateway.remove_worker(drain=True)``
  — bounded graceful drain, then live stream migration off the retiring
  lane (KV chain over the wire, zero re-prefilled tokens). The replay
  resume is the ladder's last rung, never the plan.
- **Scale-up actuator**: probe-before-register — a spawned lane joins
  the rings ONLY after a passing ``/health`` probe, so the ring never
  routes to a lane that is still compiling or dead on arrival.
- **Role-rebalance arm** (PR 14): ``Gateway.set_worker_role`` — the
  drain + migrate + undrain role flip — driven by the observed
  prefill:decode pressure ratio with a hysteresis band.

The controller is crash-tolerant by construction: every decision is
idempotent (spawn of a member → ``already-member``; retire of a
non-member → ``unknown-lane``), every actuator is bounded by a timeout,
and a wedged actuator — a lane that will not drain, a spawn that never
turns healthy — lands the fleet in a NAMED degraded-but-serving state
(``drain-wedged`` / ``spawn-wedged``) instead of hanging the loop.
Every decision bumps a ``FleetCounters`` field AND drops a matching
``fleet`` marker span (counters == spans, chaos-asserted by
``tools/fault_injection.py --elastic``).

Engagement is ``--autoscale`` (default off: no controller thread, no
``/stats`` ``fleet`` block, wire bytes identical to the static fleet).
The ``/admin/fleet`` manual surface works either way — manual actions
run the same actuator ladders on an unstarted controller.
"""

from __future__ import annotations

import concurrent.futures
import threading
import time
from typing import Dict, List, Optional

from tpu_engine.serving.clients import HttpWorkerClient

# Named degraded-but-serving states (DESIGN.md "Elastic fleet").
DEGRADED_SPAWN_WEDGED = "spawn-wedged"
DEGRADED_DRAIN_WEDGED = "drain-wedged"


def lane_pressure(health: dict) -> Optional[float]:
    """Fold one lane's ``/health`` body into a scalar pressure.

    Ladder (most-informative signal wins): AIMD adaptive limit (queue
    fill against the *adapted* depth), plain admission queue fill,
    decode-slot occupancy. An engaged brownout stage clamps the lane to
    saturated (>= 1.0) regardless — the lane is already degrading
    itself. ``None`` when the body carries no load signal at all (the
    caller drops the lane from the mean instead of reading it as idle).
    """
    if not isinstance(health, dict):
        return None
    p: Optional[float] = None
    adm = health.get("admission")
    if isinstance(adm, dict):
        depth = float(adm.get("queue_depth", 0) or 0)
        adaptive = adm.get("adaptive")
        limit = 0.0
        if isinstance(adaptive, dict):
            limit = float(adaptive.get("limit", 0) or 0)
        if limit <= 0:
            limit = float(adm.get("max_queue_depth", 0) or 0)
        if limit > 0:
            p = depth / limit
    if p is None:
        gen = health.get("generator")
        if isinstance(gen, dict):
            slots = float(gen.get("n_slots", 0) or 0)
            if slots > 0:
                p = float(gen.get("active", 0) or 0) / slots
    bo = health.get("brownout")
    if isinstance(bo, dict) and int(bo.get("stage", 0) or 0) > 0:
        p = max(p or 0.0, 1.0)
    return None if p is None else max(0.0, p)


class StandbyLaneProvider:
    """Warm standby pool: pre-launched worker ADDRESSES the controller
    checks out on scale-up and returns on scale-down. The classic
    chips-are-provisioned-but-idle elastic shape — spawn is instant
    (the probe gate still applies: a standby that died while parked
    never reaches the ring), retire hands the address back for the next
    ramp. Thread-safe; ``spawn`` returns ``None`` when the pool is dry."""

    def __init__(self, addresses: Optional[List[str]] = None):
        self._lock = threading.Lock()
        self._standby: List[str] = list(addresses or [])
        self._leased: set = set()

    def add(self, address: str) -> None:
        with self._lock:
            if address not in self._standby and address not in self._leased:
                self._standby.append(address)

    def spawn(self) -> Optional[str]:
        with self._lock:
            if not self._standby:
                return None
            addr = self._standby.pop(0)
            self._leased.add(addr)
            return addr

    def destroy(self, handle) -> None:
        """A lease that never turned healthy goes back to standby (the
        operator may revive the process; the probe gate re-screens it)."""
        self.retire(handle)

    def retire(self, handle) -> None:
        with self._lock:
            addr = str(handle)
            self._leased.discard(addr)
            if addr not in self._standby:
                self._standby.append(addr)

    def capacity(self) -> int:
        with self._lock:
            return len(self._standby)


class InProcessLaneProvider:
    """Spawn lanes as in-process worker objects from a factory —
    ``factory(index) -> WorkerNode``-like object with a ``node_id`` and
    ``get_health()``. Powers ``serve_combined --autoscale`` and the
    in-process fleets of ``tests/test_autoscaler.py``, where a "lane" is a
    scheduler instance, not a remote process. Retired lanes are looked
    up by either the object or its lane NAME (the controller retires by
    name), stopped, and reported to ``on_retire`` so the host app can
    drop them from its own bookkeeping."""

    def __init__(self, factory, max_lanes: int = 0, on_retire=None):
        self._factory = factory
        self._max = int(max_lanes)
        self._on_retire = on_retire
        self._lock = threading.Lock()
        self._by_name: Dict[str, object] = {}
        self._next_idx = 0

    def spawn(self):
        with self._lock:
            if self._max and len(self._by_name) >= self._max:
                return None
            idx = self._next_idx
            self._next_idx += 1
        try:
            worker = self._factory(idx)
        except Exception:
            return None
        if worker is not None:
            with self._lock:
                self._by_name[str(getattr(worker, "node_id", worker))] = \
                    worker
        return worker

    def destroy(self, handle) -> None:
        self.retire(handle)

    def retire(self, handle) -> None:
        name = str(getattr(handle, "node_id", handle))
        with self._lock:
            worker = self._by_name.pop(name, None)
        if worker is None:
            worker = handle if not isinstance(handle, str) else None
        if worker is None:
            return
        stop = getattr(worker, "stop", None)
        if callable(stop):
            try:
                stop()
            except Exception:
                pass
        if self._on_retire is not None:
            try:
                self._on_retire(worker)
            except Exception:
                pass

    def capacity(self) -> Optional[int]:
        with self._lock:
            if not self._max:
                return None  # unbounded
            return max(0, self._max - len(self._by_name))


class FleetAutoscaler:
    """The gateway-side elastic-fleet controller.

    Two halves share one actuator ladder:

    - ``start()`` runs the closed loop (``--autoscale``): each tick
      observes per-lane pressure, publishes the mean, auto-clears stale
      ``spawn-wedged`` states, and actuates at most ONE decision —
      spawn (mean above ``autoscale_up_pressure``), retire (below
      ``autoscale_down_pressure``), or role flip (prefill:decode
      pressure ratio outside the hysteresis band) — subject to the
      min/max lane clamps, the actuation cooldown, and the blind-hold
      rule (no decision on zero samples; no retirement unless EVERY
      lane was observed — an unobservable lane must never read as
      idle). Suppressed decisions count as ``decisions_held``.
    - ``scale_up`` / ``scale_down`` / ``rebalance`` are the manual
      ``/admin/fleet`` actuations; they never touch the loop's
      thread-owned state, so an UNSTARTED controller serves them with
      identical semantics (probe gate, drain+migrate ladder, named
      degraded states, counters==spans).
    """

    def __init__(self, gateway, provider=None, config=None):
        self.gateway = gateway
        self.provider = provider
        self.config = config if config is not None else gateway.config
        self._stop_event = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Bounded actuation pool: a wedged remove_worker occupies one
        # slot past its timeout instead of hanging the caller. Created
        # on demand — the manual /admin/fleet surface outlives stop().
        self._exec: Optional[concurrent.futures.ThreadPoolExecutor] = None
        self._exec_lock = threading.Lock()
        # Loop-owned state (touched only from _run/_tick; registered as
        # thread-owned in tools/analyze/registry.py).
        self._last_action_ts = 0.0
        self._rebalance_armed = True

    # -- lifecycle ------------------------------------------------------------

    @property
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> None:
        if self.running:
            return
        self._stop_event.clear()
        self._thread = threading.Thread(
            target=self._run, name="fleet-autoscaler", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop_event.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=5.0)
        self._thread = None
        # A stopped controller still serves /admin/fleet with identical
        # semantics: re-arm the probe gate's wait and retire the
        # actuator pool (a later manual action re-creates it).
        self._stop_event.clear()
        with self._exec_lock:
            ex, self._exec = self._exec, None
        if ex is not None:
            ex.shutdown(wait=False)

    def _actuators(self) -> concurrent.futures.ThreadPoolExecutor:
        with self._exec_lock:
            if self._exec is None:
                self._exec = concurrent.futures.ThreadPoolExecutor(
                    max_workers=2, thread_name_prefix="fleet-actuator")
            return self._exec

    def _run(self) -> None:
        interval = max(0.05, float(self.config.autoscale_interval_s))
        while not self._stop_event.wait(interval):
            try:
                self._tick()
            except Exception:
                # The loop must survive any single tick's failure — a
                # controller crash must never take serving with it.
                pass

    # -- observation ----------------------------------------------------------

    def observe(self) -> Dict[str, Optional[float]]:
        """One pressure sample per lane (``None`` = unreachable or no
        load signal). Uses the dedicated probe connection on HTTP lanes
        so pool exhaustion by long streams never reads as pressure-0."""
        out: Dict[str, Optional[float]] = {}
        for lane, client in self.gateway.lane_clients().items():
            try:
                probe = getattr(client, "probe_health", None)
                health = probe(timeout_s=2.0) if callable(probe) \
                    else client.health()
                out[lane] = lane_pressure(health)
            except Exception:
                out[lane] = None
        return out

    def fleet_pressure(self, samples: Dict[str, Optional[float]]) -> float:
        vals = [v for v in samples.values() if v is not None]
        return sum(vals) / len(vals) if vals else 0.0

    # -- the closed loop ------------------------------------------------------

    def _tick(self) -> None:
        gw = self.gateway
        samples = self.observe()
        lanes = sorted(samples)
        mean = self.fleet_pressure(samples)
        if getattr(self.config, "autoscale_slo_feed", False):
            # SLO burn feed (observability plane, opt-in): take the MAX
            # of lane pressure and the worst objective's burn mapped to
            # [0, 1]. The feed only ever ADDS pressure — an idle fleet
            # burning budget (e.g. TTFT blown by compile stalls) scales
            # up, but a healthy burn can never mask lane saturation.
            try:
                mean = max(mean, gw.slo_pressure())
            except Exception:
                pass  # telemetry must never wedge the control loop
        gw.fleet_observe(mean)
        blind = sum(1 for v in samples.values() if v is None)

        # Blind-hold: a lane that cannot be observed (health blocked
        # behind a compile, a saturated accept loop, a stalled box) must
        # never read as IDLE. With no samples at all there is no basis
        # for any decision; scaling DOWN additionally requires every
        # lane observed — the unobservable lane may be the loaded one,
        # and retirement is the unsafe direction (scale-up on partial
        # data only adds capacity).
        if blind == len(samples):
            gw._fleet_count("decisions_held", reason="blind",
                            pressure=round(mean, 4))
            return

        # Recovery sweep: a spawn-wedged lane that later turned healthy
        # and joined the ring clears its own state (drain-wedged is an
        # operator signal — a kill -9 mid-drain stays latched until
        # /admin/fleet clear says it was seen).
        for lane, reason in list(gw.fleet_status()["degraded"].items()):
            if reason == DEGRADED_SPAWN_WEDGED and lane in samples:
                gw.fleet_clear_degraded(lane)

        if self._maybe_rebalance(samples):
            return

        n = len(lanes)
        up = mean > float(self.config.autoscale_up_pressure)
        down = mean < float(self.config.autoscale_down_pressure)
        if not up and not down:
            return
        max_lanes = int(self.config.autoscale_max_lanes)
        min_lanes = max(1, int(self.config.autoscale_min_lanes))
        if up and max_lanes and n >= max_lanes:
            gw._fleet_count("decisions_held", reason="max-lanes",
                            pressure=round(mean, 4))
            return
        if up and (self.provider is None
                   or self.provider.capacity() == 0):
            gw._fleet_count("decisions_held", reason="provider-exhausted",
                            pressure=round(mean, 4))
            return
        if down and n <= min_lanes:
            gw._fleet_count("decisions_held", reason="min-lanes",
                            pressure=round(mean, 4))
            return
        if down and blind:
            gw._fleet_count("decisions_held", reason="blind",
                            pressure=round(mean, 4))
            return
        now = time.monotonic()
        if now - self._last_action_ts \
                < float(self.config.autoscale_cooldown_s):
            gw._fleet_count("decisions_held", reason="cooldown",
                            pressure=round(mean, 4))
            return
        if up:
            res = self.scale_up()
        else:
            victim = self._pick_victim(samples)
            if victim is None:
                gw._fleet_count("decisions_held", reason="no-victim",
                                pressure=round(mean, 4))
                return
            res = self.scale_down(name=victim)
        if res.get("status") != "already-member":
            self._last_action_ts = time.monotonic()

    def _maybe_rebalance(self, samples: Dict[str, Optional[float]]) -> bool:
        """The role-rebalance arm: flip one lane prefill<->decode when
        the observed pressure ratio leaves the hysteresis band; re-arm
        only once it returns inside band/2. Never strands a role at
        zero lanes. Returns True when a flip was actuated."""
        band = float(self.config.autoscale_rebalance_band)
        if band <= 1.0 or not self.config.disagg:
            return False
        roles = self.gateway.worker_roles()
        pre = [v for l, v in samples.items()
               if v is not None and roles.get(l) == "prefill"]
        dec = [v for l, v in samples.items()
               if v is not None and roles.get(l) in ("decode", "both")]
        if not pre or not dec:
            return False
        eps = 1e-3
        ratio = (sum(pre) / len(pre) + eps) / (sum(dec) / len(dec) + eps)
        if not self._rebalance_armed:
            if 2.0 / band <= ratio <= band / 2.0:
                self._rebalance_armed = True
            return False
        now = time.monotonic()
        if now - self._last_action_ts \
                < float(self.config.autoscale_cooldown_s):
            return False
        target_role = None
        if ratio > band and sum(
                1 for l in samples if roles.get(l) in ("decode", "both")) > 1:
            # Prefill side starved: flip the least-pressured decode lane.
            target_role = "prefill"
            pool = [l for l in samples
                    if roles.get(l) in ("decode", "both")]
        elif ratio < 1.0 / band and sum(
                1 for l in samples if roles.get(l) == "prefill") > 1:
            target_role = "decode"
            pool = [l for l in samples if roles.get(l) == "prefill"]
        if target_role is None:
            return False
        victim = min(pool, key=lambda l: (samples.get(l) or 0.0, l))
        self._rebalance_armed = False
        res = self.rebalance(victim, target_role)
        if res.get("ok"):
            self._last_action_ts = time.monotonic()
        return True

    def _pick_victim(self, samples: Dict[str, Optional[float]]) \
            -> Optional[str]:
        """Scale-down victim: a reachable, non-degraded, non-ejected
        lane — lowest (ring weight, journaled streams, pressure), so
        the cheapest, emptiest lane drains first and the fewest streams
        ride the migration path. Under disagg, never the last lane of
        a role."""
        gw = self.gateway
        degraded = gw.fleet_status()["degraded"]
        streams: Dict[str, int] = {}
        for _rid, lane in gw.active_streams().items():
            streams[lane] = streams.get(lane, 0) + 1
        roles = gw.worker_roles()
        role_counts: Dict[str, int] = {}
        for lane in samples:
            role_counts[roles.get(lane, "both")] = \
                role_counts.get(roles.get(lane, "both"), 0) + 1
        candidates = []
        for lane, p in samples.items():
            if p is None or lane in degraded:
                continue
            if gw._probe_state.ejected(lane):
                continue
            role = roles.get(lane, "both")
            if self.config.disagg and role in ("prefill", "decode") \
                    and role_counts.get(role, 0) <= 1:
                continue
            candidates.append(
                (gw._ring.node_weight(lane), streams.get(lane, 0),
                 p, lane))
        if not candidates:
            return None
        return min(candidates)[3]

    # -- actuators (shared by the loop and /admin/fleet) ----------------------

    def scale_up(self, worker=None) -> dict:
        """Probe-then-register: acquire a lane (the given worker, or
        one from the provider), poll its ``/health`` until it reports
        healthy, and only then put it on the rings. A lane that never
        turns healthy within ``autoscale_spawn_timeout_s`` is handed
        back to the provider and latches the named ``spawn-wedged``
        degraded state — the fleet keeps serving on what it has."""
        gw = self.gateway
        cfg = self.config
        from_provider = worker is None
        if from_provider:
            worker = self.provider.spawn() if self.provider is not None \
                else None
            if worker is None:
                gw._fleet_count("scale_up_attempted", source="provider")
                gw._fleet_count("scale_up_failed",
                                reason="provider-exhausted")
                return {"ok": False, "status": "provider-exhausted"}
        if isinstance(worker, str):
            probe_client = HttpWorkerClient(
                worker, timeout_s=cfg.worker_timeout_s,
                default_port=cfg.default_worker_port, pool_size=2)
            name_hint = probe_client.url
            probe = lambda: probe_client.probe_health(timeout_s=2.0)
        else:
            name_hint = str(getattr(worker, "node_id", worker))
            probe = worker.get_health
        if name_hint in gw.lane_clients():
            return {"ok": True, "status": "already-member",
                    "worker": name_hint}
        gw._fleet_count("scale_up_attempted", worker=name_hint)
        deadline = time.monotonic() + float(cfg.autoscale_spawn_timeout_s)
        healthy = False
        while time.monotonic() < deadline:
            try:
                if bool(probe().get("healthy")):
                    healthy = True
                    break
            except Exception:
                pass
            if self._stop_event.wait(0.2):
                break
        if not healthy:
            gw.fleet_enter_degraded(name_hint, DEGRADED_SPAWN_WEDGED)
            gw._fleet_count("scale_up_failed", worker=name_hint,
                            reason=DEGRADED_SPAWN_WEDGED)
            if from_provider and self.provider is not None:
                try:
                    self.provider.destroy(worker)
                except Exception:
                    pass
            return {"ok": False, "status": DEGRADED_SPAWN_WEDGED,
                    "worker": name_hint}
        name = gw.add_worker(worker)
        gw.fleet_clear_degraded(name)
        gw._fleet_count("scale_up_completed", worker=name)
        return {"ok": True, "status": "registered", "worker": name}

    def scale_down(self, name: Optional[str] = None,
                   manual: bool = False) -> dict:
        """Retire one lane through the PR 11 ladder: bounded graceful
        drain, live stream migration, ring removal — zero tokens lost
        (replay resume is the ladder's own last rung). The whole
        actuation is bounded: a removal that exceeds the drain +
        migration budget latches ``drain-wedged`` and returns with the
        fleet still serving; a drain CALL that failed inside a removal
        that otherwise completed latches the same state as an operator
        signal (the kill -9 mid-drain shape) while membership still
        shrinks."""
        gw = self.gateway
        if name is None:
            name = self._pick_victim(
                {l: 0.0 for l in gw.lane_clients()})
            if name is None:
                return {"ok": False, "status": "no-victim"}
        if name not in gw.lane_clients():
            return {"ok": False, "status": "unknown-lane", "worker": name}
        gw._fleet_count("scale_down_attempted", worker=name,
                        manual=manual)
        before = gw.migration.get("drain_failures")
        budget = (float(self.config.drain_timeout_s)
                  + 2.0 * float(self.config.migrate_timeout_s) + 15.0)
        fut = self._actuators().submit(gw.remove_worker, name, True)
        try:
            fut.result(timeout=budget)
        except concurrent.futures.TimeoutError:
            gw.fleet_enter_degraded(name, DEGRADED_DRAIN_WEDGED)
            gw._fleet_count("scale_down_failed", worker=name,
                            reason="actuator-timeout")
            return {"ok": False, "status": DEGRADED_DRAIN_WEDGED,
                    "worker": name}
        except Exception as exc:
            gw._fleet_count("scale_down_failed", worker=name,
                            reason="remove-error")
            return {"ok": False, "status": "remove-failed",
                    "worker": name, "error": str(exc)[:200]}
        wedged = gw.migration.get("drain_failures") > before
        if wedged:
            gw.fleet_enter_degraded(name, DEGRADED_DRAIN_WEDGED)
        if self.provider is not None \
                and hasattr(self.provider, "retire"):
            try:
                self.provider.retire(name)
            except Exception:
                pass
        gw._fleet_count("scale_down_completed", worker=name,
                        wedged=wedged)
        return {"ok": True,
                "status": "removed-degraded" if wedged else "removed",
                "worker": name}

    def rebalance(self, name: str, role: str) -> dict:
        """Flip one lane's role through ``Gateway.set_worker_role`` —
        the /admin/role drain + migrate + set-role + undrain path, whose
        failure leg restores admissions and the old role on both sides."""
        gw = self.gateway
        gw._fleet_count("rebalance_attempted", worker=name, role=role)
        if name not in gw.lane_clients():
            gw._fleet_count("rebalance_failed", worker=name,
                            reason="unknown-lane")
            return {"ok": False, "status": "unknown-lane", "worker": name}
        try:
            res = gw.set_worker_role(name, role)
        except Exception as exc:
            gw._fleet_count("rebalance_failed", worker=name,
                            reason="flip-error")
            return {"ok": False, "status": "rebalance-failed",
                    "worker": name, "error": str(exc)[:200]}
        if res.get("ok"):
            gw._fleet_count("rebalance_completed", worker=name, role=role)
            return {"ok": True, "status": "rebalanced", "worker": name,
                    "role": role}
        gw._fleet_count("rebalance_failed", worker=name,
                        reason="flip-refused")
        return {"ok": False, "status": "rebalance-failed", "worker": name,
                "error": str(res.get("error", ""))[:200]}
