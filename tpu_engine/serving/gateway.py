"""Gateway: consistent-hash routing with circuit-breaker-guarded failover.

Capability parity with the reference gateway
(``/root/reference/src/gateway.cpp``): requests route to the lane owning
``request_id`` on the hash ring (``:41``); on failure every other lane is
tried in ring order (``:51-59``); each lane is guarded by a circuit breaker
(5 failures / 2 successes / 30 s, ``:19-23``); ``get_stats`` exposes the
exact ``/stats`` schema (``:63-77``).

TPU-native shape: lanes are in-process dispatch targets over the chips of a
``jax.sharding.Mesh`` (``LocalWorkerClient``) — the reference's HTTP
fan-out becomes a function call and the scatter/gather rides ICI inside the
compiled executable. The HTTP client mode keeps the reference's
multi-process/multi-host deployment working unchanged (DCN between hosts).

Improvements over the reference (documented, not silent):
- elastic membership: ``add_worker``/``remove_worker`` at runtime (the
  reference's ring had removeNode but no caller — dead workers needed a
  gateway restart, ``README.md:336-339``), with an optional drain
  (lame-duck) mode for graceful removal;
- routing falls back to a random key when ``request_id`` is absent instead
  of raising;
- a resilience layer (``serving/resilience.py``, DESIGN.md "Request
  resilience"): per-request deadlines threaded edge→lane, failover under
  a global retry budget with exponential backoff + jitter, and hedged
  dispatch for idempotent ops — the slow-lane/overload story the
  breaker-only reference has no answer for. All knobs default
  off/permissive; with defaults the routing behavior and wire schemas are
  byte-identical to the reference parity described above;
- crash-tolerant streaming (``failover_streams``, DESIGN.md
  "Crash-tolerant streaming"): a /generate/stream journal that resumes a
  mid-stream lane failure on another ring lane (prompt ⧺ emitted tokens,
  budget offset) and splices the continuation byte-identically, plus a
  proactive /health prober (``health_probe_interval_s``) that ejects dead
  lanes from rotation in O(probe interval) and restores them on recovery.
  Both default off.
"""

from __future__ import annotations

import collections
import concurrent.futures
import json
import threading
import time
import uuid
from typing import Dict, List, Optional, Union

from tpu_engine.core.circuit_breaker import CircuitBreaker
from tpu_engine.core.consistent_hash import ConsistentHash
from tpu_engine.serving.clients import (
    HttpWorkerClient,
    LocalWorkerClient,
    WorkerError,
)
from tpu_engine.serving.http import sse_event
from tpu_engine.serving.overload import (
    OverloadCounters,
    SheddingStats,
    TenantRateLimiter,
    TIER_ADMIT_FRAC,
    TIER_NAMES,
    load_retry_after,
    parse_priority,
    tier_limit,
)
from tpu_engine.serving.prefix_directory import PrefixDirectory
from tpu_engine.serving.resilience import (
    AffinityCounters,
    FailoverCounters,
    FleetCounters,
    HandoffCounters,
    LatencyTracker,
    MigrationCounters,
    PrefixDirCounters,
    ProbeStateMachine,
    ResilienceCounters,
    RetryBudget,
    backoff_delay,
)
from tpu_engine.utils.config import GatewayConfig
from tpu_engine.utils.deadline import (
    Deadline,
    DeadlineExceeded,
    Overloaded,
    ShedError,
)
from tpu_engine.serving.slo import (
    OBJECTIVE_SOURCES,
    SloTracker,
    completion_hists,
)
from tpu_engine.utils.streams import relay
from tpu_engine.utils.tracing import (
    SpanRecorder,
    TraceContext,
    stitch_trace,
)


class GatewayError(Exception):
    pass


# Ops safe to hedge: a duplicate dispatch returns the identical answer and
# costs only compute (cache-first /infer, teacher-forced /score). /generate
# is excluded — duplicating a whole decode loop is the one cost hedging
# must never pay, and a stream cannot be "first response wins".
_HEDGEABLE_OPS = frozenset({"infer", "infer_raw", "score"})

# _try_node outcome for a lane that SHED the request (overloaded/draining):
# failure for failover purposes, but distinguishable from a fault — if the
# WHOLE ring sheds, the request must surface as 503 + Retry-After
# (congestion), never the 500-class "all workers failed" (outage).
_SHED = object()


def _ok(result) -> bool:
    return result is not None and result is not _SHED


def _parse_sse(frame: bytes) -> Optional[dict]:
    """One SSE frame (``sse_event`` output) -> its JSON payload, or None
    for anything unparseable (relayed verbatim, never dropped)."""
    try:
        text = frame.decode()
    except Exception:
        return None
    text = text.strip()
    if not text.startswith("data: "):
        return None
    try:
        evt = json.loads(text[len("data: "):])
    except Exception:
        return None
    return evt if isinstance(evt, dict) else None


class _StreamRecord:
    """One journaled /generate/stream's migration state: which lane
    currently serves it, and the one-shot handoff slot the drain
    orchestrator fills (continuation iterator + destination lane) for
    the RELAY thread to splice. The handoff is an exchange with three
    terminal states — offered, failed, abandoned — resolved exactly
    once under ``_hlock``: an orchestrator whose offer loses the race
    against the relay's timeout must dispose of its continuation
    iterator itself (the relay has already moved on to the replay
    fallback)."""

    __slots__ = ("request_id", "payload", "deadline", "ctx", "lane",
                 "_hlock", "_ready", "_it", "_dest", "_error",
                 "_abandoned", "handoff", "spliced_handoff")

    def __init__(self, request_id: str, payload: dict, deadline, ctx,
                 lane: Optional[str]):
        self.request_id = request_id
        self.payload = payload
        self.deadline = deadline
        self.ctx = ctx
        self.lane = lane
        # Disaggregated serving: True while the steady-state
        # prefill→decode handoff orchestrator owns this stream's next
        # migrated terminal (counts into the `handoff` family, not
        # `migration`); cleared after the first splice so a LATER
        # drain-time migration counts normally. Written by the relay
        # thread and the stream's orchestrator only. `spliced_handoff`
        # remembers whether the LATEST splice was a handoff, so a
        # post-splice in-band import refusal attributes its fallback to
        # the right counter family.
        self.handoff = False
        self.spliced_handoff = False
        self._hlock = threading.Lock()
        self._ready = threading.Event()
        self._it = None
        self._dest: Optional[str] = None
        self._error: Optional[str] = None
        self._abandoned = False

    def offer(self, it, dest: str) -> bool:
        """Orchestrator: hand the continuation to the relay. False when
        the relay already abandoned the wait — the caller must dispose
        of ``it``."""
        with self._hlock:
            if self._abandoned or self._ready.is_set():
                return False
            self._it, self._dest = it, dest
            self._ready.set()
            return True

    def fail(self, reason: str) -> None:
        """Orchestrator: no continuation is coming — the relay falls
        back to the replay resume."""
        with self._hlock:
            if not self._abandoned and not self._ready.is_set():
                self._error = reason
                self._ready.set()

    def await_handoff(self, timeout_s: float):
        """Relay: block for the orchestrator's verdict. Returns
        (iterator, dest_lane) on success, None on failure or timeout —
        after None the slot is ABANDONED (a late offer is refused) and
        re-armed for a possible later migration. An offer that raced in
        between the Event timeout and this lock acquisition still WINS
        (the continuation exists — dropping it here would leak a live
        iterator and duplicate the decode on the replay lane)."""
        ok = self._ready.wait(timeout=max(0.0, timeout_s))
        with self._hlock:
            if self._it is not None:
                # Offered — possibly a hair after the wait timed out,
                # but before the relay could abandon: take it.
                out = (self._it, self._dest)
                self._abandoned = False
            else:
                out = None
                # Timed out with nothing offered: refuse late offers
                # (the orchestrator disposes). A FAILED handoff is
                # consumed, not abandoned.
                self._abandoned = not ok and self._error is None
            # Re-arm: this stream may be migrated again later.
            self._ready.clear()
            self._it = self._dest = self._error = None
            return out

    def rearm(self) -> None:
        """Relay: clear a stale abandonment before the next migration
        window (called when a new segment starts relaying)."""
        with self._hlock:
            if not self._ready.is_set():
                self._abandoned = False

    def pending_offer(self) -> bool:
        """True while an OFFERED continuation sits unconsumed — the
        drain orchestrator waits these out before returning (the caller
        is about to kill the source process; a relay that has not yet
        taken its handoff would read a dead socket first and replay)."""
        with self._hlock:
            return self._ready.is_set() and self._it is not None

    def take_unconsumed(self):
        """Stream teardown: pop an offered-but-never-consumed
        continuation (the relay ended another way) so the caller can
        dispose of it — an orphan iterator would pin the destination's
        admission depth."""
        with self._hlock:
            if self._ready.is_set() and self._it is not None:
                it = self._it
                self._it = self._dest = self._error = None
                self._ready.clear()
                return it
            return None


class _RouteTrace:
    """Per-request trace state threaded through the routing layers: the
    route span's context (every attempt / resilience-decision span parents
    here) and whether the CLIENT supplied a traceparent — only then is the
    context re-forwarded to workers, so traceless requests keep their wire
    bytes identical to the pre-tracing protocol (anonymous correlation
    rides the request_id-derived trace id instead)."""

    __slots__ = ("request_id", "parent", "ctx", "outcome")

    def __init__(self, request_id: str, parent: Optional[TraceContext]):
        self.request_id = request_id
        self.parent = parent
        self.ctx = (parent.child() if parent is not None
                    else TraceContext.root(request_id))
        self.outcome = "error"

    @property
    def traced(self) -> bool:
        return self.parent is not None


class _StreamLedger:
    """Which lanes served each request_id, hop by hop — the index the
    cross-lane trace stitcher (GET /admin/trace/<rid>) walks to know
    WHOSE ring buffers hold a mobile stream's span fragments. Mobility
    machinery records one entry per hop (admit / handoff / migrate /
    resume) at the exact points the stream's serving lane changes;
    entries OUTLIVE the stream record (stitching is a postmortem read).
    Bounded FIFO over request_ids; own lock (ledger writes happen inside
    relay loops that must never contend with routing's _lock)."""

    def __init__(self, capacity: int = 512):
        self.capacity = max(1, int(capacity))
        self._entries: "collections.OrderedDict" = collections.OrderedDict()
        self._llock = threading.Lock()

    def hop(self, request_id: str, lane: str, kind: str,
            trace_id: Optional[str] = None) -> None:
        with self._llock:
            ent = self._entries.get(request_id)
            if ent is None:
                while len(self._entries) >= self.capacity:
                    self._entries.popitem(last=False)
                ent = {"trace_id": trace_id, "hops": []}
                self._entries[request_id] = ent
            elif trace_id and not ent["trace_id"]:
                ent["trace_id"] = trace_id
            ent["hops"].append({"lane": lane, "kind": kind,
                                "ts": round(time.time(), 6)})

    def get(self, request_id: str) -> Optional[dict]:
        with self._llock:
            ent = self._entries.get(request_id)
            if ent is None:
                return None
            return {"trace_id": ent["trace_id"],
                    "hops": [dict(h) for h in ent["hops"]]}

    def summary(self) -> dict:
        with self._llock:
            return {"streams": len(self._entries),
                    "capacity": self.capacity,
                    "hops": sum(len(e["hops"])
                                for e in self._entries.values())}


class Gateway:
    def __init__(self, workers=None, config: Optional[GatewayConfig] = None):
        """``workers``: list of worker URLs (HTTP mode), WorkerNode objects
        (local mode), or a mix."""
        self.config = config or GatewayConfig()
        self._ring = ConsistentHash(self.config.virtual_nodes)
        # Multi-model serving: one sub-ring per model name so a request's
        # "model" field restricts routing AND failover to lanes that
        # actually serve it (Triton-style; the reference is one model per
        # worker with no model awareness at the gateway).
        self._model_rings: Dict[str, ConsistentHash] = {}
        # Workers with UNKNOWN model (HTTP URLs carry no metadata): while
        # any exist, an unmatched "model" falls back to the global ring
        # with worker-side validation instead of a 400 — they might serve
        # it.
        self._untyped: set = set()
        self._clients: Dict[str, object] = {}
        self._breakers: Dict[str, CircuitBreaker] = {}
        self._lock = threading.Lock()
        self._total_requests = 0
        self._failovers = 0
        # Resilience layer (all knobs default off/permissive — see
        # GatewayConfig): deadline admission + budgeted, backed-off
        # failover + hedged dispatch, every decision counted.
        self.resilience = ResilienceCounters()
        self._retry_budget = RetryBudget(self.config.retry_budget_ratio,
                                         self.config.retry_budget_min,
                                         self.config.retry_budget_window_s)
        # PER-LANE latency windows: a global window would let a slow lane
        # receiving >(1-q) of traffic drag the hedge quantile up to its
        # own latency, self-disabling hedging for exactly the lane it
        # exists to cover.
        self._latency: Dict[str, LatencyTracker] = {}
        self._hedge_pool: Optional[concurrent.futures.ThreadPoolExecutor] = None
        # Disagg handoff orchestrators get their own bounded executor
        # (created on first use): they block for whole prefill
        # durations and must not starve the hedge/drain pool.
        self._handoff_exec: Optional[
            concurrent.futures.ThreadPoolExecutor] = None
        # Requests without a "model" field in multi-model mode route to
        # the first-registered model (deterministic default) instead of
        # whichever lane the global ring happens to own.
        self.default_model: Optional[str] = None
        # Tracing: the gateway's own span ring — one ``route`` span per
        # request with ``attempt`` children (primary / retry / hedge as
        # siblings) and zero-duration ``resilience`` decision markers, so
        # every shed/retry/hedge the counters report is explainable
        # per-request in /trace/export.
        self.tracer = SpanRecorder(self.config.trace_capacity)
        # Crash-tolerant streaming + proactive lane health (DESIGN.md
        # "Crash-tolerant streaming"): stream-resume and prober decisions
        # counted here, lanes the prober ejected excluded from dispatch.
        self.failover = FailoverCounters()
        # Live stream migration (DESIGN.md "Live stream migration"):
        # per-stream KV handoff on migrate-mode drain. Decisions counted
        # here (each with a `migration` marker span); the active-stream
        # registry the drain orchestrator walks lives under self._lock.
        self.migration = MigrationCounters()
        self._streams: Dict[str, _StreamRecord] = {}
        # Disaggregated prefill/decode serving (DESIGN.md "Disaggregated
        # serving"): per-lane roles (absent = "both") drive role-aware
        # routing while config.disagg is on and the fleet is actually
        # split; every handoff decision is counted here with a matching
        # `kv_handoff` marker span. The role map lives under self._lock.
        self.handoff = HandoffCounters()
        self._roles: Dict[str, str] = {}
        # Topology-aware ring (DESIGN.md "Tensor-parallel serving" —
        # the AoiZora placement framing): per-lane mesh-shape labels
        # ({tp, mesh_shape, devices}, absent = one chip) discovered
        # from worker config (local lanes), the disagg role-discovery
        # /health read, or prober sweeps (HTTP lanes). A labelled lane
        # weights its VIRTUAL NODES by device count on every ring —
        # the ring hashes over chips, not lanes, so a TP=4 lane beside
        # TP=1 lanes draws 4x the hash share (it holds 4x the KV pool
        # at equal per-device HBM). Unlabelled fleets keep the
        # reference-exact ring byte-for-byte. Lives under self._lock.
        self._topology: Dict[str, dict] = {}
        self._topology_updates = 0  # re-weights applied (info counter)
        # Prefix-affinity routing (DESIGN.md "Prefix-affinity routing"):
        # decisions counted here; per-lane assignment totals and the
        # recent-dispatch window (imbalance signal) under self._lock.
        self.affinity = AffinityCounters()
        self._affinity_assigned: Dict[str, int] = {}
        self._lane_recent: Dict[str, object] = {}  # lane -> deque[ts]
        # Fleet prefix directory (DESIGN.md "Fleet-wide prefix tier"):
        # bounded fingerprint -> {lane, blocks, generation} hint cache
        # keyed by the SAME _affinity_fingerprint the affinity router
        # hashes — but independent of prefix_affinity (the directory
        # pays off exactly when routing CAN'T converge shared prefixes
        # onto one lane). Populated from prober /health summaries and
        # post-completion updates; entries die by per-lane generation
        # stamp on removal/drain/eject/recovery. Lives under self._lock;
        # None at defaults — /stats and wire bytes stay identical.
        self.prefix_dir = PrefixDirCounters()
        # _prefix_dir_on is the config-constant hot-path gate (set once
        # here, never reassigned); the directory itself moves only under
        # self._lock.
        self._prefix_dir_on = bool(getattr(self.config,
                                           "prefix_directory", False))
        self._prefix_dir: Optional[PrefixDirectory] = (
            PrefixDirectory(getattr(self.config,
                                    "prefix_directory_capacity", 512))
            if self._prefix_dir_on else None)
        # Adaptive overload control (DESIGN.md "Overload control"):
        # priority-tiered admission against the in-flight gauge, the
        # per-tenant token bucket, and the load-derived Retry-After.
        # Every decision counted in the additive /stats `overload` block
        # with a matching `overload` marker span.
        self.overload = OverloadCounters()
        self._tenant_bucket: Optional[TenantRateLimiter] = (
            TenantRateLimiter(self.config.tenant_rate,
                              self.config.tenant_burst)
            if self.config.tenant_rate > 0 else None)
        # In-flight requests currently inside the routing layer — the
        # gauge the tier fractions admit against (guarded by _lock).
        self._inflight = 0
        # Recent shed rate: the pressure source for load_retry_after
        # when no in-flight gauge is configured.
        self._shed_stats = SheddingStats()
        self._ejected: set = set()
        # Consistent-hash ring over the PREFILL-CAPABLE lanes (role
        # prefill|both) — the disagg primary hashes the affinity
        # fingerprint (or request_id) here so shared prefixes still
        # converge on one prefill lane. Maintained beside the main ring
        # (membership changes + role flips); ConsistentHash self-locks.
        self._prefill_ring = ConsistentHash(self.config.virtual_nodes)
        # Elastic fleet (DESIGN.md "Elastic fleet"): every autoscaler /
        # /admin/fleet decision counted here with a matching `fleet`
        # marker span. The named degraded-but-serving states (lane ->
        # reason, e.g. "spawn-wedged", "drain-wedged") and the last
        # observed fleet pressure live under self._lock; the controller
        # itself (serving/autoscaler.py) attaches via engage_autoscaler
        # and is None at defaults — wire bytes stay identical.
        self.fleet = FleetCounters()
        self._fleet_degraded: Dict[str, str] = {}
        self._fleet_pressure: Optional[float] = None
        self._autoscaler = None
        # Observability plane (DESIGN.md "Observability plane"; both
        # default off — absent, /stats and wire bytes stay identical).
        # The stream ledger records which lanes served each request_id
        # so /admin/trace/<rid> can stitch a mobile stream's fragments;
        # the SLO tracker turns the existing TTFT/ITL/completion
        # histograms into windowed error-budget burn.
        self._ledger: Optional[_StreamLedger] = (
            _StreamLedger(getattr(self.config, "trace_ledger_capacity",
                                  512))
            if getattr(self.config, "trace_stitch", False) else None)
        # Bounded lane→client handles kept past removal (drained lanes
        # stay reachable for postmortem trace stitching).
        self._retired_clients: Dict[str, object] = {}
        self._slo = SloTracker.from_config(self.config)
        self._probe_state = ProbeStateMachine(
            self.config.health_probe_failures)
        self._prober_stop = threading.Event()
        self._prober_thread: Optional[threading.Thread] = None
        for w in workers or []:
            self.add_worker(w)
        if self.config.health_probe_interval_s > 0:
            self._prober_thread = threading.Thread(
                target=self._probe_loop, name="gw-prober", daemon=True)
            self._prober_thread.start()

    def stop(self) -> None:
        """Stop the background health prober and the fleet autoscaler
        (idempotent; routing itself keeps working)."""
        scaler = self._autoscaler
        if scaler is not None:
            scaler.stop()
        self._prober_stop.set()
        t = self._prober_thread
        if t is not None:
            t.join(timeout=5)
            self._prober_thread = None

    # -- membership (elastic; reference ring was fixed at launch) ------------

    @staticmethod
    def _normalize_topology(topo) -> Optional[dict]:
        """A /health (or worker-config) topology label -> the canonical
        {tp, devices} dict, or None for unlabelled/one-chip lanes (the
        absent-key default — rings stay reference-exact)."""
        if not isinstance(topo, dict):
            return None
        try:
            devices = int(topo.get("devices", topo.get("tp", 1)))
            tp = int(topo.get("tp", devices))
        except (TypeError, ValueError):
            # Malformed labels normalize to "one chip", never raise: a
            # probe-path exception here would read as a FAILED health
            # probe and eject a perfectly healthy lane.
            return None
        if devices <= 1:
            return None
        out = {"tp": tp, "devices": devices}
        if isinstance(topo.get("mesh_shape"), dict):
            out["mesh_shape"] = dict(topo["mesh_shape"])
        return out

    def _lane_weight(self, name: str) -> int:
        """Virtual-node weight for a lane: its labelled device count
        (topology-aware ring), 1 when unlabelled."""
        with self._lock:
            topo = self._topology.get(name)
        return int(topo["devices"]) if topo else 1

    def add_worker(self, worker) -> str:
        model_name = None
        role = "both"
        topo = None
        if isinstance(worker, str):
            client = HttpWorkerClient(
                worker,
                timeout_s=self.config.worker_timeout_s,
                default_port=self.config.default_worker_port,
                gen_timeout_s=self.config.gen_timeout_s,
            )
            name = client.url
            if self.config.disagg:
                # Role (and topology) discovery for HTTP lanes (URLs
                # carry no metadata): one best-effort /health read —
                # absent keys or an unreachable lane read "both" on one
                # chip, today's behavior. Only paid when disagg is on;
                # plain HTTP fleets pick their topology labels up from
                # the health prober's sweeps instead.
                try:
                    health = client.health()
                    role = str(health.get("role", "both"))
                    topo = self._normalize_topology(
                        health.get("topology"))
                except Exception:
                    role = "both"
        else:
            client = LocalWorkerClient(worker)
            name = worker.node_id
            spec = getattr(getattr(worker, "engine", None), "spec", None)
            model_name = getattr(spec, "name", None)
            cfg = getattr(worker, "config", None)
            role = str(getattr(cfg, "role", "both") or "both")
            tp = int(getattr(cfg, "tp", 1) or 1)
            if tp > 1:
                from tpu_engine.parallel.mesh import tp_topology_label

                topo = self._normalize_topology(tp_topology_label(tp))
        if role not in ("prefill", "decode", "both"):
            role = "both"
        weight = int(topo["devices"]) if topo else 1
        with self._lock:
            self._clients[name] = client
            self._breakers[name] = self._make_breaker()
            if role != "both":
                self._roles[name] = role
            if topo is not None:
                self._topology[name] = topo
            if model_name is None:
                self._untyped.add(name)
        self._ring.add_node(name, weight)
        if role != "decode":
            self._prefill_ring.add_node(name, weight)
        if model_name is not None:
            with self._lock:
                ring = self._model_rings.get(model_name)
                if ring is None:
                    # Populate BEFORE publishing: a concurrent _route must
                    # never see an empty ring for a registered model.
                    ring = ConsistentHash(self.config.virtual_nodes)
                    ring.add_node(name, weight)
                    self._model_rings[model_name] = ring
                else:
                    ring.add_node(name, weight)
                if self.default_model is None:
                    self.default_model = model_name
        return name

    def _apply_topology(self, name: str, topo) -> None:
        """Adopt a lane's freshly-discovered topology label (prober
        sweeps: an HTTP lane's /health is the only place its mesh shape
        exists) and re-weight its virtual nodes on every ring it is a
        member of. No-op while the label is unchanged — steady-state
        sweeps touch nothing."""
        topo = self._normalize_topology(topo)
        with self._lock:
            if name not in self._clients:
                return
            prev = self._topology.get(name)
            if topo == prev:
                return
            if topo is None:
                self._topology.pop(name, None)
            else:
                self._topology[name] = topo
            rings = list(self._model_rings.values())
        weight = int(topo["devices"]) if topo else 1
        # ConsistentHash self-locks; resize outside the gateway lock.
        # reweight_node is atomic (membership check + resize under one
        # ring-lock acquisition), so a remove_worker racing this sweep
        # can never be interleaved into a resurrected ghost lane — the
        # resize simply misses (False) once the removal lands.
        applied = self._ring.reweight_node(name, weight)
        self._prefill_ring.reweight_node(name, weight)
        for ring in rings:
            ring.reweight_node(name, weight)
        if applied:
            with self._lock:
                if name in self._clients:
                    self._topology_updates += 1
                else:
                    self._topology.pop(name, None)

    def _make_breaker(self):
        """Native breaker when the C++ core is loaded — the native HTTP
        front shares the same breaker object for its hit-path gate."""
        try:
            from tpu_engine.core import native

            if native.available():
                return native.NativeCircuitBreaker(
                    self.config.failure_threshold,
                    self.config.success_threshold,
                    self.config.breaker_timeout_s,
                )
        except Exception:
            pass
        return CircuitBreaker(
            self.config.failure_threshold,
            self.config.success_threshold,
            self.config.breaker_timeout_s,
        )

    def breaker_for(self, name: str):
        with self._lock:
            return self._breakers.get(name)

    # -- proactive lane health (prober) ---------------------------------------

    def _probe_loop(self) -> None:
        """Background prober: GET every lane's /health each interval;
        `health_probe_failures` consecutive failures eject the lane from
        dispatch (no breaker penalty — ejection is reversible and
        fleet-wide in one sweep), the next success restores it. Catches a
        dead or wedged worker in O(probe interval) instead of one
        breaker trip per victim request."""
        interval = self.config.health_probe_interval_s
        while not self._prober_stop.wait(interval):
            with self._lock:
                clients = dict(self._clients)
            for name, client in clients.items():
                ok = False
                try:
                    # Dedicated probe connection where the client offers
                    # one (HTTP lanes): probes must never contend with
                    # data traffic for pool slots.
                    probe = getattr(client, "probe_health", client.health)
                    body = probe()
                    ok = bool(body.get("healthy", False))
                    # Topology labels ride the same read: an HTTP lane's
                    # mesh shape exists nowhere but its /health, so the
                    # prober is where TP=4 lanes pick up their per-chip
                    # vnode weight (no-op while the label is unchanged).
                    self._apply_topology(name, body.get("topology"))
                    # Directory seeding rides the same read: the lane's
                    # bounded top-K radix summaries (present only with
                    # --prefix-fetch on worker-side) become fleet-wide
                    # fingerprint->owner entries.
                    if self._prefix_dir_on:
                        self._seed_prefix_dir(
                            name, body.get("prefix_fingerprints"))
                except Exception:
                    ok = False  # unreachable = failed probe
                action = self._probe_state.record(name, ok)
                with self._lock:
                    present = name in self._clients
                if not present:
                    # Removed while this sweep held the stale snapshot:
                    # record() just resurrected its state — drop it again
                    # so a later lane reusing the name starts clean (and
                    # unique elastic lane names don't leak entries).
                    self._probe_state.forget(name)
                    continue
                if action is None:
                    continue
                with self._lock:
                    if name not in self._clients:
                        continue  # removed between the checks
                    if action == "eject":
                        self._ejected.add(name)
                    else:
                        self._ejected.discard(name)
                self.failover.bump("prober_ejections" if action == "eject"
                                   else "prober_restores")
                self._prober_span(name, action)
                # Both transitions void the lane's directory entries: an
                # ejected lane can't serve a peer fetch, and a RECOVERED
                # lane may have restarted with an empty radix tree — its
                # chains must be re-learned, not assumed.
                if self._prefix_dir_on:
                    with self._lock:
                        dropped = self._prefix_dir.invalidate_lane(name)
                    self._prefix_dir_count("invalidations", lane=name,
                                           action=action, dropped=dropped)

    def _prober_span(self, lane: str, action: str) -> None:
        """Zero-duration ``prober`` marker span per eject/restore — the
        counters say how often, the spans say WHICH lane and when
        (fault_injection --crash asserts the two agree)."""
        ctx = TraceContext.root(f"prober:{lane}").child()
        self.tracer.record(
            "prober", "prober", "gateway", 0,
            trace_id=ctx.trace_id, span_id=ctx.span_id,
            start_ts=time.time(), attrs={"lane": lane, "action": action})

    def ejected_lanes(self) -> List[str]:
        with self._lock:
            return sorted(self._ejected)

    def remove_worker(self, name: str, drain: bool = False) -> None:
        """Remove a lane from every ring. ``drain=True`` = graceful
        (lame-duck) removal: the lane refuses NEW admissions first — so a
        request racing the ring update sheds with 503 instead of failing —
        while in-flight work runs to completion off-ring. The drain call
        is BOUNDED (``drain_timeout_s``): a wedged lane's acknowledgment
        must never hang a membership change — the failure is counted
        (``drain_failures``) and removal proceeds. With
        ``migrate_streams`` on, every journaled in-flight stream on the
        lane is then EXPORTED and resumed mid-stream elsewhere (zero
        re-prefilled tokens) before the lane leaves the rings; any
        per-stream failure falls back to the replay resume. The default
        stays the abrupt removal existing callers expect."""
        if drain:
            with self._lock:
                client = self._clients.get(name)
            if client is not None and hasattr(client, "drain"):
                fut = self._pool().submit(client.drain)
                try:
                    fut.result(timeout=self.config.drain_timeout_s)
                except Exception as exc:
                    # Wedged or unreachable lane: count it, drop the
                    # marker span, and carry on — plain removal is all
                    # we have (the abandoned call finishes or dies on
                    # its pool thread).
                    self._migration_count(None, "drain_failures",
                                          lane=name,
                                          error=str(exc)[:120])
            if self.config.migrate_streams:
                self._migrate_lane_streams(name, client)
        self._ring.remove_node(name)
        self._prefill_ring.remove_node(name)
        with self._lock:
            rings = dict(self._model_rings)
            removed_client = self._clients.pop(name, None)
            if self._ledger is not None and removed_client is not None:
                # The stitcher may still need this lane's span fragments
                # (a drained lane is alive, just not a member): keep a
                # BOUNDED handle so /admin/trace can reach it postmortem.
                self._retired_clients[name] = removed_client
                while len(self._retired_clients) > 8:
                    self._retired_clients.pop(
                        next(iter(self._retired_clients)))
            self._breakers.pop(name, None)
            self._latency.pop(name, None)  # stale window must not feed thresholds
            self._lane_recent.pop(name, None)
            self._untyped.discard(name)
            self._ejected.discard(name)
            self._roles.pop(name, None)
            self._topology.pop(name, None)
            # Generation-stamp invalidation: the departing lane's radix
            # tree leaves the fleet with it — every directory entry
            # naming it is a dead hint (a later lane reusing the name
            # starts at a fresh generation, so stragglers die lazily).
            pd_dropped = (self._prefix_dir.invalidate_lane(name)
                          if self._prefix_dir is not None else None)
        if pd_dropped is not None:
            self._prefix_dir_count("invalidations", lane=name,
                                   action="remove", dropped=pd_dropped)
        # A later lane reusing the name must start with clean probe state.
        self._probe_state.forget(name)
        for ring in rings.values():
            ring.remove_node(name)
        with self._lock:
            # Prune emptied sub-rings and re-point the no-field default —
            # removing the default model's last lane must not strand every
            # field-less request on a dead ring forever.
            for mdl, ring in list(self._model_rings.items()):
                if not ring.get_all_nodes():
                    del self._model_rings[mdl]
            if self.default_model not in self._model_rings:
                self.default_model = (sorted(self._model_rings)[0]
                                      if self._model_rings else None)

    def worker_names(self) -> List[str]:
        return self._ring.get_all_nodes()

    # -- elastic fleet (DESIGN.md "Elastic fleet") ----------------------------

    def lane_clients(self) -> Dict[str, object]:
        """{lane: client} membership snapshot (one lock acquisition) —
        the autoscaler's observation loop and tests."""
        with self._lock:
            return dict(self._clients)

    def _fleet_count(self, decision: str, **attrs) -> None:
        """Bump a fleet counter AND drop a zero-duration ``fleet``
        marker span (same counters==spans discipline as the
        migration/handoff markers; fault_injection --elastic asserts
        the two agree)."""
        self.fleet.bump(decision)
        ctx = TraceContext.root(f"fleet:{decision}").child()
        self.tracer.record(
            "fleet", "fleet", "gateway", 0,
            trace_id=ctx.trace_id, span_id=ctx.span_id,
            start_ts=time.time(), attrs={"decision": decision, **attrs})

    def fleet_observe(self, pressure: float) -> None:
        """Publish the controller's latest fleet-pressure observation
        (drives the /stats ``fleet.pressure`` gauge)."""
        with self._lock:
            self._fleet_pressure = round(float(pressure), 4)

    def fleet_enter_degraded(self, lane: str, reason: str) -> None:
        """Latch a NAMED degraded-but-serving state for ``lane``
        (``spawn-wedged``: a scale-up that never turned healthy;
        ``drain-wedged``: a scale-down whose drain leg wedged or whose
        actuator timed out). Serving continues unchanged — the state is
        an operator signal, visible in /stats ``fleet`` and
        /admin/fleet until cleared. Idempotent per (lane, reason)."""
        with self._lock:
            if self._fleet_degraded.get(lane) == reason:
                return
            self._fleet_degraded[lane] = reason
        self._fleet_count("degraded_entered", lane=lane, reason=reason)
        # Flight-recorder anomaly hook: entering a degraded fleet state
        # is exactly the moment an operator wants the last N ticks of
        # every lane on disk. Best-effort — lanes without a recorder
        # (or unreachable ones) simply skip.
        for name, client in self.lane_clients().items():
            if hasattr(client, "flight_dump"):
                try:
                    client.flight_dump(f"fleet_degraded:{reason}")
                except Exception:
                    pass

    def fleet_clear_degraded(self, lane: str) -> bool:
        """Clear a lane's degraded state (controller recovery sweep or
        operator /admin/fleet clear). True if a state was latched."""
        with self._lock:
            reason = self._fleet_degraded.pop(lane, None)
        if reason is None:
            return False
        self._fleet_count("degraded_cleared", lane=lane, reason=reason)
        return True

    def fleet_status(self) -> dict:
        """The /admin/fleet status body: membership, named degraded
        states, controller engagement, and last observed pressure."""
        with self._lock:
            degraded = dict(self._fleet_degraded)
            pressure = self._fleet_pressure
        lanes = self.worker_names()
        out = {
            "state": ("degraded:" + ",".join(sorted(set(degraded.values())))
                      if degraded else "steady"),
            "lanes": sorted(lanes),
            "degraded": degraded,
            "autoscale": self._autoscaler is not None
            and self._autoscaler.running,
        }
        if pressure is not None:
            out["pressure"] = pressure
        return out

    # -- observability plane (DESIGN.md "Observability plane") ---------------

    def slo_status(self, named_hists: Optional[dict] = None) -> Optional[dict]:
        """The /admin/slo payload, or None when no objective is
        configured. ``named_hists`` is the combined front's merged
        ``{family: {node: hist}}`` map; without it the gateway gathers
        what it can reach directly — in-process lanes expose their live
        ``latency_histograms()``, remote (HTTP) lanes contribute nothing
        (their TTFT/ITL windows live behind /metrics text, not live
        objects; the completion objective still covers them because it
        reads the GATEWAY's own request-level histograms)."""
        if self._slo is None:
            return None
        if named_hists is None:
            named_hists = {}
            for lane, client in self.lane_clients().items():
                w = getattr(client, "worker", None)
                if w is None or not hasattr(w, "latency_histograms"):
                    continue
                for name, by_node in w.latency_histograms().items():
                    named_hists.setdefault(name, {}).update(by_node)
        by_objective = {}
        for name, family in OBJECTIVE_SOURCES.items():
            if family is None:
                # "completion" = the gateway's own generate-op spans:
                # full client-visible latency including failover,
                # handoff, and migration time.
                by_objective[name] = completion_hists([self.tracer])
            else:
                by_objective[name] = list(
                    (named_hists.get(family) or {}).values())
        return self._slo.status(by_objective)

    def slo_pressure(self, named_hists: Optional[dict] = None) -> float:
        """The autoscaler feed: worst objective burn mapped to [0, 1]
        (0.0 with no tracker — the feed is strictly additive)."""
        if self._slo is None:
            return 0.0
        status = self.slo_status(named_hists)
        return SloTracker.pressure(status or {})

    def stitched_trace(self, request_id: str,
                       fragments: Optional[dict] = None) -> dict:
        """The /admin/trace/<request_id> body: every lane's span
        fragments for one (possibly thrice-moved) stream merged into a
        single tree. The stream ledger supplies the trace_id and the
        hop history when stitching is on; without a ledger entry (plain
        deployments, evicted entries) the stitch still works from the
        request_id + derived trace_id — the ledger is an index, not the
        data. Lane fragment collection is best-effort: a dead lane
        contributes nothing rather than failing the whole stitch (its
        spans died with it; the synthetic ``evicted_parent`` roots keep
        the surviving tree connected)."""
        entry = (self._ledger.get(request_id)
                 if self._ledger is not None else None)
        if fragments is None:
            fragments = {"gateway": self.tracer.snapshot()}
            for lane, client in self.lane_clients().items():
                if not hasattr(client, "trace_spans"):
                    continue
                try:
                    spans = client.trace_spans()
                except Exception:
                    continue
                if spans:
                    fragments.setdefault(lane, spans)
            # A drained lane is alive but no longer a ring member — the
            # ledger remembers it served this stream, so chase its
            # fragments through the retired-client handle (kept by
            # remove_worker) or a fresh HTTP probe (best-effort: a
            # KILLED lane's spans died with it and simply fail here).
            with self._lock:
                retired = dict(self._retired_clients)
            for hop in (entry or {}).get("hops", ()):
                lane = hop.get("lane") or ""
                if not lane or lane in fragments:
                    continue
                client = retired.get(lane)
                if client is None and ":" in lane:
                    try:
                        from tpu_engine.serving.clients import (
                            HttpWorkerClient,
                        )

                        client = HttpWorkerClient(lane, timeout_s=3.0)
                    except Exception:
                        continue
                if client is None or not hasattr(client, "trace_spans"):
                    continue
                try:
                    spans = client.trace_spans()
                except Exception:
                    continue
                if spans:
                    fragments.setdefault(lane, spans)
        out = stitch_trace(fragments, request_id,
                           trace_id=(entry or {}).get("trace_id"))
        if entry is not None:
            out["hops"] = entry["hops"]
        return out

    def engage_autoscaler(self, provider=None):
        """Create (and start) the closed-loop fleet controller —
        called by the serving app when ``--autoscale`` is set. Returns
        the controller; idempotent (a live controller is reused)."""
        if self._autoscaler is None:
            from tpu_engine.serving.autoscaler import FleetAutoscaler

            self._autoscaler = FleetAutoscaler(self, provider=provider,
                                               config=self.config)
        if self.config.autoscale:
            self._autoscaler.start()
        return self._autoscaler

    def _fleet_controller(self):
        """The controller backing /admin/fleet: the engaged autoscaler,
        or an UNSTARTED one (manual actuations share the exact probe /
        drain+migrate ladders, counters, and degraded-state handling
        the closed loop uses — defaults-off deployments get the same
        semantics without any background thread)."""
        if self._autoscaler is None:
            from tpu_engine.serving.autoscaler import FleetAutoscaler

            self._autoscaler = FleetAutoscaler(self, provider=None,
                                               config=self.config)
        return self._autoscaler

    def fleet_admin(self, payload: dict) -> dict:
        """/admin/fleet: the elastic-fleet operator surface. Actions —
        ``status`` (fleet + controller state), ``add`` (probe-then-
        register a lane: a worker address, registered on the rings only
        after a passing /health probe), ``remove`` (retire a member
        through the drain + PR 11 stream-migration ladder), ``rebalance``
        (flip a lane's role through the /admin/role path), ``clear``
        (drop a lane's latched degraded state). Every failure answers a
        named, non-raising status."""
        action = str(payload.get("action", "status"))
        ctl = self._fleet_controller()
        if action == "status":
            out = {"ok": True, **self.fleet_status()}
            out["counters"] = self.fleet.as_dict()
            return out
        if action == "add":
            worker = payload.get("worker")
            if not worker:
                return {"ok": False, "status": "missing-worker"}
            return ctl.scale_up(worker=worker)
        if action == "remove":
            name = payload.get("worker")
            if not name:
                return {"ok": False, "status": "missing-worker"}
            return ctl.scale_down(name=str(name), manual=True)
        if action == "rebalance":
            name, role = payload.get("worker"), payload.get("role")
            if not name or not role:
                return {"ok": False, "status": "missing-worker-or-role"}
            return ctl.rebalance(str(name), str(role))
        if action == "clear":
            name = payload.get("worker")
            if not name:
                return {"ok": False, "status": "missing-worker"}
            cleared = self.fleet_clear_degraded(str(name))
            return {"ok": True,
                    "status": "cleared" if cleared else "not-degraded"}
        return {"ok": False, "status": f"unknown-action:{action}"[:80]}

    # -- request path ---------------------------------------------------------

    def route_request(self, payload: dict) -> dict:
        return self._route(payload, op="infer")

    def route_request_raw(self, payload: dict) -> bytes:
        """Hot path: response stays pre-serialized bytes end-to-end (the
        reference re-parses and re-encodes the float array at every hop)."""
        return self._route(payload, op="infer_raw")

    def route_score(self, payload: dict) -> dict:
        """Route /score (teacher-forced logprobs) like /infer."""
        return self._route(payload, op="score")

    def route_generate(self, payload: dict) -> dict:
        """Route a /generate request the same way as /infer: ring primary,
        breaker-gated, ring-order failover. Under active
        disaggregation the blocking call rides the same prefill→decode
        handoff path as the stream (collapsed into the blocking
        response)."""
        if self._disagg_split() is not None:
            return self._generate_via_handoff(payload)
        return self._route(payload, op="generate")

    def route_generate_stream(self, payload: dict):
        """Streaming variant: same routing; the selected lane's SSE
        event-chunk iterator is handed back for chunked transfer.
        Breaker accounting happens at admission (iterator creation) AND
        on mid-stream lane faults (below).

        Default (``failover_streams`` off): a mid-stream failure still
        ends the client's stream (error event or truncation — same
        frames, same wire behavior as before), but the dying lane's
        breaker now records the fault, preserving the breaker signal the
        old buffering HTTP shim got for free at iterator creation. With
        ``failover_streams`` on, the gateway journals every token event
        it relays and a retryable mid-stream failure RESUMES the stream
        on another ring lane (prompt ⧺ emitted tokens as a forced
        prefix), splicing the continuation so the client sees one
        seamless, byte-identical stream — the request is bound to the
        fleet, not to the lane that happened to start it."""
        if not (self.config.failover_streams
                or self.config.migrate_streams
                or self.config.disagg):
            info: dict = {}
            it = self._route(payload, op="generate_stream",
                             out_info=info)
            return self._breaker_watched(it, info.get("lane"))
        # migrate_streams implies the journal: the replay resume IS the
        # migration fallback ladder's last rung (MIGRATION.md). Disagg
        # needs the journal for the same reason — the handoff's last
        # rung is the replay resume.
        return self._stream_with_failover(payload)

    def _breaker_watched(self, it, lane: Optional[str]):
        """Relay a stream iterator byte-identically, but feed a
        mid-stream LANE fault to the lane's breaker — admission-time
        accounting alone would let a lane that admits streams and then
        dies stay CLOSED forever. Two fault shapes: a mid-iteration
        exception (transport death), and a worker-side in-band terminal
        error EVENT marked retryable (device fault re-framed as SSE —
        the shape the old buffering HTTP shim surfaced as a WorkerError
        at dispatch). Request-fault and shed signals pass through
        unpenalized (`shed` marker / exception class), the same
        classification `_try_node` applies at admission."""
        def watched():
            try:
                for frame in it:
                    # Cheap prefilter keeps the per-token hot path at
                    # relay cost: only terminal frames carry "done".
                    if b'"done"' in frame:
                        evt = _parse_sse(frame)
                        if (evt is not None and evt.get("done")
                                and "error" in evt
                                and evt.get("retryable")
                                and not evt.get("shed")):
                            self._stream_fault_penalty(lane)
                    yield frame
            except (KeyError, ValueError, TypeError):
                raise
            except ShedError as exc:
                if getattr(exc, "lane_suspect", False):
                    self._stream_fault_penalty(lane)  # hang signature
                raise
            except Exception:
                self._stream_fault_penalty(lane)
                raise
        # A frame out for a frame in: a front's writer may drive it
        # wherever it may drive `it`.
        return relay(watched(), it)

    def _stream_fault_penalty(self, lane: Optional[str]) -> None:
        breaker = self.breaker_for(lane) if lane else None
        if breaker is not None:
            breaker.record_failure()

    def _resume_payload(self, payload: dict, emitted: List[int],
                        max_new: int,
                        deadline: Optional[Deadline]) -> dict:
        """The resume request: the original payload with the emitted
        tokens appended to the prompt as a forced prefix and the token
        budget offset by the emitted count. Determinism across the
        splice boundary needs no extra wire fields: the scheduler
        samples with fold_in(seed, absolute position) and replays
        penalty counts / stop matching from the (prompt ⧺ emitted)
        prefix at admission, so greedy AND seeded sampled continuations
        are byte-identical to an uninterrupted run (tests/test_failover
        pins this; MIGRATION.md documents the positional-fold
        requirement)."""
        prompt = [int(t) for t in payload.get("prompt_tokens", ())]
        resume = {**payload,
                  "prompt_tokens": prompt + list(emitted),
                  "max_new_tokens": max_new - len(emitted)}
        if deadline is not None:
            # Forward the budget REMAINING now — a resume must never
            # restart the client's clock.
            resume["deadline_ms"] = max(0.0, deadline.remaining_ms())
        return resume

    def _resume_span(self, request_id: str, ctx, index: int,
                     replayed: int, outcome: str,
                     lane: Optional[str]) -> None:
        """One ``resume`` span per resume attempt, parented under the
        request's trace — resumes_attempted and these spans must agree
        (fault_injection --crash asserts it)."""
        child = ctx.child()
        self.tracer.record(
            request_id, "resume", "gateway", 0,
            trace_id=child.trace_id, span_id=child.span_id,
            parent_id=ctx.span_id, start_ts=time.time(),
            attrs={"resume": index, "tokens_replayed": replayed,
                   "outcome": outcome, "lane": lane or "?"})

    def _stream_with_failover(self, payload: dict):
        """Crash-tolerant /generate/stream: the journal is the request
        payload plus every token relayed so far; a retryable mid-stream
        failure (transport death, truncated stream, a worker error event
        marked retryable, a drain shed) re-dispatches to the next ring
        lane as a resume — consuming the PR 1 retry budget and the
        request's original deadline — and the continuation is spliced in
        with no duplicated or missing tokens. Non-resumable ends (budget
        exhausted, deadline expired, all lanes down, resume cap) emit a
        terminal error event carrying ``retryable``, ``trace_id``, and
        ``tokens_emitted`` so the CLIENT can resume manually."""
        rid = payload.get("request_id")
        if rid is None:
            rid = uuid.uuid4().hex
            payload = {**payload, "request_id": rid}
        request_id = str(rid)
        # Pin the deadline ONCE: every resume forwards what remains.
        deadline = Deadline.from_request(
            payload, default_ms=self.config.default_deadline_ms)
        try:
            max_new = int(payload.get("max_new_tokens", 32))
        except (TypeError, ValueError):
            # Malformed budget: let the normal path 400 it.
            return self._route(payload, op="generate_stream")
        parent = TraceContext.from_request(payload)
        ctx = (parent.child() if parent is not None
               else TraceContext.root(request_id))
        cfg = self.config
        ledger = self._ledger
        t_root = time.time()
        if ledger is not None:
            # Cross-lane trace stitching (--trace-stitch): forward the
            # STREAM-ROOT context in the payload once — the first
            # dispatch, every replay resume (_resume_payload copies the
            # payload), and both mobility continuations (built from
            # record.payload) inherit it, so each segment's
            # route/attempt/worker spans join ONE tree under the root
            # span recorded at stream end. Off (default), traceless
            # payloads keep their wire bytes byte-identical.
            payload = {**payload, "traceparent": ctx.to_traceparent()}
        # Disaggregated serving: while the fleet is split, the FIRST
        # segment is stamped `handoff` — routed to a prefill-capable
        # lane which parks the row after prefill for the
        # export-after-prefill command. The record keeps the UNSTAMPED
        # payload: resumes and continuations must never re-park.
        disagg = self._disagg_split() is not None
        dispatch_payload = payload
        if disagg:
            dispatch_payload = {
                **payload, "handoff": True,
                "handoff_park_ms": cfg.handoff_timeout_s * 1000.0}
        info: dict = {}
        # Admission of the FIRST segment keeps every existing semantic:
        # shed/400/no-workers raise here, before the 200 SSE commits.
        first = self._route(dispatch_payload, op="generate_stream",
                            out_info=info)
        if ledger is not None:
            ledger.hop(request_id, info.get("lane") or "?", "admit",
                       ctx.trace_id)
        # Migrate mode (and disagg — the handoff rides the same relay):
        # register the stream so the orchestrator can find it (which
        # lane serves it, its payload and deadline) and hand the relay
        # a continuation. Registered only AFTER the first segment
        # admitted — a stream that never started has nothing to
        # migrate.
        record: Optional[_StreamRecord] = None
        if cfg.migrate_streams or disagg:
            record = _StreamRecord(request_id, payload, deadline, ctx,
                                   info.get("lane"))
            with self._lock:
                self._streams[request_id] = record
        if disagg and record is not None:
            lane = info.get("lane")
            with self._lock:
                lane_role = self._roles.get(lane, "both")
            if lane_role == "prefill":
                # The steady-state handoff orchestrator owns this
                # stream's prefill→decode hop from here (one handoff-
                # pool thread per stream, bounded by handoff_timeout_s).
                self._handoff_pool().submit(self._handoff_stream,
                                            record, lane)
            else:
                # The stamped stream landed COLOCATED — ring fallback
                # past the prefill lanes, or a model ring with no split
                # (disagg activation is fleet-wide; this request's ring
                # may not be). A both/decode lane decodes fine itself:
                # no pointless KV transfer — just release the park so
                # the row never waits out a window nobody will collect
                # (the cancel pre-empts a row that has not parked yet).
                self._handoff_pool().submit(self._cancel_colocated_hold,
                                            record, lane)

        def terminal_error(reason: str, retryable: bool,
                           emitted: List[int]) -> bytes:
            return sse_event({
                "done": True, "error": str(reason)[:300],
                "retryable": bool(retryable),
                "request_id": request_id, "trace_id": ctx.trace_id,
                "tokens_emitted": len(emitted),
                "tokens": list(emitted)})

        def spliced_inner():
            emitted: List[int] = []
            it = first
            lane = info.get("lane")
            resumes = 0
            while True:
                # failure: (reason, retryable, lane_fault) — lane_fault
                # feeds the lane's breaker; sheds and client-budget
                # expiries don't (the healthy-lane rule).
                failure: Optional[tuple] = None
                finished = False
                migrated_evt = False
                try:
                    try:
                        for frame in it:
                            evt = _parse_sse(frame)
                            if evt is None:
                                yield frame  # not ours to interpret
                                continue
                            if not evt.get("done"):
                                toks = evt.get("tokens")
                                if isinstance(toks, list):
                                    # Materialize BEFORE extending: a
                                    # malformed token raising mid-extend
                                    # would leave the journal holding
                                    # tokens of a frame the client never
                                    # received, and the resume would
                                    # splice past them.
                                    emitted.extend([int(t) for t in toks])
                                yield frame
                                continue
                            if "error" in evt:
                                # Worker-side terminal error: its own
                                # `retryable` classification decides
                                # (absent = not retryable — never resume
                                # blind); a `shed` marker means a HEALTHY
                                # lane refused (drain/overload) — resume
                                # without a breaker penalty. A `migrated`
                                # marker means the row was EXPORTED: the
                                # drain orchestrator is (or was) moving
                                # it — await the handoff below instead
                                # of replay-resuming blind.
                                retr = bool(evt.get("retryable", False))
                                migrated_evt = bool(evt.get("migrated"))
                                if (evt.get("import_refused")
                                        and record is not None):
                                    # The spliced continuation's import
                                    # was refused post-dispatch
                                    # (checksum / geometry / pool
                                    # pressure): attribute the replay
                                    # fallback to the MIGRATION — or to
                                    # the HANDOFF when the latest
                                    # splice was the steady-state hop —
                                    # the destination lane is healthy.
                                    if record.spliced_handoff:
                                        self._handoff_count(
                                            "handoff_fallbacks",
                                            record=record, lane=lane,
                                            cause="import_refused")
                                    else:
                                        self._migration_count(
                                            record, "migration_fallbacks",
                                            lane=lane,
                                            cause="import_refused")
                                failure = (str(evt.get("error")), retr,
                                           retr
                                           and not evt.get("shed", False)
                                           and not migrated_evt
                                           and not evt.get(
                                               "import_refused", False))
                            else:
                                # Clean terminal: rewrite the summary to
                                # the FULL spliced stream (a resumed
                                # segment's summary covers only its
                                # continuation).
                                done = {**evt, "request_id": request_id,
                                        "tokens": list(emitted)}
                                if resumes:
                                    done["resumed"] = resumes
                                yield sse_event(done)
                                finished = True
                            break
                        else:
                            # Iterator exhausted without a terminal
                            # event: the lane died between frames
                            # (kill -9 closes the socket mid-chunk) —
                            # resumable truncation.
                            failure = ("stream truncated mid-generation",
                                       True, True)
                    finally:
                        # Settle the segment iterator NOW, not at GC: a
                        # finished HTTP segment reads one step past the
                        # done event so its pooled connection releases
                        # clean; every other exit closes it promptly
                        # (dead conns must never wait for a collector).
                        if finished:
                            try:
                                next(it)
                            except StopIteration:
                                pass
                            except Exception:
                                pass
                        try:
                            it.close()
                        except Exception:
                            pass
                except DeadlineExceeded as exc:
                    # Budget spent: terminal. No lane penalty UNLESS the
                    # lane held the request past the budget without
                    # answering (lane_suspect — the hang signature, same
                    # rule _route applies at admission).
                    failure = (str(exc), False,
                               bool(getattr(exc, "lane_suspect", False)))
                except ShedError as exc:
                    failure = (str(exc), True, False)  # drain: move on
                except Exception as exc:
                    failure = (str(exc), True, True)   # transport fault
                if finished:
                    return
                reason, retryable, lane_fault = failure
                if migrated_evt and record is not None:
                    # The row was EXPORTED off its lane: await the drain
                    # orchestrator's continuation (bounded by the
                    # transfer budget AND the stream's original
                    # deadline) and splice it — the client sees one
                    # seamless stream with zero re-prefilled tokens.
                    # Any failure — export refused, destination full or
                    # dead, checksum mismatch, timeout — falls through
                    # to the replay resume below: the fallback ladder's
                    # last rung needs nothing from either side.
                    is_handoff = record.handoff
                    wait_s = (cfg.handoff_timeout_s if is_handoff
                              else cfg.migrate_timeout_s) + 5.0
                    if deadline is not None:
                        wait_s = min(wait_s,
                                     max(0.0, deadline.remaining_s()))
                    handoff = record.await_handoff(wait_s)
                    if handoff is not None:
                        it, new_lane = handoff
                        lane = new_lane
                        record.lane = new_lane
                        record.spliced_handoff = is_handoff
                        if ledger is not None:
                            ledger.hop(request_id, new_lane or "?",
                                       "handoff" if is_handoff
                                       else "migrate", ctx.trace_id)
                        if is_handoff:
                            # The steady-state prefill→decode hop
                            # landed: the decode lane adopted the chain
                            # with zero re-prefilled tokens.
                            record.handoff = False
                            self._handoff_count("handoffs_spliced",
                                                record=record,
                                                lane=new_lane)
                            self.handoff.bump("tokens_handed_off",
                                              len(emitted))
                        else:
                            self._migration_count(record,
                                                  "streams_migrated",
                                                  lane=new_lane)
                            self.migration.bump("tokens_migrated",
                                                len(emitted))
                        continue
                    if is_handoff:
                        record.handoff = False
                        self._handoff_count("handoff_fallbacks",
                                            record=record, lane=lane)
                        reason = (f"handoff fell back to replay "
                                  f"({reason})")
                    else:
                        self._migration_count(record,
                                              "migration_fallbacks",
                                              lane=lane)
                        reason = (f"migration fell back to replay "
                                  f"({reason})")
                    retryable = True
                self.failover.bump("stream_failures")
                if lane_fault:
                    # Admission recorded a breaker SUCCESS for this lane;
                    # without this, a lane that admits streams and then
                    # dies mid-generation would stay CLOSED forever.
                    self._stream_fault_penalty(lane)
                if len(emitted) >= max_new > 0:
                    # The budget was fully delivered; only the terminal
                    # frame was lost. Synthesize it — nothing to resume.
                    done = {"done": True, "request_id": request_id,
                            "tokens": list(emitted)}
                    if resumes:
                        done["resumed"] = resumes
                    yield sse_event(done)
                    return
                if not retryable:
                    yield terminal_error(reason, False, emitted)
                    return
                if deadline is not None and deadline.expired():
                    self._count(None, "deadline_expired")
                    yield terminal_error(
                        f"deadline exceeded after mid-stream failure "
                        f"({reason})", False, emitted)
                    return
                if resumes >= cfg.failover_max_resumes:
                    yield terminal_error(
                        f"stream failed after {resumes} resumes "
                        f"({reason})", True, emitted)
                    return
                # Budget accounting rides the resume DISPATCH below, not a
                # separate pre-draw: the dead lane is (almost always) the
                # rid's ring primary, so the skip-path failover march
                # charges the global retry budget one token per alternate
                # lane tried — a resume costs exactly what any other
                # extra dispatch costs, and budget exhaustion surfaces
                # from _route as the terminal error.
                resumes += 1
                replayed = len(emitted)
                self.failover.bump("resumes_attempted")
                self.failover.bump("tokens_replayed", replayed)
                resume = self._resume_payload(payload, emitted, max_new,
                                              deadline)
                skip = (lane,) if lane else ()
                nxt_info: dict = {}
                try:
                    it = self._route(resume, op="generate_stream",
                                     skip=skip, out_info=nxt_info)
                except Exception as exc:
                    # No lane could admit the resume (all down, all
                    # shedding, or the deadline died en route).
                    self.failover.bump("resumes_failed")
                    self._resume_span(request_id, ctx, resumes, replayed,
                                      "failed", lane)
                    yield terminal_error(
                        f"resume dispatch failed ({exc})",
                        not isinstance(exc, DeadlineExceeded), emitted)
                    return
                self.failover.bump("resumes_succeeded")
                lane = nxt_info.get("lane")
                self._resume_span(request_id, ctx, resumes, replayed,
                                  "ok", lane)
                if ledger is not None:
                    ledger.hop(request_id, lane or "?", "resume",
                               ctx.trace_id)
                # A lane death IS an anomaly: ask the resume lane's
                # flight recorder for a postmortem dump named for the
                # event (no-op on lanes without the recorder armed) —
                # the black box fault_injection --stitch checks after
                # its kill -9.
                resume_client = self.lane_clients().get(lane or "")
                if resume_client is not None and hasattr(
                        resume_client, "flight_dump"):
                    try:
                        resume_client.flight_dump(
                            f"failover_resume:{request_id}")
                    except Exception:
                        pass
                if record is not None:
                    # The replay segment owns the stream now: a LATER
                    # migrate-mode drain of its lane must find it, and
                    # a stale abandoned handoff must not refuse it.
                    record.lane = lane
                    record.rearm()

        def spliced():
            try:
                yield from spliced_inner()
            finally:
                if ledger is not None:
                    # The STREAM-ROOT span, recorded at stream end with
                    # span_id == ctx.span_id: every hop marker
                    # (migration / kv_handoff / resume) and each
                    # segment's route span parent here, so the stitched
                    # tree is orphan-free by construction — the exact
                    # property fault_injection --stitch asserts.
                    self.tracer.record(
                        request_id, "stream", "gateway",
                        (time.time() - t_root) * 1e6,
                        trace_id=ctx.trace_id, span_id=ctx.span_id,
                        parent_id=(parent.span_id
                                   if parent is not None else None),
                        start_ts=t_root, attrs={"stitched": True})
                if record is not None:
                    with self._lock:
                        if self._streams.get(request_id) is record:
                            del self._streams[request_id]
                    # An offered continuation the relay never consumed
                    # (the stream ended another way — e.g. the source's
                    # terminal frames were lost to a kill before the
                    # migrated marker arrived): dispose of it, or the
                    # destination's admission depth stays pinned.
                    orphan = record.take_unconsumed()
                    if orphan is not None:
                        self._dispose_iter(orphan)
        return spliced()

    # -- live stream migration (DESIGN.md "Live stream migration") ------------

    def _migration_count(self, record: Optional[_StreamRecord],
                         decision: str, **attrs) -> None:
        """Bump a migration counter AND drop a zero-duration
        ``migration`` marker span — parented under the stream's request
        trace when there is one (same counters==spans discipline as the
        resilience/failover/affinity markers; fault_injection --migrate
        asserts the two agree)."""
        self.migration.bump(decision)
        if record is not None:
            child = record.ctx.child()
            rid, parent = record.request_id, record.ctx.span_id
        else:
            child = TraceContext.root(f"migration:{decision}").child()
            rid, parent = "migration", None
        self.tracer.record(
            rid, "migration", "gateway", 0,
            trace_id=child.trace_id, span_id=child.span_id,
            parent_id=parent, start_ts=time.time(),
            attrs={"decision": decision, **attrs})

    def active_streams(self) -> Dict[str, str]:
        """{request_id: serving lane} for every journaled stream the
        migrate registry currently tracks (tests + diagnostics)."""
        with self._lock:
            return {rid: rec.lane or "?"
                    for rid, rec in self._streams.items()}

    def _migrate_lane_streams(self, name: str, client) -> None:
        """Export every journaled stream the draining lane serves and
        resume each on another lane — concurrently, each under the
        stream's ORIGINAL deadline with a per-transfer timeout. Returns
        once every migration settled (or the overall bound passed);
        per-stream failures have already armed the replay fallback."""
        with self._lock:
            records = [r for r in self._streams.values()
                       if r.lane == name]
        if not records:
            return
        futs = [self._pool().submit(self._migrate_stream, rec, name,
                                    client)
                for rec in records]
        concurrent.futures.wait(
            futs, timeout=self.config.migrate_timeout_s * 2.0 + 10.0)
        # Don't return while a relay has not yet TAKEN its offered
        # continuation: the caller's next step is typically killing the
        # source process (rolling restart), and an unconsumed handoff
        # would lose that race — the relay would hit the dead socket
        # before the migrated terminal and replay instead of splicing.
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with self._lock:
                live = [r for r in records
                        if self._streams.get(r.request_id) is r]
            if not any(r.pending_offer() for r in live):
                break
            time.sleep(0.05)

    def _migrate_stream(self, record: _StreamRecord, source: str,
                        client) -> None:
        """One stream's migration: export off the source (ends the
        source's stream with a ``migrated`` terminal), pick the
        destination by the affinity fingerprint, dispatch the
        continuation, and offer it to the relay thread. EVERY failure
        resolves the handoff as failed — the relay's replay resume
        completes the stream from the journal, which needs nothing from
        either side (both sides' partial state is self-cleaning: export
        releases the source row; a refused import releases its pins and
        fresh blocks before raising)."""
        rid = record.request_id
        self._migration_count(record, "migrations_attempted", lane=source)
        deadline = record.deadline
        budget = self.config.migrate_timeout_s
        if deadline is not None:
            budget = min(budget, max(0.1, deadline.remaining_s()))
        export = None
        refused_cleanly = True
        try:
            reason = "source lane has no migrate surface"
            if client is not None and hasattr(client, "migrate"):
                fut = self._pool().submit(
                    client.migrate, {"request_id": rid}, budget)
                resp = fut.result(timeout=budget + 1.0)
                if resp.get("ok"):
                    export = {k: v for k, v in resp.items()
                              if k not in ("ok", "node_id")}
                else:
                    reason = str(resp.get("reason", "export refused"))
        except Exception as exc:
            refused_cleanly = False  # a late export may still land
            reason = f"export failed: {exc}"
        if export is None:
            # Includes the benign cases (stream just finished, row still
            # prefilling): the relay either never sees a migrated
            # terminal, or replays — both complete the stream. The
            # fallback is armed only when a terminal might still arrive
            # (timeout/transport): a clean worker refusal produces none,
            # and latching a stale failure would poison a later
            # migration window of the still-running stream.
            self._migration_count(record, "export_refusals", lane=source,
                                  reason=reason[:120])
            if not refused_cleanly:
                record.fail(reason)
            return
        try:
            dest = self._pick_migration_dest(record, source)
            if dest is None:
                self._migration_count(record, "destination_unavailable",
                                      lane=source)
                record.fail("no destination lane available")
                return
            cont = {**record.payload, "request_id": rid,
                    "migrate_import": export}
            if deadline is not None:
                cont["deadline_ms"] = max(0.0, deadline.remaining_ms())
            result = self._try_node(dest, cont, op="generate_stream")
            if not _ok(result):
                self._migration_count(record, "import_dispatch_failed",
                                      lane=dest)
                record.fail(f"destination {dest} refused the "
                            f"continuation")
                return
            if not record.offer(result, dest):
                # The relay timed out and owns the replay fallback now:
                # dispose of the orphan continuation so the
                # destination's admission depth and connection release.
                self._dispose_iter(result)
        except Exception as exc:
            self._migration_count(record, "import_dispatch_failed",
                                  lane=source, error=str(exc)[:120])
            record.fail(f"migration failed: {exc}")

    def _pick_migration_dest(self, record: _StreamRecord,
                             source: str) -> Optional[str]:
        """Destination preference: the lane owning the prompt-prefix
        AFFINITY fingerprint (its radix tree most likely already holds
        the prompt's blocks — the import re-adopts them and ships
        less), then the request_id's ring lane, then ring order — the
        first candidate that is present, un-ejected, and
        breaker-admitted; never the source."""
        payload = record.payload
        mdl = payload.get("model")
        with self._lock:
            if mdl is None and len(self._model_rings) > 1:
                mdl = self.default_model
            ring = (self._model_rings.get(str(mdl))
                    if mdl is not None else self._ring)
        if ring is None:
            ring = self._ring
        candidates: List[str] = []
        fp = self._affinity_fingerprint(payload)
        if fp is not None:
            try:
                candidates.append(ring.get_node(fp))
            except RuntimeError:
                pass
        try:
            candidates.append(ring.get_node(record.request_id))
        except RuntimeError:
            pass
        candidates += ring.get_all_nodes()
        seen = set()
        for lane in candidates:
            if lane == source or lane in seen:
                continue
            seen.add(lane)
            if self._lane_admits(lane):
                return lane
        return None

    def _dispose_iter(self, it) -> None:
        """Drain an orphaned stream iterator in the background: running
        it to exhaustion is the one path that releases the serving
        side's admission depth and pooled connection whether or not the
        generator ever started (close() on an unstarted generator skips
        its finally)."""
        def drain():
            try:
                for _ in it:
                    pass
            except Exception:
                pass
            finally:
                try:
                    it.close()
                except Exception:
                    pass
        threading.Thread(target=drain, name="gw-migrate-dispose",
                         daemon=True).start()

    # -- disaggregated prefill/decode serving (DESIGN.md) ----------------------

    def worker_roles(self) -> Dict[str, str]:
        """{lane: role} for every member lane (absent map entry =
        "both") — tests, diagnostics, and the /stats handoff block."""
        with self._lock:
            return {name: self._roles.get(name, "both")
                    for name in self._clients}

    def _disagg_split(self, ring=None):
        """(prefill_capable, decode_capable) lane lists over ``ring``
        (default: the whole fleet), or None unless disagg routing
        should engage: the flag on, at least one DEDICATED prefill
        lane, and at least one decode-capable lane beside it. An
        all-"both" fleet — or disagg off — returns None and routes
        byte-identically to today."""
        if not self.config.disagg:
            return None
        nodes = ring.get_all_nodes() if ring is not None else None
        with self._lock:
            if nodes is None:
                nodes = list(self._clients)
            roles = {n: self._roles.get(n, "both") for n in nodes}
        if not any(r == "prefill" for r in roles.values()):
            return None
        prefill = [n for n in nodes if roles[n] != "decode"]
        decode = [n for n in nodes if roles[n] != "prefill"]
        if not prefill or not decode:
            return None
        return prefill, decode

    def _lane_admits(self, lane: str) -> bool:
        """Present, un-ejected, breaker-admitted — the dispatchability
        gate every handoff candidate walk applies."""
        with self._lock:
            present = lane in self._clients
            ejected = lane in self._ejected
            breaker = self._breakers.get(lane)
        return (present and not ejected and breaker is not None
                and breaker.allow_request())

    def _handoff_count(self, decision: str,
                       record: Optional[_StreamRecord] = None,
                       trace: Optional[_RouteTrace] = None,
                       **attrs) -> None:
        """Bump a handoff counter AND drop a zero-duration
        ``kv_handoff`` marker span — parented under the stream's
        request trace (record) or the route span (trace) when either
        exists. Same counters==spans discipline as the migration
        markers; fault_injection --disagg asserts the two agree."""
        self.handoff.bump(decision)
        if decision not in HandoffCounters.SPAN_FIELDS:
            return
        if record is not None:
            child = record.ctx.child()
            rid, parent = record.request_id, record.ctx.span_id
        elif trace is not None:
            child = trace.ctx.child()
            rid, parent = trace.request_id, trace.ctx.span_id
        else:
            child = TraceContext.root(f"handoff:{decision}").child()
            rid, parent = "handoff", None
        self.tracer.record(
            rid, "kv_handoff", "gateway", 0,
            trace_id=child.trace_id, span_id=child.span_id,
            parent_id=parent, start_ts=time.time(),
            attrs={"decision": decision, **attrs})

    def _handoff_primary(self, ring, ring_primary: str, payload: dict,
                         skip: tuple,
                         trace: Optional[_RouteTrace]) -> str:
        """Disagg primary selection: hash the prompt's affinity
        fingerprint (radix sharing keeps paying fleet-wide) — or the
        request_id when affinity is off / nothing to fingerprint — on
        the PREFILL ring, walking its ring order for the first
        admittable prefill-capable lane. No admittable prefill lane →
        ring order over everyone (``prefill_unavailable``): the request
        serves colocated on whatever lane, today's behavior."""
        split = self._disagg_split(ring)
        if split is None:
            return ring_primary
        prefill_set = set(split[0])
        fp = (self._affinity_fingerprint(payload)
              if self.config.prefix_affinity else None)
        key = fp if fp is not None else str(
            payload.get("request_id") or "")
        candidates: List[str] = []
        try:
            candidates.append(self._prefill_ring.get_node(key))
        except RuntimeError:
            pass
        candidates += self._prefill_ring.get_all_nodes()
        seen = set()
        for lane in candidates:
            if lane in seen or lane in skip or lane not in prefill_set:
                continue
            seen.add(lane)
            if self._lane_admits(lane):
                self._handoff_count("prefill_routed", trace=trace,
                                    lane=lane)
                return lane
        self._handoff_count("prefill_unavailable", trace=trace)
        return ring_primary

    def set_worker_role(self, name: str, role: str) -> dict:
        """/admin/role: flip one lane's serving role at runtime — fleet
        rebalancing under diurnal load. Rides the existing graceful
        machinery: bounded drain first (new admissions shed while the
        flip lands), live streams migrated off when --migrate-streams
        is on, then the worker-side flip, undrain, and the role maps /
        prefill ring update. A failed worker flip restores admissions
        and reports — the lane keeps its old role everywhere."""
        role = str(role)
        if role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role must be prefill|decode|both, got {role!r}")
        with self._lock:
            client = self._clients.get(name)
        if client is None:
            raise ValueError(f"unknown worker '{name}'")
        drained = False
        if hasattr(client, "drain"):
            fut = self._pool().submit(client.drain)
            try:
                fut.result(timeout=self.config.drain_timeout_s)
                drained = True
            except Exception as exc:
                # Same bounded-drain contract as remove_worker: count
                # it and carry on — the flip itself is still safe.
                self._migration_count(None, "drain_failures", lane=name,
                                      error=str(exc)[:120])
        def _undrain():
            # UNCONDITIONAL (idempotent): a drain call that timed out
            # here may still have landed worker-side moments later —
            # unlike remove_worker, this lane STAYS in the fleet, and
            # a silently-draining member would shed every admission
            # until an operator noticed.
            if hasattr(client, "undrain"):
                try:
                    client.undrain()
                except Exception:
                    pass

        if self.config.migrate_streams:
            try:
                self._migrate_lane_streams(name, client)
            except Exception as exc:
                # A failed migration leg must RESTORE the lane, not
                # strand it draining with its old role half-applied:
                # admissions reopen and both the worker and the gateway
                # role map keep the pre-flip role (per-stream failures
                # inside the leg already armed their replay fallbacks;
                # this catches the leg itself dying).
                _undrain()
                return {"ok": False, "node_id": name,
                        "error": f"migration leg failed: {exc}"[:300]}

        try:
            if hasattr(client, "set_role"):
                client.set_role(role)
            else:
                raise WorkerError("lane has no role surface")
        except Exception as exc:
            _undrain()
            return {"ok": False, "node_id": name,
                    "error": str(exc)[:300]}
        _undrain()
        with self._lock:
            if role == "both":
                self._roles.pop(name, None)
            else:
                self._roles[name] = role
        # Prefill-ring membership follows the role (idempotent ops);
        # re-entry keeps the lane's topology vnode weight.
        if role == "decode":
            self._prefill_ring.remove_node(name)
        elif name not in self._prefill_ring.get_all_nodes():
            self._prefill_ring.add_node(name, self._lane_weight(name))
        self._handoff_count("role_flips", lane=name, role=role)
        return {"ok": True, "node_id": name, "role": role,
                "drained": drained}

    def _handoff_stream(self, record: _StreamRecord,
                        source: Optional[str]) -> None:
        """Steady-state prefill→decode handoff orchestrator (one per
        disagg stream, off the gateway pool): ask the source for an
        export-AFTER-PREFILL (the command parks on its decode loop and
        snapshots the row — first token, sampling state, KV chain — the
        moment prefill completes), pick a decode lane by load, dispatch
        the ``migrate_import`` continuation, and offer it to the relay.
        EVERY failure leaves the stream completable without us: an
        unexported row unparks and decodes locally (colocated
        fallback); an exported-but-unspliced stream lands on the PR 6
        replay resume. Both are byte-identical."""
        rid = record.request_id
        record.handoff = True
        self._handoff_count("handoffs_attempted", record=record,
                            lane=source or "?")
        deadline = record.deadline
        budget = self.config.handoff_timeout_s
        if deadline is not None:
            budget = min(budget, max(0.1, deadline.remaining_s()))
        with self._lock:
            client = self._clients.get(source) if source else None
        export = None
        refused_cleanly = True  # a missing surface produces no terminal
        reason = "source lane has no migrate surface"
        if client is not None and hasattr(client, "migrate"):
            try:
                # Direct call on THIS pool thread: client.migrate is
                # already bounded by its own payload/socket timeouts —
                # a nested pool submit would hold two of the shared
                # 256 workers per in-flight handoff for the whole
                # prefill duration.
                resp = client.migrate(
                    {"request_id": rid, "wait_prefill": True}, budget)
                if resp.get("ok"):
                    export = {k: v for k, v in resp.items()
                              if k not in ("ok", "node_id")}
                else:
                    reason = str(resp.get("reason", "export refused"))
            except Exception as exc:
                # Ambiguous: a timed-out export may still have landed
                # worker-side, so a `migrated` terminal MAY arrive.
                refused_cleanly = False
                reason = f"export failed: {exc}"
        if export is None:
            # Nothing left this lane: cancel any lingering hold so the
            # row resumes local decoding NOW instead of at the park
            # bound, and let the relay keep relaying the source stream.
            self._handoff_count("export_refusals", record=record,
                                lane=source or "?", reason=reason[:120])
            record.handoff = False
            if not refused_cleanly:
                # Arm the relay's fallback ONLY when a migrated
                # terminal might still arrive; a clean refusal produces
                # none, and a latched stale failure would poison a
                # LATER drain migration's handoff window (instant
                # replay instead of awaiting the offer).
                record.fail(reason)
            self._cancel_source_hold(record, client, rid)
            return
        try:
            dests = self._handoff_candidates(record, source)
            if not dests:
                # The row is GONE from the source (exported): the
                # relay's replay resume finishes the stream.
                self._handoff_count("destination_unavailable",
                                    record=record, lane=source or "?")
                record.fail("no decode-capable destination lane")
                return
            cont = {**record.payload, "request_id": rid,
                    "migrate_import": export}
            cont.pop("handoff", None)
            cont.pop("handoff_park_ms", None)
            if deadline is not None:
                cont["deadline_ms"] = max(0.0, deadline.remaining_ms())
            result, dest = None, None
            for cand in dests:
                # A draining/overloaded candidate sheds (_SHED): try
                # the next decode lane instead of abandoning the hop.
                result = self._try_node(cand, cont, op="generate_stream")
                if _ok(result):
                    dest = cand
                    break
            if dest is None:
                self._handoff_count("dispatch_failed", record=record,
                                    lane=dests[0])
                record.fail("every decode lane refused the continuation")
                return
            if not record.offer(result, dest):
                # The relay moved on (timeout → replay fallback owns
                # the stream): dispose of the orphan continuation.
                self._dispose_iter(result)
        except Exception as exc:
            self._handoff_count("dispatch_failed", record=record,
                                lane=source or "?", error=str(exc)[:120])
            record.fail(f"handoff failed: {exc}")

    def _handoff_pool(self) -> concurrent.futures.ThreadPoolExecutor:
        """Dedicated bounded executor for handoff orchestration: each
        orchestrator blocks up to handoff_timeout_s on the export-
        after-prefill call, and riding the shared hedge pool would let
        a disagg burst starve hedged dispatches and drain calls."""
        with self._lock:
            if self._handoff_exec is None:
                self._handoff_exec = \
                    concurrent.futures.ThreadPoolExecutor(
                        max_workers=64, thread_name_prefix="gw-handoff")
            return self._handoff_exec

    def _cancel_colocated_hold(self, record: _StreamRecord,
                               lane: Optional[str]) -> None:
        """A handoff-stamped stream that landed on a non-prefill lane:
        no hop is coming — release the park (or pre-empt it) so local
        decode starts immediately."""
        with self._lock:
            client = self._clients.get(lane) if lane else None
        self._cancel_source_hold(record, client, record.request_id)

    def _cancel_source_hold(self, record: _StreamRecord, client,
                            rid: str) -> None:
        """Best-effort release of a parked source row after a failed
        export (the row would otherwise wait out its park bound before
        resuming local decode)."""
        if client is None or not hasattr(client, "migrate"):
            return
        try:
            resp = client.migrate({"request_id": rid, "cancel": True},
                                  5.0)
            if resp.get("cancelled"):
                self._handoff_count("holds_cancelled", record=record)
        except Exception:
            pass

    def _handoff_candidates(self, record: _StreamRecord,
                            source: Optional[str]) -> List[str]:
        """Decode-capable destination lanes for one handoff, best
        first: fewest journaled active streams (the load signal the
        stream registry already tracks), ring order as the tiebreak;
        never the source, the prober-ejected, or a breaker-open lane
        (draining lanes shed at dispatch — the caller walks to the
        next candidate)."""
        payload = record.payload
        mdl = payload.get("model")
        with self._lock:
            if mdl is None and len(self._model_rings) > 1:
                mdl = self.default_model
            ring = (self._model_rings.get(str(mdl))
                    if mdl is not None else self._ring)
        if ring is None:
            ring = self._ring
        split = self._disagg_split(ring)
        decode = split[1] if split else ring.get_all_nodes()
        with self._lock:
            load: Dict[str, int] = {}
            for rec in self._streams.values():
                if rec.lane:
                    load[rec.lane] = load.get(rec.lane, 0) + 1
        order = {n: i for i, n in enumerate(ring.get_all_nodes())}
        cands = [n for n in decode
                 if n != source and self._lane_admits(n)]
        cands.sort(key=lambda n: (load.get(n, 0),
                                  order.get(n, len(order))))
        return cands

    def _generate_via_handoff(self, payload: dict) -> dict:
        """Blocking /generate under active disaggregation: the prefill
        lane → KV handoff → decode lane path runs as the internal
        stream and collapses into the blocking response shape.
        Admission refusals raise before any consumption (same wire
        classes as the direct dispatch); a terminal error event
        surfaces as the gateway-level failure it is."""
        it = self._stream_with_failover(payload)
        final = None
        try:
            for frame in it:
                evt = _parse_sse(frame)
                if evt is not None and evt.get("done"):
                    final = evt
        finally:
            try:
                it.close()
            except Exception:
                pass
        if final is None:
            raise GatewayError("stream ended without a terminal event")
        if "error" in final:
            raise GatewayError(str(final["error"]))
        return {k: v for k, v in final.items() if k != "done"}

    # -- prefix-affinity routing ----------------------------------------------

    def _affinity_fingerprint(self, payload: dict) -> Optional[str]:
        """Block-aligned fingerprint of the prompt's leading tokens:
        floor(len/affinity_block_size) full blocks, capped at
        affinity_prefix_blocks — the exact granularity the workers'
        radix trees share at, so two requests with equal fingerprints
        have reusable KV blocks in common. None when the prompt has no
        full block (or is malformed — the normal path will 400 it).

        Unified stateless serving rides the same rings: stateless
        payloads (/infer's "input_data", score/embed bodies without
        prompt_tokens) have no token prefix to fingerprint, so this
        returns None and the router degrades gracefully to its
        content-hash / round-robin tiers — no special-case lane class,
        one routing policy for every request family."""
        toks = payload.get("prompt_tokens")
        if not isinstance(toks, (list, tuple)):
            return None
        cfg = self.config
        bs = max(1, int(cfg.affinity_block_size))
        n = min((len(toks) // bs) * bs,
                bs * max(1, int(cfg.affinity_prefix_blocks)))
        if n <= 0:
            return None
        try:
            return "prefix:" + ",".join(str(int(t)) for t in toks[:n])
        except (TypeError, ValueError):
            return None

    def _count_lane_dispatch(self, lane: str) -> None:
        """Stamp one generate-class dispatch on the lane's recent-window
        deque — the load signal the imbalance fallback compares. Only
        kept while that fallback is configured (the sole reader), and
        trimmed on write so a long-lived gateway never accumulates
        beyond one window of timestamps per lane."""
        if int(self.config.affinity_max_imbalance) <= 0:
            return
        now = time.monotonic()
        horizon = now - self.config.affinity_window_s
        with self._lock:
            dq = self._lane_recent.get(lane)
            if dq is None:
                dq = self._lane_recent[lane] = collections.deque()
            while dq and dq[0] < horizon:
                dq.popleft()
            dq.append(now)

    def _recent_dispatches(self, lanes) -> Dict[str, int]:
        horizon = time.monotonic() - self.config.affinity_window_s
        out = {}
        with self._lock:
            for lane in lanes:
                dq = self._lane_recent.get(lane)
                while dq and dq[0] < horizon:
                    dq.popleft()
                out[lane] = len(dq) if dq else 0
        return out

    def _affinity_count(self, trace: Optional[_RouteTrace], decision: str,
                        lane: Optional[str] = None) -> None:
        """Bump an affinity counter AND drop a zero-duration ``affinity``
        marker span under the request's route span (same counters==spans
        discipline as the resilience markers)."""
        self.affinity.bump(decision)
        if trace is not None:
            child = trace.ctx.child()
            attrs = {"decision": decision}
            if lane is not None:
                attrs["lane"] = lane
            self.tracer.record(
                trace.request_id, "affinity", "gateway", 0,
                trace_id=child.trace_id, span_id=child.span_id,
                parent_id=trace.ctx.span_id, start_ts=time.time(),
                attrs=attrs)

    def _affinity_primary(self, ring, ring_primary: str, payload: dict,
                          skip: tuple,
                          trace: Optional[_RouteTrace]) -> str:
        """The affinity half of primary selection: route generate-class
        requests to the lane owning the prompt-prefix fingerprint so
        shared prefixes converge where the KV blocks already live.
        Falls back to ``ring_primary`` (the request_id ring — the exact
        pre-affinity behavior, failover machinery unchanged) when there
        is nothing to fingerprint, the affinity lane is skipped (a
        resume off a dead lane), ejected by the prober, refused by its
        breaker, or running hotter than its least-loaded ring peer by
        more than affinity_max_imbalance recent dispatches."""
        fp = self._affinity_fingerprint(payload)
        if fp is None:
            self._affinity_count(trace, "no_fingerprint")
            return ring_primary
        try:
            lane = ring.get_node(fp)
        except RuntimeError:
            return ring_primary
        if skip and lane in skip:
            # Stream resume: the affinity lane just died mid-stream —
            # ring order takes over (the skip branch of _route_inner).
            self._affinity_count(trace, "resume_skips", lane=lane)
            return ring_primary
        with self._lock:
            ejected = lane in self._ejected
            breaker = self._breakers.get(lane)
        if ejected or breaker is None or not breaker.allow_request():
            self._affinity_count(trace, "ejected_fallbacks", lane=lane)
            return ring_primary
        imb = int(self.config.affinity_max_imbalance)
        if imb > 0 and lane != ring_primary:
            recent = self._recent_dispatches(ring.get_all_nodes())
            if recent.get(lane, 0) - min(recent.values()) >= imb:
                self._affinity_count(trace, "imbalance_fallbacks",
                                     lane=lane)
                return ring_primary
        self._affinity_count(trace, "affinity_routed", lane=lane)
        with self._lock:
            self._affinity_assigned[lane] = (
                self._affinity_assigned.get(lane, 0) + 1)
        return lane

    # -- fleet prefix directory (DESIGN.md "Fleet-wide prefix tier") ----------

    def _prefix_dir_count(self, decision: str,
                          trace: Optional[_RouteTrace] = None,
                          **attrs) -> None:
        """Bump a prefix-directory counter AND drop a zero-duration
        ``prefix_dir`` marker span — under the request's route span when
        one exists (hint attachment, lookup misses), else root-context
        (prober seeds, membership invalidations). Same counters==spans
        discipline as the affinity/fleet markers; fault_injection
        --fleet-prefix asserts the two agree."""
        self.prefix_dir.bump(decision)
        span_attrs = {"decision": decision,
                      **{k: v for k, v in attrs.items() if v is not None}}
        if trace is not None:
            child = trace.ctx.child()
            self.tracer.record(
                trace.request_id, "prefix_dir", "gateway", 0,
                trace_id=child.trace_id, span_id=child.span_id,
                parent_id=trace.ctx.span_id, start_ts=time.time(),
                attrs=span_attrs)
        else:
            ctx = TraceContext.root(f"prefix_dir:{decision}").child()
            self.tracer.record(
                "prefix_dir", "prefix_dir", "gateway", 0,
                trace_id=ctx.trace_id, span_id=ctx.span_id,
                start_ts=time.time(), attrs=span_attrs)

    def _seed_prefix_dir(self, lane: str, summaries) -> None:
        """Turn one lane's bounded /health radix summaries into
        directory entries (prober sweep seeding). One ``seeded``
        bump+span per sweep that changed anything — per-entry spans
        would drown the recorder at probe cadence; ``evictions`` is a
        span-free value counter for the same reason."""
        if not isinstance(summaries, list) or not summaries:
            return
        recorded = evicted = deepest = 0
        for entry in summaries[:32]:
            if not isinstance(entry, dict):
                continue
            fp = self._affinity_fingerprint(
                {"prompt_tokens": entry.get("tokens")})
            try:
                blocks = int(entry.get("blocks", 0))
            except (TypeError, ValueError):
                continue
            if fp is None or blocks <= 0:
                continue
            with self._lock:
                if lane not in self._clients:
                    return  # removed mid-sweep: nothing to advertise
                cur = self._prefix_dir.lookup(fp)
                if (cur is not None and cur["lane"] == lane
                        and cur["blocks"] >= blocks):
                    continue  # already known this deep; LRU-touched
                evicted += self._prefix_dir.record(fp, lane, blocks)
            recorded += 1
            deepest = max(deepest, blocks)
        if evicted:
            self.prefix_dir.bump("evictions", evicted)
        if recorded:
            self._prefix_dir_count("seeded", lane=lane,
                                   entries=recorded, deepest=deepest)

    def _attach_prefix_hint(self, payload: dict, primary: str,
                            trace: Optional[_RouteTrace]) -> None:
        """Stamp the directory's owner lane onto a generate-class
        payload as ``prefix_hint`` so the SERVING lane — wherever ring
        order, affinity, or failover actually lands the request — can
        pull the owner's KV chain peer-to-peer instead of re-prefilling
        it. No hint when the prompt has no full block, the directory
        names nobody (or the entry went stale), or the owner IS the
        chosen primary (the request lands on the blocks already). The
        hint rides the payload through failover: a retry lane benefits
        exactly like the primary."""
        fp = self._affinity_fingerprint(payload)
        if fp is None:
            return  # nothing a radix tree could share at block grain
        with self._lock:
            entry = self._prefix_dir.lookup(fp)
            client = (self._clients.get(entry["lane"])
                      if entry is not None else None)
        if entry is None or client is None:
            self._prefix_dir_count("lookup_misses", trace=trace)
            return
        if entry["lane"] == primary:
            return  # affinity already converged us onto the owner
        hint = {"lane": entry["lane"], "fingerprint": fp,
                "blocks": int(entry["blocks"])}
        addr = getattr(client, "url", None)
        if addr:
            hint["addr"] = addr
        payload["prefix_hint"] = hint
        self._prefix_dir_count("hints_attached", trace=trace,
                               lane=entry["lane"],
                               blocks=int(entry["blocks"]))

    def _record_prefix_owner(self, payload: dict, lane: str) -> None:
        """Post-completion directory update: the lane that just served a
        generate-class dispatch indexed this prompt in its radix tree at
        admission, so it now owns the fingerprint's chain. The record
        keeps a live DEEPER entry on another lane (a prober-seeded deep
        chain must not be demoted by a shallow completion); an unchanged
        entry is LRU-touched without a bump (bounded span volume)."""
        fp = self._affinity_fingerprint(payload)
        if fp is None:
            return
        toks = payload.get("prompt_tokens") or ()
        bs = max(1, int(self.config.affinity_block_size))
        blocks = len(toks) // bs
        if blocks <= 0:
            return
        with self._lock:
            if lane not in self._clients:
                return
            cur = self._prefix_dir.lookup(fp)
            if (cur is not None and cur["lane"] == lane
                    and cur["blocks"] >= blocks):
                return
            evicted = self._prefix_dir.record(fp, lane, blocks)
        if evicted:
            self.prefix_dir.bump("evictions", evicted)
        self._prefix_dir_count("recorded", lane=lane, blocks=blocks)

    def _route(self, payload: dict, op: str, skip: tuple = (),
               out_info: Optional[dict] = None) -> dict:
        """``skip``: lanes excluded from dispatch for this route (the
        stream-resume path skips the lane that just died mid-stream).
        ``out_info``: optional dict the dispatch layer fills with
        ``{"lane": name}`` on success — the resume journal needs to know
        which lane served a stream to skip it on the next attempt."""
        # In-flight gauge + shed-rate window (overload control only — a
        # defaults-only gateway pays nothing): the gauge covers the
        # request's whole residency — blocking ops until their response,
        # streams until their event iterator finishes (the decrement is
        # handed to a wrapper below), so a stream-heavy fleet's gauge
        # actually fills and tier admission/pressure stay live.
        overload_on = (self.config.overload_control
                       or self._tenant_bucket is not None)
        with self._lock:
            self._total_requests += 1
            if overload_on:
                self._inflight += 1
        self._retry_budget.record_request()
        # Anonymous requests get a stable server-side request_id (minted
        # once, forwarded to the lane, echoed in the response) instead of
        # the old route-on-a-random-key: the id doubles as the trace root,
        # so even an id-less request is correlatable end to end.
        rid = payload.get("request_id")
        if rid is None:
            rid = uuid.uuid4().hex
            payload = {**payload, "request_id": rid}
        request_id = str(rid)
        trace = _RouteTrace(request_id, TraceContext.from_request(payload))
        t0 = time.perf_counter()
        start = time.time()
        handed_off = False
        try:
            result = self._route_inner(payload, op, request_id, trace,
                                       skip=skip, out_info=out_info)
            trace.outcome = "ok"
            if (overload_on and op == "generate_stream"
                    and hasattr(result, "__iter__")):
                # The stream occupies the gauge until its iterator
                # settles; the wrapper owns the decrement from here.
                result = self._inflight_watched(result)
                handed_off = True
            return result
        except ShedError as exc:
            trace.outcome = exc.kind
            raise
        except Exception:
            trace.outcome = "error"
            raise
        finally:
            if overload_on:
                if not handed_off:
                    with self._lock:
                        self._inflight -= 1
                # Congestion refusals (not deadline expiries, not faults)
                # feed the shed-rate pressure window.
                self._shed_stats.record(trace.outcome == "overloaded")
            self.tracer.record(
                request_id, "route", "gateway",
                (time.perf_counter() - t0) * 1e6,
                trace_id=trace.ctx.trace_id, span_id=trace.ctx.span_id,
                parent_id=(trace.parent.span_id if trace.parent is not None
                           else None),
                start_ts=start, attrs={"op": op, "outcome": trace.outcome})

    def _count(self, trace: Optional[_RouteTrace], decision: str) -> None:
        """Bump a resilience counter AND drop a zero-duration marker span
        under the request's route span — the counters say how often, the
        markers say for WHICH requests (tools/fault_injection.py asserts
        the two agree)."""
        self.resilience.bump(decision)
        if trace is not None:
            child = trace.ctx.child()
            self.tracer.record(
                trace.request_id, "resilience", "gateway", 0,
                trace_id=child.trace_id, span_id=child.span_id,
                parent_id=trace.ctx.span_id, start_ts=time.time(),
                attrs={"decision": decision})

    def _route_inner(self, payload: dict, op: str, request_id: str,
                     trace: _RouteTrace, skip: tuple = (),
                     out_info: Optional[dict] = None) -> dict:
        # Deadline admission: an already-expired request sheds HERE — one
        # cheap 503 + Retry-After instead of a doomed dispatch chain (and,
        # downstream, a burned batch row).
        deadline = Deadline.from_request(
            payload, default_ms=self.config.default_deadline_ms)
        if deadline is not None and deadline.expired():
            self._count(trace, "deadline_rejected")
            exc = self._shed(DeadlineExceeded(
                "deadline exceeded at gateway admission"))
            exc.stage = "gateway_admission"
            raise exc
        # Overload control (default off): per-tenant rate limiting and
        # priority-tiered admission against the in-flight gauge — the
        # lowest tier sheds first, and every refusal carries a
        # load-derived Retry-After.
        if self._tenant_bucket is not None or self.config.overload_control:
            self._overload_admit(payload, trace)
        # "model" restricts routing AND failover to that model's sub-ring;
        # without the field, multi-model gateways use the deterministic
        # default model, single-model gateways the global ring.
        mdl = payload.get("model")
        probing = False  # model unknown to the gateway; workers validate
        with self._lock:
            multi = len(self._model_rings) > 1
            untyped = bool(self._untyped)
            if mdl is None and multi:
                mdl = self.default_model
            if mdl is not None:
                ring = self._model_rings.get(str(mdl))
                if ring is None and untyped:
                    # Workers with unknown models (HTTP URLs carry no
                    # metadata) might serve it: probe the global ring and
                    # let each worker's _check_model decide — a mismatch
                    # fails over instead of 400ing a servable request.
                    ring, probing = self._ring, True
            else:
                ring = self._ring
            # Snapshot the served-model list for the error below while
            # the lock is still held — iterating the live dict after
            # release races add_worker/remove_worker.
            known = sorted(self._model_rings) if ring is None else ()
        if ring is None:
            raise ValueError(            # wire 400, not a lane failure
                f"unknown model '{mdl}'; serving {known}")
        try:
            primary = ring.get_node(request_id)
        except RuntimeError:  # every lane of this model was removed
            raise GatewayError(f"no workers available for model '{mdl}'")
        if payload.get("handoff") and op == "generate_stream":
            # Disaggregated first segment: the prefill ring owns
            # primary selection (affinity fingerprint folded in), with
            # ring order over everyone as the colocated fallback.
            primary = self._handoff_primary(ring, primary, payload,
                                            skip, trace)
        elif (self.config.prefix_affinity
                and op in ("generate", "generate_stream")):
            primary = self._affinity_primary(ring, primary, payload,
                                             skip, trace)
        # Fleet prefix tier: AFTER primary selection (any flavor) the
        # directory gets one shot at stamping a peer-fetch hint — the
        # tier is routing-neutral (never changes which lane serves, only
        # what the serving lane can skip re-prefilling).
        if (self._prefix_dir_on
                and op in ("generate", "generate_stream")
                and "prefix_hint" not in payload):
            self._attach_prefix_hint(payload, primary, trace)

        if skip and primary in skip:
            # The resume path excludes the lane that just failed its
            # stream: go straight to ring-order failover (budgeted and
            # deadline-bounded like any other failover march).
            with self._lock:
                self._failovers += 1
            return self._failover(ring, primary, payload, op, probing,
                                  deadline, skip=skip, trace=trace,
                                  out_info=out_info)
        if self.config.hedge_enabled and op in _HEDGEABLE_OPS:
            return self._route_hedged(ring, primary, payload, op,
                                      probing, deadline, trace)
        result = self._try_node(primary,
                                self._with_deadline(payload, deadline),
                                op=op, probing=probing, trace=trace,
                                out_info=out_info, ring=ring)
        if not _ok(result):
            with self._lock:
                self._failovers += 1
            result = self._failover(ring, primary, payload, op,
                                    probing, deadline, skip=skip,
                                    shed_seen=result is _SHED, trace=trace,
                                    out_info=out_info)
        return result

    def _shed(self, exc):
        """Stamp a shed-class exception with the Retry-After hint: the
        configured constant, or — with overload control on — that base
        scaled by measured pressure (monotone: the more saturated the
        fleet, the longer clients are told to stay away)."""
        if self.config.overload_control:
            exc.retry_after_s = load_retry_after(
                self.config.shed_retry_after_s, self._overload_pressure())
        else:
            exc.retry_after_s = self.config.shed_retry_after_s
        return exc

    # -- overload control ------------------------------------------------------

    def _inflight_watched(self, it):
        """Relay a stream iterator unchanged, decrementing the in-flight
        gauge exactly once when it finishes (exhaustion, error, or the
        client closing early)."""
        def watched():
            try:
                yield from it
            finally:
                with self._lock:
                    self._inflight -= 1
        return relay(watched(), it)

    def _overload_pressure(self) -> float:
        """Measured congestion in [0, inf): the in-flight gauge's fill
        fraction when one is configured, else the recent shed rate —
        either way 0 when idle and growing with actual refusal risk."""
        if self.config.overload_max_inflight > 0:
            with self._lock:
                inflight = self._inflight
            return inflight / self.config.overload_max_inflight
        return self._shed_stats.pressure()

    def _overload_count(self, trace: Optional[_RouteTrace], decision: str,
                        **attrs) -> None:
        """Bump an overload counter AND drop a zero-duration ``overload``
        marker span under the request's route span (same counters==spans
        discipline as the resilience/affinity markers; fault_injection
        --overload asserts the two agree)."""
        self.overload.bump(decision)
        if trace is not None:
            child = trace.ctx.child()
            self.tracer.record(
                trace.request_id, "overload", "gateway", 0,
                trace_id=child.trace_id, span_id=child.span_id,
                parent_id=trace.ctx.span_id, start_ts=time.time(),
                attrs={"decision": decision, **attrs})

    def _overload_admit(self, payload: dict,
                        trace: Optional[_RouteTrace]) -> None:
        """Gateway overload admission, cheapest check first. Order
        matters: the tenant bucket refuses a flooding tenant even while
        the fleet has headroom (fairness is not a congestion question);
        tier admission then sheds lowest-tier-first as the in-flight
        gauge fills, and only a gauge at its full limit refuses
        top-tier work."""
        cfg = self.config
        if self._tenant_bucket is not None:
            tenant = str(payload.get("tenant", "default"))
            ok, wait = self._tenant_bucket.allow(tenant)
            if not ok:
                self._overload_count(trace, "rate_limited", tenant=tenant)
                exc = self._shed(Overloaded(
                    f"tenant '{tenant}' over its rate limit "
                    f"({cfg.tenant_rate:g} req/s)"))
                # The bucket knows its actual refill time; never suggest
                # retrying sooner than a token can exist.
                exc.retry_after_s = max(exc.retry_after_s, wait)
                exc.cause = "rate_limit"
                exc.stage = "gateway_admission"
                raise exc
        if not cfg.overload_control:
            return
        # Unknown value -> wire 400 whenever the master switch is on,
        # gauge or no gauge — a typo'd priority must never silently ride
        # as routable traffic (MIGRATION.md documents the contract).
        tier = parse_priority(payload)
        limit = cfg.overload_max_inflight
        if limit <= 0:
            return  # no gauge: tier admission off, validation only
        with self._lock:
            inflight = self._inflight  # includes this request
        if inflight > limit:
            self._overload_count(trace, "shed_depth",
                                 tier=TIER_NAMES[tier])
            exc = self._shed(Overloaded(
                f"gateway at max in-flight {limit}"))
            exc.cause = "depth"
            exc.stage = "gateway_admission"
            raise exc
        if (tier < len(TIER_ADMIT_FRAC) - 1
                and inflight > tier_limit(limit, tier)):
            self._overload_count(trace, "shed_tier",
                                 tier=TIER_NAMES[tier])
            exc = self._shed(Overloaded(
                f"gateway shedding priority tier '{TIER_NAMES[tier]}' "
                f"at {inflight}/{limit} in flight"))
            exc.cause = "tier"
            exc.stage = "gateway_admission"
            raise exc

    @staticmethod
    def _with_deadline(payload: dict, deadline: Optional[Deadline]) -> dict:
        """Deadline propagation: each dispatch carries the budget REMAINING
        at dispatch time (recomputed per attempt, so retries after backoff
        forward a smaller number). No deadline → payload untouched, wire
        bytes identical to the pre-resilience gateway."""
        if deadline is None:
            return payload
        return {**payload, "deadline_ms": max(0.0, deadline.remaining_ms())}

    def _failover(self, ring, primary: str, payload: dict, op: str,
                  probing: bool, deadline: Optional[Deadline],
                  skip: tuple = (), shed_seen: bool = False,
                  trace: Optional[_RouteTrace] = None,
                  out_info: Optional[dict] = None) -> dict:
        """Ring-order failover across every other lane (gateway.cpp:51-59)
        — now deadline-bounded, budgeted, and backed off: each attempt
        consumes the global retry budget (failover storms cannot amplify
        an outage past `1 + ratio`), sleeps an exponential+jittered delay
        (base 0 = reference's immediate march), and stops the moment the
        client's budget is gone. A march where at least one lane SHED
        (rather than failed) terminates as Overloaded (wire 503 +
        Retry-After): fleet congestion must read as back-off-and-retry,
        never as an outage."""
        cfg = self.config
        attempt = 0
        for node in ring.get_all_nodes():
            if node == primary or node in skip:
                continue
            if deadline is not None and deadline.expired():
                self._count(trace, "deadline_expired")
                exc = self._shed(DeadlineExceeded(
                    "deadline exceeded during failover"))
                exc.stage = "failover"
                raise exc
            if not self._retry_budget.try_acquire():
                self._count(trace, "retry_budget_exhausted")
                if shed_seen:
                    # A lane SHED this request before the budget ran out:
                    # the march is ending under congestion, and congestion
                    # must surface as 503 + Retry-After (back off and
                    # retry), never the 500-class outage below.
                    exc = self._shed(Overloaded(
                        "retry budget exhausted after a lane shed the "
                        "request (overloaded, not failed)"))
                    exc.stage = "failover"
                    raise exc
                raise GatewayError(
                    "retry budget exhausted (retries capped at "
                    f"{cfg.retry_budget_ratio:.0%} of recent requests)")
            delay = backoff_delay(attempt, cfg.retry_backoff_base_ms,
                                  cfg.retry_backoff_max_ms,
                                  cfg.retry_jitter)
            if delay > 0:
                if deadline is not None:
                    delay = min(delay, max(0.0, deadline.remaining_s()))
                self._count(trace, "backoff_waits")
                time.sleep(delay)
            self._count(trace, "retries")
            result = self._try_node(node,
                                    self._with_deadline(payload, deadline),
                                    op=op, probing=probing, trace=trace,
                                    kind="retry", out_info=out_info,
                                    ring=ring)
            if _ok(result):
                return result
            shed_seen = shed_seen or result is _SHED
            attempt += 1
        if shed_seen:
            exc = self._shed(Overloaded(
                "all lanes shed the request (overloaded or draining)"))
            exc.stage = "failover"
            raise exc
        raise GatewayError("All workers failed or unavailable")

    def _pool(self) -> concurrent.futures.ThreadPoolExecutor:
        # Generous cap: with hedging on, EVERY hedgeable dispatch rides
        # this pool (1-2 threads per in-flight request), and the serving
        # front is thread-per-request with no cap of its own — an
        # undersized pool would throttle overall concurrency, not just
        # hedges. 256 sits far above the stdlib front's practical
        # concurrency; threads spawn on demand, so idle cost is zero.
        with self._lock:
            if self._hedge_pool is None:
                self._hedge_pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=256, thread_name_prefix="gw-hedge")
            return self._hedge_pool

    def _lane_tracker(self, node: str) -> LatencyTracker:
        with self._lock:
            tracker = self._latency.get(node)
            if tracker is None:
                tracker = self._latency[node] = LatencyTracker()
            return tracker

    def _hedge_threshold_s(self, primary: Optional[str] = None) -> float:
        """When to give up waiting on `primary`: the best OTHER lane's
        latency quantile — "hedge once the primary exceeds what a healthy
        alternative would take at p-q" — floored at hedge_min_ms (and
        pure hedge_min_ms until some other lane has enough samples).
        Excluding the primary's own window keeps a degraded lane from
        raising its own threshold. primary=None (stats) uses all lanes."""
        cfg = self.config
        thr = cfg.hedge_min_ms / 1000.0
        with self._lock:
            trackers = [t for n, t in self._latency.items() if n != primary]
        quantiles = [t.quantile(cfg.hedge_quantile) for t in trackers
                     if len(t) >= cfg.hedge_min_samples]
        quantiles = [q for q in quantiles if q is not None]
        if quantiles:
            thr = max(thr, min(quantiles))
        return thr

    def _route_hedged(self, ring, primary: str, payload: dict, op: str,
                      probing: bool, deadline: Optional[Deadline],
                      trace: Optional[_RouteTrace] = None) -> dict:
        """Hedged dispatch (idempotent ops only): wait `threshold` on the
        primary; if it is merely SLOW — the failure mode breakers cannot
        see — fire the next ring lane and take whichever answers first.
        The loser's result is discarded ("cancelled" at the routing layer;
        its lane simply finishes and the breaker records its outcome).
        Hedges consume the retry budget, so a quantile collapse cannot
        double fleet load. Tracing: primary and hedge dispatches record
        sibling ``attempt`` spans (same trace_id, distinct span_ids) under
        the route span."""
        pool = self._pool()
        p_started = threading.Event()
        t_start: list = [None]

        def _primary_task():
            t_start[0] = time.perf_counter()
            p_started.set()
            return self._try_node(primary,
                                  self._with_deadline(payload, deadline),
                                  op, probing, trace=trace, kind="primary",
                                  ring=ring)

        p_fut = pool.submit(_primary_task)

        def _record_primary(fut):
            # Feed the quantile EVERY primary completion (measured from its
            # dispatch start), wherever the route ended up: recording only
            # within-threshold successes would censor the sample at the
            # threshold and pin it at hedge_min_ms forever; recording
            # whole-route time would inflate it with backoff/failover
            # exactly when lanes degrade.
            try:
                r = fut.result()
            except BaseException:
                return
            if _ok(r) and t_start[0] is not None:
                self._lane_tracker(primary).record(
                    time.perf_counter() - t_start[0])

        p_fut.add_done_callback(_record_primary)
        # Arm the hedge timer only once the dispatch actually STARTED: a
        # saturated pool queues tasks, and hedging a primary that never
        # ran would amplify load against perfectly healthy lanes — the
        # exact spiral hedging must not feed.
        if not p_started.wait(timeout=None if deadline is None
                              else max(0.0, deadline.remaining_s())):
            # The task never started (saturated pool): cancel it so the
            # queued thunk doesn't later dispatch a request nobody will
            # read — abandoned dispatches against an already-saturated
            # fleet are the amplification spiral this wait guards.
            p_fut.cancel()
            self._count(trace, "deadline_expired")
            raise self._shed(DeadlineExceeded(
                "deadline exceeded before primary dispatch started"))
        thr = self._hedge_threshold_s(primary)
        deadline_clamped = (deadline is not None
                            and deadline.remaining_s() < thr)
        if deadline_clamped:
            thr = max(0.0, deadline.remaining_s())
        try:
            result = p_fut.result(timeout=thr)
        except concurrent.futures.TimeoutError:
            if deadline_clamped:
                # The wait ended because the CLIENT's budget ran out, not
                # because the lane exceeded the latency threshold: a hedge
                # here would burn a shared retry-budget token dispatching
                # a request the hedge lane must immediately shed. Ride out
                # the remaining budget on the primary instead.
                return self._await_primary(p_fut, ring, primary, payload,
                                           op, probing, deadline, trace)
            result = None
        else:
            if _ok(result):
                return result  # latency recorded by the done-callback
            # Primary failed FAST (dead or shedding lane): plain budgeted
            # failover.
            with self._lock:
                self._failovers += 1
            return self._failover(ring, primary, payload, op, probing,
                                  deadline, shed_seen=result is _SHED,
                                  trace=trace)

        # Primary exceeded the hedge threshold. Pick the next lane whose
        # breaker admits traffic; no budget, no lane → ride out the primary.
        hedge_node = next(
            (n for n in ring.get_all_nodes()
             if n != primary and self._breaker_allows(n)), None)
        if hedge_node is None or not self._retry_budget.try_acquire():
            if hedge_node is not None:
                self._count(trace, "retry_budget_exhausted")
            return self._await_primary(p_fut, ring, primary, payload, op,
                                       probing, deadline, trace)
        self._count(trace, "hedges")
        h_fut = pool.submit(self._try_node, hedge_node,
                            self._with_deadline(payload, deadline),
                            op, probing, trace=trace, kind="hedge",
                            ring=ring)
        pending = {p_fut: primary, h_fut: hedge_node}
        first_error: Optional[BaseException] = None
        shed_seen = False
        while pending:
            timeout = (None if deadline is None
                       else max(0.0, deadline.remaining_s()))
            done, _ = concurrent.futures.wait(
                list(pending), timeout=timeout,
                return_when=concurrent.futures.FIRST_COMPLETED)
            if not done:  # deadline ran out waiting on both lanes
                self._count(trace, "deadline_expired")
                raise self._shed(DeadlineExceeded(
                    "deadline exceeded awaiting hedged dispatch"))
            for fut in done:
                pending.pop(fut)
                try:
                    result = fut.result()
                except BaseException as exc:
                    first_error = first_error or exc
                    continue
                if _ok(result):
                    self._count(trace, "hedge_wins" if fut is h_fut
                                else "hedge_losses")
                    return result
                shed_seen = shed_seen or result is _SHED
        # Both lanes failed/shed: budgeted failover over the remainder.
        with self._lock:
            self._failovers += 1
        try:
            return self._failover(ring, primary, payload, op, probing,
                                  deadline, skip=(hedge_node,),
                                  shed_seen=shed_seen, trace=trace)
        except GatewayError:
            if first_error is not None:
                raise first_error
            raise

    def _await_primary(self, p_fut, ring, primary, payload, op, probing,
                       deadline: Optional[Deadline],
                       trace: Optional[_RouteTrace] = None) -> dict:
        """Hedge unavailable: block on the primary alone (deadline-bounded),
        then fall back to plain failover if it ultimately failed."""
        timeout = (None if deadline is None
                   else max(0.0, deadline.remaining_s()))
        try:
            result = p_fut.result(timeout=timeout)
        except concurrent.futures.TimeoutError:
            self._count(trace, "deadline_expired")
            raise self._shed(DeadlineExceeded(
                "deadline exceeded awaiting primary lane"))
        if _ok(result):
            return result
        with self._lock:
            self._failovers += 1
        return self._failover(ring, primary, payload, op, probing, deadline,
                              shed_seen=result is _SHED, trace=trace)

    def _breaker_allows(self, node: str) -> bool:
        with self._lock:
            breaker = self._breakers.get(node)
        return breaker is not None and breaker.allow_request()

    def _try_node(self, node: str, payload: dict, op: str = "infer",
                  probing: bool = False,
                  trace: Optional[_RouteTrace] = None,
                  kind: str = "primary",
                  out_info: Optional[dict] = None,
                  ring=None) -> Optional[dict]:
        """Breaker-gated dispatch (reference tryNode, gateway.cpp:80-128).
        Returns None on failure so the caller can fail over. `probing`:
        the gateway couldn't resolve the request's model itself, so a
        worker's model-mismatch rejection (a client-class 4xx/ValueError)
        means "try the next lane" — no breaker penalty, no terminal 400.

        Tracing: each dispatch records an ``attempt`` span (child of the
        route span; ``kind`` = primary | retry | hedge, sibling attempts
        share the trace_id with distinct span_ids). When the CLIENT
        supplied a traceparent, the attempt's own context is re-forwarded
        in the payload — worker-side spans then parent under this exact
        attempt; traceless payloads are forwarded untouched."""
        with self._lock:
            client = self._clients.get(node)
            breaker = self._breakers.get(node)
            ejected = node in self._ejected
        if client is None or breaker is None:
            return None
        if ejected:
            # The health prober took this lane out of rotation: skip it
            # like a failed dispatch (the caller fails over) with no
            # breaker penalty — ejection is the prober's reversible call.
            # Fail OPEN when probe evidence alone has ejected every lane
            # of THIS request's ring (e.g. a fleet-wide compile stall
            # tripping a tight scheduler_stall_s): ejection is only
            # honored while at least one peer remains in rotation, so
            # request evidence — the breakers — stays the last word on a
            # total outage. Per-ring, not fleet-wide: one model's lanes
            # all dying must fail open for THAT model even while other
            # models' lanes are healthy.
            peers = ring.get_all_nodes() if ring is not None else None
            with self._lock:
                if peers is None:
                    peers = list(self._clients)
                all_ejected = all(p in self._ejected for p in peers)
            if not all_ejected:
                return None
        if not breaker.allow_request():
            return None
        ctx = None
        if trace is not None:
            ctx = trace.ctx.child()
            if trace.traced:
                payload = {**payload, "traceparent": ctx.to_traceparent()}
        t0 = time.perf_counter()
        start = time.time()
        outcome = "error"

        def _span():
            if trace is not None:
                self.tracer.record(
                    trace.request_id, "attempt", "gateway",
                    (time.perf_counter() - t0) * 1e6,
                    trace_id=ctx.trace_id, span_id=ctx.span_id,
                    parent_id=trace.ctx.span_id, start_ts=start,
                    attrs={"lane": node, "kind": kind, "outcome": outcome})

        try:
            response = getattr(client, op)(payload)
            breaker.record_success()
            outcome = "ok"
            if (self.config.prefix_affinity
                    and op in ("generate", "generate_stream")):
                self._count_lane_dispatch(node)
            if (self._prefix_dir_on
                    and op in ("generate", "generate_stream")):
                # Post-completion update: this lane's radix tree indexed
                # the prompt at admission — record it as the owner so
                # the NEXT shared-prefix request can fetch from here.
                self._record_prefix_owner(payload, node)
            if out_info is not None:
                out_info["lane"] = node
            return response
        except WorkerError:
            breaker.record_failure()
            outcome = "failed"
            return None
        except Overloaded:
            # The lane SHED the request (queue full / draining): healthy
            # but busy — fail over without a breaker penalty (a breaker
            # trip would amplify the overload into an outage).
            self._count(trace, "shed_overloaded")
            outcome = "shed"
            return _SHED
        except DeadlineExceeded as exc:
            # The client's budget is gone; no other lane can help. A
            # lane_suspect expiry (the lane HELD the request past its
            # budget without answering — hang signature) still feeds the
            # breaker so a dead lane loses its hash share; a clean worker
            # 503 does not.
            if getattr(exc, "lane_suspect", False):
                breaker.record_failure()
            self._count(trace, "deadline_expired")
            outcome = "deadline"
            shed = self._shed(DeadlineExceeded(
                f"deadline exceeded at lane {node}"))
            shed.stage = "lane"
            raise shed
        except ValueError:
            if probing:
                outcome = "wrong_model"
                return None  # wrong-model lane; healthy — no penalty
            raise
        finally:
            _span()

    # -- observability --------------------------------------------------------

    def _resilience_configured(self) -> bool:
        cfg = self.config
        return (cfg.default_deadline_ms is not None or cfg.hedge_enabled
                or cfg.retry_budget_ratio is not None
                or cfg.retry_backoff_base_ms > 0)

    def get_stats(self) -> dict:
        """Exact /stats schema (``gateway.cpp:63-77``).

        Every membership-adjacent snapshot (breakers, role map,
        topology block, lane list, affinity totals, in-flight gauge,
        fleet degraded map) is taken under ONE ``_lock`` acquisition —
        the same idiom as the PR 8 ``_route_inner`` fix. Snapshotting
        them piecemeal let a concurrent add/remove land between the
        acquisitions, publishing a torn read: a lane present in the
        ``handoff.roles`` map but missing from ``topology.ring_weights``
        (or vice versa) within one response body."""
        with self._lock:
            items = list(self._breakers.items())
            total, failovers = self._total_requests, self._failovers
            active_streams = len(self._streams)
            lanes = sorted(self._clients)
            roles = {n: self._roles.get(n, "both") for n in lanes}
            topo = dict(self._topology)
            topo_updates = self._topology_updates
            aff_assigned = dict(self._affinity_assigned)
            inflight = self._inflight
            fleet_degraded = dict(self._fleet_degraded)
            fleet_pressure = self._fleet_pressure
            prefix_dir_state = (self._prefix_dir.stats()
                                if self._prefix_dir is not None else None)
        out = {
            "total_workers": len(items),
            # Additive fields (reference /stats has only total_workers +
            # circuit_breakers; extra keys don't break its parsers).
            "total_requests": total,
            "failovers": failovers,
            "circuit_breakers": [
                {
                    "node": node,
                    "state": br.state_name(),
                    "failures": br.failure_count,
                    "successes": br.success_count,
                }
                for node, br in items
            ],
        }
        # Additive, and only once the resilience layer is configured or
        # has made a decision (deadline-carrying request, shed, retry,
        # hedge): a defaults-only deployment's /stats stays byte-identical
        # to the breaker-only schema above.
        if self._resilience_configured() or self.resilience.any_nonzero():
            res = self.resilience.as_dict()
            if self._retry_budget.enabled:
                res["retry_budget"] = self._retry_budget.stats()
            if self.config.hedge_enabled:
                res["hedge_threshold_ms"] = round(
                    self._hedge_threshold_s() * 1000.0, 3)
            out["resilience"] = res
        # Additive "failover" block (crash-tolerant streaming + prober),
        # present only once the feature is configured or has decided
        # something — defaults-only /stats stays byte-identical.
        if (self.config.failover_streams
                or self.config.health_probe_interval_s > 0
                or self.failover.any_nonzero()):
            fo = self.failover.as_dict()
            fo["ejected_lanes"] = self.ejected_lanes()
            out["failover"] = fo
        # Additive "migration" block (live stream migration + the
        # bounded-drain counter), same gating discipline.
        if self.config.migrate_streams or self.migration.any_nonzero():
            mig = self.migration.as_dict()
            mig["active_streams"] = active_streams
            out["migration"] = mig
        # Additive "handoff" block (disaggregated prefill/decode
        # serving), same gating discipline: present only once
        # configured or exercised.
        if self.config.disagg or self.handoff.any_nonzero():
            ho = self.handoff.as_dict()
            ho["roles"] = roles
            out["handoff"] = ho
        # Additive "topology" block (topology-aware ring), present only
        # once any lane carries a mesh-shape label — an all-single-chip
        # fleet's /stats stays byte-identical. Reports each labelled
        # lane's mesh shape plus every lane's vnode weight, so an
        # operator can see exactly how the ring maps chips.
        if topo:
            out["topology"] = {
                "lanes": topo,
                "ring_weights": {n: max(1, self._ring.node_weight(n))
                                 for n in lanes},
                "updates": topo_updates,
            }
        # Additive "affinity" block (prefix-affinity routing), same
        # gating discipline: a defaults-only /stats stays byte-identical.
        if self.config.prefix_affinity or self.affinity.any_nonzero():
            aff = self.affinity.as_dict()
            aff["assigned"] = aff_assigned
            out["affinity"] = aff
        # Additive "prefix_directory" block (fleet prefix tier), same
        # gating discipline: present only with --prefix-fetch (the
        # counters can't move while the directory is None), so a
        # defaults-off /stats stays byte-identical.
        if prefix_dir_state is not None or self.prefix_dir.any_nonzero():
            pd = self.prefix_dir.as_dict()
            if prefix_dir_state is not None:
                pd.update(prefix_dir_state)
            out["prefix_directory"] = pd
        # Additive "overload" block (adaptive overload control), same
        # gating discipline: present only once configured or exercised.
        if (self.config.overload_control or self._tenant_bucket is not None
                or self.overload.any_nonzero()):
            ov = self.overload.as_dict()
            ov["pressure"] = round(self._overload_pressure(), 4)
            ov["inflight"] = inflight
            if self.config.overload_max_inflight > 0:
                ov["max_inflight"] = self.config.overload_max_inflight
            if self._tenant_bucket is not None:
                ov["tenants"] = self._tenant_bucket.tenants()
            out["overload"] = ov
        # Additive "fleet" block (elastic fleet: autoscaler +
        # /admin/fleet), same gating discipline: present only once the
        # controller is configured or a fleet decision was made.
        if self.config.autoscale or self.fleet.any_nonzero():
            fl = self.fleet.as_dict()
            fl["lanes"] = len(lanes)
            fl["degraded"] = fleet_degraded
            if fleet_pressure is not None:
                fl["pressure"] = fleet_pressure
            out["fleet"] = fl
        # Additive "slo" block (observability plane): present only once
        # latency objectives are configured — windowed error-budget burn
        # over the histograms the fleet already keeps, zero new
        # measurement paths. Defaults-off /stats stays byte-identical.
        if self._slo is not None:
            slo = self.slo_status()
            if slo is not None:
                out["slo"] = slo
        # Additive "trace_ledger" block: which streams the stitcher can
        # currently reassemble (present only with --trace-stitch).
        if self._ledger is not None:
            out["trace_ledger"] = self._ledger.summary()
        return out
