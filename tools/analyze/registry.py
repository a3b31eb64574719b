"""The guarded-state registry: engine-lint's project-specific knowledge.

Everything the analyzers know about tpu_engine that is not derivable
from the AST lives here — which attributes each lock owns, which
classes document "caller holds the lock", which receiver expressions
alias which class, which counter families must pair with marker spans,
and where the per-tick hot path starts.

Annotating new code (see DESIGN.md "Static analysis"):
- a new lock-guarded structure -> add a ``GuardedEntry`` (and, if other
  modules reach it through an alias like ``pool``, a receiver alias +
  ``LOCK_ALIASES`` row);
- a class whose methods assume the caller holds the lock -> add
  ``Class.*`` to ``caller_locked``;
- a new decision-counter family with marker spans -> add its receiver
  attribute to ``counter_receivers``;
- a new scheduler tick/admission path -> add its root to
  ``tick_entries`` so the per-tick jit rule covers it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class GuardedEntry:
    """Attributes owned by `lock`. ``mode`` "rw": every access needs the
    lock; "w": only mutation does (readers tolerate staleness — the
    double-checked executable caches, GIL-safe stats reads)."""
    attrs: Tuple[str, ...]
    lock: str                     # canonical lock name
    classes: Tuple[str, ...]      # owner classes (for `self.<attr>`)
    receivers: Tuple[str, ...] = ()  # non-self receiver exprs (aliases)
    mode: str = "rw"


@dataclasses.dataclass(frozen=True)
class ThreadOwnedEntry:
    """Attributes owned by one thread: touched only by functions
    reachable from `entries` (the thread's run loop) or __init__."""
    attrs: Tuple[str, ...]
    owner_class: str
    module: str
    entries: Tuple[str, ...]      # qualified entry methods (thread roots)
    thread: str                   # human name for messages


@dataclasses.dataclass
class Registry:
    package: str
    # (class scope or None, with-expression, canonical lock name)
    lock_aliases: Tuple[Tuple[Optional[str], str, str], ...]
    reentrant: frozenset
    guarded: Tuple[GuardedEntry, ...]
    thread_owned: Tuple[ThreadOwnedEntry, ...]
    caller_locked: frozenset      # "Class.*" or "Class.method" patterns
    receiver_aliases: Dict[str, str]
    counter_receivers: frozenset  # attr names of decision-counter objects
    span_tracer_attrs: frozenset  # receiver tails whose .record() is a span
    span_sink_attrs: frozenset    # receiver tails whose .stage() is a span
    hot_static_params: frozenset  # param names treated as trace-static
    tick_entries: Tuple[str, ...]  # per-tick path roots (module:qual)
    cli_module: str
    config_module: str
    config_classes: Tuple[str, ...]

    def canonical_lock(self, expr: str,
                       class_name: Optional[str]) -> Optional[str]:
        """Map a `with <expr>` context expression to a canonical lock
        name. Explicit aliases first (optionally class-scoped), then the
        naming convention: any self/module attribute ending in "lock"."""
        for scope, alias, name in self.lock_aliases:
            if alias == expr and (scope is None or scope == class_name):
                return name
        if expr.startswith("self.") and "." not in expr[5:]:
            attr = expr[5:]
            if attr.endswith("lock") and class_name:
                return f"{class_name}.{attr}"
        if "." not in expr and expr.endswith("lock"):
            return f"<module>.{expr}"
        return None

    def is_caller_locked(self, fi) -> bool:
        if fi.class_name is None:
            return False
        return (f"{fi.class_name}.*" in self.caller_locked
                or f"{fi.class_name}.{fi.name}" in self.caller_locked)


# -- the tpu_engine instance --------------------------------------------------

_RECEIVER_ALIASES = {
    # BlockPool, reached from the scheduler and from RadixTree.
    "pool": "BlockPool",
    "self._pool": "BlockPool",
    # StateSlabPool (state_slab family), reached from the scheduler.
    "spool": "StateSlabPool",
    "self._spool": "StateSlabPool",
    # The pool's radix tree, driven under the pool lock.
    "pool.radix": "RadixTree",
    "self._pool.radix": "RadixTree",
    "self.radix": "RadixTree",
    # Gateway collaborators (lock-order edges).
    "ring": "ConsistentHash",
    "self._ring": "ConsistentHash",
    "breaker": "CircuitBreaker",
    "self._retry_budget": "RetryBudget",
    "self._probe_state": "ProbeStateMachine",
    "self.resilience": "ResilienceCounters",
    "self.failover": "FailoverCounters",
    "self.affinity": "AffinityCounters",
    "self.overload": "OverloadCounters",
    "self.migration": "MigrationCounters",
    "self.handoff": "HandoffCounters",
    "self.fleet": "FleetCounters",
    "self.prefix_dir": "PrefixDirCounters",
    "self._prefix_dir": "PrefixDirectory",
    "self._tenant_bucket": "TenantRateLimiter",
    "self._shed_stats": "SheddingStats",
    "self._aimd": "AIMDLimit",
    "self._brownout": "BrownoutController",
    "self.tracer": "SpanRecorder",
}

ENGINE_REGISTRY = Registry(
    package="tpu_engine",
    lock_aliases=(
        # `self.lock` is scoped per owning class — an unscoped alias
        # would canonicalize EVERY pool's internal `with self.lock:` to
        # BlockPool.lock (StateSlabPool's would be wrong).
        ("BlockPool", "self.lock", "BlockPool.lock"),
        ("RadixTree", "self.lock", "BlockPool.lock"),
        (None, "pool.lock", "BlockPool.lock"),
        (None, "self._pool.lock", "BlockPool.lock"),
        # The state-slab pool's own lock (state_slab family).
        ("StateSlabPool", "self.lock", "StateSlabPool.lock"),
        (None, "spool.lock", "StateSlabPool.lock"),
        (None, "self._spool.lock", "StateSlabPool.lock"),
        # Conditions share their underlying lock: nesting them with it
        # would self-deadlock, so they must canonicalize together.
        ("BatchProcessor", "self._cv", "BatchProcessor._lock"),
        ("AdmissionController", "self._idle", "AdmissionController._lock"),
    ),
    # RLocks: BlockPool eviction runs inside alloc; StateSlabPool
    # mirrors the discipline (stats helpers may nest).
    reentrant=frozenset({"BlockPool.lock", "StateSlabPool.lock"}),
    guarded=(
        # Block pool bookkeeping + the pool-ordering dispatch surface
        # (the quantized pool's host scale slots pair 1:1 with the host
        # payload slots and move under the same lock).
        GuardedEntry(
            attrs=("_free", "_ref", "_host_free", "_host_k", "_host_v",
                   "_host_ks", "_host_vs",
                   "radix", "_promoting", "prefix_hit_tokens",
                   "prefilled_tokens"),
            lock="BlockPool.lock",
            classes=("BlockPool",),
            receivers=("pool", "self._pool")),
        # Donated dispatch surfaces: the payload pool and (quantized
        # mode) its per-slot scale arrays — every write replaces them
        # under the pool lock so gathers order against donations.
        GuardedEntry(
            attrs=("caches", "scales"),
            lock="BlockPool.lock",
            classes=("BlockPool",),
            receivers=("pool", "self._pool")),
        # State slab pool bookkeeping + its donated dispatch surface
        # (state_slab family: the slab tensor is replaced under the
        # pool lock exactly like BlockPool.caches, so admission writes
        # / chain exports order against decode-tick donations).
        GuardedEntry(
            attrs=("_free", "_ref", "slab", "rows_admitted",
                   "rows_released", "exports", "imports"),
            lock="StateSlabPool.lock",
            classes=("StateSlabPool",),
            receivers=("spool", "self._spool")),
        # Gateway membership / routing state (+ the overload-control
        # in-flight gauge the tier fractions admit against, + the
        # disaggregated-serving role map, + the elastic-fleet controller
        # maps: named degraded states and the published pressure gauge).
        GuardedEntry(
            attrs=("_clients", "_breakers", "_ejected", "_model_rings",
                   "_untyped", "_latency", "_lane_recent",
                   "_affinity_assigned", "_hedge_pool", "default_model",
                   "_total_requests", "_failovers", "_inflight",
                   "_streams", "_roles", "_topology",
                   "_topology_updates", "_fleet_degraded",
                   "_fleet_pressure", "_retired_clients",
                   "_prefix_dir"),
            lock="Gateway._lock",
            classes=("Gateway",)),
        # Consistent-hash ring internals (vnode map + per-node topology
        # weights): the ring self-locks; every public method takes
        # _lock, and _drop_labels documents "caller holds it".
        GuardedEntry(
            attrs=("_ring", "_sorted_hashes", "_weights"),
            lock="ConsistentHash._lock",
            classes=("ConsistentHash",)),
        # Live-stream-migration handoff slot: the orchestrator/relay
        # exchange resolves exactly once under the record's own lock.
        GuardedEntry(
            attrs=("_it", "_dest", "_error", "_abandoned"),
            lock="_StreamRecord._hlock",
            classes=("_StreamRecord",)),
        # Overload control (serving/overload.py): per-tenant token
        # buckets, the AIMD limit state, the brownout ladder state, and
        # the gateway shed-rate window — each class owns one lock.
        GuardedEntry(
            attrs=("_buckets",),
            lock="TenantRateLimiter._lock",
            classes=("TenantRateLimiter",)),
        GuardedEntry(
            attrs=("_limit", "_last_decrease", "_increases", "_decreases"),
            lock="AIMDLimit._lock",
            classes=("AIMDLimit",)),
        GuardedEntry(
            attrs=("_stage", "_over", "_under", "_escalations",
                   "_restores", "_pressure", "_binding"),
            lock="BrownoutController._lock",
            classes=("BrownoutController",)),
        GuardedEntry(
            attrs=("_sheds", "_requests"),
            lock="SheddingStats._lock",
            classes=("SheddingStats",)),
        # Breaker state machine.
        GuardedEntry(
            attrs=("_state", "_failure_count", "_success_count",
                   "_last_failure_time"),
            lock="CircuitBreaker._lock",
            classes=("CircuitBreaker",)),
        # Worker request counters.
        GuardedEntry(
            attrs=("_total_requests", "_cache_hits"),
            lock="WorkerNode._counter_lock",
            classes=("WorkerNode",)),
        # Scheduler executable caches: double-checked reads are the
        # documented idiom, so only WRITES must hold the compile lock.
        GuardedEntry(
            attrs=("_prefill_exe", "_insert_exe", "_decode_exe",
                   "_window_exe"),
            lock="ContinuousGenerator._exe_lock",
            classes=("ContinuousGenerator",),
            mode="w"),
        # Flight recorder (observability plane): the per-tick ring moves
        # under the recorder's own lock (decode-thread appends vs
        # /admin/timeline readers).
        GuardedEntry(
            attrs=("_flight_ring",),
            lock="ContinuousGenerator._flight_lock",
            classes=("ContinuousGenerator",)),
        # Flight-recorder configuration + dump bookkeeping: mutation is
        # locked (HTTP forced dumps race the decode thread's anomaly
        # dumps); GIL-safe /stats reads tolerate staleness.
        GuardedEntry(
            attrs=("_flight_capacity", "_flight_dump_dir",
                   "_flight_dumps", "_flight_last_dump",
                   "_flight_last_dump_ts"),
            lock="ContinuousGenerator._flight_lock",
            classes=("ContinuousGenerator",),
            mode="w"),
        # Stream ledger (observability plane): hop entries move under
        # the ledger's own lock — ledger writes happen inside relay
        # loops that must never contend with routing's Gateway._lock.
        GuardedEntry(
            attrs=("_entries",),
            lock="_StreamLedger._llock",
            classes=("_StreamLedger",)),
        # SLO tracker (observability plane): the per-objective burn
        # window deques move under the tracker's own lock.
        GuardedEntry(
            attrs=("_samples",),
            lock="SloTracker._lock",
            classes=("SloTracker",)),
    ),
    thread_owned=(
        # Scheduler row tables: the decode loop owns them; the prefill
        # thread and stats() readers must not touch them (documented
        # GIL-safe reads carry explicit lockfree-ok waivers).
        ThreadOwnedEntry(
            attrs=("_tables", "_row_blocks", "_row_req", "_row_emitted",
                   "_pending", "_export_waiting", "_hold_cancel_tags",
                   "_slab_rows", "_flight_prev", "_flight_miss_window"),
            owner_class="ContinuousGenerator",
            module="tpu_engine.runtime.scheduler",
            entries=("ContinuousGenerator._loop",),
            thread="continuous-decode"),
        # Elastic-fleet control loop: the actuation cooldown stamp and
        # the rebalance hysteresis arm belong to the controller thread
        # alone — the manual /admin/fleet actuators (scale_up /
        # scale_down / rebalance) are deliberately stateless so they
        # never touch these from HTTP handler threads.
        ThreadOwnedEntry(
            attrs=("_last_action_ts", "_rebalance_armed"),
            owner_class="FleetAutoscaler",
            module="tpu_engine.serving.autoscaler",
            entries=("FleetAutoscaler._run",),
            thread="fleet-autoscaler"),
    ),
    # BlockPool/RadixTree methods document "caller holds the pool lock":
    # the analyzer checks their CALL sites instead of their bodies.
    caller_locked=frozenset({"BlockPool.*", "RadixTree.*",
                             "StateSlabPool.*",
                             "PrefixDirectory.*",
                             "TenantRateLimiter._evict_idle",
                             "SheddingStats._gc",
                             "ConsistentHash._drop_labels",
                             "ConsistentHash._resize_locked"}),
    receiver_aliases=_RECEIVER_ALIASES,
    counter_receivers=frozenset({"resilience", "failover", "affinity",
                                 "overload", "migration", "handoff",
                                 "fleet", "slo", "prefix_dir"}),
    span_tracer_attrs=frozenset({"tracer", "recorder"}),
    span_sink_attrs=frozenset({"sink"}),
    hot_static_params=frozenset({"cfg", "config", "dtype", "attn_fn",
                                 "head", "interpret", "mesh", "spec"}),
    tick_entries=(
        "tpu_engine.runtime.scheduler:ContinuousGenerator._loop_body",
        "tpu_engine.runtime.scheduler:ContinuousGenerator._prefill_loop",
        "tpu_engine.runtime.scheduler:ContinuousGenerator._tick_mixed",
        "tpu_engine.runtime.scheduler:ContinuousGenerator._tick_spec",
        "tpu_engine.runtime.scheduler:ContinuousGenerator."
        "_tick_slab_mixed",
        # Unified stateless serving (PR 20): one-shot rows dispatch from
        # the same decode loop — the per-tick jit rule covers both the
        # group collector and the per-kind dispatcher. No new row
        # tables: stateless admission reuses _row_req/_row_emitted/
        # _done/_held, already decode-thread-owned above.
        "tpu_engine.runtime.scheduler:ContinuousGenerator."
        "_tick_stateless",
        "tpu_engine.runtime.scheduler:ContinuousGenerator."
        "_dispatch_oneshot",
    ),
    cli_module="tpu_engine.serving.cli",
    config_module="tpu_engine.utils.config",
    config_classes=("WorkerConfig", "GatewayConfig"),
)
