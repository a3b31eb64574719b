"""Distributed tracing — span trees, trace propagation, and the profiler.

The reference's only observability is a per-batch stopwatch divided by
batch size (``/root/reference/src/worker_node.cpp:108-123``) surfaced as
``inference_time_us``; no spans, no trace ids, no profiler (SURVEY.md §5).
The first cut here kept exactly that shape: one flat ``infer`` span per
request. This module now carries a real tracing subsystem:

- `TraceContext` — a W3C-traceparent-style (trace_id, span_id) pair.
  Wire form is one optional ``"traceparent"`` request field
  (``00-<32 hex>-<16 hex>-01``), carried next to ``deadline_ms`` and
  re-forwarded (re-parented) at each hop: edge → gateway → worker client
  → worker → batcher/continuous scheduler. Requests WITHOUT the field get
  a trace root **derived deterministically from request_id** at every hop
  (same id → same trace_id, no wire change), so anonymous requests stay
  correlatable while their wire bytes stay byte-identical to the
  pre-tracing protocol.
- `SpanRecorder` — a lock-guarded ring buffer of spans, now hierarchical:
  each span may carry (trace_id, span_id, parent_id, start_ts) plus free
  attrs. Request-level spans (the old flat ``infer``/``generate`` rows)
  and stage spans (``queue_wait``, ``batch_form``, ``device_compute``,
  ``cache_lookup``, ``serialize``, ``admission``, ...) share the ring;
  ``summary()`` keeps its original schema over request spans only, and
  every span also feeds a per-stage `LatencyHistogram` for Prometheus
  exposition (``utils.metrics``). Bounded memory: O(capacity) spans +
  a fixed histogram per stage; ``capacity=0`` disables recording.
- `export_chrome` — Chrome trace-event / Perfetto-loadable JSON of the
  ring contents (``GET /trace/export``), parent/child linkage in args.
- `TraceSink` — (recorder, node, request_id, parent ctx) bundled so
  runtime components (continuous scheduler) can record stage spans
  without importing the serving layer.
- `profiler_start` / `profiler_stop` — ``jax.profiler`` session wrappers
  (XLA device traces viewable in TensorBoard / Perfetto), driven by
  ``POST /admin/profile`` on the combined server.
- `TickClock` — the scheduler's one tick clock: four contiguous phases
  (form, dispatch, wait, apply) and the loop between two ticks as attrs
  of the tick span AND as ``jax.profiler.TraceAnnotation``s on the
  device trace's clock, the scheduler thread's time off the CPU in the
  phases that never block by design, the host gap between ticks, and
  the slow-tick stderr line.
- `CompileCounter` / `compile_counter()` — XLA compilations and their
  seconds, process-wide, from ``jax.monitoring``.
- `GcCounter` / `gc_counter()` — the interpreter's garbage collections
  and their pause seconds, process-wide, from ``gc.callbacks``.
- `StreamClock` — a streamed request's token events on their way out,
  by the front's stream writer or the handler's thread: wake-up and
  delivery, summed onto its request span.
- `STEP_PARTS` / `step_part` — the DEVICE's equivalent of the tick's
  phases: the parts of a step (`attn/read`, `mixer/chunk`,
  `moe/experts`, `head`, `sample` ...), each a named scope of JAX that
  every family's step opens, so every op of a tick carries its part in
  the device trace's metadata (``tf_op``); `tick_name` names a tick's
  compiled program for its width (``jit_tick_w256`` on the trace's
  "XLA Modules" line). ``benchmarks/lib/xplane_scopes.py`` sums a
  trace by part.
"""

from __future__ import annotations

import gc
import hashlib
import math
import re
import statistics
import sys
import threading
import time
import uuid
from collections import deque
from typing import Dict, List, Optional, Tuple

from tpu_engine.utils.metrics import LatencyHistogram

# Request-level ops: one span per request, the rows the original flat
# recorder kept. summary() aggregates these ONLY, so its numbers keep
# meaning "per-request latency" now that stage spans share the ring.
_REQUEST_OPS = frozenset({"infer", "generate", "generate_stream", "score",
                          "route"})

_TRACEPARENT_RE = re.compile(
    r"^00-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$")

# A scheduler tick's four contiguous phases (`TickClock`): `<phase>_us`
# attrs on the tick span, `tick.<phase>` profiler annotations.
TICK_PHASES = ("form", "dispatch", "wait", "apply")
# The phases in which the scheduler thread makes no call that blocks by
# design (the loop between two ticks is the third): wall time less the
# thread's CPU time there is time without the interpreter lock or without
# a core, `<phase>_offcpu_us`. `dispatch` and `wait` block by design.
OFFCPU_PHASES = ("form", "apply")
# ... read on one loop iteration in this many: the thread's CPU clock is a
# system call, 0.4 us on a plain kernel and 45 us on the v5e hosts', where
# five reads a tick cost 4 % of a 5.5 ms decode period (PERF.md, PR 42).
CPU_CLOCK_EVERY = 8
# The loop's statements between two ticks, in order (`TickClock.loop_part`):
# `loop_<part>_us` attrs, `loop.admit.<part>` annotations under `loop.admit`.
LOOP_PARTS = ("exports", "capacity", "admit", "expire")
# The parts of a step on the DEVICE (`step_part`): what every family's
# `*_step_rows_ragged` and the scheduler's `step_core` open as
# named scopes, so an op's path in a profiler trace
# (`jit(tick_w256)/while/body/closed_call/attn/read/dot_general`) says
# which part of the model it belongs to. A norm and a residual add go with
# the part they feed. `plan` is what a step works out once, outside the
# layer loop, from the tables and the rows' lengths; `attn/read` is the
# pool's read with everything the call needs around it (relayouts, the
# gather of tall tiles), `mixer/chunk` and `mixer/step` the two forms of a
# recurrent layer whole; `moe/experts` the gather of pairs, the grouped
# products WHATEVER implements them, and the combine. A new mechanism adds
# a part here and a reader under benchmarks/layer_metrics/
# (benchmarks/lib/xplane_scopes.py spells the same tuple).
STEP_PARTS = (
    "embed", "plan",
    "attn/qkv", "attn/write", "attn/read", "attn/out",
    "mixer/in", "mixer/chunk", "mixer/step", "mixer/out",
    "mlp",
    "moe/route", "moe/experts", "moe/shared",
    "head",
    "sample", "sample/reveal",
)


def step_part(name: str):
    """The scope of one of `STEP_PARTS`: metadata on the ops traced inside
    it and nothing else (the executable, its fusions and the persistent
    compile cache's key are the same with and without)."""
    import jax

    if name not in STEP_PARTS:
        raise ValueError(f"{name!r} is not one of STEP_PARTS")
    return jax.named_scope(name)


def tick_name(width: int, run_width: int = 1, kind: str = "tick") -> str:
    """The name of a tick's compiled program: `tick_w1`, `tick_w256`,
    `spec_w5`; `tick_w1_r4` on a lane whose rows decode by blocks of 4. The
    trace's "XLA Modules" line reads `jit_<name>(<program id>)`, so a
    program's runs can be told by width."""
    name = f"{kind}_w{width}"
    return name if run_width == 1 else f"{name}_r{run_width}"


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def derive_trace_id(request_id: str) -> str:
    """Deterministic trace id for requests that carry no traceparent:
    every hop derives the SAME id from the request_id, so gateway and
    worker spans correlate without adding a byte to the wire."""
    return hashlib.md5(b"tpu-trace:"
                       + str(request_id).encode()).hexdigest()


class TraceContext:
    """One (trace_id, span_id) position in a trace tree. ``span_id`` is
    the CURRENT span — ``from_request`` yields the caller's span (this
    hop's parent); ``child()`` mints this hop's own."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    @classmethod
    def from_request(cls, payload) -> Optional["TraceContext"]:
        """Parse the request's ``traceparent`` field. W3C semantics for a
        malformed value: ignore it (trace as if absent), never fail the
        request over telemetry."""
        tp = payload.get("traceparent") if isinstance(payload, dict) else None
        if not isinstance(tp, str):
            return None
        m = _TRACEPARENT_RE.match(tp.strip().lower())
        if m is None:
            return None
        return cls(m.group(1), m.group(2))

    @classmethod
    def root(cls, request_id=None) -> "TraceContext":
        tid = (derive_trace_id(request_id) if request_id is not None
               else uuid.uuid4().hex)
        return cls(tid, new_span_id())

    def child(self) -> "TraceContext":
        return TraceContext(self.trace_id, new_span_id())

    def to_traceparent(self) -> str:
        return f"00-{self.trace_id}-{self.span_id}-01"

    def __repr__(self) -> str:
        return f"TraceContext({self.to_traceparent()})"


class SpanRecorder:
    """Lock-guarded ring buffer of spans + per-stage latency histograms.

    ``record`` keeps its original positional signature (request_id, op,
    node, duration_us) — additive keyword fields carry the tree structure.
    ``capacity=0`` disables span recording entirely (histograms included).
    """

    def __init__(self, capacity: int = 2048):
        self.capacity = int(capacity)
        self._spans = deque(maxlen=max(0, self.capacity))
        self._lock = threading.Lock()
        self._hists: Dict[str, LatencyHistogram] = {}

    def record(self, request_id: str, op: str, node: str, duration_us,
               *, cached: bool = False, batch_size: int = 1,
               trace_id: Optional[str] = None,
               span_id: Optional[str] = None,
               parent_id: Optional[str] = None,
               start_ts: Optional[float] = None,
               attrs: Optional[dict] = None) -> None:
        if self.capacity <= 0:
            return
        span = {
            "request_id": request_id,
            "op": op,
            "node": node,
            "duration_us": int(duration_us),
            "cached": cached,
            "batch_size": batch_size,
            "ts": time.time(),
        }
        if trace_id is not None:
            span["trace_id"] = trace_id
        if span_id is not None:
            span["span_id"] = span_id
        if parent_id is not None:
            span["parent_id"] = parent_id
        if start_ts is not None:
            span["start_ts"] = start_ts
        if attrs:
            span["attrs"] = attrs
        hist = self._hists.get(op)
        with self._lock:
            self._spans.append(span)
            if hist is None:
                hist = self._hists.setdefault(op, LatencyHistogram())
        hist.observe(float(duration_us) / 1e6)

    def recent(self, n: int = 100) -> List[dict]:
        with self._lock:
            items = list(self._spans)
        return items[-n:]

    def snapshot(self) -> List[dict]:
        """Every span currently in the ring (export path)."""
        with self._lock:
            return list(self._spans)

    def summary(self) -> dict:
        """The original ``/trace`` summary schema, aggregated over
        request-level spans only (stage spans would double-count)."""
        items = [s for s in self.snapshot() if s["op"] in _REQUEST_OPS]
        if not items:
            return {"spans": 0}
        durs = sorted(s["duration_us"] for s in items)
        return {
            "spans": len(items),
            "cached": sum(1 for s in items if s["cached"]),
            "duration_us": {"p50": percentile(durs, 50),
                            "p90": percentile(durs, 90),
                            "p99": percentile(durs, 99),
                            "max": durs[-1]},
        }

    def stage_summary(self) -> dict:
        """Per-op latency summary over EVERY span in the ring — the
        queue-wait vs device-compute breakdown ``bench.py`` scrapes.
        Additive endpoint data; the original summary() is untouched."""
        by_op: Dict[str, List[int]] = {}
        for s in self.snapshot():
            by_op.setdefault(s["op"], []).append(s["duration_us"])
        out = {}
        for op, durs in sorted(by_op.items()):
            durs.sort()
            out[op] = {
                "count": len(durs),
                "mean_us": round(sum(durs) / len(durs), 1),
                "p50_us": percentile(durs, 50),
                "p90_us": percentile(durs, 90),
                "p99_us": percentile(durs, 99),
                "max_us": durs[-1],
            }
        return out

    def histograms(self) -> Dict[str, LatencyHistogram]:
        """Live per-stage histogram objects (rendered by utils.metrics)."""
        with self._lock:
            return dict(self._hists)


class TraceSink:
    """Recorder + identity bundle handed into runtime components (the
    continuous scheduler) so they can record stage spans for a request
    without importing the serving layer. ``None``-safe at every call
    site: runtime code threads an Optional[TraceSink]."""

    __slots__ = ("recorder", "node", "request_id", "ctx")

    def __init__(self, recorder: SpanRecorder, node: str, request_id: str,
                 ctx: TraceContext):
        self.recorder = recorder
        self.node = node
        self.request_id = request_id
        self.ctx = ctx

    def stage(self, op: str, duration_us: float,
              start_ts: Optional[float] = None, **attrs) -> None:
        child = self.ctx.child()
        self.recorder.record(
            self.request_id, op, self.node, duration_us,
            trace_id=child.trace_id, span_id=child.span_id,
            parent_id=self.ctx.span_id, start_ts=start_ts,
            attrs=attrs or None)

    def between(self, op: str, t0: float, t1: Optional[float] = None,
                **attrs) -> None:
        """A stage span from two ``time.perf_counter()`` marks (`t1`
        omitted: now). The wall-clock start is derived from the marks,
        so consecutive stages that share a mark leave no hole."""
        now = time.perf_counter()
        end = now if t1 is None else t1
        self.stage(op, (end - t0) * 1e6,
                   start_ts=time.time() - (now - t0), **attrs)


def percentile(vals: List, p: float):
    """Nearest-rank (ceil) percentile: the smallest value with at least
    p% of samples ≤ it (monotonic, standard). The helper SORTS a copy
    itself — it used to require pre-sorted input and silently returned
    garbage on anything else (a known bench footgun: an unsorted latency
    list produced plausible-looking nonsense percentiles). Sorting an
    already-sorted list is O(n) in Timsort, so the hardening costs
    existing callers nothing. The previous ``int(p/100*len)`` truncation
    indexed one past the nearest rank (over-reporting mid percentiles)
    and could swing either way on small samples."""
    if not vals:
        return None
    svals = sorted(vals)
    rank = math.ceil(p / 100.0 * len(svals))  # 1-based
    return svals[min(len(svals) - 1, max(0, rank - 1))]


def _span_start_ts(s: dict) -> float:
    start = s.get("start_ts")
    if start is None:  # legacy rows stamp completion time only
        start = s["ts"] - s["duration_us"] / 1e6
    return start


def _span_event(s: dict, tid: int) -> dict:
    args = {"request_id": s["request_id"]}
    for k in ("trace_id", "span_id", "parent_id", "cached",
              "batch_size"):
        if k in s:
            args[k] = s[k]
    args.update(s.get("attrs") or {})
    return {
        "name": s["op"], "cat": "serving", "ph": "X",
        "ts": _span_start_ts(s) * 1e6,
        "dur": max(0, int(s["duration_us"])),
        "pid": 1, "tid": tid, "args": args,
    }


def _tick_phase_events(s: dict, tid: int) -> List[dict]:
    """The four phases of a tick span as child events: the span carries
    one start and four contiguous durations (`TickClock`), so the ring
    holds one entry a tick and the export still shows where it went."""
    attrs = s.get("attrs") or {}
    if any(f"{p}_us" not in attrs for p in TICK_PHASES):
        return []
    out, ts = [], _span_start_ts(s) * 1e6
    for p in TICK_PHASES:
        out.append({"name": f"tick.{p}", "cat": "serving", "ph": "X",
                    "ts": ts, "dur": max(0.0, attrs[f"{p}_us"]),
                    "pid": 1, "tid": tid,
                    "args": {"request_id": s["request_id"],
                             "parent_op": s["op"],
                             "seq": attrs.get("seq")}})
        ts += attrs[f"{p}_us"]
    return out


def _synthesize_evicted_roots(events: List[dict]) -> List[dict]:
    """Ring-capacity eviction can drop a parent span while its children
    survive, leaving exported events whose ``parent_id`` matches nothing —
    Perfetto then renders the children as unrelated top-level rows. For
    every dangling parent id, emit ONE synthetic zero-duration root event
    named ``evicted_parent`` (claiming that span_id, anchored at its
    earliest child's start) so the tree stays connected and the gap is
    visibly labeled instead of silently flat."""
    seen = set()
    for ev in events:
        sid = ev.get("args", {}).get("span_id")
        if sid is not None:
            seen.add(sid)
    dangling: Dict[str, dict] = {}
    for ev in events:
        args = ev.get("args", {})
        pid = args.get("parent_id")
        if pid is None or pid in seen:
            continue
        prev = dangling.get(pid)
        if prev is None or ev["ts"] < prev["ts"]:
            dangling[pid] = {
                "name": "evicted_parent", "cat": "serving", "ph": "X",
                "ts": ev["ts"], "dur": 0, "pid": 1, "tid": ev["tid"],
                "args": {
                    "request_id": args.get("request_id"),
                    "span_id": pid,
                    "evicted_parent": True,
                    **({"trace_id": args["trace_id"]}
                       if "trace_id" in args else {}),
                },
            }
    return [dangling[k] for k in sorted(dangling)]


def spans_to_chrome(named_spans: Dict[str, List[dict]]) -> dict:
    """Chrome trace-event JSON from named span lists (recorder-snapshot
    schema) — one tid per name, metadata thread_name events, synthetic
    ``evicted_parent`` roots for dangling parent links."""
    events: List[dict] = []
    for tid, name in enumerate(sorted(named_spans), start=1):
        events.append({"ph": "M", "name": "thread_name", "pid": 1,
                       "tid": tid, "args": {"name": name}})
        for s in named_spans[name]:
            events.append(_span_event(s, tid))
            events.extend(_tick_phase_events(s, tid))
    events.extend(_synthesize_evicted_roots(events))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_chrome(recorders: Dict[str, SpanRecorder]) -> dict:
    """Chrome trace-event JSON of every recorder's ring — loadable in
    Perfetto / chrome://tracing. One tid per node (named via metadata
    events); complete ("X") events carry trace_id/span_id/parent_id in
    ``args`` so tooling can rebuild the exact span tree."""
    return spans_to_chrome(
        {node: rec.snapshot() for node, rec in recorders.items()})


def stitch_trace(fragments: Dict[str, List[dict]], request_id: str,
                 trace_id: Optional[str] = None) -> dict:
    """Merge per-lane span fragments into ONE trace for a mobile stream.

    ``fragments`` maps lane/node name -> span dicts (recorder-snapshot
    schema). A span belongs to the stream when its request_id matches, or
    (when ``trace_id`` is given) when its trace_id matches — hop marker
    spans and per-attempt children all carry the request_id, so both
    filters converge on the same tree. Returns the merged span list
    (start-time ordered), the lanes that contributed, the orphan count
    BEFORE synthetic-root repair, and a Perfetto-loadable ``chrome``
    rendering (with ``evicted_parent`` roots synthesized so the tree is
    always connected)."""
    tid = trace_id or derive_trace_id(request_id)
    picked: Dict[str, List[dict]] = {}
    for lane, spans in fragments.items():
        mine = [s for s in spans
                if s.get("request_id") == request_id
                or s.get("trace_id") == tid]
        if mine:
            picked[lane] = mine
    all_spans = [dict(s, lane=lane)
                 for lane, spans in sorted(picked.items())
                 for s in spans]
    all_spans.sort(key=_span_start_ts)
    have = {s["span_id"] for s in all_spans if "span_id" in s}
    orphans = sum(1 for s in all_spans
                  if s.get("parent_id") is not None
                  and s["parent_id"] not in have)
    return {
        "request_id": request_id,
        "trace_id": tid,
        "lanes": sorted(picked),
        "spans": all_spans,
        "orphans": orphans,
        "chrome": spans_to_chrome(picked),
    }


_profile_lock = threading.Lock()
_profile_dir: Optional[str] = None


def profiler_start(log_dir: str) -> dict:
    """Begin a jax.profiler trace (device + host) into `log_dir`."""
    global _profile_dir
    import jax

    with _profile_lock:
        if _profile_dir is not None:
            return {"error": f"profiler already running -> {_profile_dir}"}
        jax.profiler.start_trace(log_dir)
        _profile_dir = log_dir
    return {"ok": True, "log_dir": log_dir}


def profiler_stop() -> dict:
    global _profile_dir
    import jax

    with _profile_lock:
        if _profile_dir is None:
            return {"error": "profiler not running"}
        jax.profiler.stop_trace()
        out, _profile_dir = _profile_dir, None
    return {"ok": True, "log_dir": out}


# -- the scheduler's tick clock and the compile counter -----------------------

SLOW_TICK_FACTOR = 5.0       # a tick this many medians long says where
SLOW_TICK_HISTORY = 20       # ... the median of the last ticks of its width
SLOW_TICK_MIN_HISTORY = 5
SLOW_TICK_LINE_EVERY_S = 10.0
# ... with, of its span's attrs: the phases, then on the CPU or off it,
# in a collection or a compilation or neither.
SLOW_TICK_SAYS = (*(f"{p}_us" for p in TICK_PHASES), "gap_us", "loop_us",
                  *(f"{p}_offcpu_us" for p in (*OFFCPU_PHASES, "loop")),
                  "compile_us", "gc_us")


class CompileCounter:
    """XLA compilations and their seconds, process-wide: a
    ``jax.monitoring`` duration listener on the backend-compile event,
    which jax 0.9.0 records around every executable it builds (a hit in
    the persistent compilation cache included: the program still had no
    executable in this process). A compile on ANY thread counts, so with
    several lanes in one process a tick may carry a neighbour's."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration_secs: float, **_kwargs) -> None:
        if event == self.EVENT:
            with self._lock:
                self.count += 1
                self.seconds += duration_secs

    def snapshot(self) -> dict:
        with self._lock:
            return {"count": self.count, "seconds": round(self.seconds, 6)}


_compile_counter: Optional[CompileCounter] = None
_compile_counter_lock = threading.Lock()


def compile_counter() -> CompileCounter:
    """The process's one `CompileCounter`; the first call registers it
    (``jax.monitoring`` listeners are process-global, so is the count)."""
    global _compile_counter
    with _compile_counter_lock:
        if _compile_counter is None:
            from jax import monitoring

            counter = CompileCounter()
            monitoring.register_event_duration_secs_listener(counter)
            _compile_counter = counter
    return _compile_counter


class GcCounter:
    """The interpreter's garbage collections and the seconds they paused
    it, process-wide: a ``gc.callbacks`` entry, which runs only when a
    collection does, on the thread that set it off and with the
    interpreter lock held: every Python thread waits the pause out,
    whoever started it, so a tick is charged a neighbour's too. `gen2`
    counts the full collections (the long ones)."""

    def __init__(self, wall=time.perf_counter):
        self._wall = wall
        self._t0: Optional[float] = None
        self.count = 0
        self.gen2 = 0
        self.seconds = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = self._wall()
        elif self._t0 is not None:
            self.seconds += self._wall() - self._t0
            self._t0 = None
            self.count += 1
            self.gen2 += info.get("generation") == 2

    def snapshot(self) -> dict:
        return {"count": self.count, "seconds": round(self.seconds, 6),
                "gen2": self.gen2}


_gc_counter: Optional[GcCounter] = None
_gc_counter_lock = threading.Lock()


def gc_counter() -> GcCounter:
    """The process's one `GcCounter`; the first call registers it."""
    global _gc_counter
    with _gc_counter_lock:
        if _gc_counter is None:
            _gc_counter = GcCounter()
            gc.callbacks.append(_gc_counter)
    return _gc_counter


class _TickMarks:
    """What the clock keeps of one tick from `begin` to `end`: under the
    pipeline its form and dispatch are marked one loop iteration before
    its wait and apply."""

    __slots__ = ("seq", "us", "offcpu_us", "width", "ctx_tokens", "notes",
                 "compile_s", "gc_s", "gap_us", "overlapped", "enqueued",
                 "t_begin", "loop")

    def __init__(self, seq: int):
        self.seq = seq
        self.us = dict.fromkeys(TICK_PHASES, 0.0)
        self.offcpu_us: Dict[str, float] = {}   # the phases it was read in
        self.width = self.ctx_tokens = 0
        self.notes: Dict[str, object] = {}
        self.compile_s = self.gc_s = 0.0
        self.gap_us: Optional[float] = None
        self.overlapped = 0
        self.enqueued = False
        self.t_begin = 0.0
        # `loop_us`, its parts and `period_us`, where it follows a tick.
        self.loop: Dict[str, float] = {}


class TickClock:
    """One lane's tick clock, marked by the decode thread only.

    A tick has four phases: ``begin()`` opens `form`, ``dispatch()``,
    ``wait()`` and ``apply()`` each close the phase before and open
    their own, ``end()`` closes the tick and returns its start, duration
    and the span attrs. A lane that reads a tick's results before it
    forms the next marks them back to back. A lane that runs one tick
    ahead marks, in one loop iteration, `form` and `dispatch` of tick
    N+1 and then `wait` and `apply` of tick N: the four phases still
    tile the host's time between two `loop.admit`s, and each tick's span
    carries its OWN four (``end()`` returns the oldest tick not yet
    ended). ``leave()`` ends an iteration that enqueued a tick and had
    none to wait for; ``wait()`` without ``begin()`` is an iteration
    that only lands the tick in flight. The same marks open and close
    ``jax.profiler.TraceAnnotation``s (`tick` with children `tick.form`
    ... `tick.apply`, and `loop.admit` for the loop's work between and
    before ticks, with children `loop.admit.exports` ...
    `loop.admit.expire` where the loop says ``loop_part()``), which
    cost nothing measurable without a profiler session and otherwise
    land on the host plane of the device trace.

    The loop is the fifth measured phase. `loop_us`: the `loop.admit`
    time since the ``begin()`` before, up to this tick's ``begin()``,
    on the span of the tick that `begin()` opens; `loop_<part>_us`: what
    of it lay behind each ``loop_part()`` mark (the rest is the tail of
    the tick before: its span's record, the returns); `period_us`: from
    the ``begin()`` of the tick enqueued before to this tick's. All
    only where the tick follows another (as `gap_us`), so one iteration
    of a lane that runs ahead reads: `period_us` of tick N+2 =
    `form_us + dispatch_us` of N+1, `wait_us + apply_us` of N and
    `loop_us` of N+2.

    `form_offcpu_us`, `apply_offcpu_us`, `loop_offcpu_us`: the phase's
    wall time less the decode thread's own CPU time in it
    (``time.thread_time_ns``), never below 0. In these phases the thread
    makes no call that blocks by design, so what is left is time it
    waited for the interpreter lock, or for a core. The CPU clock is
    read on one loop iteration in `cpu_every` (`CPU_CLOCK_EVERY`: a
    read is a system call), from one ``end()`` to the next, so a tick
    carries the attrs of the phases that fell into such an iteration
    (its loop and form in one, its apply in the next) or none. Where
    that clock ticks coarsely (10 ms on the v5e hosts: a phase of 4 ms
    reads 0 or 10 ms of CPU), the CPU time beyond a stretch's wall time
    is owed to the same phase's next stretches: one tick's attr says
    little there, their MEAN over a window is right. `gc_us`: the
    collector's pauses (`GcCounter`) inside the tick's phases and its
    loop, whichever thread set them off.

    `gap_us`: the time the device had nothing queued because of the
    host, up to this tick's `dispatch`. After a tick whose results were
    read with nothing queued behind it: from that `wait`'s end. Behind a
    tick still in flight: 0, unless a ``probe()`` saw that tick finished
    already, then from the first such probe. Only between back-to-back
    ticks: ``end(live=False)`` and ``idle()`` break the chain.
    `overlapped`: 1 if the tick was enqueued while its predecessor's
    results were not yet read."""

    def __init__(self, compiles: CompileCounter,
                 gcs: Optional[GcCounter] = None,
                 wall=time.perf_counter, cpu_ns=time.thread_time_ns,
                 cpu_every: int = CPU_CLOCK_EVERY):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._compiles = compiles
        self._gcs = gcs if gcs is not None else GcCounter()
        self._wall, self._cpu_ns = wall, cpu_ns
        self._cpu_every = max(1, int(cpu_every))
        self._iterations = 0
        self._cpu_on = True        # this iteration reads the CPU clock
        self._open: List[object] = []
        self._phase: Optional[str] = None
        self._marks: Optional[_TickMarks] = None   # the open phase's tick
        self._t0 = 0.0
        self._cpu0: Optional[int] = None
        self._compile_s0 = self._gc_s0 = 0.0
        self._cpu_owed = dict.fromkeys((*OFFCPU_PHASES, "loop"), 0.0)
        self._formed: Optional[_TickMarks] = None
        self._flight: deque = deque()              # enqueued, not ended
        self._chained = False
        self._idle_since: Optional[float] = None
        self._begin_prev: Optional[float] = None   # of the tick enqueued last
        self._in_loop = False      # a `loop.admit` after a tick is open
        self._part: Optional[str] = None
        self._part_t0 = 0.0
        self._reset_loop()
        self._recent: Dict[int, deque] = {}
        self._last_slow_line = 0.0
        self.seq = 0

    def _push(self, name: str) -> None:
        annotation = self._annotation(name)
        annotation.__enter__()
        self._open.append(annotation)

    def _close(self) -> None:
        while self._open:
            self._open.pop().__exit__(None, None, None)

    def _reset_loop(self) -> None:
        self._loop_us = self._loop_gc_s = 0.0
        # None once a stretch of the loop went by with the CPU clock unread.
        self._loop_offcpu_us: Optional[float] = 0.0
        self._loop_parts = dict.fromkeys(LOOP_PARTS, 0.0)

    def _open_loop(self) -> float:
        """After a tick's marks: the loop's time runs from here, and
        with it the next loop iteration, which reads the CPU clock or
        does not."""
        now = self._enter(None, None)
        self._close()
        self._push("loop.admit")
        self._in_loop = True
        self._iterations += 1
        self._cpu_on = self._iterations % self._cpu_every == 0
        if self._cpu_on and self._cpu0 is None:
            self._cpu0 = self._cpu_ns()
        return now

    def _end_part(self, now: float) -> None:
        if self._part is not None:
            if self._in_loop:
                self._loop_parts[self._part] += (now - self._part_t0) * 1e6
            self._part = None

    def _offcpu_us(self, phase: str, wall_us: float, cpu: int) -> float:
        """Wall less CPU time of a stretch of `phase` that ends now,
        never below 0; CPU time beyond the wall time (a coarse CPU
        clock charges a whole tick of its own to the stretch it falls
        in) is carried to the phase's next stretches."""
        off = wall_us - (cpu - self._cpu0) / 1e3 - self._cpu_owed[phase]
        self._cpu_owed[phase] = max(0.0, -off)
        return max(0.0, off)

    def _enter(self, phase: Optional[str],
               marks: Optional[_TickMarks]) -> float:
        """Close the open phase into its tick's marks (the loop's
        stretch into the loop's sums) and open `phase` of `marks`
        (None: the iteration's ticks are marked)."""
        now = self._wall()
        cpu = self._cpu_ns() if self._cpu_on else None
        us = (now - self._t0) * 1e6
        if self._phase is not None:
            done = self._marks
            done.us[self._phase] += us
            if cpu is not None and self._phase in OFFCPU_PHASES:
                done.offcpu_us[self._phase] = (
                    done.offcpu_us.get(self._phase, 0.0)
                    + self._offcpu_us(self._phase, us, cpu))
            done.compile_s += self._compiles.seconds - self._compile_s0
            done.gc_s += self._gcs.seconds - self._gc_s0
            self._open.pop().__exit__(None, None, None)
        elif phase is not None:
            self._end_part(now)
            if self._in_loop:
                self._loop_us += us
                if cpu is None:
                    self._loop_offcpu_us = None
                elif self._loop_offcpu_us is not None:
                    self._loop_offcpu_us += self._offcpu_us("loop", us, cpu)
                self._loop_gc_s += self._gcs.seconds - self._gc_s0
                self._in_loop = False
            self._close()
            self._push("tick")
        self._phase, self._marks = phase, marks
        self._t0, self._cpu0 = now, cpu
        self._compile_s0, self._gc_s0 = (self._compiles.seconds,
                                         self._gcs.seconds)
        if phase is not None:
            self._push("tick." + phase)
        return now

    def admit(self) -> None:
        """Top of a scheduler loop iteration: the host's work before a
        tick (exports, pool growth, admission). After a tick the
        annotation is already open (``end()`` opened it), so the time
        between two ticks is one `loop.admit` with no hole."""
        if not self._open:
            self._push("loop.admit")

    def loop_part(self, name: str) -> None:
        """The loop's statements from here to the next mark (or to the
        tick's ``begin()``) are its part `name` of `LOOP_PARTS`: a
        `loop.admit.<name>` annotation under `loop.admit`, and
        `loop_<name>_us` on the span of the tick that follows."""
        if self._phase is not None:
            return
        now = self._wall()
        if self._part is not None:
            self._open.pop().__exit__(None, None, None)
            self._end_part(now)
        self._part, self._part_t0 = name, now
        self._push("loop.admit." + name)

    def idle(self) -> None:
        """The loop found nothing to dispatch, or the tick raised between
        its marks: close what is open and forget the ticks not ended;
        the next tick follows an idle lane, not a host gap."""
        self._close()
        self._phase = self._marks = self._formed = self._part = None
        self._flight.clear()
        self._chained = self._in_loop = False
        self._idle_since = self._begin_prev = None
        self._reset_loop()

    def begin(self) -> None:
        self.seq += 1
        marks = self._formed = _TickMarks(self.seq)
        marks.t_begin = now = self._enter("form", marks)
        if self._chained or self._flight:
            marks.loop = {"loop_us": round(self._loop_us, 1)}
            if self._loop_offcpu_us is not None:
                marks.loop["loop_offcpu_us"] = round(self._loop_offcpu_us, 1)
            for part, us in self._loop_parts.items():
                marks.loop[f"loop_{part}_us"] = round(us, 1)
            if self._begin_prev is not None:
                marks.loop["period_us"] = round(
                    (now - self._begin_prev) * 1e6, 1)
            marks.gc_s = self._loop_gc_s
        self._reset_loop()

    def probe(self, ready: bool) -> None:
        """`ready`: the newest tick enqueued has finished on the device
        (its result's ``is_ready()``), so the device has had nothing
        queued since some moment before this one."""
        if ready and self._idle_since is None:
            self._idle_since = self._wall()

    def dispatch(self, width: int, rows: int, ctx_tokens: int) -> None:
        """The batch is formed; the step executable is called next."""
        marks = self._formed
        marks.width, marks.ctx_tokens = int(width), int(ctx_tokens)
        marks.enqueued = True
        now = self._enter("dispatch", marks)
        if self._chained or self._flight:
            marks.gap_us = (0.0 if self._idle_since is None else
                            round((now - self._idle_since) * 1e6, 1))
        self._open[0].set_metadata(seq=marks.seq, width=marks.width,
                                   rows=int(rows),
                                   ctx_tokens=marks.ctx_tokens)

    def _enqueued(self) -> None:
        marks, self._formed = self._formed, None
        if marks is None:
            return
        if not marks.enqueued:      # formed, and nothing was there to step
            self.seq -= 1
            return
        marks.overlapped = int(bool(self._flight))
        self._flight.append(marks)
        self._begin_prev = marks.t_begin
        self._idle_since = None     # the device has this tick queued

    def leave(self) -> None:
        """The step is enqueued and the iteration has no tick to wait
        for: the loop goes on beside the device."""
        self._enqueued()
        self._open_loop()

    def wait(self) -> None:
        """The step is enqueued (if the iteration formed one); the host
        now blocks on the results of the oldest tick in flight."""
        self._enqueued()
        self._enter("wait", self._flight[0])

    def apply(self) -> None:
        """The host has the results."""
        now = self._enter("apply", self._flight[0])
        if len(self._flight) == 1:
            self._idle_since = now  # nothing is queued behind them

    def note(self, **attrs) -> None:
        """What the scheduler knows of the tick beyond its phases: counts
        the step brought back with its results (a routed model's
        `moe_assignments`, `moe_experts_touched`), the `sampler` body its
        rows asked for; on a block-decoding lane `run_width` (the tokens a
        generating row feeds; the span's `width` stays a prompt chunk's
        compiled width, 1 without one), `denoise_rows`, `commit_rows`,
        `attn_pairs` and, when the tick lands, `blocks_finished`. They ride
        the span of the tick whose phase is open, beside `ctx_tokens`."""
        self._marks.notes.update(attrs)

    def end(self, live: bool, node: str) -> Tuple[float, float, dict]:
        """(start_ts, duration_us, attrs) of the tick whose results were
        just applied. `live`: a row was still dispatchable when it ended.
        The duration is the sum of the tick's own four phases and the
        span ends now: beside a tick in flight, the other tick's `form`
        and `dispatch` lie between this one's `dispatch` and `wait`."""
        now = self._open_loop()
        marks = self._flight.popleft()
        attrs = {f"{p}_us": round(marks.us[p], 1) for p in TICK_PHASES}
        dur_us = sum(marks.us.values())
        for p, us in marks.offcpu_us.items():
            attrs[f"{p}_offcpu_us"] = round(us, 1)
        attrs.update(marks.loop)
        if marks.gap_us is not None:
            attrs["gap_us"] = marks.gap_us
        self._chained = live or bool(self._flight)
        if not self._chained:
            self._idle_since = None
        attrs["overlapped"] = marks.overlapped
        attrs["ctx_tokens"] = marks.ctx_tokens
        attrs.update(marks.notes)
        attrs["compile_us"] = int(marks.compile_s * 1e6)
        attrs["gc_us"] = int(marks.gc_s * 1e6)
        attrs["seq"] = marks.seq
        self._say_if_slow(dur_us, attrs, node, now, marks.width, marks.seq)
        return time.time() - dur_us / 1e6, dur_us, attrs

    def _say_if_slow(self, dur_us: float, attrs: dict, node: str,
                     now: float, width: int, seq: int) -> None:
        recent = self._recent.setdefault(
            width, deque(maxlen=SLOW_TICK_HISTORY))
        if (len(recent) >= SLOW_TICK_MIN_HISTORY
                and now - self._last_slow_line >= SLOW_TICK_LINE_EVERY_S):
            median_us = statistics.median(recent)
            if dur_us > SLOW_TICK_FACTOR * median_us:
                self._last_slow_line = now
                said = " ".join(f"{k}={attrs[k]}" for k in SLOW_TICK_SAYS
                                if k in attrs)
                print(f"slow tick: node={node} seq={seq} "
                      f"width={width} duration_us={dur_us:.0f} "
                      f"median_us={median_us:.0f} {said}",
                      file=sys.stderr, flush=True)
        recent.append(dur_us)


class StreamClock:
    """One streamed request's token events on their way out, marked by
    whoever drives them and summed (2,700 events a second on a full
    lane would flood any ring): the sums ride the `generate_stream` span
    the stream records once.

    Per event, ``woke(item)`` when the stream's outbox has handed it to
    the event iterator and ``delivered()`` when its bytes were handed to
    the socket: `wake` = the scheduler's put (`item.t_put`) -> `woke`,
    how long the tokens lay there before a thread took them up;
    `deliver` = `woke` -> `delivered`, everything downstream of the lane
    (the gateway's relay and journal, the chunk framing, the send). High
    `wake`: the lane starves whoever delivers; high `deliver` less its
    CPU: a slow reader, a full socket, or the wait for the interpreter
    lock.

    Two drivers mark it. The front's stream writer (``serving/http.py``)
    takes a tick's events up in one pass: `woke(item, driven=True)` as
    its `next` reaches each, one `delivered(now)` a stream when the
    pass's bytes are out, `add_cpu` for the event's share of the
    writer thread's CPU time, and ONE ``TraceAnnotation("stream.
    deliver")`` a pass on the writer's line of the profiler's host
    plane. A handler thread that iterates the stream itself marks as
    PR 42 had it: `delivered()` when the generator is resumed after its
    `yield`, the annotation an event on the handler's line, and the
    thread's CPU time from its first event's `woke` to ``attrs()``, read
    twice a STREAM and not twice an event (the thread's CPU clock is a
    system call, `CPU_CLOCK_EVERY`): between two deliveries the thread
    is blocked in the outbox's `get`, so the difference is the
    deliveries' CPU time and a few microseconds an event of waking up."""

    __slots__ = ("events", "wake_us_sum", "wake_us_max", "deliver_us_sum",
                 "_wall", "_cpu_ns", "_annotation", "_open", "_t0", "_cpu0",
                 "_cpu_us")

    def __init__(self, wall=time.perf_counter, cpu_ns=time.thread_time_ns):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._wall, self._cpu_ns = wall, cpu_ns
        self.events = 0
        self.wake_us_sum = self.wake_us_max = self.deliver_us_sum = 0.0
        self._open = None
        self._t0, self._cpu0, self._cpu_us = None, None, 0.0

    def woke(self, item, driven: bool = False) -> float:
        """The outbox's `get` returned `item`; the time it did.
        `driven`: in a writer's pass, which holds the annotation and
        apportions its thread's CPU time itself."""
        now = self._wall()
        wake_us = max(0.0, now - getattr(item, "t_put", now)) * 1e6
        self.events += 1
        self.wake_us_sum += wake_us
        if wake_us > self.wake_us_max:
            self.wake_us_max = wake_us
        if not driven:
            if self._cpu0 is None:
                self._cpu0 = self._cpu_ns()
            self._open = self._annotation("stream.deliver")
            self._open.__enter__()
        self._t0 = now
        return now

    def delivered(self, now: Optional[float] = None) -> bool:
        """The open event's bytes were handed to the socket (at `now`,
        if the caller read the clock). False if no event was open: its
        other driver had closed it."""
        if self._t0 is None:
            return False
        if now is None:
            now = self._wall()
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        self.deliver_us_sum += max(0.0, now - self._t0) * 1e6
        self._t0 = None
        return True

    def add_cpu(self, cpu_us: float) -> None:
        """A writer's share of its thread's CPU time for this stream's
        events."""
        self._cpu_us += cpu_us

    def attrs(self) -> dict:
        """The span attrs of a stream that had a token event."""
        if not self.events:
            return {}
        cpu_us = self._cpu_us
        if self._cpu0 is not None:
            cpu_us += (self._cpu_ns() - self._cpu0) / 1e3
        return {"events": self.events,
                "wake_us_sum": round(self.wake_us_sum, 1),
                "wake_us_max": round(self.wake_us_max, 1),
                "deliver_us_sum": round(self.deliver_us_sum, 1),
                "deliver_cpu_us_sum": round(cpu_us, 1)}
