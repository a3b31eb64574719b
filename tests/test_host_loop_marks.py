"""Who holds the host between two ticks (PR 42): the loop as the tick
clock's fifth measured phase, the scheduler thread's time off the CPU, the
collector's pauses, and a streamed token's way out (utils.tracing
`TickClock`, `GcCounter`, `StreamClock`, and their use by the continuous
scheduler and the lane).

Contracts under test:
- on a fake pair of clocks: `loop_us` and `period_us` are on a tick that
  follows another and absent after `idle()`; the four `loop_<part>_us`
  add up to no more than `loop_us`; `<phase>_offcpu_us` is wall less the
  thread's CPU time, never below 0, and only for form, apply and the loop;
  a lane that runs one tick ahead puts each loop on the span of the tick
  it precedes, and the phases tile the period;
- a forced `gc.collect()` is charged to the tick whose phase was open, and
  the process's counter moves;
- the `slow tick:` line says loop, off-CPU and collector time;
- on a CPU lane: every `mixed_step` span of a busy lane carries the new
  attrs, `stats()` has `gc`, the annotations nest under `loop.admit`;
- a streamed request's `generate_stream` span carries `events` = its token
  events with `wake`/`deliver` sums, an exported stream's segment span
  carries them too, and the bytes on the wire are a plain list's.
"""

import gc
import glob
import json
import threading
import time

import jax
import pytest

from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.runtime.scheduler import ContinuousGenerator, StreamDelta
from tpu_engine.serving.http import sse_event
from tpu_engine.utils import tracing
from tpu_engine.utils.tracing import (
    LOOP_PARTS,
    OFFCPU_PHASES,
    TICK_PHASES,
    CompileCounter,
    GcCounter,
    SpanRecorder,
    StreamClock,
    TickClock,
)

_ensure_builtin_models_imported()

PART_KEYS = tuple(f"loop_{p}_us" for p in LOOP_PARTS)
OFFCPU_KEYS = ("form_offcpu_us", "apply_offcpu_us", "loop_offcpu_us")
STREAM_KEYS = ("events", "wake_us_sum", "wake_us_max", "deliver_us_sum",
               "deliver_cpu_us_sum")


class Clocks:
    """A wall clock and a thread-CPU clock moved by hand: `run` is time on
    the CPU (both move), `off` is time off it (the wall clock alone)."""

    def __init__(self):
        self.wall_s, self.cpu_ns = 100.0, 0

    def run(self, ms):
        self.wall_s += ms / 1e3
        self.cpu_ns += int(ms * 1e6)

    def off(self, ms):
        self.wall_s += ms / 1e3

    def clock(self, gcs=None, cpu_every=1):
        return TickClock(CompileCounter(), gcs, wall=lambda: self.wall_s,
                         cpu_ns=lambda: self.cpu_ns, cpu_every=cpu_every)


def _tick_in_order(c, clock, live=True, form=2.0, wait=5.0, apply_off=0.0):
    clock.begin()
    c.run(form)
    clock.dispatch(width=1, rows=1, ctx_tokens=8)
    c.run(1.0)
    clock.wait()
    c.off(wait)
    clock.apply()
    c.run(1.0)
    c.off(apply_off)
    return clock.end(live, "n")[2]


def _loop(c, clock, tail=0.05, parts=(0.1, 0.2, 0.3, 0.4), off=3.0):
    """One pass of the loop's statements as `_loop_body` marks them."""
    c.run(tail)                       # the tick's tail, before the loop's top
    clock.admit()
    for name, ms in zip(LOOP_PARTS, parts):
        clock.loop_part(name)
        c.run(ms)
    c.off(off)                        # ... inside the last part


def test_loop_and_period_on_a_chained_tick_and_absent_after_idle():
    c = Clocks()
    clock = c.clock()
    clock.admit()
    clock.loop_part("exports")        # the loop before an idle lane's first
    c.run(7.0)                        # tick is nobody's
    one = _tick_in_order(c, clock)
    assert not {"loop_us", "period_us", *PART_KEYS,
                "loop_offcpu_us"} & set(one)
    _loop(c, clock)
    two = _tick_in_order(c, clock, apply_off=2.0)
    assert two["loop_us"] == pytest.approx(4050.0)
    assert [two[k] for k in PART_KEYS] == pytest.approx(
        [100.0, 200.0, 300.0, 3400.0])
    assert sum(two[k] for k in PART_KEYS) <= two["loop_us"]
    # begin to begin: the four phases of tick one and the loop.
    assert two["period_us"] == pytest.approx(2000 + 1000 + 5000 + 1000
                                             + 4050)
    assert two["period_us"] == pytest.approx(
        sum(one[f"{p}_us"] for p in TICK_PHASES) + two["loop_us"])
    assert two["loop_offcpu_us"] == pytest.approx(3000.0)
    assert two["form_offcpu_us"] == 0.0
    assert two["apply_offcpu_us"] == pytest.approx(2000.0)
    # The lane goes idle: the next tick follows no other.
    clock.idle()
    clock.admit()
    clock.loop_part("admit")
    c.run(20.0)                       # an idle lane's wait for a request
    three = _tick_in_order(c, clock)
    assert not {"loop_us", "period_us", *PART_KEYS} & set(three)
    assert "gap_us" not in three
    # A tick that ends with no row live breaks the chain as `idle()` does.
    last = _tick_in_order(c, clock, live=False)
    assert "loop_us" in last
    c.run(1.0)
    assert "loop_us" not in _tick_in_order(c, clock)
    clock.idle()


def test_offcpu_is_clipped_at_zero_and_only_for_the_phases_that_never_block():
    c = Clocks()
    clock = c.clock()
    _tick_in_order(c, clock)
    clock.admit()
    clock.loop_part("exports")
    c.run(1.0)
    c.cpu_ns += 5_000_000             # a CPU clock that ran ahead of the wall
    clock.begin()
    c.run(1.0)
    c.cpu_ns += 9_000_000
    clock.dispatch(width=1, rows=1, ctx_tokens=8)
    c.off(4.0)                        # blocked in the step's call: by design
    clock.wait()
    c.off(6.0)
    clock.apply()
    c.run(0.5)
    c.off(0.25)
    attrs = clock.end(True, "n")[2]
    assert attrs["loop_offcpu_us"] == 0.0 and attrs["form_offcpu_us"] == 0.0
    assert attrs["apply_offcpu_us"] == pytest.approx(250.0)
    assert {k for k in attrs if k.endswith("_offcpu_us")} == set(OFFCPU_KEYS)
    assert OFFCPU_PHASES == ("form", "apply")
    clock.idle()


def test_the_cpu_clock_is_read_on_one_iteration_in_cpu_every():
    """From one `end()` to the next: a tick's loop and form fall into one
    iteration, its apply (a lane in order: the same one). The other ticks
    carry no off-CPU attr, and every tick its loop and period."""
    c = Clocks()
    reads = [0]

    def cpu_ns():
        reads[0] += 1
        return c.cpu_ns

    clock = TickClock(CompileCounter(), wall=lambda: c.wall_s, cpu_ns=cpu_ns,
                      cpu_every=3)
    ticks = []
    for _ in range(9):
        ticks.append(_tick_in_order(c, clock, apply_off=0.5))
        _loop(c, clock)
    with_cpu = [i for i, a in enumerate(ticks) if "form_offcpu_us" in a]
    assert with_cpu == [0, 3, 6]       # the first, then every third `end()`
    for i, a in enumerate(ticks):
        keys = {k for k in a if k.endswith("_offcpu_us")}
        assert keys == (set(OFFCPU_KEYS[:2 if i == 0 else 3])
                        if i in with_cpu else set())
        assert ("loop_us" in a) == (i > 0)
    assert ticks[3]["loop_offcpu_us"] == pytest.approx(3000.0)
    assert ticks[3]["apply_offcpu_us"] == pytest.approx(500.0)
    # The first iteration's five marks; the read that opens a later one
    # and its five marks, twice; the read the 9th `end()` opened a fourth
    # with: none on the other six.
    assert reads[0] == 5 + 2 * 6 + 1
    assert tracing.CPU_CLOCK_EVERY == 8
    clock.idle()


def test_a_coarse_cpu_clock_s_excess_is_owed_to_the_phase_s_next_stretches():
    """A CPU clock that ticks in steps of 10 ms (the v5e hosts'): a form of
    4 ms on the CPU reads 10 ms of CPU time once and none twice. No attr
    goes below 0 and their sum is the wall time less the CPU time."""
    c = Clocks()
    clock = c.clock()
    forms = []
    for cpu_ms in (10, 0, 0, 0):
        clock.begin()
        c.off(4.0)                         # the wall clock alone ...
        c.cpu_ns += cpu_ms * 1_000_000     # ... and the CPU clock's step
        clock.dispatch(width=1, rows=1, ctx_tokens=8)
        clock.wait()
        clock.apply()
        c.run(1.0)
        attrs = clock.end(True, "n")[2]
        forms.append(attrs["form_offcpu_us"])
        assert attrs["apply_offcpu_us"] == 0.0    # a phase owes its own
    assert forms == pytest.approx([0.0, 0.0, 2000.0, 4000.0])
    assert sum(forms) == pytest.approx(4 * 4000.0 - 10000.0)
    clock.idle()


def test_a_lane_one_tick_ahead_puts_each_loop_on_its_own_span():
    """tick 1 enqueued alone, then every iteration forms tick N+1 and lands
    tick N; the loops before the ticks take 0.5, 0.7, 0.9 ms."""
    c = Clocks()
    clock = c.clock()
    spans = []

    def form_and_enqueue(form_ms):
        clock.begin()
        c.run(form_ms)
        clock.dispatch(width=1, rows=1, ctx_tokens=8)
        c.run(0.5)

    def land(wait_ms, apply_ms):
        clock.wait()
        c.off(wait_ms)
        clock.apply()
        c.run(apply_ms)
        spans.append(clock.end(True, "n")[2])

    form_and_enqueue(1.0)                          # tick 1
    clock.leave()
    _loop(c, clock, tail=0.0, parts=(0.1, 0.1, 0.2, 0.1), off=0.0)   # 0.5
    form_and_enqueue(1.5)                          # tick 2
    land(3.0, 1.0)                                 # ... lands tick 1
    _loop(c, clock, tail=0.1, parts=(0.1, 0.1, 0.3, 0.1), off=0.0)   # 0.7
    form_and_enqueue(2.0)                          # tick 3
    land(2.0, 1.25)                                # ... lands tick 2
    _loop(c, clock, tail=0.2, parts=(0.1, 0.2, 0.3, 0.1), off=0.0)   # 0.9
    form_and_enqueue(2.5)                          # tick 4
    land(1.0, 0.75)                                # ... lands tick 3
    one, two, three = spans
    assert [s["seq"] for s in spans] == [1, 2, 3]
    assert "loop_us" not in one and "period_us" not in one
    assert two["loop_us"] == pytest.approx(500.0)
    assert three["loop_us"] == pytest.approx(700.0)
    assert two["loop_admit_us"] == pytest.approx(200.0)
    assert three["loop_admit_us"] == pytest.approx(300.0)
    assert (two["form_us"], three["form_us"]) == pytest.approx((1500.0,
                                                               2000.0))
    # begin(2) -> begin(3): tick 2's form and dispatch, tick 1's wait and
    # apply, tick 3's loop. The phases tile the period.
    assert three["period_us"] == pytest.approx(
        two["form_us"] + two["dispatch_us"] + one["wait_us"]
        + one["apply_us"] + three["loop_us"])
    assert two["period_us"] == pytest.approx(
        one["form_us"] + one["dispatch_us"] + two["loop_us"])
    # An iteration that only lands the tick in flight (an export command
    # in hand, the lane drained) splits the loop; both stretches count.
    clock.admit()
    clock.loop_part("exports")
    c.run(0.3)
    land(0.5, 0.5)                                 # lands tick 4, no begin
    _loop(c, clock, tail=0.0, parts=(0.1, 0.1, 0.1, 0.1), off=0.0)
    form_and_enqueue(1.0)                          # tick 5
    clock.wait()
    clock.apply()
    assert spans[-1]["seq"] == 4 and spans[-1]["loop_us"] == pytest.approx(
        900.0)
    five = clock.end(False, "n")[2]
    # 0.3 ms before the landing, 0.4 ms after it.
    assert five["loop_us"] == pytest.approx(300.0 + 400.0)
    assert five["loop_exports_us"] == pytest.approx(300.0 + 100.0)
    clock.idle()


def test_gc_counter_counts_pauses_by_its_clock():
    c = Clocks()
    counter = GcCounter(wall=lambda: c.wall_s)
    counter("stop", {"generation": 0})             # a stop with no start
    assert counter.snapshot() == {"count": 0, "seconds": 0.0, "gen2": 0}
    for generation, ms in ((0, 1.0), (2, 60.0), (1, 3.0)):
        counter("start", {"generation": generation})
        c.off(ms)
        counter("stop", {"generation": generation, "collected": 0,
                         "uncollectable": 0})
    assert counter.snapshot() == {"count": 3, "seconds": 0.064, "gen2": 1}


def test_a_forced_collection_is_charged_to_the_open_tick():
    counter = tracing.gc_counter()
    assert counter is tracing.gc_counter() and counter in gc.callbacks
    clock = TickClock(CompileCounter(), counter)
    before = counter.snapshot()

    def tick(collect_in=None):
        clock.begin()
        if collect_in == "form":
            gc.collect()
        clock.dispatch(width=1, rows=1, ctx_tokens=8)
        clock.wait()
        clock.apply()
        attrs = clock.end(True, "n")[2]
        if collect_in == "loop":
            clock.admit()
            clock.loop_part("admit")
            gc.collect()
        return attrs

    tick()
    held = tick("form")
    after = counter.snapshot()
    assert after["count"] >= before["count"] + 1
    assert after["gen2"] >= before["gen2"] + 1
    assert after["seconds"] > before["seconds"]
    assert 0 < held["gc_us"] <= held["form_us"]
    # A pause in the loop rides the span of the tick the loop precedes.
    tick("loop")
    following = tick()
    assert 0 < following["gc_us"] <= following["loop_us"] + 1
    clock.idle()


def test_the_slow_tick_line_says_loop_offcpu_and_collector(capsys):
    c = Clocks()
    clock = c.clock()
    for _ in range(tracing.SLOW_TICK_MIN_HISTORY):
        _tick_in_order(c, clock, form=1.0, wait=1.0)
        _loop(c, clock)
    assert capsys.readouterr().err == ""
    clock.begin()
    c.off(150.0)                       # far off, in form, off the CPU
    clock.dispatch(width=1, rows=1, ctx_tokens=8)
    clock.wait()
    clock.apply()
    attrs = clock.end(True, "lane-7")[2]
    line = capsys.readouterr().err.strip()
    assert line.startswith("slow tick: node=lane-7 seq=6 width=1 ")
    for key in ("form_us", "gap_us", "loop_us", *OFFCPU_KEYS, "compile_us",
                "gc_us"):
        assert f" {key}={attrs[key]}" in line, key
    assert attrs["form_offcpu_us"] == pytest.approx(150000.0)
    assert tracing.SLOW_TICK_SAYS[:4] == tuple(f"{p}_us"
                                               for p in TICK_PHASES)
    clock.idle()


# -- on a CPU lane -------------------------------------------------------------

@pytest.fixture(scope="module")
def spec():
    return create_model("gpt2-small-test", max_seq=128)


@pytest.fixture(scope="module")
def params(spec):
    return spec.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def lane(spec, params):
    gen = ContinuousGenerator(spec, params=params, dtype="float32",
                              n_slots=4, step_chunk=4, max_seq=128,
                              kv_block_size=16, prefill_chunk=16,
                              mixed_token_budget=16,
                              prefix_sharing=False)
    gen.tracer = SpanRecorder(8192)
    gen.trace_node = "lane"
    gen.submit(list(range(1, 20)), max_new_tokens=3).result(120)
    yield gen
    gen.stop()


def _wait_idle(gen):
    limit = time.monotonic() + 30
    while gen.stats()["active"] and time.monotonic() < limit:
        time.sleep(0.005)
    time.sleep(0.06)


def test_every_tick_of_a_busy_lane_says_its_loop_and_the_phases_tile(lane):
    _wait_idle(lane)
    seq0 = lane._clock.seq
    lane.submit([5, 9, 3], max_new_tokens=12).result(60)
    _wait_idle(lane)
    ticks = [s["attrs"] for s in lane.tracer.snapshot()
             if s["op"] == "mixed_step" and s["attrs"]["seq"] > seq0]
    assert len(ticks) >= 10
    first, rest = ticks[0], ticks[1:]
    assert not {"loop_us", "period_us"} & set(first)
    assert "gc_us" in first
    for a in rest:
        assert {"loop_us", "period_us", *PART_KEYS, "gc_us",
                "compile_us"} <= set(a)
        assert sum(a[k] for k in PART_KEYS) <= a["loop_us"] + 1
        assert all(a[k] >= 0 for k in (*PART_KEYS, "gc_us"))
        for k in OFFCPU_KEYS:
            assert 0 <= a.get(k, 0) <= a[k.replace("_offcpu", "")] + 1
    # One loop iteration in eight reads the thread's CPU clock: a tick's
    # loop and form in one, its apply in the next.
    for k in OFFCPU_KEYS:
        assert 1 <= sum(k in a for a in ticks) <= len(ticks) // 8 + 1
    by_seq = {a["seq"]: a for a in ticks}
    tiled = 0
    for a in rest:
        one, two = by_seq.get(a["seq"] - 2), by_seq.get(a["seq"] - 1)
        if one is None or not (a["overlapped"] and two["overlapped"]):
            continue
        tiled += 1
        assert a["period_us"] == pytest.approx(
            two["form_us"] + two["dispatch_us"] + one["wait_us"]
            + one["apply_us"] + a["loop_us"], rel=0.01, abs=5)
    assert tiled >= 5
    stats = lane.stats()
    assert set(stats["gc"]) == {"count", "seconds", "gen2"}
    assert lane._gcs is tracing.gc_counter()


def test_the_loop_s_parts_are_annotations_under_loop_admit(lane, tmp_path):
    from jax.profiler import ProfileData

    _wait_idle(lane)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        lane.submit(list(range(1, 25)), max_new_tokens=6).result(60)
        _wait_idle(lane)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    parts = tuple("loop.admit." + p for p in LOOP_PARTS)
    events = sorted(
        (e.start_ns, -e.duration_ns, e.name)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name in ("loop.admit", *parts))
    loops = [(s, s - d) for s, d, name in events if name == "loop.admit"]
    inside = {loop: [] for loop in loops}
    for s, d, name in events:
        if name == "loop.admit":
            continue
        home = [loop for loop in loops if loop[0] <= s and s - d <= loop[1]]
        if not home:
            # Only at the capture's two ends: the `loop.admit` around it
            # was open when the capture began, or when it stopped.
            assert s - d <= loops[0][0] or s >= loops[-1][1]
            continue
        assert len(home) == 1, "a part lies inside ONE loop.admit"
        inside[home[0]].append(name)
    full = [names for names in inside.values() if names]
    assert len(full) >= 6
    # Between two ticks: the four parts, in order, once.
    assert full.count(list(parts)) >= len(full) - 2


def _worker(spec, params, node_id, **config):
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig

    engine = InferenceEngine(spec, params=params, dtype="float32",
                             batch_buckets=(1, 2))
    return WorkerNode(WorkerConfig(
        node_id=node_id, model="gpt2-small-test", dtype="float32",
        gen_scheduler="continuous", gen_max_batch_size=2,
        gen_kv_block_size=16, gen_prefill_chunk=16,
        gen_mixed_token_budget=16, **config), engine=engine)


def _token_events(frames):
    out = []
    for frame in frames:
        evt = json.loads(frame.decode().split("data: ", 1)[1])
        if "tokens" in evt and not evt.get("done"):
            out.append(evt["tokens"])
    return out


def test_a_stream_s_span_sums_its_token_events_way_out(spec, params):
    w = _worker(spec, params, "st1")
    try:
        frames = []
        for frame in w.handle_generate_stream({"request_id": "s1",
                                               "prompt_tokens": [5, 9, 3],
                                               "max_new_tokens": 9}):
            frames.append(frame)
            time.sleep(0.002)          # a reader that takes its time
        events = _token_events(frames)
        assert sum(len(e) for e in events) == 9
        span = next(s for s in w.tracer.snapshot()
                    if s["op"] == "generate_stream")
        attrs = span["attrs"]
        assert set(attrs) == {"ttft_us", *STREAM_KEYS}
        assert attrs["events"] == len(events)
        assert 0 <= attrs["wake_us_max"] <= attrs["wake_us_sum"]
        assert attrs["wake_us_sum"] <= span["duration_us"] * len(events)
        # The reader slept 2 ms an event, off the CPU, inside `deliver`.
        assert attrs["deliver_us_sum"] >= 2000 * len(events)
        assert 0 <= attrs["deliver_cpu_us_sum"] <= (attrs["deliver_us_sum"]
                                                    - 1000 * len(events))
        # (its CPU time: from the first event to the span, two reads)
        assert attrs["deliver_us_sum"] <= span["duration_us"] + 5000
    finally:
        w.stop()


def test_an_exported_stream_s_segment_span_carries_the_sums(spec, params):
    w = _worker(spec, params, "st2")
    gen = w.generator
    try:
        # Hold the lane once, after the third token, until the export
        # command waits in its queue: the export finds the row mid-stream.
        push, held = gen._push_stream, []

        def push_then_hold(row, req):
            push(row, req)
            if req.streamed >= 3 and not held:
                held.append(row)
                limit = time.monotonic() + 30
                while gen._migrate_q.empty() and time.monotonic() < limit:
                    time.sleep(0.001)

        gen._push_stream = push_then_hold
        frames, exported = [], {}

        def consume():
            for frame in w.handle_generate_stream(
                    {"request_id": "s2", "prompt_tokens": [5, 9, 3],
                     "max_new_tokens": 40}):
                frames.append(frame)

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        limit = time.monotonic() + 60
        while len(frames) < 3 and time.monotonic() < limit:
            time.sleep(0.002)
        exported.update(w.handle_migrate_export({"request_id": "s2"}))
        t.join(60)
        assert exported["ok"], exported
        events = _token_events(frames)
        assert 3 <= sum(len(e) for e in events) < 40
        last = json.loads(frames[-1].decode().split("data: ", 1)[1])
        assert last.get("retryable") and "error" in last
        span = next(s for s in w.tracer.snapshot()
                    if s["op"] == "generate_stream")
        assert span["attrs"]["segment"] == "exported"
        assert set(span["attrs"]) == {"segment", *STREAM_KEYS}
        assert span["attrs"]["events"] == len(events)
        assert span["attrs"]["deliver_us_sum"] >= 0
    finally:
        gen.__dict__.pop("_push_stream", None)
        w.stop()


def test_a_stream_delta_is_a_list_on_the_wire_and_knows_its_put():
    delta = StreamDelta([7, 8])
    delta.t_put = time.perf_counter()          # as `_push_stream` does
    assert delta == [7, 8] and isinstance(delta, list) and len(delta) == 2
    assert sse_event({"tokens": delta}) == sse_event({"tokens": [7, 8]})
    # The handler's clock: the put's time travels with the item; an item
    # without one (a plain list) has waited no time.
    now, cpu, reads = [delta.t_put + 0.004], [7_000_000], [0]

    def cpu_ns():
        reads[0] += 1
        return cpu[0]

    way = StreamClock(wall=lambda: now[0], cpu_ns=cpu_ns)
    assert way.attrs() == {}
    way.woke(delta)
    now[0] += 0.001
    cpu[0] += 300_000
    way.delivered()
    way.woke([9])
    cpu[0] += 200_000
    way.delivered()
    assert way.attrs() == {"events": 2,
                           "wake_us_sum": pytest.approx(4000.0, abs=1),
                           "wake_us_max": pytest.approx(4000.0, abs=1),
                           "deliver_us_sum": pytest.approx(1000.0, abs=1),
                           "deliver_cpu_us_sum": pytest.approx(500.0)}
    assert reads[0] == 2       # a stream, not an event: it is a system call


def test_a_writer_s_pass_marks_a_stream_clock_without_reading_the_cpu():
    """PR 43: the front's stream writer takes an event up (`woke(item,
    driven=True)`), closes it when the pass's sends returned
    (`delivered(t)`) and adds the event's share of its own thread's CPU
    time; the generator's `delivered()` on its next resume then finds
    nothing open. A stream handed back to its handler goes on as before,
    and its thread's CPU time is added to the writer's share."""
    delta = StreamDelta([7])
    delta.t_put = 100.0
    now, cpu, reads = [100.002], [5_000_000], [0]

    def cpu_ns():
        reads[0] += 1
        return cpu[0]

    way = StreamClock(wall=lambda: now[0], cpu_ns=cpu_ns)
    assert way.woke(delta, driven=True) == pytest.approx(100.002)
    assert way.delivered(100.0025) is True      # the sends' own clock
    assert way.delivered() is False             # the generator, resumed
    way.add_cpu(40.0)
    assert reads[0] == 0
    assert way.attrs() == {"events": 1,
                           "wake_us_sum": pytest.approx(2000.0, abs=1),
                           "wake_us_max": pytest.approx(2000.0, abs=1),
                           "deliver_us_sum": pytest.approx(500.0, abs=1),
                           "deliver_cpu_us_sum": pytest.approx(40.0)}
    now[0] = 100.010
    way.woke([8])                               # handed back: the handler's
    now[0] += 0.001
    cpu[0] += 300_000
    assert way.delivered() is True
    assert way.attrs()["events"] == 2
    assert way.attrs()["deliver_us_sum"] == pytest.approx(1500.0, abs=1)
    assert way.attrs()["deliver_cpu_us_sum"] == pytest.approx(340.0)


def test_the_scheduler_wakes_a_driven_stream_s_writer_once_a_tick(lane):
    """An outbox a writer is attached to hands `put` the writer's wake;
    the loop calls it after a tick's last put, not once a row: two rows'
    tokens of one tick are marked ready before the one wake."""
    from tpu_engine.utils.streams import StreamOutbox

    log = []
    boxes = [StreamOutbox(), StreamOutbox()]

    def wake():
        log.append("wake")

    for n, box in enumerate(boxes):
        box.attach(lambda n=n: log.append(n) or wake)
    futs = [lane.submit([5, 9, 3 + n], max_new_tokens=6, stream=box)
            for n, box in enumerate(boxes)]
    for fut in futs:
        assert len(fut.result(timeout=120)) == 6
    limit = time.monotonic() + 30
    while log.count("wake") < 2 and time.monotonic() < limit:
        time.sleep(0.01)
    # Every mark is followed by a wake before the lane goes idle, and
    # somewhere both rows' marks precede one wake.
    assert log[-1] == "wake" and {0, 1} <= set(log)
    runs = "".join("w" if x == "wake" else str(x) for x in log).split("w")
    assert any({"0", "1"} <= set(run) for run in runs), log
    for box in boxes:
        items = []
        while box.has_next() and not (items and items[-1] is None):
            items.append(box.get(timeout=1))
        assert items[-1] is None
        assert sum(len(i) for i in items[:-1]) == 6


def test_the_probe_before_the_dispatch_is_read_by_that_dispatch(lane,
                                                               monkeypatch):
    """`gap_us` is fixed where the clock marks the dispatch, from what the
    probes saw until then. The scheduler asks whether the tick in flight
    has finished at the form's start and again when the batch is formed:
    the second probe must come BEFORE the dispatch mark, or a tick that
    finished during the form reads as still running (gap 0 on a lane
    whose host is the slower: PR 43 found the two the wrong way round,
    hidden as long as the stream handlers' convoy kept the loop long)."""
    calls = []
    clock = lane._clock
    probe, dispatch, begin = clock.probe, clock.dispatch, clock.begin
    monkeypatch.setattr(clock, "begin",
                        lambda: (calls.append("begin"), begin())[1])
    monkeypatch.setattr(clock, "probe",
                        lambda ready: (calls.append("probe"), probe(ready))[1])
    monkeypatch.setattr(
        clock, "dispatch",
        lambda *a: (calls.append("dispatch"), dispatch(*a))[1])
    assert len(lane.submit([4, 8, 15, 16], max_new_tokens=12)
               .result(timeout=120)) == 12
    _wait_idle(lane)
    ticks = " ".join(calls).split("begin")
    behind_a_tick = [t.split() for t in ticks if "probe" in t and
                     "dispatch" in t]
    assert len(behind_a_tick) >= 8
    for tick in behind_a_tick:
        at = tick.index("dispatch")
        assert tick[:at].count("probe") == 2, tick
