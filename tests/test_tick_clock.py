"""The tick and the request say where their time went (utils.tracing
`TickClock`, `CompileCounter`, `TraceSink.between`, and their use by the
continuous scheduler and the lane).

Contracts under test, on a CPU lane:
- a `mixed_step` span's four phases add up to its duration, every tick,
  and every tick function (paged, speculative, state slab) records them
  through the one tick helper;
- `gap_us` is absent after an idle lane and present between back-to-back
  ticks: the host's time between two ticks on a lane that reads each
  tick's results first, 0 (or what a probe saw) behind a tick in flight;
- a lane that runs one tick ahead marks `form` and `dispatch` of tick N+1
  and then `wait` and `apply` of tick N in one iteration, and each tick's
  span carries its own four phases;
- `queue_wait` + `slot_wait` + `prefill` cover submit -> first token with
  no hole, under one trace id;
- a row the token budget starves says so on its `prefill` span;
- the compile counter moves for a new width and only then;
- the phases are `jax.profiler.TraceAnnotation`s on the scheduler's
  thread, nested and in order, and children of the tick in /trace/export;
- a slow tick names its phase on stderr, at most once in ten seconds;
- `trace_capacity=0` serves and records nothing.
"""

import glob
import time

import jax
import pytest

from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.runtime.scheduler import ContinuousGenerator
from tpu_engine.utils import tracing
from tpu_engine.utils.tracing import (
    TICK_PHASES,
    CompileCounter,
    SpanRecorder,
    TickClock,
    TraceContext,
    TraceSink,
    export_chrome,
)

_ensure_builtin_models_imported()

PHASE_KEYS = tuple(f"{p}_us" for p in TICK_PHASES)


@pytest.fixture(scope="module")
def spec():
    return create_model("gpt2-small-test", max_seq=128)


@pytest.fixture(scope="module")
def params(spec):
    return spec.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def lane(spec, params):
    """One mixed lane with its span ring, prefix sharing off (so the
    prefill thread does no radix lookup between queue_wait and slot_wait)."""
    gen = ContinuousGenerator(spec, params=params, dtype="float32",
                              n_slots=4, step_chunk=4, max_seq=128,
                              kv_block_size=16, prefill_chunk=16,
                              mixed_token_budget=16,
                              prefix_sharing=False)
    gen.tracer = SpanRecorder(8192)
    gen.trace_node = "lane"
    # Compile both step widths before any test reads the clock.
    gen.submit(list(range(1, 20)), max_new_tokens=3).result(120)
    yield gen
    gen.stop()


def _submit(gen, rid, prompt, max_new, **kw):
    sink = TraceSink(gen.tracer, gen.trace_node, rid, TraceContext.root(rid))
    return gen.submit(prompt, max_new_tokens=max_new, sink=sink, **kw)


def _ticks(gen, since=0):
    return [s for s in gen.tracer.snapshot()
            if s["op"] == "mixed_step" and s["attrs"]["seq"] > since]


def _wait_idle(gen):
    limit = time.monotonic() + 30
    while gen.stats()["active"] and time.monotonic() < limit:
        time.sleep(0.005)
    time.sleep(0.06)   # past the idle loop's 20 ms admission wait


def test_phases_add_up_to_the_tick_and_keep_the_old_fields(lane):
    seq0 = lane._clock.seq
    _submit(lane, "sum", list(range(1, 40)), 6).result(60)
    ticks = _ticks(lane, seq0)
    assert len(ticks) >= 6
    for s in ticks:
        a = s["attrs"]
        assert {"prefill_tokens", "decode_rows", "width", "ctx_tokens",
                "compile_us", "seq", *PHASE_KEYS} <= set(a)
        assert sum(a[k] for k in PHASE_KEYS) == pytest.approx(
            s["duration_us"], rel=0.01, abs=2)
        assert s["start_ts"] <= s["ts"]
        assert s["ts"] - s["start_ts"] == pytest.approx(
            s["duration_us"] / 1e6, abs=0.005)
    assert [s["attrs"]["seq"] for s in ticks] == list(
        range(seq0 + 1, seq0 + 1 + len(ticks)))
    # 39 prompt tokens at chunk 16: contexts 16, 32, 39, then pos + 1.
    assert [s["attrs"]["ctx_tokens"] for s in ticks[:5]] == [16, 32, 39,
                                                             40, 41]
    assert [s["attrs"]["width"] for s in ticks[:4]] == [16, 16, 16, 1]


@pytest.mark.parametrize("order", ["ahead", "in_order"])
def test_gap_only_between_back_to_back_ticks(lane, order):
    _wait_idle(lane)
    if order == "in_order":
        lane._may_run_ahead = lambda: False    # the drained case
    try:
        seq0 = lane._clock.seq
        _submit(lane, "gap-" + order, [5, 9, 3], 5).result(60)
        _wait_idle(lane)
    finally:
        lane.__dict__.pop("_may_run_ahead", None)
    ticks = _ticks(lane, seq0)
    assert len(ticks) >= 4
    assert "gap_us" not in ticks[0]["attrs"]   # the lane was idle before it
    for prev, s in zip(ticks, ticks[1:]):
        if order == "in_order":
            # wait's end of the tick before -> this tick's dispatch: at
            # least that tick's apply and this tick's form.
            assert s["attrs"]["overlapped"] == 0
            assert s["attrs"]["gap_us"] >= (prev["attrs"]["apply_us"]
                                            + s["attrs"]["form_us"]) * 0.99
        else:
            # Enqueued behind the tick before: the device had it queued
            # when that tick ended, unless a probe saw it end earlier.
            # Held on ONE clock, the scheduler thread's (ROADMAP C0: a
            # span's `time.time()` record stamp comes after the probe by
            # however long the thread was held, so it bounds nothing): the
            # idle mark is set after the tick before was enqueued, so not
            # before its begin, and the gap ends at this tick's dispatch:
            # gap <= begin-to-begin + this tick's form.
            a = s["attrs"]
            assert a["overlapped"] == 1
            assert a["seq"] == prev["attrs"]["seq"] + 1
            assert 0 <= a["gap_us"] <= a["period_us"] + a["form_us"] + 1


def test_request_stages_cover_submit_to_first_token(lane):
    for i, n in enumerate((3, 40, 70)):
        rid = f"cover-{i}"
        before = lane.ttft_hist.snapshot()["sum"]
        _submit(lane, rid, [(j * 7) % 90 + 1 for j in range(n)], 2).result(60)
        ttft_us = (lane.ttft_hist.snapshot()["sum"] - before) * 1e6
        mine = {s["op"]: s for s in lane.tracer.snapshot()
                if s["request_id"] == rid}
        stages = [mine[op] for op in ("queue_wait", "slot_wait", "prefill")]
        assert sum(s["duration_us"] for s in stages) == pytest.approx(
            ttft_us, abs=2000)
        assert {s["trace_id"] for s in stages} == {
            tracing.derive_trace_id(rid)}
        assert len({s["parent_id"] for s in stages}) == 1
        assert mine["slot_wait"]["attrs"] == {"parked": False}
        # Consecutive stages share a mark: each starts where the last ended.
        for a, b in zip(stages, stages[1:]):
            assert b["start_ts"] == pytest.approx(
                a["start_ts"] + a["duration_us"] / 1e6, abs=0.002)
        chunks = -(-n // 16)
        assert mine["prefill"]["attrs"]["chunks"] == chunks
        assert mine["prefill"]["attrs"]["prompt_len"] == n


def test_a_row_behind_a_long_prompt_reports_starved_ticks(lane):
    _wait_idle(lane)
    long_prompt = [(j * 13) % 90 + 1 for j in range(90)]
    first = _submit(lane, "starver", long_prompt, 2)
    second = _submit(lane, "starved", [(j * 5) % 90 + 1 for j in range(30)],
                     2)
    first.result(60)
    second.result(60)
    spans = {s["request_id"]: s["attrs"] for s in lane.tracer.snapshot()
             if s["op"] == "prefill"}
    # The budget (16 a tick) goes to the lowest-numbered prefilling row.
    assert spans["starver"]["starved_ticks"] == 0
    assert spans["starver"]["starved_us"] == 0
    assert spans["starved"]["starved_ticks"] >= 1
    assert spans["starved"]["starved_us"] > 0
    assert spans["starved"]["chunks"] >= 2


def test_compile_counter_moves_only_for_a_new_program(spec, params, lane):
    counter = tracing.compile_counter()
    assert counter is tracing.compile_counter() is lane._compiles
    before = lane.stats()["compile"]
    seq0 = lane._clock.seq
    _submit(lane, "warm", list(range(1, 30)), 4).result(60)
    after = lane.stats()["compile"]
    assert after == before            # both widths were compiled already
    assert all(s["attrs"]["compile_us"] == 0 for s in _ticks(lane, seq0))
    # A lane of another chunk width compiles a new step program.
    other = ContinuousGenerator(spec, params=params, dtype="float32",
                                n_slots=4, step_chunk=4, max_seq=128,
                                kv_block_size=16, prefill_chunk=32,
                                mixed_token_budget=32,
                                prefix_sharing=False)
    other.tracer = SpanRecorder(256)
    try:
        other.submit(list(range(1, 30)), max_new_tokens=2).result(120)
        grown = other.stats()["compile"]
        assert grown["count"] >= after["count"] + 1
        assert grown["seconds"] > after["seconds"]
        first = [s for s in other.tracer.snapshot()
                 if s["op"] == "mixed_step"][0]
        assert first["attrs"]["compile_us"] > 0
        assert first["attrs"]["dispatch_us"] >= first["attrs"]["compile_us"]
    finally:
        other.stop()


def test_compile_counter_counts_the_backend_compile_event_only():
    counter = CompileCounter()
    counter("/jax/core/compile/jaxpr_trace_duration", 1.0)
    assert counter.snapshot() == {"count": 0, "seconds": 0.0}
    counter(CompileCounter.EVENT, 0.25, fun_name="f")
    counter(CompileCounter.EVENT, 0.5)
    assert counter.snapshot() == {"count": 2, "seconds": 0.75}


@pytest.mark.parametrize("kind", ["spec", "slab"])
def test_every_tick_function_uses_the_one_clock(kind, spec, params):
    if kind == "spec":
        gen = ContinuousGenerator(spec, params=params, dtype="float32",
                                  n_slots=2, step_chunk=4, max_seq=128,
                                  kv_block_size=16, prefill_chunk=16,
                                  mixed_token_budget=16,
                                  spec_k=2)
        prompt = [3, 3, 3, 3, 3, 3]
    else:
        slab_spec = create_model("ssd-small-test")
        gen = ContinuousGenerator(
            slab_spec, params=slab_spec.init(jax.random.PRNGKey(0)),
            dtype="float32", n_slots=2, state_rows=4, prefill_chunk=8,
            mixed_token_budget=16)
        prompt = list(range(1, 12))
    gen.tracer = SpanRecorder(512)
    try:
        gen.submit(prompt, max_new_tokens=4).result(180)
        spans = gen.tracer.snapshot()
        ticks = [s for s in spans if s["op"] == "mixed_step"]
        assert ticks
        for s in ticks:
            assert sum(s["attrs"][k] for k in PHASE_KEYS) == pytest.approx(
                s["duration_us"], rel=0.01, abs=2)
        if kind == "spec":
            verify = [s for s in spans if s["op"] == "spec_verify"]
            assert len(verify) == len(ticks)
            assert set(verify[0]["attrs"]) == {"decode_rows", "proposed",
                                               "accepted", "width"}
            assert [s["duration_us"] for s in verify] == [
                s["duration_us"] for s in ticks]
        else:
            # A recurrence attends no context.
            assert {s["attrs"]["ctx_tokens"] for s in ticks} == {0}
    finally:
        gen.stop()


def test_profiler_capture_holds_the_phases_nested_and_in_order(lane,
                                                               tmp_path):
    from jax.profiler import ProfileData

    _wait_idle(lane)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _submit(lane, "prof", list(range(1, 25)), 6).result(60)
        _wait_idle(lane)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    names = ("tick", *(f"tick.{p}" for p in TICK_PHASES), "loop.admit")
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in line.events
                      if e.name in names]
            if events:
                lines.append(events)
    assert len(lines) == 1, "the annotations sit on ONE host thread"
    events = sorted(lines[0], key=lambda e: (e[1], -e[2]))
    ticks = [e for e in events if e[0] == "tick"]
    assert len(ticks) >= 6
    four = [f"tick.{p}" for p in TICK_PHASES]
    held = []
    for name, start, end, stats in ticks:
        inside = [e for e in events
                  if e[0] != "tick" and start <= e[1] and e[2] <= end]
        held.append([e[0] for e in inside])
        for a, b in zip(inside, inside[1:]):
            assert a[2] <= b[1]                    # in order, no overlap
        if "tick.dispatch" in held[-1]:
            assert {"seq", "width", "rows", "ctx_tokens"} <= set(stats)
    # One tick ahead: the first iteration only enqueues, every later one
    # forms and enqueues the next tick and then lands the one before, the
    # last finds nothing to step (the row's last token is in flight) and
    # only lands.
    assert held[0] == four[:2] and held[-1] == [four[0]] + four[2:]
    assert held[1:-1] == [four] * (len(ticks) - 2)
    # Between two ticks the loop's work is one `loop.admit`.
    for a, b in zip(ticks, ticks[1:]):
        between = [e[0] for e in events if a[2] <= e[1] and e[2] <= b[1]]
        assert between == ["loop.admit"]


def test_trace_export_shows_the_phases_as_children_of_the_tick(lane):
    _submit(lane, "export", [5, 9, 3], 3).result(60)
    events = export_chrome({"lane": lane.tracer})["traceEvents"]
    ticks = [e for e in events if e["name"] == "mixed_step"]
    children = [e for e in events if e["name"].startswith("tick.")]
    assert ticks and len(children) == 4 * len(ticks)
    by_seq = {}
    for e in children:
        by_seq.setdefault(e["args"]["seq"], []).append(e)
    for tick in ticks:
        kids = by_seq[tick["args"]["seq"]]
        assert [k["name"] for k in kids] == [f"tick.{p}"
                                             for p in TICK_PHASES]
        assert kids[0]["ts"] == tick["ts"]
        assert all(k["tid"] == tick["tid"] for k in kids)
        for a, b in zip(kids, kids[1:]):
            assert b["ts"] == pytest.approx(a["ts"] + a["dur"])
        assert kids[-1]["ts"] + kids[-1]["dur"] == pytest.approx(
            tick["ts"] + tick["dur"], abs=0.01 * tick["dur"] + 2)
    # One ring entry a tick, as before: the children are made at export.
    assert not [s for s in lane.tracer.snapshot()
                if s["op"].startswith("tick.")]


def test_a_slow_tick_names_its_phase_once_in_ten_seconds(capsys):
    clock = TickClock(CompileCounter())

    def tick(wait_s):
        clock.begin()
        clock.dispatch(width=1, rows=1, ctx_tokens=8)
        clock.wait()
        time.sleep(wait_s)
        clock.apply()
        return clock.end(True, "lane-7")

    for _ in range(tracing.SLOW_TICK_MIN_HISTORY):
        tick(0.001)
    assert capsys.readouterr().err == ""
    _, dur_us, attrs = tick(0.05)
    lines = capsys.readouterr().err.strip().splitlines()
    assert len(lines) == 1
    line = lines[0]
    assert line.startswith("slow tick: node=lane-7 seq=6 width=1 ")
    for key in (*PHASE_KEYS, "gap_us", "compile_us"):
        assert f" {key}={attrs[key]}" in line
    assert attrs["wait_us"] > 0.9 * dur_us
    tick(0.05)                         # within ten seconds: no second line
    assert capsys.readouterr().err == ""
    clock.idle()


def test_a_tick_ahead_keeps_each_tick_s_own_four_phases():
    """The marks of a lane that runs ahead, by hand: tick 1 enqueued alone
    (`leave`), tick 2 formed and enqueued before tick 1 is landed, then
    tick 2 landed alone. Each `end()` gives the OLDEST tick not ended, with
    its own form and dispatch from the iteration before."""
    clock = TickClock(CompileCounter())
    clock.begin()                                  # tick 1
    time.sleep(0.02)       # ten times tick 2's: a loaded machine's sleep
    clock.note(sampler="greedy")                   # overshoots by ms
    clock.dispatch(width=16, rows=1, ctx_tokens=16)
    clock.leave()
    clock.admit()
    clock.begin()                                  # tick 2, behind tick 1
    clock.probe(False)
    time.sleep(0.002)
    clock.note(sampler="plain")
    clock.dispatch(width=1, rows=1, ctx_tokens=17)
    clock.wait()                                   # ... for tick 1
    time.sleep(0.003)
    clock.apply()
    clock.note(moe_assignments=7)                  # came back with tick 1
    _, dur1, one = clock.end(True, "n")
    assert (one["seq"], one["overlapped"], one["ctx_tokens"]) == (1, 0, 16)
    assert one["sampler"] == "greedy" and one["moe_assignments"] == 7
    assert one["form_us"] >= 20000 and one["wait_us"] >= 3000
    assert "gap_us" not in one                     # after an idle lane
    assert dur1 == pytest.approx(sum(one[k] for k in PHASE_KEYS), abs=1)
    clock.wait()                                   # tick 2 landed alone
    clock.apply()
    _, dur2, two = clock.end(False, "n")
    assert (two["seq"], two["overlapped"], two["ctx_tokens"]) == (2, 1, 17)
    assert two["sampler"] == "plain" and "moe_assignments" not in two
    assert 2000 <= two["form_us"] < 20000          # its own form, not 1's
    assert two["gap_us"] == 0.0                    # enqueued behind tick 1
    assert dur2 == pytest.approx(sum(two[k] for k in PHASE_KEYS), abs=1)
    # A probe that sees the tick in flight finished dates the device's
    # idle time from then; a tick formed with nothing to step is dropped.
    clock.begin()                                  # tick 3 after a dead lane
    clock.dispatch(width=1, rows=1, ctx_tokens=3)
    clock.leave()
    clock.begin()                                  # tick 4
    clock.probe(True)
    time.sleep(0.002)
    clock.dispatch(width=1, rows=1, ctx_tokens=4)
    clock.wait()
    clock.apply()
    three = clock.end(True, "n")[2]
    assert "gap_us" not in three and three["seq"] == 3
    clock.begin()                                  # nothing to step: dropped
    clock.wait()
    clock.apply()
    four = clock.end(False, "n")[2]
    assert four["seq"] == 4 and four["gap_us"] >= 2000
    assert clock.seq == 4
    clock.idle()


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


def test_trace_capacity_zero_serves_and_records_nothing(spec, params):
    w = _worker(spec, params, "quiet", trace_capacity=0)
    try:
        out = w.handle_generate({"request_id": "q1",
                                 "prompt_tokens": [5, 9, 3],
                                 "max_new_tokens": 4})
        assert len(out["tokens"]) == 4
        assert w.tracer.snapshot() == []
        assert w.generator._clock.seq >= 4     # the clock still ran
    finally:
        w.stop()


def test_lane_ttft_on_the_stream_span_and_compiles_at_metrics(spec, params):
    from tpu_engine.utils.metrics import render_prometheus

    w = _worker(spec, params, "tt1")
    try:
        list(w.handle_generate_stream({"request_id": "s1",
                                       "prompt_tokens": [5, 9, 3],
                                       "max_new_tokens": 5}))
        spans = {s["op"]: s for s in w.tracer.snapshot()
                 if s["request_id"] == "s1"}
        stream = spans["generate_stream"]
        ttft_us = stream["attrs"]["ttft_us"]
        inner = sum(spans[op]["duration_us"]
                    for op in ("queue_wait", "slot_wait", "prefill"))
        # Receipt by the lane -> first event out: the scheduler's stages
        # and the hand-over through the stream queue.
        assert inner <= ttft_us <= stream["duration_us"] + 5000
        assert spans["slot_wait"]["parent_id"] == stream["span_id"]
        health = w.get_health()
        compiled = health["generator"]["compile"]
        assert compiled["count"] >= 1 and compiled["seconds"] > 0
        body = render_prometheus([health]).decode()
        assert f'tpu_engine_compile_total{{node="tt1"}} {compiled["count"]}' \
            in body
        assert "tpu_engine_compile_seconds_total{" in body
    finally:
        w.stop()
