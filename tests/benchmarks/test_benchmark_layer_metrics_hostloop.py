"""The nine per-layer readers PR 42 lists (the loop between two ticks, the
scheduler thread's time off the CPU, the stream handlers' delivery, the
collector) on a made-up run.

`WANT` is this file's part of the table of pins: the hook in
tests/conftest.py joins every `test_benchmark_layer_metrics_*.py`'s `WANT`
to the table test_benchmark_layer_metrics.py holds the `per_layer` list to."""

import copy
import importlib.util
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH, ROOT  # noqa: E402

SIX = ["gpt2-large.chat", "mistral-7b-v0.2-8l.docqa", "gpt2-large.batch",
       "moonlight-16b-a3b-7l.solve", "laguna-s-2.1-5l.repo",
       "olmo-hybrid-7b-12l.digest"]
# name: (unit, source, layer, moves, cells), in the order they were appended
LISTED = {
    "sched.decode_period_ms": ("ms", "program_span", "scheduler tick",
                               "tokens_per_s",
                               ["gpt2-large.batch", "gpt2-large.chat"]),
    "sched.loop_ms": ("ms", "program_span", "scheduler tick",
                      "tokens_per_s", SIX),
    "sched.host_offcpu_ms": ("ms", "program_span", "scheduler tick",
                             "tokens_per_s", SIX),
    "front.stream_cpu_ms_per_tick": ("ms", "program_span",
                                     "HTTP front and gateway",
                                     "tokens_per_s", SIX),
    "lane.stream_wake_ms": ("ms", "program_span", "lane and admission",
                            "itl_p95_ms", SIX),
    "front.stream_deliver_ms": ("ms", "program_span",
                                "HTTP front and gateway", "itl_p95_ms", SIX),
    "device.idle_loop": ("%", "device_trace", "device", "tokens_per_s", SIX),
    "device.idle_stream": ("%", "device_trace", "device", "tokens_per_s",
                           SIX),
    "step.gc_ms_per_s": ("ms/s", "program_counter", "step function",
                         "itl_p95_ms", SIX),
}


def _reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


def _span(op, us, **attrs):
    return {"op": op, "duration_us": us, "attrs": attrs, "ts": 0.0}


def _tick(width, period=None, loop=None, offcpu=(None, None, None)):
    """`offcpu`: (form, apply, loop), None where the program did not read
    the thread's CPU clock (it does on one loop iteration in eight)."""
    attrs = {"width": width, "form_us": 4000.0, "dispatch_us": 600.0,
             "wait_us": 40.0, "apply_us": 1000.0, "gc_us": 0,
             "compile_us": 0}
    if loop is not None:
        attrs.update(loop_us=loop, loop_exports_us=50.0,
                     loop_capacity_us=100.0, loop_admit_us=150.0,
                     loop_expire_us=200.0)
    for key, us in zip(("form_offcpu_us", "apply_offcpu_us",
                        "loop_offcpu_us"), offcpu):
        if us is not None:
            attrs[key] = us
    if period is not None:
        attrs["period_us"] = period
    return _span("mixed_step", 5640, **attrs)


def _stream(events, wake, deliver, cpu, **more):
    return _span("generate_stream", 3000000, events=events,
                 wake_us_sum=wake, wake_us_max=900.0, deliver_us_sum=deliver,
                 deliver_cpu_us_sum=cpu, **more)


def _stats(ticks, gc_s):
    return {"mixed": {"ticks": ticks, "overlapped_ticks": ticks},
            "gc": {"count": int(gc_s * 100), "seconds": gc_s, "gen2": 1}}


# A window of 50 s and 2500 ticks. Of worker_1's five ticks the first
# followed an idle lane (no loop, no period) and the last carried a chunk.
RUN = {
    "stats_before": {"worker_1": _stats(300, 1.0)},
    "stats_after": {"worker_1": _stats(2800, 1.25)},
    "spans": {
        "gateway": [_span("route", 200)],
        "worker_1": [
            _tick(1, offcpu=(100.0, None, None)),
            _tick(1, 12000.0, 6000.0, (300.0, 200.0, 4000.0)),
            _tick(1, 13000.0, 7000.0, (None, 400.0, None)),
            _tick(1, 12500.0, 6500.0),
            _tick(256, 190000.0, 8000.0, (200.0, None, 3000.0)),
            _stream(2500, 500000.0, 4000000.0, 3000000.0, ttft_us=480000),
            _stream(1500, 300000.0, 3000000.0, 2000000.0, ttft_us=520000),
            _stream(1000, 200000.0, 1500000.0, 1000000.0,
                    segment="exported"),
            _span("generate_stream", 100000, segment="error"),
        ],
    },
    "trace": {"busy_s": 1.5, "window_s": 2.5, "planes": 1, "op_seconds": {}},
    "host_phases": {"window_s": 2.5, "idle_s": 1.0, "idle_host_s": 0.9,
                    "idle_by_phase": {"tick.form": 0.3, "tick.apply": 0.1,
                                      "tick.dispatch": 0.05,
                                      "tick.wait": 0.05, "loop.admit": 0.5}},
    "host_threads": {"planes": 1, "window_s": 2.5, "idle_s": 1.0,
                     "by_name": {"stream.deliver": {
                         "events": 8000, "sum_s": 2.0, "union_s": 1.2,
                         "idle_s": 0.4}}},
    "seconds": 50.0,
}
EMPTY = {"stats_before": {}, "stats_after": {}, "spans": {}, "trace": None,
         "seconds": 50.0}
# The parent's program under this PR's benchmark files: the spans and
# counters of PR 40, a trace with `loop.admit` and no `stream.deliver`.
PARENT = copy.deepcopy(RUN)
for _stat in (*PARENT["stats_before"].values(),
              *PARENT["stats_after"].values()):
    del _stat["gc"]
PARENT["spans"]["worker_1"] = [
    _span("mixed_step", 5640, width=1, form_us=4000.0, dispatch_us=600.0,
          wait_us=40.0, apply_us=1000.0, gap_us=0.0, overlapped=1),
    _span("generate_stream", 3000000, ttft_us=480000),
    _span("generate_stream", 100000, segment="error"),
]
PARENT["host_threads"] = {"planes": 1, "window_s": 2.5, "idle_s": 1.0,
                          "by_name": {}}

WANT = {
    "sched.decode_period_ms": 12.5,       # of 12, 13, 12.5: width 1 alone
    "sched.loop_ms": 6.5,                 # of 6, 7, 6.5, 8
    "sched.host_offcpu_ms": 4.0,          # means: form 0.2, apply 0.3, loop 3.5
    "front.stream_cpu_ms_per_tick": 2.4,  # 6 000 000 us over 2500 ticks
    "lane.stream_wake_ms": 0.2,           # 1 000 000 us over 5000 events
    "front.stream_deliver_ms": 1.7,       # 8 500 000 us over 5000 events
    "device.idle_loop": 20.0,             # 0.5 of 2.5 s
    "device.idle_stream": 16.0,           # 0.4 of 2.5 s
    "step.gc_ms_per_s": 5.0,              # 0.25 s over 50 s
}


def test_the_nine_are_pinned_here():
    assert sorted(WANT) == sorted(LISTED)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _reader(name)(RUN) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_with_nothing_to_read_returns_nothing(name):
    assert _reader(name)(EMPTY) is None


@pytest.mark.parametrize("name", sorted(set(WANT) - {"device.idle_loop"}))
def test_the_parents_program_reads_nothing_and_raises_nothing(name):
    assert _reader(name)(PARENT) is None


def test_the_parents_trace_has_held_the_loop_s_annotation_since_pr_25():
    """`loop.admit` is older than this PR, and lib/host_phases.py has split
    idle by it all along: the one of the nine a parent's traced run reads."""
    assert _reader("device.idle_loop")(PARENT) == pytest.approx(20.0)
    older = dict(PARENT, host_phases={"window_s": 2.5, "idle_host_s": 0.9})
    assert _reader("device.idle_loop")(older) is None
    assert _reader("device.idle_loop")(dict(PARENT, host_phases={})) is None
    stale = dict(PARENT, trace=dict(PARENT["trace"], window_s=2.6))
    assert _reader("device.idle_loop")(stale) is None


def test_idle_in_the_loop_is_a_part_of_idle_on_the_host():
    loop = _reader("device.idle_loop")(RUN)
    host = 100 * RUN["host_phases"]["idle_host_s"] / RUN["trace"]["window_s"]
    idle = 100 * (1 - RUN["trace"]["busy_s"] / RUN["trace"]["window_s"])
    assert loop <= host <= idle
    assert _reader("device.idle_stream")(RUN) <= idle


def test_the_period_is_read_from_decode_only_ticks_the_loop_from_all():
    run = copy.deepcopy(RUN)
    run["spans"]["worker_1"] = [_tick(256, 190000.0, 8000.0,
                                      (0.0, 0.0, 1000.0))]
    assert _reader("sched.decode_period_ms")(run) is None
    assert _reader("sched.loop_ms")(run) == pytest.approx(8.0)
    assert _reader("sched.host_offcpu_ms")(run) == pytest.approx(1.0)
    # A tick that followed an idle lane carries neither; a window in which
    # one of the three phases was never read has no sum.
    run["spans"]["worker_1"] = [_tick(1, offcpu=(100.0, 100.0, None))]
    for name in ("sched.decode_period_ms", "sched.loop_ms",
                 "sched.host_offcpu_ms"):
        assert _reader(name)(run) is None


def test_no_collection_reads_zero_and_two_lanes_share_one_collector():
    quiet = dict(RUN, stats_before={"worker_1": _stats(300, 1.0)},
                 stats_after={"worker_1": _stats(2800, 1.0)})
    assert _reader("step.gc_ms_per_s")(quiet) == 0.0
    two = dict(RUN,
               stats_before={"a": _stats(0, 1.0), "b": _stats(100, 1.0)},
               stats_after={"a": _stats(1500, 1.25), "b": _stats(1100, 1.25)})
    # One process, one collector: not the sum. The ticks of both lanes are
    # one set of ticks for the handlers' CPU time.
    assert _reader("step.gc_ms_per_s")(two) == pytest.approx(5.0)
    assert _reader("front.stream_cpu_ms_per_tick")(two) == pytest.approx(2.4)


def test_streams_without_a_token_event_count_for_nothing():
    run = copy.deepcopy(RUN)
    run["spans"]["worker_1"] = [s for s in run["spans"]["worker_1"]
                                if "events" not in s["attrs"]]
    for name in ("front.stream_cpu_ms_per_tick", "lane.stream_wake_ms",
                 "front.stream_deliver_ms"):
        assert _reader(name)(run) is None
    # No tick in the window: no denominator.
    still = dict(RUN, stats_after=RUN["stats_before"])
    assert _reader("front.stream_cpu_ms_per_tick")(still) is None


def test_the_nine_are_listed_as_the_issue_lists_them():
    """ISSUE 42: appended at the end, in this order, each with its cells by
    name, `layer` spelled as the entries before spell it, all `lower`."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:6] == SIX
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("sched.decode_period_ms")
    assert names[first:first + 9] == list(LISTED)
    assert names[first - 1] == "sched.overlap_tick_share"
    older_layers = {m["layer"] for m in bench["per_layer"][:first]}
    for name, (unit, source, layer, moves, listed) in LISTED.items():
        m = by_name[name]
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"], name
        assert (m["unit"], m["source"], m["layer"], m["moves"],
                m["better"]) == (unit, source, layer, moves, "lower"), name
        assert m["workloads"][:len(listed)] == listed, name
        assert set(m["workloads"]) <= set(cells)
        assert layer in older_layers


def test_the_attrs_the_readers_read_are_the_programs():
    """The names are the program's: a tick clock and a stream clock on
    clocks moved by hand, and the collector's counter, give every key a
    reader asks for."""
    sys.path.insert(0, ROOT)
    from tpu_engine.utils import tracing

    now = [10.0]
    clock = tracing.TickClock(tracing.CompileCounter(), wall=lambda: now[0],
                              cpu_ns=lambda: 0, cpu_every=1)
    attrs = None
    for _ in range(2):
        clock.begin()
        clock.dispatch(width=1, rows=1, ctx_tokens=1)
        clock.wait()
        clock.apply()
        attrs = clock.end(True, "n")[2]
        clock.admit()
        for part in tracing.LOOP_PARTS:
            clock.loop_part(part)
            now[0] += 0.001
    clock.idle()
    made_up = RUN["spans"]["worker_1"][1]["attrs"]
    assert set(made_up) - {"width"} <= set(attrs)
    assert attrs["loop_us"] == pytest.approx(4000.0)
    way = tracing.StreamClock(wall=lambda: now[0], cpu_ns=lambda: 0)
    way.woke([1])
    way.delivered()
    stream = RUN["spans"]["worker_1"][5]["attrs"]
    assert set(stream) - {"ttft_us"} == set(way.attrs())
    assert set(RUN["stats_after"]["worker_1"]["gc"]) == set(
        tracing.GcCounter().snapshot())
