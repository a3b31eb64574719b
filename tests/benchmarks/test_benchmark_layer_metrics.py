"""Every per-layer reader on a small made-up run object: what it reads,
its arithmetic, and that it returns nothing where there is nothing.

`OLDER_RUN` is a program before PR 25 (one lane, the older spans and
counters only); `RUN` adds a second lane whose program marks its ticks'
phases, counts its compilations and records the request's stages, and what
run.py hands a reader since PR 27: the configuration, the cell and the
traced slice. The second lane's ticks have the first's durations and
widths, so no reading of an older metric moves."""

import copy

import importlib.util
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH, ROOT  # noqa: E402


def _reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


def _span(op, us, start_ts=None, **attrs):
    span = {"op": op, "duration_us": us, "attrs": attrs, "ts": 0.0}
    if start_ts is not None:
        span["start_ts"] = start_ts
    return span


OLDER_RUN = {
    "stats_before": {"worker_1": {"mixed": {"ticks": 10,
                                            "decode_tokens": 100}}},
    "stats_after": {"worker_1": {"mixed": {"ticks": 30,
                                           "decode_tokens": 500}}},
    "spans": {
        "gateway": [_span("route", 200), _span("route", 400),
                    _span("route", 900)],
        "worker_1": [_span("queue_wait", 300), _span("queue_wait", 100),
                     _span("mixed_step", 150000, width=1),
                     _span("mixed_step", 160000, width=1),
                     _span("mixed_step", 170000, width=1),
                     _span("mixed_step", 400000, width=256)],
    },
    "pool_samples": [
        {"t": 1.0, "kv_pool": {"worker_1": {"blocks_total": 100,
                                            "blocks_free": 80}}},
        {"t": 1.5, "kv_pool": {"worker_1": {"blocks_total": 100,
                                            "blocks_free": 35}}},
        {"t": 2.0, "kv_pool": {"worker_1": None}},
    ],
    "trace": {"busy_s": 2.0, "window_s": 2.5,
              "op_seconds": {"%_paged_call f32[32,20,1,64]": 0.3,
                             "%_paged_call f32[32,20,256,64]": 0.5,
                             "%copy bf16[1,1537,16,20,64]": 1.2}},
    "records": [
        {"ok": True, "due": 0.0, "first": 0.2, "events": [[0.2, 1]]},
        {"ok": True, "due": 1.0, "first": 1.5, "events": [[1.5, 1]]},
        {"ok": True, "due": 2.0, "first": 2.3, "events": [[2.3, 1]]},
        {"ok": False, "due": 3.0, "first": None, "events": []},
    ],
    "peaks": None,
    "device": {"memory_peak_bytes": 8364008960},
    "seconds": 10.0,
    # What run.py hands every reader: the merged readers of a kind of
    # kernel take the kernel's name and every size from it (PR 68).
    "config": {"kwargs": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                          "n_kv_heads": 2},
               "serving": {"dtype": "bfloat16"}},
}

EMPTY = {"stats_before": {}, "stats_after": {}, "spans": {},
         "pool_samples": [], "trace": None, "records": [], "peaks": None,
         "device": {}, "seconds": 10.0, "config": {}, "cell": {},
         "slice": None}

# `host_phases` is the trace's host plane as lib/host_phases.py reduces it.
# The slice is [100, 103): of worker_2's four ticks the first two lie wholly
# inside it, the third ends after it and the fourth began before it.
RUN = copy.deepcopy(OLDER_RUN)
RUN["stats_before"]["worker_2"] = {"compile": {"count": 7, "seconds": 1.5}}
RUN["stats_after"]["worker_2"] = {"compile": {"count": 9, "seconds": 2.5}}
RUN["spans"]["worker_2"] = [
    _span("mixed_step", 150000, 100.5, width=1, form_us=4000.0,
          dispatch_us=1000.0, wait_us=140000.0, apply_us=5000.0,
          ctx_tokens=1000),
    _span("mixed_step", 160000, 101.0, width=1, form_us=4000.0,
          dispatch_us=2000.0, wait_us=150000.0, apply_us=4000.0,
          gap_us=9000.0, ctx_tokens=3000),
    _span("mixed_step", 170000, 102.9, width=1, form_us=5000.0,
          dispatch_us=1000.0, wait_us=158000.0, apply_us=6000.0,
          gap_us=12000.0, ctx_tokens=5000),
    _span("mixed_step", 400000, 99.9, width=256, form_us=6000.0,
          dispatch_us=3000.0, wait_us=387000.0, apply_us=4000.0,
          gap_us=10000.0, ctx_tokens=7000),
    _span("prefill", 500000, prompt_len=600, chunks=3, starved_ticks=0,
          starved_us=0),
    _span("prefill", 9000000, prompt_len=900, chunks=4, starved_ticks=17,
          starved_us=8400000),
    _span("slot_wait", 700, parked=False),
    _span("slot_wait", 300, parked=False),
    _span("slot_wait", 90000, parked=True),
    _span("generate_stream", 900000, ttft_us=480000),
    _span("generate_stream", 800000, ttft_us=520000),
    _span("generate_stream", 100000, segment="error"),
]
RUN["host_phases"] = {"idle_host_s": 0.4}
RUN["trace"]["planes"] = 1
RUN["slice"] = {"begin": 100.0, "end": 103.0}
# 4000 context tokens x 2 layers x (K, V) x 2 KV heads x 16 x 2 bytes =
# 1 024 000 bytes: 0.4 s at this made-up memory, against 0.8 s of kernel.
RUN["peaks"] = {"hbm_bytes_per_s": 2.56e6, "bf16_flops_per_s": 1e12}
RUN["cell"] = {"name": "made-up", "chips": 2}
# PR 35: the window began at 98.4 on the spans' clock, so worker_2's
# width-256 tick (99.9 to 100.3) has its midpoint 1.7 s into the window,
# inside the second request's first gap; that request's last event brings
# two tokens at once. Four samples, one across the prefill tick.
RUN["window_start"] = 98.4
RUN["records"][1]["events"] = [[1.5, 1], [1.9, 1], [2.0, 2]]
RUN["records"][2]["events"] = [[2.3, 1], [2.4, 1]]

# The three cells of the uniform step share this run, at a made-up
# configuration of that step (bench_paths.pins finds a cell's pins by this).
CELLS = ("gpt2-large.chat", "mistral-7b-v0.2-8l.docqa", "gpt2-large.batch")
# The hook in tests/conftest.py, which no benchmark PR may edit, wraps a
# `_listed` of this module unless it finds this name. It has had nothing to
# do since PR 68; the PR that deletes it deletes this line (PERF.md, 7).
_listed_in_full = None

WANT = {
    "client.ttft_p50_ms": 300.0,
    "gateway.route_ms": 0.4,
    "lane.queue_wait_ms": 0.1,
    "sched.decode_rows_per_tick": 20.0,
    "sched.prefill_tick_share": 25.0,
    "kv.blocks_peak_share": 65.0,
    "step.decode_ms": 160.0,
    "step.prefill_ms": 400.0,
    "kernel.paged_attn_busy": 40.0,
    "device.idle": 20.0,
    "device.hbm_peak_gb": 8.36400896,
    # PR 25's readers of the request's stages (its three of the tick's
    # phases, `sched.host_gap_ms` and `step.*_device_ms`, went with PR 68)
    "sched.budget_wait_ms": 4200.0,
    "lane.slot_wait_ms": 0.7,
    "lane.ttft_p50_ms": 480.0,
    "step.compiles": 2,
    "device.idle_host": 16.0,
    # PR 27's
    "kernel.paged_attn_roofline": 50.0,
    # PR 35's
    "sched.itl_prefill_share": 25.0,
}
SINCE_PR_25 = {"sched.budget_wait_ms", "lane.slot_wait_ms",
               "lane.ttft_p50_ms", "step.compiles",
               "sched.itl_prefill_share"}


def _pinned_elsewhere():
    """The names the other test_benchmark_layer_metrics_*.py files pin."""
    import glob
    import importlib

    names = set()
    for path in glob.glob(os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "test_benchmark_layer_metrics_*.py")):
        names |= set(getattr(importlib.import_module(
            os.path.basename(path)[:-3]), "WANT", {}))
    return names


def test_every_listed_metric_is_pinned_and_every_pin_is_listed():
    """A merged reader is pinned once a family, here and in the family's
    file, each at that configuration's sizes."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    assert listed == set(WANT) | _pinned_elsewhere()


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _reader(name)(RUN) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_with_nothing_to_read_returns_nothing(name):
    assert _reader(name)(EMPTY) is None


def test_a_trace_in_which_no_op_ran_gives_no_device_number():
    idle = dict(RUN, trace={"busy_s": 0.0, "window_s": 0.0, "op_seconds": {}})
    assert _reader("device.idle")(idle) is None
    assert _reader("kernel.paged_attn_busy")(idle) is None


# -- PR 25's readers (folded in from test_benchmark_layer_metrics_tracing.py) --

@pytest.mark.parametrize("name", sorted(SINCE_PR_25))
def test_reader_returns_nothing_on_a_program_without_the_marks(name):
    """The older run object: the older spans and counters only.
    (`device.idle_host` would go and look for a trace file on the disk; its
    case is test_benchmark_host_phases.py's.)"""
    assert _reader(name)(OLDER_RUN) is None


def test_the_second_lane_moves_no_older_reading():
    for name in sorted(set(WANT) - SINCE_PR_25):
        if name in ("device.idle_host", "kernel.paged_attn_roofline"):
            continue
        assert _reader(name)(OLDER_RUN) == pytest.approx(WANT[name]), name


def test_a_program_that_does_not_count_compilations_reads_nothing_not_zero():
    warm = dict(RUN, stats_after={"worker_2": {"compile": {"count": 7}}},
                stats_before={"worker_2": {"compile": {"count": 7}}})
    assert _reader("step.compiles")(warm) == 0
    older = dict(RUN, stats_after={"worker_1": {"mixed": {}}},
                 stats_before={"worker_1": {"mixed": {}}})
    assert _reader("step.compiles")(older) is None


def test_idle_host_reads_nothing_from_a_trace_without_annotations():
    assert _reader("device.idle_host")(dict(RUN, host_phases={})) is None


# -- PR 27's readers -----------------------------------------------------------

def test_the_roofline_counts_only_ticks_wholly_inside_the_slice():
    reader = _reader("kernel.paged_attn_roofline")
    wide = dict(RUN, slice={"begin": 99.0, "end": 104.0})
    # All four ticks: 16 000 context tokens, four times the work.
    assert reader(wide) == pytest.approx(200.0)
    none_inside = dict(RUN, slice={"begin": 100.6, "end": 100.9})
    assert reader(none_inside) is None


@pytest.mark.parametrize("missing", ["slice", "peaks", "trace"])
def test_the_roofline_reads_nothing_without_slice_peaks_or_trace(missing):
    assert _reader("kernel.paged_attn_roofline")(
        dict(RUN, **{missing: None})) is None


def test_the_roofline_spreads_the_lanes_work_over_the_device_planes():
    """Two lanes on two chips: op_seconds are seconds a plane, so the work
    of both lanes' ticks is divided by the number of planes."""
    two = dict(RUN, trace=dict(RUN["trace"], planes=2))
    assert _reader("kernel.paged_attn_roofline")(two) == pytest.approx(25.0)


def test_the_roofline_of_a_real_shape_on_the_real_peaks_is_far_under_100():
    """A decode tick of the 36-layer MHA configuration: 32 rows at 176
    tokens is 1.04 GB of K and V, 1.27 ms of the v5e's memory; the kernel's
    33.0 ms a tick (PERF.md, PR 26) gives under 4 %."""
    with open(os.path.join(BENCH, "lib", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    with open(os.path.join(BENCH, "configs", "gpt2-large.json")) as f:
        config = json.load(f)
    run = dict(RUN, peaks=peaks, config=config,
               trace={"busy_s": 0.045, "window_s": 0.057, "planes": 1,
                      "op_seconds": {"%_paged_call f32[32,20,1,64]": 0.033}},
               spans={"worker_1": [_span("mixed_step", 50000, 101.0, width=1,
                                         ctx_tokens=32 * 176)]})
    share = _reader("kernel.paged_attn_roofline")(run)
    assert share == pytest.approx(100 * (1038090240 / 819e9) / 0.033)
    assert 3.0 < share < 5.0


# -- PR 35's reader ------------------------------------------------------------

def test_an_event_of_n_tokens_across_a_prefill_tick_counts_n_times():
    """The samples are `itl_p95_ms`'s own: an event that brings n tokens
    after a gap gives n samples, all across the tick or none."""
    reader = _reader("sched.itl_prefill_share")
    run = copy.deepcopy(RUN)
    run["records"][1]["events"] = [[1.5, 1], [1.9, 3], [2.0, 1]]
    assert reader(run) == pytest.approx(100.0 * 3 / 5)
    # Decode ticks' midpoints count for nothing; a window without a prefill
    # tick reads 0, not nothing: every sample was looked at.
    for span in run["spans"]["worker_2"]:
        if span["op"] == "mixed_step":
            span["attrs"]["width"] = 1
    run["spans"]["worker_1"] = []
    assert reader(run) == 0.0
    # A failed request has no samples; no sample at all reads nothing.
    for r in run["records"]:
        r["ok"] = False
    assert reader(run) is None
