"""Every per-layer reader on a small made-up run object: what it reads,
its arithmetic, and that it returns nothing where there is nothing."""

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


def _span(op, us, **attrs):
    return {"op": op, "duration_us": us, "attrs": attrs, "ts": 0.0}


RUN = {
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
}

EMPTY = {"stats_before": {}, "stats_after": {}, "spans": {},
         "pool_samples": [], "trace": None, "records": [], "peaks": None,
         "device": {}, "seconds": 10.0}

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
}


def _listed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]]


def test_every_listed_metric_is_pinned_here():
    assert sorted(_listed()) == sorted(WANT)


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
