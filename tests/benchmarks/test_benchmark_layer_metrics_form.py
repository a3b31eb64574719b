"""The two per-layer readers PR 47 lists for the six cells `sched.loop_ms`
names (`sched.form_ms`, `sched.form_transfers_per_tick`) on a made-up run.

`WANT` is this file's part of the table of pins: the hook in
tests/conftest.py joins every `test_benchmark_layer_metrics_*.py`'s `WANT`
to the table test_benchmark_layer_metrics.py holds the `per_layer` list to."""

import importlib.util
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH, ROOT  # noqa: E402

FORM_MS = "sched.form_ms"
PER_TICK = "sched.form_transfers_per_tick"


def _compute(name, run):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute(run)


def _tick(width, form_us=None):
    attrs = {"width": width, "dispatch_us": 550.0, "wait_us": 900.0,
             "apply_us": 100.0}
    if form_us is not None:
        attrs["form_us"] = form_us
    return {"op": "mixed_step", "duration_us": 2000, "attrs": attrs,
            "ts": 0.0}


def _stats(ticks, transfers=None):
    mixed = {"ticks": ticks, "dispatches": ticks, "overlapped_ticks": ticks}
    if transfers is not None:
        mixed["form_transfers"] = transfers
    return {"worker_1": {"mixed": mixed}}


# A window of 2500 ticks that each sent one control block; of the lane's
# five spans one carried a chunk (a wider block: the same one transfer).
RUN = {
    "stats_before": _stats(300, 310),
    "stats_after": _stats(2800, 2810),
    "spans": {
        "gateway": [{"op": "route", "duration_us": 200, "attrs": {},
                     "ts": 0.0}],
        "worker_1": [_tick(1, 420.0), _tick(1, 450.0), _tick(1, 440.0),
                     _tick(1, 460.0), _tick(256, 700.0),
                     {"op": "generate_stream", "duration_us": 3000000,
                      "attrs": {"events": 9}, "ts": 0.0}],
    },
}
WANT = {FORM_MS: 0.45, PER_TICK: 1.0}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _compute(name, RUN) == pytest.approx(WANT[name])


def test_the_median_is_over_every_tick_that_carries_the_attr():
    spans = {"worker_1": [_tick(1, 4000.0), _tick(1, 3900.0), _tick(1),
                          _tick(256, 4200.0)],
             "worker_2": [_tick(1, 100.0), _tick(1, 200.0)]}
    assert _compute(FORM_MS, {"spans": spans}) == pytest.approx(3.9)


@pytest.mark.parametrize("before, after, want", [
    (_stats(0, 0), _stats(400, 6000), 15.0),      # each input alone
    (_stats(100, 100), _stats(500, 1300), 3.0),
    (_stats(100, 100), _stats(100, 100), None),   # no tick in the window
])
def test_a_window_reads_its_own_transfers(before, after, want):
    got = _compute(PER_TICK, {"stats_before": before, "stats_after": after})
    assert got == (pytest.approx(want) if want is not None else None)


def test_two_lanes_are_read_as_one_set_of_ticks():
    before = {"a": _stats(0, 0)["worker_1"], "b": _stats(50, 50)["worker_1"]}
    after = {"a": _stats(300, 300)["worker_1"],
             "b": _stats(150, 350)["worker_1"]}
    assert _compute(PER_TICK, {"stats_before": before,
                               "stats_after": after}) == pytest.approx(1.5)


@pytest.mark.parametrize("before, after", [
    # The parent's program: `stats()["mixed"]` without the counter.
    (_stats(1), _stats(9)),
    ({"worker_1": {}}, {"worker_1": {}}),
    # A lane that came up inside the window.
    ({}, _stats(9, 9)),
    ({}, {}),
])
def test_the_counter_s_reader_finds_nothing_where_there_is_nothing(before,
                                                                   after):
    assert _compute(PER_TICK, {"stats_before": before,
                               "stats_after": after}) is None


@pytest.mark.parametrize("spans", [
    {}, {"gateway": [_tick(1, 400.0)]},       # the gateway forms no tick
    {"worker_1": [_tick(1), _tick(256)]},     # a program without the clock
])
def test_the_span_s_reader_finds_nothing_where_there_is_nothing(spans):
    assert _compute(FORM_MS, {"spans": spans}) is None


def test_the_metrics_list_the_six_cells_sched_loop_ms_lists():
    """ISSUE 47: appended last, in this order, with `sched.loop_ms`'s
    cells; the layer as the scheduler's other metrics spell it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = list(per_layer)
    assert names.index(FORM_MS) > names.index("sched.loop_ms")
    assert names[names.index(FORM_MS) + 1] == PER_TICK
    cells = per_layer["sched.loop_ms"]["workloads"]
    assert len(cells) == 6
    for name, unit, source in ((FORM_MS, "ms", "program_span"),
                               (PER_TICK, "count", "program_counter")):
        m = per_layer[name]
        assert m["workloads"] == cells
        assert (m["layer"], m["moves"], m["better"], m["unit"],
                m["source"]) == ("scheduler tick", "itl_p95_ms", "lower",
                                 unit, source)
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]


def test_the_counter_and_the_attr_are_the_lane_s():
    """The names are the program's: a mixed lane's `stats()["mixed"]`
    holds `form_transfers` beside `ticks`, at most three a tick, and its
    `mixed_step` spans carry `form_us`."""
    sys.path.insert(0, ROOT)
    import jax

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.runtime.scheduler import ContinuousGenerator
    from tpu_engine.utils.tracing import SpanRecorder

    _ensure_builtin_models_imported()
    spec = create_model("gpt2-small-test", max_seq=64)
    gen = ContinuousGenerator(spec, params=spec.init(jax.random.PRNGKey(0)),
                              dtype="float32", n_slots=2, max_seq=64,
                              kv_block_size=16, prefill_chunk=16,
                              mixed_step=True, mixed_token_budget=16)
    gen.tracer = SpanRecorder(256)
    try:
        assert gen.stats()["mixed"]["form_transfers"] == 0
        gen.submit(prompt=[1, 2, 3], max_new_tokens=4).result(timeout=120)
        mixed = gen.stats()["mixed"]
        assert set(RUN["stats_after"]["worker_1"]["mixed"]) <= set(mixed)
        assert 0 < mixed["form_transfers"] <= 3 * mixed["ticks"]
        ticks = [s for s in gen.tracer.snapshot() if s["op"] == "mixed_step"]
        assert ticks and all("form_us" in s["attrs"] for s in ticks)
    finally:
        gen.stop()
