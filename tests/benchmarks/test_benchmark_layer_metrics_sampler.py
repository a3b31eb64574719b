"""The two per-layer readers PR 38 lists for all five cells
(`step.sampler_sort_busy`, `step.sampler_sort_tick_share`) on a made-up
run.

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


def _reader(metric):
    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


def _stats(ticks, greedy, plain, filtered):
    return {"worker_1": {"mixed": {
        "ticks": ticks, "sample_greedy_ticks": greedy,
        "sample_plain_ticks": plain, "sample_filtered_ticks": filtered}}}


# A 3 s slice of which the device was busy 2 s: 0.44 s in the sampler's
# sort, 0.01 s in the expert layer's sort of a tick's pairs; a window of
# 1000 ticks of which 150 took the filtered body.
RUN = {
    "trace": {"busy_s": 2.0, "window_s": 3.0, "planes": 1, "op_seconds": {
        "%sort (tuple)": 0.44,
        "%sort.3 s32[3264]": 0.01,
        "%broadcast_divide_fusion (tuple)": 0.05,
        "%ragged-dot-none f32[1728,2816]": 0.45,
        "%fusion bf16[32,1,2048]": 1.05}},
    "stats_before": _stats(200, 150, 30, 20),
    "stats_after": _stats(1200, 900, 130, 170),
}
WANT = {
    "step.sampler_sort_busy": 22.5,
    "step.sampler_sort_tick_share": 15.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _reader(name)(RUN) == pytest.approx(WANT[name])


def test_a_trace_without_a_sort_reads_zero_not_nothing():
    """What every cell reads once no tick of its traffic filters: the
    record that the sort is gone, so the metric stays on the line."""
    run = dict(RUN, trace=dict(RUN["trace"], op_seconds={
        "%_paged_call f32[32,20,1,64]": 1.0, "%fusion bf16[32,1280]": 1.0}))
    assert _reader("step.sampler_sort_busy")(run) == 0.0


def test_a_window_without_a_filtered_tick_reads_zero_not_nothing():
    run = dict(RUN, stats_after=_stats(1200, 1100, 80, 20))
    assert _reader("step.sampler_sort_tick_share")(run) == 0.0


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_finds_nothing_where_there_is_nothing_to_read(name):
    """The parent's program counts no bodies (`mixed` without the three
    counters) and a lane may have no mixed ticks at all; a run may have no
    trace, or one in which no operation ran."""
    run = dict(RUN, trace=None,
               stats_before={"worker_1": {"mixed": {"ticks": 1}}},
               stats_after={"worker_1": {"mixed": {"ticks": 9}}})
    assert _reader(name)(run) is None
    run["trace"] = dict(RUN["trace"], busy_s=0.0, op_seconds={})
    run["stats_before"] = run["stats_after"] = {"worker_1": {}}
    assert _reader(name)(run) is None
    run["stats_before"] = run["stats_after"] = _stats(7, 7, 0, 0)
    assert _reader(name)(run) is None    # no tick in the window


def test_both_metrics_list_the_five_cells_by_name():
    """ISSUE 38: on every cell the benchmark had, by name (the set of
    metrics WITHOUT a list is pinned elsewhere, and a later cell joins
    by being appended here); layer and moves as the step function's
    other metrics. Found by name, not by place: later PRs append."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name, source in zip(sorted(WANT),
                            ("device_trace", "program_counter")):
        m = listed[name]
        assert m["workloads"][:5] == cells[:5]
        assert set(m["workloads"]) <= set(cells)
        assert (m["layer"], m["moves"], m["better"], m["unit"],
                m["source"]) == ("step function", "tokens_per_s", "lower",
                                 "%", source)


def test_the_counters_the_reader_reads_are_the_schedulers():
    """The names are the program's: `stats()["mixed"]` of a lane that
    never ticked already holds the three counters beside `ticks`."""
    sys.path.insert(0, ROOT)
    from tpu_engine.runtime.generator import SAMPLER_BODIES

    assert SAMPLER_BODIES == ("greedy", "plain", "filtered")
    made_up = RUN["stats_after"]["worker_1"]["mixed"]
    assert set(made_up) == {"ticks"} | {
        f"sample_{body}_ticks" for body in SAMPLER_BODIES}
