"""The per-layer reader PR 40 lists for all six cells
(`sched.overlap_tick_share`) on a made-up run.

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

NAME = "sched.overlap_tick_share"


def _compute(run):
    path = os.path.join(BENCH, "layer_metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_sched_overlap_tick_share", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute(run)


def _stats(ticks, overlapped, lagged=0):
    return {"worker_1": {"mixed": {"ticks": ticks,
                                   "overlapped_ticks": overlapped,
                                   "lagged_rows": lagged}}}


# A window of 2500 ticks, of which 2400 were enqueued behind a running one.
RUN = {"stats_before": _stats(300, 250), "stats_after": _stats(2800, 2650)}
WANT = {NAME: 96.0}


def test_reader_arithmetic():
    assert _compute(RUN) == pytest.approx(WANT[NAME])


@pytest.mark.parametrize("before, after, want", [
    (_stats(300, 250), _stats(2800, 250), 0.0),       # a lane that drains
    (_stats(0, 0), _stats(40, 39), 97.5),             # from an idle lane
    (_stats(10, 9), _stats(10, 9), None),             # no tick in the window
])
def test_a_window_reads_its_own_ticks(before, after, want):
    got = _compute({"stats_before": before, "stats_after": after})
    assert got == (pytest.approx(want) if want is not None else None)


def test_two_lanes_are_read_as_one_set_of_ticks():
    before = {"a": _stats(0, 0)["worker_1"], "b": _stats(100, 0)["worker_1"]}
    after = {"a": _stats(300, 300)["worker_1"],
             "b": _stats(200, 0)["worker_1"]}
    assert _compute({"stats_before": before,
                     "stats_after": after}) == pytest.approx(75.0)


@pytest.mark.parametrize("before, after", [
    # The parent's program: `mixed` without the counter.
    ({"worker_1": {"mixed": {"ticks": 1}}},
     {"worker_1": {"mixed": {"ticks": 9}}}),
    # A lane with no mixed ticks at all; a lane that came up in the window.
    ({"worker_1": {}}, {"worker_1": {}}),
    ({}, _stats(9, 8)),
])
def test_the_reader_finds_nothing_where_there_is_nothing_to_read(before,
                                                                 after):
    assert _compute({"stats_before": before, "stats_after": after}) is None


def test_the_metric_lists_the_six_cells_by_name():
    """ISSUE 40: on every cell the benchmark had, by name; layer and
    moves as the scheduler tick's other `tokens_per_s` metrics. Found by
    name, not by place: later PRs append."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    m = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert m["workloads"][:6] == cells[:6] == [
        "gpt2-large.chat", "mistral-7b-v0.2-8l.docqa", "gpt2-large.batch",
        "moonlight-16b-a3b-7l.solve", "laguna-s-2.1-5l.repo",
        "olmo-hybrid-7b-12l.digest"]
    assert set(m["workloads"]) <= set(cells)
    assert (m["layer"], m["moves"], m["better"], m["unit"], m["source"]) == (
        "scheduler tick", "tokens_per_s", "higher", "%", "program_counter")
    assert sorted(m) == ["better", "layer", "moves", "name", "source",
                         "unit", "workloads"]


def test_the_counters_the_reader_reads_are_the_schedulers():
    """The names are the program's: `stats()["mixed"]` of a lane that
    never ticked already holds both counters beside `ticks`."""
    sys.path.insert(0, ROOT)
    import jax

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    _ensure_builtin_models_imported()
    spec = create_model("gpt2-small-test", max_seq=64)
    gen = ContinuousGenerator(spec, params=spec.init(jax.random.PRNGKey(0)),
                              dtype="float32", n_slots=2, max_seq=64,
                              kv_block_size=16, prefill_chunk=16,
                              mixed_step=True, mixed_token_budget=16)
    try:
        mixed = gen.stats()["mixed"]
        made_up = RUN["stats_after"]["worker_1"]["mixed"]
        assert set(made_up) <= set(mixed)
        assert all(mixed[name] == 0 for name in made_up)
    finally:
        gen.stop()
