"""The per-layer reader PR 43 lists for all six cells
(`front.stream_writer_share`) on a made-up run.

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

NAME = "front.stream_writer_share"


def _compute(run):
    path = os.path.join(BENCH, "layer_metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_front_stream_writer_share", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute(run)


def _stats(by_writer, by_handler, passes=0, blocked=0, **reasons):
    return {"worker_1": {"stream": {
        "writer_events": by_writer, "handler_events": by_handler,
        "writer_passes": passes, "would_block": blocked,
        "handler_by_reason": reasons}}}


# A window of 80,000 token events, of which a reader that stopped reading
# for a while had 1,600 sent by its own handler thread.
RUN = {"stats_before": _stats(10000, 400, 320, 0, unregistered=400),
       "stats_after": _stats(88400, 2000, 2820, 3, unregistered=400,
                             would_block=1600)}
WANT = {NAME: 98.0}


def test_reader_arithmetic():
    assert _compute(RUN) == pytest.approx(WANT[NAME])


@pytest.mark.parametrize("before, after, want", [
    (_stats(0, 0), _stats(0, 640, unregistered=640), 0.0),  # a journal reads
    (_stats(500, 0), _stats(8500, 0), 100.0),
    (_stats(500, 20), _stats(500, 20), None),    # no token event in the window
])
def test_a_window_reads_its_own_events(before, after, want):
    got = _compute({"stats_before": before, "stats_after": after})
    assert got == (pytest.approx(want) if want is not None else None)


def test_two_lanes_are_read_as_one_set_of_events():
    before = {"a": _stats(0, 0)["worker_1"], "b": _stats(100, 100)["worker_1"]}
    after = {"a": _stats(300, 0)["worker_1"],
             "b": _stats(100, 200)["worker_1"]}
    assert _compute({"stats_before": before,
                     "stats_after": after}) == pytest.approx(75.0)


@pytest.mark.parametrize("before, after", [
    # The parent's program: `stats()` without the block.
    ({"worker_1": {"mixed": {"ticks": 1}}},
     {"worker_1": {"mixed": {"ticks": 9}}}),
    ({"worker_1": {}}, {"worker_1": {}}),
    # A lane that came up inside the window.
    ({}, _stats(9, 8)),
    ({}, {}),
])
def test_the_reader_finds_nothing_where_there_is_nothing_to_read(before,
                                                                 after):
    assert _compute({"stats_before": before, "stats_after": after}) is None


def test_the_metric_lists_the_six_cells_by_name():
    """ISSUE 43: appended last, on every cell the benchmark had, by name;
    the layer as the front's other metrics spell it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    names = [m["name"] for m in bench["per_layer"]]
    m = bench["per_layer"][names.index(NAME)]
    assert names[names.index(NAME) - 1] == "step.gc_ms_per_s"
    assert m["workloads"][:6] == cells[:6] == [
        "gpt2-large.chat", "mistral-7b-v0.2-8l.docqa", "gpt2-large.batch",
        "moonlight-16b-a3b-7l.solve", "laguna-s-2.1-5l.repo",
        "olmo-hybrid-7b-12l.digest"]
    assert set(m["workloads"]) <= set(cells)
    assert (m["layer"], m["moves"], m["better"], m["unit"], m["source"]) == (
        "HTTP front and gateway", "tokens_per_s", "higher", "%",
        "program_counter")
    assert m["layer"] in {o["layer"] for o in bench["per_layer"]
                          if o["name"] != NAME}
    assert sorted(m) == ["better", "layer", "moves", "name", "source",
                         "unit", "workloads"]


def test_the_counters_the_reader_reads_are_the_lanes():
    """The names are the program's: `stats()["stream"]` of a lane that
    never streamed already holds every counter of the made-up run."""
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
        stream = gen.stats()["stream"]
        made_up = RUN["stats_after"]["worker_1"]["stream"]
        assert set(made_up) == set(stream)
        assert all(stream[name] == 0 for name in made_up
                   if name != "handler_by_reason")
        assert stream["handler_by_reason"] == {}
    finally:
        gen.stop()


def test_a_writer_marked_stream_carries_the_attrs_pr_42_s_readers_read():
    """The six readers PR 42 brought read the `generate_stream` span's
    sums (the made-up run of test_benchmark_layer_metrics_hostloop.py
    holds their keys). Since PR 43 the front's stream writer marks most
    events, in one pass a tick: the same keys, the deliver time from the
    sends' own clock, the CPU time the writer's share. (ISSUE 43 asked
    for this as an extension of that file; a file the benchmark has is
    not edited by a `perf_opt` PR, so the case lives here.)"""
    sys.path.insert(0, ROOT)
    import test_benchmark_layer_metrics_hostloop as hostloop
    from tpu_engine.utils import tracing

    now = [10.0]
    driven = tracing.StreamClock(wall=lambda: now[0], cpu_ns=lambda: 0)
    driven.woke([1], driven=True)
    now[0] += 0.001
    assert driven.delivered(now[0]) and not driven.delivered()
    driven.add_cpu(250.0)
    stream = hostloop.RUN["spans"]["worker_1"][5]["attrs"]
    assert set(stream) - {"ttft_us"} == set(driven.attrs())
    assert driven.attrs()["deliver_us_sum"] == pytest.approx(1000.0)
    assert driven.attrs()["deliver_cpu_us_sum"] == pytest.approx(250.0)
