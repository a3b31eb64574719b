"""The per-layer reader PR 54 lists for the four cells whose rows own a
recurrent state (`kernel.state_step_live_share`) on a made-up run, and the
counters it reads on a lane's own spans.

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

NAME = "kernel.state_step_live_share"


def _compute(run):
    path = os.path.join(BENCH, "layer_metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_live_step", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute(run)


def _tick(kernel=None, rows=None, slots=None, width=1):
    attrs = {"width": width}
    if rows is not None:
        attrs[f"{kernel}_step_rows"] = rows
    if slots is not None:
        attrs[f"{kernel}_step_slots"] = slots
    return {"op": "mixed_step", "duration_us": 27000, "attrs": attrs,
            "ts": 0.0}


# agents' ticks: a chunk every tick, about 40 of the lane's 64 slots step.
RUN = {
    "spans": {
        "gateway": [{"op": "route", "duration_us": 200, "attrs": {},
                     "ts": 0.0}],
        "worker_1": [_tick("ssd", 40, 64, 256), _tick("ssd", 38, 64, 256),
                     _tick("ssd", 64, 64), _tick("ssd", 0, 64, 256),
                     {"op": "generate_stream", "duration_us": 3000000,
                      "attrs": {"events": 9}, "ts": 0.0}],
    },
}
WANT = {NAME: 100.0 * 142 / 256}


def test_reader_arithmetic():
    assert _compute(RUN) == pytest.approx(WANT[NAME])


@pytest.mark.parametrize("kernel", ["gdn", "kda", "ssd"])
def test_the_reader_takes_the_counters_under_each_kernel_s_name(kernel):
    spans = {"worker_1": [_tick(kernel, 14, 16), _tick(kernel, 13, 16, 256)]}
    assert _compute({"spans": spans}) == pytest.approx(100.0 * 27 / 32)


def test_two_lanes_are_read_as_one_set_of_ticks():
    spans = {"a": [_tick("kda", 128, 128)], "b": [_tick("kda", 69, 128, 256)]}
    assert _compute({"spans": spans}) == pytest.approx(100.0 * 197 / 256)


@pytest.mark.parametrize("spans", [
    {}, {"gateway": [_tick("ssd", 40, 64)]},    # the gateway reads nothing
    # The parent's program notes the rows that step and not the slots.
    {"worker_1": [_tick("ssd", 40), _tick("ssd", 38, width=256)]},
    # A lane with no recurrent state.
    {"worker_1": [_tick(), _tick(width=256)]},
])
def test_the_reader_finds_nothing_where_there_is_nothing(spans):
    assert _compute({"spans": spans}) is None


def test_the_metric_is_listed_for_the_cells_whose_lanes_call_a_state_step():
    """ISSUE 54: listed for the cells whose lanes call a state step, which
    are the cells on the step's merged roofline since PR 68; the layer as
    the kernels' other metrics spell it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    m = by_name[NAME]
    assert m["workloads"][:4] == [
        "olmo-hybrid-7b-12l.digest", "kimi-linear-48b-a3b-5l.reason",
        "falcon-h1-34b-6l.converse", "nemotron-3-super-120b-a12b-11l.agents"]
    for kind in ("busy", "roofline"):
        assert by_name[f"kernel.state_step_{kind}"]["workloads"] == \
            m["workloads"]
    assert (m["layer"], m["moves"], m["better"], m["unit"], m["source"]) == (
        "kernels", "tokens_per_s", "higher", "%", "program_span")
    assert m["layer"] == by_name["kernel.state_step_roofline"]["layer"]
    assert sorted(m) == ["better", "layer", "moves", "name", "source",
                         "unit", "workloads"]


@pytest.mark.parametrize("model", ["olmo_hybrid_small", "kimi_linear_small",
                                   "falcon_h1_small", "nemotron_h_small"])
def test_a_lane_s_spans_carry_the_slots_beside_the_rows_that_step(model):
    """The counters at their source: a lane of each family on the CPU puts
    `<kernel>_step_slots` (its slots) beside `<kernel>_step_rows` on every
    `mixed_step` span, and the reader reads their share."""
    sys.path.insert(0, ROOT)
    import jax

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.runtime.scheduler import ContinuousGenerator
    from tpu_engine.utils.tracing import SpanRecorder

    _ensure_builtin_models_imported()
    spec = create_model(model)
    kernel = spec.config.recurrence
    gen = ContinuousGenerator(spec, params=spec.init(jax.random.PRNGKey(0)),
                              dtype="float32", n_slots=4, kv_block_size=16,
                              prefill_chunk=16, prefix_sharing=False)
    gen.tracer = SpanRecorder(256)
    try:
        futures = [gen.submit(prompt=prompt, max_new_tokens=5)
                   for prompt in ([5, 9, 3, 7, 2], [11, 4, 6], [8, 1])]
        for future in futures:
            future.result(timeout=300)
        spans = [s for s in gen.tracer.snapshot() if s["op"] == "mixed_step"]
    finally:
        gen.stop()
    assert spans
    for span in spans:
        attrs = span["attrs"]
        assert attrs[f"{kernel}_step_slots"] == 4
        assert 0 <= attrs[f"{kernel}_step_rows"] <= 3
    rows = sum(s["attrs"][f"{kernel}_step_rows"] for s in spans)
    assert 0 < rows < 4 * len(spans)
    assert _compute({"spans": {"worker_1": spans}}) == pytest.approx(
        100.0 * rows / (4 * len(spans)))
