"""The per-layer reader PR 52 lists for the three cells of the uniform step
(`step.pool_write_slots_over_tokens`) on a made-up run, and the counters it
reads on a lane's own spans.

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

NAME = "step.pool_write_slots_over_tokens"


def _compute(run):
    path = os.path.join(BENCH, "layer_metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_pool_write", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute(run)


def _tick(width, write=None):
    attrs = {"width": width}
    if write is not None:
        attrs["write_slots"], attrs["write_tokens"] = write
    return {"op": "mixed_step", "duration_us": 6800, "attrs": attrs,
            "ts": 0.0}


# Three decode ticks of 32 rows, which the reader leaves out, and two chunk
# ticks of the list's 288 indices: a full budget and one short prompt.
RUN = {
    "spans": {
        "gateway": [{"op": "route", "duration_us": 200, "attrs": {},
                     "ts": 0.0}],
        "worker_1": [_tick(1, (32, 32)), _tick(1, (32, 31)),
                     _tick(256, (288, 280)), _tick(1, (32, 32)),
                     _tick(256, (288, 152)),
                     {"op": "generate_stream", "duration_us": 3000000,
                      "attrs": {"events": 9}, "ts": 0.0}],
    },
}
WANT = {NAME: 576 / 432}


def test_reader_arithmetic():
    assert _compute(RUN) == pytest.approx(WANT[NAME])


def test_the_parent_s_write_over_every_slot_reads_the_slots_over_the_tokens():
    spans = {"worker_1": [_tick(256, (8192, 280)), _tick(256, (8192, 296))]}
    assert _compute({"spans": spans}) == pytest.approx(16384 / 576)


def test_two_lanes_are_read_as_one_set_of_ticks():
    spans = {"a": [_tick(256, (288, 260))], "b": [_tick(256, (276, 100))]}
    assert _compute({"spans": spans}) == pytest.approx(564 / 360)


@pytest.mark.parametrize("spans", [
    {}, {"gateway": [_tick(256, (288, 280))]},  # the gateway reads nothing
    # The parent's program, and a family whose step is its own: the span
    # says nothing of the write.
    {"worker_1": [_tick(1), _tick(256)]},
    # A window of decode ticks alone.
    {"worker_1": [_tick(1, (32, 32)), _tick(1, (32, 30))]},
    # A chunk tick that fed no row.
    {"worker_1": [_tick(256, (288, 0))]},
])
def test_the_reader_finds_nothing_where_there_is_nothing(spans):
    assert _compute({"spans": spans}) is None


def test_the_metric_is_listed_last_for_the_three_cells_of_the_uniform_step():
    """ISSUE 52: appended for the cells whose lanes run
    `transformer_step_rows_ragged`; the layer as the step's other metrics
    spell it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) > names.index("kernel.paged_walk_fetch_over_ctx")
    m = bench["per_layer"][names.index(NAME)]
    assert m["workloads"] == ["gpt2-large.chat", "mistral-7b-v0.2-8l.docqa",
                              "gpt2-large.batch"]
    # the cells of the uniform step, the first on the block pool's list
    assert m["workloads"] == bench["per_layer"][names.index(
        "kv.blocks_peak_share")]["workloads"][:3]
    assert (m["layer"], m["moves"], m["better"], m["unit"], m["source"]) == (
        "step function", "tokens_per_s", "lower", "ratio", "program_span")
    assert m["layer"] == bench["per_layer"][names.index(
        "step.prefill_ms")]["layer"]
    assert sorted(m) == ["better", "layer", "moves", "name", "source",
                         "unit", "workloads"]


@pytest.mark.parametrize("quant", ["", "int8"])
def test_a_lane_s_spans_carry_the_write_of_each_tick(quant):
    """The counters at their source: a uniform lane on the CPU puts
    `write_slots` and `write_tokens` on every `mixed_step` span, and the
    reader reads the chunk ticks' (the list of budget + rows indices over
    what the ticks held), the int8 pool's lane as the plain one's."""
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
                              dtype="float32", n_slots=4, max_seq=64,
                              kv_block_size=16, prefill_chunk=16,
                              mixed_token_budget=16, kv_quantize=quant)
    gen.tracer = SpanRecorder(256)
    try:
        futures = [gen.submit(prompt=prompt, max_new_tokens=6)
                   for prompt in ([5, 9, 3, 7, 2], [11, 4, 6], [8, 1])]
        for future in futures:
            future.result(timeout=120)
        spans = [s for s in gen.tracer.snapshot() if s["op"] == "mixed_step"]
    finally:
        gen.stop()
    chunk = [s["attrs"] for s in spans if s["attrs"]["width"] > 1]
    assert chunk and len(chunk) < len(spans)
    for span in spans:
        attrs = span["attrs"]
        assert attrs["write_slots"] == (20 if attrs["width"] > 1 else 4)
        assert 0 < attrs["write_tokens"] <= attrs["write_slots"]
    assert sum(a["write_tokens"] for a in chunk) >= 10
    assert _compute({"spans": {"worker_1": spans}}) == pytest.approx(
        20 * len(chunk) / sum(a["write_tokens"] for a in chunk))
