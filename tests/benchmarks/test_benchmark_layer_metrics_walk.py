"""The two per-layer readers PR 48 lists for the gpt2-large cells
(`kernel.paged_walk_warm_share`, `kernel.paged_walk_fetch_over_ctx`) on a
made-up run, and the counters they read on a lane's own spans.

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

WARM = "kernel.paged_walk_warm_share"
FETCH = "kernel.paged_walk_fetch_over_ctx"


def _compute(name, run):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute(run)


def _tick(width, ctx_tokens, walk=None):
    attrs = {"width": width, "ctx_tokens": ctx_tokens}
    if walk is not None:
        (attrs["walk_live_tiles"], attrs["walk_warm_tiles"],
         attrs["walk_tokens_fetched"]) = walk
    return {"op": "mixed_step", "duration_us": 6800, "attrs": attrs,
            "ts": 0.0}


# Three decode ticks of 32 rows (31 behind a live row) and a chunk tick
# whose two tall tiles walk the chunk's context twice.
RUN = {
    "spans": {
        "gateway": [{"op": "route", "duration_us": 200, "attrs": {},
                     "ts": 0.0}],
        "worker_1": [_tick(1, 5600, (32, 31, 5840)),
                     _tick(1, 5632, (32, 31, 5872)),
                     _tick(1, 5664, (32, 31, 5888)),
                     _tick(256, 5904, (33, 32, 6400)),
                     {"op": "generate_stream", "duration_us": 3000000,
                      "attrs": {"events": 9}, "ts": 0.0}],
    },
}
WANT = {WARM: 100.0 * 125 / 129, FETCH: 24000 / 22800}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _compute(name, RUN) == pytest.approx(WANT[name])


def test_a_lane_with_one_live_row_is_cold_and_reads_zero_not_nothing():
    spans = {"worker_1": [_tick(1, 300, (1, 0, 304)),
                          _tick(1, 301, (1, 0, 304))]}
    assert _compute(WARM, {"spans": spans}) == 0.0
    assert _compute(FETCH, {"spans": spans}) == pytest.approx(608 / 601)


def test_two_lanes_are_read_as_one_set_of_tiles():
    spans = {"a": [_tick(1, 1000, (8, 7, 1040))],
             "b": [_tick(1, 500, (2, 0, 560))]}
    assert _compute(WARM, {"spans": spans}) == pytest.approx(70.0)
    assert _compute(FETCH, {"spans": spans}) == pytest.approx(1600 / 1500)


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("spans", [
    {}, {"gateway": [_tick(1, 100, (4, 3, 128))]},  # the gateway reads nothing
    # The parent's program, and a family whose step is its own: the span
    # says what the rows hold and nothing of the walk.
    {"worker_1": [_tick(1, 5600), _tick(256, 5904)]},
    # A tick that fed no row.
    {"worker_1": [_tick(1, 0, (0, 0, 0))]},
])
def test_the_readers_find_nothing_where_there_is_nothing(name, spans):
    assert _compute(name, {"spans": spans}) is None


def test_the_metrics_are_listed_last_for_the_two_gpt2_large_cells():
    """ISSUE 48: appended, in this order, for the cells whose step reads
    every row by one call a layer; the layer as the kernels' other
    metrics spell it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = list(per_layer)
    assert names[names.index(WARM) - 1] == "sched.form_transfers_per_tick"
    assert names[names.index(WARM) + 1] == FETCH
    for name, unit, better in ((WARM, "%", "higher"),
                               (FETCH, "ratio", "lower")):
        m = per_layer[name]
        assert m["workloads"] == ["gpt2-large.chat", "gpt2-large.batch"]
        # the cells of the uniform step among the paged read's roofline's
        assert m["workloads"] == per_layer["kernel.paged_attn_roofline"][
            "workloads"][:2]
        assert (m["layer"], m["moves"], m["better"], m["unit"],
                m["source"]) == ("kernels", "tokens_per_s", better, unit,
                                 "program_span")
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]


def test_a_lane_s_spans_carry_the_walk_of_each_tick():
    """The counters at their source: a mixed lane on the CPU puts
    `walk_live_tiles`, `walk_warm_tiles` and `walk_tokens_fetched` beside
    `ctx_tokens` on every `mixed_step` span, by the kernel's own rules
    (`ops.paged_attention.walk_counts`): whole blocks of 16 columns, never
    fewer tokens than the rows hold, a warm tile only behind a live one."""
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
                              mixed_step=True, mixed_token_budget=16)
    gen.tracer = SpanRecorder(256)
    try:
        futures = [gen.submit(prompt=prompt, max_new_tokens=6)
                   for prompt in ([5, 9, 3, 7, 2], [11, 4, 6], [8, 1])]
        for future in futures:
            future.result(timeout=120)
        spans = [s for s in gen.tracer.snapshot() if s["op"] == "mixed_step"]
    finally:
        gen.stop()
    assert spans
    for span in spans:
        attrs = span["attrs"]
        live, warm, fetched = (attrs["walk_live_tiles"],
                               attrs["walk_warm_tiles"],
                               attrs["walk_tokens_fetched"])
        assert 0 <= warm <= max(live - 1, 0) and live <= 4
        assert fetched % 16 == 0
        assert attrs["ctx_tokens"] <= fetched < attrs["ctx_tokens"] + 16 * max(
            live, 1)
    assert any(s["attrs"]["walk_warm_tiles"] for s in spans)
