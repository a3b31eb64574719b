"""The six merged per-layer readers `moonlight-16b-a3b-7l.solve` is listed on
(`kernel.paged_attn_*` since PR 68, PR 28's `kernel.mla_attn_*`;
`kernel.moe_experts_*`, `moe.*`, whose names PR 28 gave them) on the made-up
run and at the hand-computed values that pinned them: the merged readers at
THIS configuration's sizes (a latent pool, 64 whole experts of three
matrices).

`WANT` is this file's part of the table of pins: test_benchmark_layer_metrics.py
refuses a `per_layer` list that names a metric the table does not pin, a PR
that changes the program may edit no file the benchmark has, and the hook in
tests/conftest.py joins every `test_benchmark_layer_metrics_*.py`'s `WANT`
to that table."""

import importlib.util
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH  # noqa: E402

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL = "moonlight-16b-a3b-7l.solve"


def _reader(metric):
    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


with open(os.path.join(BENCH, "configs", "moonlight-16b-a3b-7l.json")) as f:
    CONFIG = json.load(f)


def _tick(start, ms, **attrs):
    return {"op": "mixed_step", "start_ts": start, "ts": start + ms / 1e3,
            "duration_us": ms * 1e3, "attrs": attrs}


def _stats(rows, assignments, touched):
    return {"worker_1": {"moe": {"rows_by_expert": rows,
                                 "assignments": assignments,
                                 "experts_touched": touched}}}


# A 3 s slice of which the device was busy 2 s: 0.4 s in the latent kernel,
# 0.8 s in the grouped products; four ticks, the first and the last cut by
# the slice's edges and left out whole.
RUN = {
    "trace": {"busy_s": 2.0, "window_s": 3.0, "planes": 1, "op_seconds": {
        "%mla_latent_read bf16[32,16,512]": 0.4,
        "%ragged-dot-none f32[192,2816]": 0.5,
        "%ragged-dot-none f32[192,2048]": 0.25,
        "%ragged-dot-metadata (tuple)": 0.05,
        "%fusion bf16[32,1,2048]": 0.8}},
    "slice": {"begin": 100.0, "end": 103.0},
    "peaks": V5E, "config": CONFIG,
    "spans": {"gateway": [], "worker_1": [
        _tick(99.99, 50, ctx_tokens=10 ** 6, moe_assignments=10 ** 6,
              moe_experts_touched=384),
        _tick(100.5, 50, ctx_tokens=48000, moe_assignments=1152,
              moe_experts_touched=370),
        _tick(101.0, 200, ctx_tokens=50000, moe_assignments=10368,
              moe_experts_touched=384),
        _tick(102.9, 200, ctx_tokens=10 ** 6, moe_assignments=10 ** 6,
              moe_experts_touched=384)]},
    # layer 0 took 10, 0, 5, 5 rows (busiest over mean 2.0), layer 1 evenly
    "stats_before": _stats([[5, 5, 5, 5], [1, 1, 1, 1]], 24, 8),
    "stats_after": _stats([[15, 5, 10, 10], [3, 3, 3, 3]], 52, 15),
}
WANT = {
    "kernel.paged_attn_busy": 20.0,
    "kernel.moe_experts_busy": 40.0,
    # 98 000 context tokens x 7 layers x 1152 B = 0.79 GB: 0.965 ms at the
    # HBM peak (the FLOPs take 0.12 ms), against 0.4 s of kernel
    "kernel.paged_attn_roofline": 100 * (98000 * 7 * 576 * 2 / 819e9) / 0.4,
    # 754 touched experts x 17.3 MB = 13.0 GB: 15.9 ms (the 11 520
    # assignments' FLOPs take 1.0 ms), against 0.8 s of kernel
    "kernel.moe_experts_roofline": 100 * (754 * 17301504 / 819e9) / 0.8,
    "moe.expert_load_imbalance": 1.5,
    "moe.rows_per_touched_expert": 4.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _reader(name)(RUN) == pytest.approx(WANT[name], rel=1e-9)


def test_the_paged_reader_reads_the_kernel_the_configuration_states():
    """A configuration that states a latent reads `mla_latent_read`; the
    same trace under one that states K and V heads looks for `_paged_call`,
    which this program does not make."""
    with open(os.path.join(BENCH, "configs", "gpt2-large.json")) as f:
        other = json.load(f)
    assert _reader("kernel.paged_attn_busy")(dict(RUN, config=other)) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """The parent's program: the paged kernel only, no `moe` group, no
    `moe_*` attrs and no context on its spans; and a run with no trace at
    all."""
    run = dict(RUN, trace=dict(RUN["trace"], op_seconds={
        "%_paged_call f32[32,20,256,64]": 1.0}))
    run["spans"] = {"gateway": [], "worker_1": [_tick(100.5, 50, width=256)]}
    run["stats_before"] = {"worker_1": {"mixed": {"ticks": 1}}}
    run["stats_after"] = {"worker_1": {"mixed": {"ticks": 9}}}
    assert _reader(name)(run) is None
    run["trace"] = run["slice"] = run["peaks"] = None
    assert _reader(name)(run) is None
