"""The seven per-layer readers of two attention classes and a held share
that PR 36 lists for `laguna-s-2.1-5l.repo` (`kernel.swa_attn_*`,
`kernel.full_attn_*`, `moe.held_assignment_share`,
`kv.window_over_full_tokens`, `kv.full_blocks_peak_share`) and the merged
`kernel.moe_experts_*` the cell is listed on since PR 68, on the made-up run
and at the hand-computed values that pinned PR 36's `kernel.moe_held_*`:
the merged pair at THIS configuration's sizes (half of 256 experts held).

`WANT` is this file's part of the table of pins: the hook in
tests/conftest.py joins every `test_benchmark_layer_metrics_*.py`'s `WANT`
to the table test_benchmark_layer_metrics.py holds the `per_layer` list to."""

import importlib.util
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH  # noqa: E402

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL = "laguna-s-2.1-5l.repo"


def _reader(metric):
    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


with open(os.path.join(BENCH, "configs", "laguna-s-2.1-5l.json")) as f:
    CONFIG = json.load(f)


def _tick(start, ms, **attrs):
    return {"op": "mixed_step", "start_ts": start, "ts": start + ms / 1e3,
            "duration_us": ms * 1e3, "attrs": attrs}


def _stats(assignments, held):
    return {"worker_1": {"moe": {"assignments": assignments,
                                 "assignments_held": held,
                                 "experts_touched": 1}}}


def _pool(t, full, window):
    return {"t": t, "kv_pool": {"worker_1": {
        "blocks_total": 17408, "blocks_free": 17408 - full,
        "full_blocks_held": full, "window_blocks_held": window,
        "window_blocks_total": 1568}}}


# A 3 s slice of which the device was busy 2 s: 0.1 s in the window layers'
# calls, 0.2 s in the full layers', 1.0 s in the grouped products; four
# ticks, the first and the last cut by the slice's edges and left out whole.
RUN = {
    "trace": {"busy_s": 2.0, "window_s": 3.0, "planes": 1, "op_seconds": {
        "%swa_window_read bf16[68,8,72,128]": 0.1,
        "%_paged_call bf16[68,8,48,128]": 0.15,
        "%_paged_call bf16[32,8,48,128]": 0.05,
        "%ragged-dot-none f32[2880,2048]": 0.6,
        "%ragged-dot-none f32[2880,3072]": 0.35,
        "%ragged-dot-metadata (tuple)": 0.05,
        "%fusion bf16[68,8,3072]": 0.7}},
    "slice": {"begin": 100.0, "end": 103.0},
    "peaks": V5E, "config": CONFIG,
    "spans": {"gateway": [], "worker_1": [
        _tick(99.99, 50, ctx_tokens_full=10 ** 7, ctx_tokens_window=10 ** 7,
              moe_assignments_held=10 ** 6, moe_experts_touched=512),
        _tick(100.5, 40, ctx_tokens_full=90000, ctx_tokens_window=16000,
              moe_assignments=11520, moe_assignments_held=5800,
              moe_experts_touched=512),
        _tick(101.0, 30, ctx_tokens_full=96000, ctx_tokens_window=16384,
              moe_assignments=1280, moe_assignments_held=640,
              moe_experts_touched=370),
        _tick(102.99, 50, ctx_tokens_full=10 ** 7, ctx_tokens_window=10 ** 7,
              moe_assignments_held=10 ** 6, moe_experts_touched=512)]},
    "stats_before": _stats(1000, 480),
    "stats_after": _stats(9000, 4560),
    # the full layers held most in the second sample
    "pool_samples": [_pool(1.0, 4000, 1000), _pool(1.5, 8704, 1088),
                     _pool(2.0, 8000, 1500)],
}
EXPERT = 3 * 3072 * 1024 * 2           # one expert's three matrices, bf16
WANT = {
    "kernel.swa_attn_busy": 5.0,
    "kernel.full_attn_busy": 10.0,
    "kernel.moe_experts_busy": 50.0,
    # 32 384 tokens x 3 window layers x 4096 B = 0.40 GB: 0.486 ms at the
    # HBM peak (the pairs' FLOPs over 72 heads take 0.018 ms), of 0.1 s
    "kernel.swa_attn_roofline":
        100 * (32384 * 3 * 4096 / 819e9) / 0.1,
    # 186 000 tokens x 2 full layers x 4096 B = 1.52 GB: 1.86 ms, of 0.2 s
    "kernel.full_attn_roofline":
        100 * (186000 * 2 * 4096 / 819e9) / 0.2,
    # 882 touched experts x 18.9 MB = 16.6 GB: 20.3 ms (the 6440 held
    # assignments' FLOPs take 0.6 ms), of 1.0 s
    "kernel.moe_experts_roofline": 100 * (882 * EXPERT / 819e9) / 1.0,
    "moe.held_assignment_share": 51.0,
    "kv.window_over_full_tokens": 0.125,
    "kv.full_blocks_peak_share": 50.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _reader(name)(RUN) == pytest.approx(WANT[name], rel=1e-9)


def test_the_window_call_is_not_counted_with_the_full_layers_calls():
    """`swa_window_read` is not summed into `kernel.full_attn_busy`, and a
    configuration whose layers attend in two classes states no single read:
    the merged paged readers (not listed for this cell) read nothing."""
    assert _reader("kernel.full_attn_busy")(RUN) == pytest.approx(10.0)
    assert _reader("kernel.paged_attn_busy")(RUN) is None
    assert _reader("kernel.paged_attn_roofline")(RUN) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """The parent's program: one pool, no window call, no `moe` group, no
    `ctx_tokens_*` or `moe_assignments_held` on its spans; and a run with
    no trace at all. A reader returns None and does not raise."""
    run = dict(RUN, trace=dict(RUN["trace"], op_seconds={
        "%mla_latent_read bf16[68,128,512]": 1.0}))
    run["spans"] = {"gateway": [], "worker_1": [
        _tick(100.5, 50, ctx_tokens=48000)]}
    run["stats_before"] = {"worker_1": {"mixed": {"ticks": 1}}}
    run["stats_after"] = {"worker_1": {"mixed": {"ticks": 9}}}
    run["pool_samples"] = [{"t": 1.0, "kv_pool": {"worker_1": {
        "blocks_total": 5120, "blocks_free": 100}}}]
    assert _reader(name)(run) is None
    run["trace"] = run["slice"] = run["peaks"] = None
    assert _reader(name)(run) is None
