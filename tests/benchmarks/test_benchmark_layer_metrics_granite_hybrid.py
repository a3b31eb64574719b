"""The thirteen merged per-layer readers `granite-4.0-h-small-10l.sessions`
is listed on since PR 68 (`kernel.paged_attn_*`, `kernel.moe_experts_*`,
`kernel.state_step_*`, `kernel.state_chunk_*`, `moe.rows_per_touched_expert`,
`moe.expert_load_imbalance`, `state.rows_peak_share`,
`state.bytes_over_cache_bytes`, `kv.blocks_peak_share`) on a made-up run, at
values computed by hand from THIS configuration's sizes: the cell came when
`per_layer` was full (PR 64) and had no reader of a kernel until the copies
were merged. Nine Mamba-2 layers of 128 heads of (64, 128) at ONE group, one
attention layer of 32 query heads over 8 KV heads of 128, 36 of 72 experts of
three 4096 x 768 matrices held.

`WANT` is this file's part of the table of pins: the hook in
tests/conftest.py joins every `test_benchmark_layer_metrics_*.py`'s `WANT`
to the table test_benchmark_layer_metrics.py holds the `per_layer` list to."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH, reader  # noqa: E402

from lib import roofline, roofline_falcon_h1, roofline_moe_mla  # noqa: E402
from lib.roofline_sizes import sizes  # noqa: E402

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL = "granite-4.0-h-small-10l.sessions"



with open(os.path.join(BENCH, "configs",
                       "granite-4.0-h-small-10l.json")) as f:
    CONFIG = json.load(f)


def _tick(start, ms, **attrs):
    return {"op": "mixed_step", "start_ts": start, "ts": start + ms / 1e3,
            "duration_us": ms * 1e3, "attrs": attrs}


STATE = 128 * 64 * 128 * 4              # a row's state, one layer: 4.19 MB
TOKEN = (128 * (2 * 64 + 1) + 2 * 1 * 128) * 4  # x, dt, B, C in, read out
EXPERT = 3 * 4096 * 768 * 2             # an expert's three matrices: 18.9 MB
ROW = 9 * (STATE + 3 * 8448 * 4)        # a row's states and conv tails
BLOCK = 1 * 16 * 2 * 1024 * 2           # a K/V block: 65,536 B


def _pool(t, blocks, rows):
    return {"t": t, "kv_pool": {"worker_1": {
        "blocks_total": 21504, "blocks_free": 21504 - blocks,
        "kv_bytes_held": blocks * BLOCK, "state_bytes_held": rows * ROW,
        "block_lanes": [1024, 1024]}}}


def _moe(assignments, held, touched, rows):
    return {"assignments": assignments, "assignments_held": held,
            "experts_touched": touched, "rows_by_expert": rows}


# Two expert layers' rows over the window: experts 0-35 held, the others
# another chip's. Layer 0: expert 3 took 150 of 1,900 rows (mean 52.8:
# 2.84); layer 1: even.
ROWS_0 = [50] * 36 + [0] * 36
ROWS_0[3] = 150
ROWS_1 = [50] * 36 + [0] * 36
# A 3 s slice of which the device was busy 2.8 s: 0.7 s in the grouped
# products, 0.5 s in `ssd_step`, 0.2 s in `ssd_chunk`, 0.07 s in the paged
# reads; two ticks wholly inside it (a chunk tick and a decode tick), two cut
# by its edges and left out whole.
RUN = {
    "trace": {"busy_s": 2.8, "window_s": 3.0, "planes": 1, "op_seconds": {
        "%ragged-dot-none f32[3200,1536]": 0.4,
        "%ragged-dot-none f32[3200,4096]": 0.25,
        "%ragged-dot-metadata (tuple)": 0.05,
        "%ssd_step (tuple)": 0.5, "%ssd_chunk (tuple)": 0.2,
        "%_paged_call bf16[64,8,4,128]": 0.04,
        "%_paged_call bf16[66,8,512,128]": 0.03,
        "%fusion f32[384,4096]": 1.0}},
    "slice": {"begin": 100.0, "end": 103.0},
    "peaks": V5E, "config": CONFIG, "cell": {"name": CELL},
    "spans": {"gateway": [], "worker_1": [
        _tick(99.99, 50, ssd_chunk_tokens=10 ** 6, ssd_chunk_rows=100,
              ssd_step_rows=1000, ctx_tokens=10 ** 8, ctx_tokens_full=10 ** 8,
              moe_assignments=10 ** 7, moe_assignments_held=10 ** 6,
              moe_experts_touched=10 ** 4),
        _tick(100.5, 47, width=256, ssd_chunk_tokens=250, ssd_chunk_rows=2,
              ssd_step_rows=50, ssd_step_slots=64, ctx_tokens=120000,
              ctx_tokens_full=120000, moe_assignments=30200,
              moe_assignments_held=15000, moe_experts_touched=360),
        _tick(101.0, 24, width=1, ssd_chunk_tokens=0, ssd_chunk_rows=0,
              ssd_step_rows=60, ssd_step_slots=64, ctx_tokens=130000,
              ctx_tokens_full=130000, moe_assignments=6000,
              moe_assignments_held=3100, moe_experts_touched=350),
        _tick(102.99, 50, ssd_chunk_tokens=10 ** 6, ssd_chunk_rows=100,
              ssd_step_rows=1000, ctx_tokens=10 ** 8, ctx_tokens_full=10 ** 8,
              moe_assignments=10 ** 7, moe_assignments_held=10 ** 6,
              moe_experts_touched=10 ** 4)]},
    "stats_before": {"worker_1": {"moe": _moe(
        1000, 500, 100, [[0] * 72, [0] * 72])}},
    "stats_after": {"worker_1": {
        "moe": _moe(1000 + 36200, 500 + 18100, 100 + 710, [ROWS_0, ROWS_1]),
        "state_pool": {"rows_total": 64, "rows_peak": 48, "rows_held": 40},
        "kv_pool": {"block_lanes": [1024, 1024],
                    "state_bytes_held": 40 * ROW}}},
    # the K/V pool held most in the second sample
    "pool_samples": [_pool(1.0, 4000, 48), _pool(1.5, 5376, 45),
                     _pool(2.0, 5000, 48)],
}
WANT = {
    "kernel.moe_experts_busy": 100 * 0.7 / 2.8,
    "kernel.state_step_busy": 100 * 0.5 / 2.8,
    "kernel.state_chunk_busy": 100 * 0.2 / 2.8,
    "kernel.paged_attn_busy": 100 * 0.07 / 2.8,
    # 710 touched experts x 18.9 MB = 13.4 GB: 16.4 ms at the HBM peak (the
    # 18,100 held pairs' 0.34 TFLOP take 1.7 ms), of 0.7 s
    "kernel.moe_experts_roofline": 100 * (710 * EXPERT / 819e9) / 0.7,
    # 110 rows x 9 layers x (2 x 4.19 MB + 67 KB) = 8.37 GB: 10.2 ms, of 0.5 s
    "kernel.state_step_roofline":
        100 * (9 * 110 * (2 * STATE + TOKEN) / 819e9) / 0.5,
    # 2 rows x 9 layers x 2 x 4.19 MB and 250 tokens x 9 x 67 KB
    "kernel.state_chunk_roofline":
        100 * (9 * (2 * 2 * STATE + 250 * TOKEN) / 819e9) / 0.2,
    # 250,000 tokens x 1 layer x 2 x 8 x 128 x 2 B = 1.02 GB: 1.25 ms; their
    # FLOPs (x 32 heads x 4 x 128) 4.1 GFLOP: 0.02 ms. Of 0.07 s
    "kernel.paged_attn_roofline": 100 * (250000 * 4096 / 819e9) / 0.07,
    "moe.rows_per_touched_expert": 18100 / 710,
    "moe.expert_load_imbalance": (150 * 36 / 1900 + 1.0) / 2,
    "state.rows_peak_share": 75.0,
    # 45 rows' states over 5376 blocks x 65,536 B
    "state.bytes_over_cache_bytes": 45 * ROW / (5376 * BLOCK),
    "kv.blocks_peak_share": 25.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert reader(name)(RUN) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(WANT))
def test_no_share_of_the_made_up_run_passes_its_peak(name):
    if name.endswith("_roofline") or name.endswith("_busy"):
        assert 0.0 < WANT[name] < 100.0


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """A program that states these sizes and runs none of it; and a run with
    no trace. A reader returns None and does not raise."""
    run = dict(RUN, trace=dict(RUN["trace"], op_seconds={
        "%fusion f32[384,4096]": 1.0}))
    run["spans"] = {"gateway": [], "worker_1": [_tick(100.5, 50, width=256)]}
    run["stats_before"] = {"worker_1": {}}
    run["stats_after"] = {"worker_1": {"mixed": {"ticks": 9}}}
    run["pool_samples"] = [{"t": 1.0, "kv_pool": {"worker_1": None}}]
    assert reader(name)(run) is None
    run["trace"] = run["slice"] = run["peaks"] = None
    assert reader(name)(run) is None


# -- the counting ----------------------------------------------------------------

def test_the_figures_pr_64_s_entry_counted_by_hand():
    """PERF.md, 'Open since PR 64' (a): an expert is 3 x 4096 x 768 x 2 B; a
    state 128 x 64 x 128 float32 a row and mamba layer, nine of them; a
    token of K and V 2 x 8 x 128 x 2 B in the one attention layer; ONE group
    of B and C (where the nearest sibling has eight)."""
    size = sizes(CONFIG)
    assert roofline_moe_mla.expert_bytes(1, 4096, 768, 2) == EXPERT \
        == 18874368
    assert roofline_falcon_h1.state_bytes(size["recurrence"]) == STATE \
        == 4194304
    assert roofline.attention_bytes(1, 1, 8, 128, 2) == 4096
    assert roofline_falcon_h1.recurrence_bytes(
        64, 64, size["recurrence"]) == 64 * 9 * (2 * STATE + TOKEN)
    assert TOKEN == (128 * 129 + 256) * 4
    # 64 rows' steps: 4.87 GB, 5.9 ms at the HBM peak
    assert 5.9e-3 < 64 * 9 * (2 * STATE + TOKEN) / 819e9 < 6.0e-3
