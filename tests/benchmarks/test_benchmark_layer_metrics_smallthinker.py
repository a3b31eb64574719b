"""The per-layer readers of `smallthinker-21b-a3b-8l.history` on a made-up
run, at values computed by hand from THIS configuration's sizes: six window
layers and two full layers of 28 query heads over 4 KV heads of 128 (2,048 B
a token and layer), 64 experts of three 2560 x 768 matrices, every one
held. Two readers are the PR's own (`kv.window_blocks_peak_share`,
`kv.window_bound_row_share`); the others are accepted readers whose lists
the cell was appended to: the two attention classes' (PR 36), the merged
readers of the grouped product and of what the router counted (PR 68), and
`moe.route_sort_busy` (PR 50).

`WANT` is this file's part of the table of pins: the hook in
tests/conftest.py joins every `test_benchmark_layer_metrics_*.py`'s `WANT`
to the table test_benchmark_layer_metrics.py holds the `per_layer` list to."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import (  # noqa: E402
    BENCH,
    listed as metrics_listed,
    load_benchmark,
    reader,
)

from lib import roofline, roofline_laguna  # noqa: E402
from lib.roofline_sizes import sizes  # noqa: E402

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL = "smallthinker-21b-a3b-8l.history"
NEW = ["kv.window_blocks_peak_share", "kv.window_bound_row_share"]
with open(os.path.join(BENCH, "configs", "smallthinker-21b-a3b-8l.json")) as f:
    CONFIG = json.load(f)


def _tick(start, ms, **attrs):
    return {"op": "mixed_step", "start_ts": start, "ts": start + ms / 1e3,
            "duration_us": ms * 1e3, "attrs": attrs}


def _pool(t, full, window):
    return {"t": t, "kv_pool": {"worker_1": {
        "blocks_total": 27136, "blocks_free": 27136 - full,
        "full_blocks_held": full, "window_blocks_held": window,
        "window_blocks_total": 8736}}}


def _moe(pairs, touched, rows):
    """Every expert is held: each routed pair forms a row here."""
    return {"assignments": pairs, "assignments_held": pairs,
            "experts_touched": touched, "rows_by_expert": rows}


# Two of the eight layers' rows over the window. Layer 0: expert 9 took 150
# of 3,300 rows (mean 51.5625: 2.909...); layer 1: even.
ROWS_0 = [50] * 64
ROWS_0[9] = 150
ROWS_1 = [50] * 64
EXPERT = 3 * 2560 * 768 * 2             # an expert's three matrices: 11.8 MB
# A 3 s slice of which the device was busy 2.0 s: 0.3 s in the window
# layers' calls, 0.2 s in the full layers', 0.9 s in the grouped products,
# 0.02 s in sorts; two ticks wholly inside it (a chunk of 256 beside 24
# decode rows, 19 of the 25 rows past the window; 28 decode rows, 21 past
# it), two cut by the slice's edges and left out whole.
RUN = {
    "trace": {"busy_s": 2.0, "window_s": 3.0, "planes": 1, "op_seconds": {
        "%swa_window_read bf16[32,1,28,128]": 0.1,
        "%swa_window_read bf16[35,128,28,128]": 0.2,
        "%_paged_call bf16[32,1,28,128]": 0.15,
        "%_paged_call bf16[35,128,28,128]": 0.05,
        "%ragged-dot-none f32[1792,1536]": 0.5,
        "%ragged-dot-none f32[1792,2560]": 0.35,
        "%ragged-dot-metadata (tuple)": 0.05,
        "%sort.12 = (s32[1792], s32[1792])": 0.02,
        "%fusion f32[32,151936]": 0.1}},
    "slice": {"begin": 100.0, "end": 103.0},
    "peaks": V5E, "config": CONFIG, "cell": {"name": CELL},
    "spans": {"gateway": [], "worker_1": [
        _tick(99.99, 50, ctx_tokens_full=10 ** 8, ctx_tokens_window=10 ** 8,
              rows_fed=10 ** 4, rows_past_window=0,
              moe_assignments=10 ** 7, moe_assignments_held=10 ** 7,
              moe_experts_touched=10 ** 4),
        _tick(100.5, 25, ctx_tokens_full=170000, ctx_tokens_window=95000,
              rows_fed=25, rows_past_window=19, moe_assignments=13440,
              moe_assignments_held=13440, moe_experts_touched=512),
        _tick(101.0, 24, ctx_tokens_full=180000, ctx_tokens_window=105000,
              rows_fed=28, rows_past_window=21, moe_assignments=1344,
              moe_assignments_held=1344, moe_experts_touched=440),
        _tick(102.99, 50, ctx_tokens_full=10 ** 8, ctx_tokens_window=10 ** 8,
              rows_fed=10 ** 4, rows_past_window=0,
              moe_assignments=10 ** 7, moe_assignments_held=10 ** 7,
              moe_experts_touched=10 ** 4)]},
    "stats_before": {"worker_1": {"moe": _moe(1000, 100,
                                              [[0] * 64, [0] * 64])}},
    "stats_after": {"worker_1": {
        "moe": _moe(1000 + 59136, 100 + 3808, [ROWS_0, ROWS_1])}},
    # the full layers held most in the second sample, the window layers in
    # the third
    "pool_samples": [_pool(1.0, 9000, 5000), _pool(1.5, 13568, 6784),
                     _pool(2.0, 12000, 7644)],
}
# The spans of the whole window are the four above: 10,000 + 25 + 28 +
# 10,000 rows fed, 40 of them past the window.
WANT = {
    "kernel.swa_attn_busy": 15.0,
    "kernel.full_attn_busy": 10.0,
    "kernel.moe_experts_busy": 45.0,
    "moe.route_sort_busy": 1.0,
    # 200,000 tokens x 6 window layers x 2,048 B = 2.46 GB: 3.0 ms at the
    # HBM peak (the pairs' FLOPs over 28 heads, 17.2 GFLOP, take 0.09 ms),
    # of 0.3 s
    "kernel.swa_attn_roofline": 100 * (200000 * 6 * 2048 / 819e9) / 0.3,
    # 350,000 tokens x 2 full layers x 2,048 B = 1.43 GB: 1.75 ms, of 0.2 s
    "kernel.full_attn_roofline": 100 * (350000 * 2 * 2048 / 819e9) / 0.2,
    # 952 touched experts x 11.8 MB = 11.2 GB: 13.7 ms (the 14,784 pairs'
    # 0.17 TFLOP take 0.9 ms), of 0.9 s
    "kernel.moe_experts_roofline": 100 * (952 * EXPERT / 819e9) / 0.9,
    "moe.rows_per_touched_expert": 59136 / 3808,
    "moe.expert_load_imbalance": (150 * 64 / 3300 + 1.0) / 2,
    "kv.window_over_full_tokens": 0.5,
    "kv.full_blocks_peak_share": 50.0,
    "kv.window_blocks_peak_share": 87.5,
    "kv.window_bound_row_share": 100 * 40 / 20053,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert reader(name)(RUN) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """The parent's program on another lane: one pool, no window call, no
    `moe` group, no `rows_past_window` on its spans; and a run with no trace
    at all. A reader returns None and does not raise (the driver lays this
    PR's readers over the parent's checkout)."""
    run = dict(RUN, trace=dict(RUN["trace"], op_seconds={
        "%mla_latent_read bf16[68,128,512]": 1.0}))
    run["spans"] = {"gateway": [], "worker_1": [
        _tick(100.5, 50, ctx_tokens=48000)]}
    run["stats_before"] = {"worker_1": {"mixed": {"ticks": 1}}}
    run["stats_after"] = {"worker_1": {"mixed": {"ticks": 9}}}
    run["pool_samples"] = [{"t": 1.0, "kv_pool": {"worker_1": {
        "blocks_total": 5120, "blocks_free": 100}}}]
    assert reader(name)(run) is None
    run["trace"] = run["slice"] = run["peaks"] = None
    run["pool_samples"] = [{"t": 1.0, "kv_pool": {"worker_1": None}}]
    assert reader(name)(run) is None


def test_the_window_lane_of_the_parent_reads_the_pool_and_not_the_rows():
    """Laguna's lane on the parent's program: its pool states the window
    kind's blocks, so the pool's new reader reads there; its spans carry no
    `rows_past_window`, so the rows' reader reads nothing. Neither is
    listed for that cell."""
    run = dict(RUN, spans={"gateway": [], "worker_1": [
        _tick(100.5, 40, ctx_tokens_full=90000, ctx_tokens_window=16000)]})
    assert reader("kv.window_blocks_peak_share")(run) == pytest.approx(87.5)
    assert reader("kv.window_bound_row_share")(run) is None
    listed = load_benchmark()
    for name in NEW:
        (entry,) = [m for m in listed["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL] and entry["layer"] == "KV pool"
        assert entry["moves"] == "tokens_per_s" and entry["unit"] == "%"


def test_the_sizes_the_readers_count_with_are_the_configuration_s():
    """The accepted readers' words (`lib/roofline_sizes.py`,
    `lib/roofline_laguna.py`) read this file: two classes of attention (no
    single read), the experts three matrices of 2560 x 768 all held."""
    size = sizes(CONFIG)
    assert size["attention"] is None and size["recurrence"] is None
    assert size["experts"] == {
        "kernel": "ragged-dot", "matrices": 3, "rows": 2560, "cols": 768,
        "held": (0, 64), "bytes_per_element": 2}
    two = roofline_laguna.sizes(CONFIG)
    assert (two["layers"], two["heads"], two["kv_heads"], two["head_dim"]) \
        == ((2, 6), (28, 28), 4, 128)
    assert roofline.attention_bytes(1, 1, 4, 128, 2) == 2048
    assert reader("kernel.paged_attn_busy")(RUN) is None


def test_the_cell_lists_what_its_lane_feeds():
    names = [m["name"] for m in metrics_listed(load_benchmark(), CELL)]
    assert set(WANT) <= set(names)
    for keyless in ("device.hbm_peak_gb", "device.idle", "device.idle_host",
                    "sched.decode_rows_per_tick", "sched.itl_prefill_share",
                    "sched.prefill_tick_share", "step.compiles",
                    "step.prefill_ms"):
        assert keyless in names
    # Two classes of attention state no single read; every tick of the cell
    # carries a chunk, so a slice holds no width-1 run; all experts held.
    for absent in ("kernel.paged_attn_busy", "kv.blocks_peak_share",
                   "step.decode_run_ms", "moe.held_assignment_share"):
        assert absent not in names
    assert [m["name"] for m in metrics_listed(load_benchmark(), CELL,
                                              "end_to_end")] \
        == ["itl_p95_ms", "tokens_per_s", "setup_s"]
