"""The thirteen merged per-layer readers
`nemotron-3-super-120b-a12b-11l.agents` is listed on since PR 68
(`kernel.moe_experts_*`, `moe.rows_per_touched_expert`,
`moe.expert_load_imbalance`, `kernel.state_step_*`, `kernel.state_chunk_*`,
`kernel.paged_attn_*`, `state.rows_peak_share`, `kv.blocks_peak_share`,
`state.bytes_over_cache_bytes`) and `moe.route_sort_busy`, on the made-up
run and at the hand-computed values that pinned PR 50's copies of them
(`kernel.moe_latent_*`, `moe.latent_*`, `kernel.ssd64_*`,
`kernel.gqa16_attn_*`, `state.ssd64_*`, `kv.ssd64_blocks_peak_share`): the
merged readers at THIS configuration's sizes (two matrices an expert in a
latent, a quarter of the experts held, one attention layer in eleven). And
the counting of an expert in a latent and of the M layers' states by
hand-computed cases.

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

from lib import (  # noqa: E402
    roofline,
    roofline_falcon_h1,
    roofline_gated_delta,
    roofline_moe_mla,
)
from lib.roofline_sizes import sizes  # noqa: E402

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL = "nemotron-3-super-120b-a12b-11l.agents"


def _reader(metric):
    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


with open(os.path.join(BENCH, "configs",
                       "nemotron-3-super-120b-a12b-11l.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "configs", "falcon-h1-34b-6l.json")) as f:
    OTHER = json.load(f)


def _tick(start, ms, **attrs):
    return {"op": "mixed_step", "start_ts": start, "ts": start + ms / 1e3,
            "duration_us": ms * 1e3, "attrs": attrs}


ROW = 5 * (128 * 64 * 128 + 3 * 10240) * 4      # a row's state: 21.59 MB
BLOCK = 1 * 16 * 2 * 256 * 2                    # a K/V block: 16,384 B


def _pool(t, blocks, rows, lanes=(256, 256)):
    return {"t": t, "kv_pool": {"worker_1": {
        "blocks_total": 35840, "blocks_free": 35840 - blocks,
        "kv_bytes_held": blocks * BLOCK, "state_bytes_held": rows * ROW,
        "block_lanes": list(lanes)}}}


def _moe(assignments, held, touched, rows):
    return {"assignments": assignments, "assignments_held": held,
            "experts_touched": touched, "rows_by_expert": rows}


STATE = 128 * 64 * 128 * 4              # a row's state, one layer: 4.19 MB
TOKEN = (128 * (2 * 64 + 1) + 2 * 8 * 128) * 4  # x, dt, B, C in, read out
EXPERT = 2 * 1024 * 2688 * 2            # an expert's two matrices: 11.0 MB
# Two expert layers' rows over the window: experts 0-127 held, the others
# another chip's. Layer 0: expert 5 took 300 of 12,800 rows (mean 100: 3.0);
# layer 1: even but for expert 9's 220 of 12,920 (mean 100.9: 2.18).
ROWS_0 = [100] * 128 + [0] * 384
ROWS_0[5], ROWS_0[6] = 300, 0
ROWS_0[7] = 0
ROWS_1 = [100] * 128 + [0] * 384
ROWS_1[9] = 220
# A 3 s slice of which the device was busy 2.9 s: 1.6 s in the grouped
# products, 0.3 s in `ssd_step`, 0.07 s in `ssd_chunk`, 0.05 s in the paged
# reads (the short and the tall call), 0.02 s sorting; two ticks wholly
# inside it (both carry a chunk), two cut by its edges and left out whole.
RUN = {
    "trace": {"busy_s": 2.9, "window_s": 3.0, "planes": 1, "op_seconds": {
        "%ragged-dot-none f32[7040,2688]": 0.85,
        "%ragged-dot-none f32[7040,1024]": 0.75,
        "%ssd_step (tuple)": 0.3, "%ssd_chunk (tuple)": 0.07,
        "%_paged_call bf16[64,2,16,128]": 0.03,
        "%_paged_call bf16[104,2,128,128]": 0.02,
        "%sort (tuple)": 0.02,
        "%fusion f32[64,32768]": 0.1}},
    "slice": {"begin": 100.0, "end": 103.0},
    "peaks": V5E, "config": CONFIG, "cell": {"name": CELL},
    "spans": {"gateway": [], "worker_1": [
        _tick(99.99, 50, ssd_chunk_tokens=10 ** 6, ssd_chunk_rows=100,
              ssd_step_rows=1000, ctx_tokens_full=10 ** 8,
              moe_assignments=10 ** 7, moe_assignments_held=10 ** 6,
              moe_experts_touched=10 ** 4),
        _tick(100.5, 42, ssd_chunk_tokens=213, ssd_chunk_rows=1,
              ssd_step_rows=43, ctx_tokens_full=90000,
              moe_assignments=28160, moe_assignments_held=7000,
              moe_experts_touched=640),
        _tick(101.0, 42, ssd_chunk_tokens=200, ssd_chunk_rows=2,
              ssd_step_rows=40, ctx_tokens_full=70000,
              moe_assignments=26400, moe_assignments_held=6600,
              moe_experts_touched=630),
        _tick(102.99, 50, ssd_chunk_tokens=10 ** 6, ssd_chunk_rows=100,
              ssd_step_rows=1000, ctx_tokens_full=10 ** 8,
              moe_assignments=10 ** 7, moe_assignments_held=10 ** 6,
              moe_experts_touched=10 ** 4)]},
    "stats_before": {"worker_1": {"moe": _moe(
        1000, 250, 100, [[0] * 512, [0] * 512])}},
    "stats_after": {"worker_1": {
        "moe": _moe(1000 + 102880, 250 + 25720, 100 + 2572,
                    [ROWS_0, ROWS_1]),
        "state_pool": {"rows_total": 64, "rows_peak": 64, "rows_held": 60},
        "kv_pool": {"block_lanes": [256, 256],
                    "state_bytes_held": 60 * ROW}}},
    # the K/V pool held most in the second sample
    "pool_samples": [_pool(1.0, 7000, 64), _pool(1.5, 8960, 62),
                     _pool(2.0, 8000, 64)],
}
WANT = {
    "kernel.moe_experts_busy": 100 * 1.6 / 2.9,
    "kernel.state_step_busy": 100 * 0.3 / 2.9,
    "kernel.state_chunk_busy": 100 * 0.07 / 2.9,
    "kernel.paged_attn_busy": 100 * 0.05 / 2.9,
    "moe.route_sort_busy": 100 * 0.02 / 2.9,
    # 1270 touched experts x 11.0 MB = 13.98 GB: 17.07 ms at the HBM peak
    # (13,600 pairs' 0.15 TFLOP take 0.8 ms), of 1.6 s
    "kernel.moe_experts_roofline": 100 * (1270 * EXPERT / 819e9) / 1.6,
    # 83 rows x 5 layers x (2 x 4.19 MB + 74 KB) = 3.51 GB: 4.29 ms of 0.3 s
    "kernel.state_step_roofline":
        100 * (5 * 83 * (2 * STATE + TOKEN) / 819e9) / 0.3,
    # 3 rows x 5 layers x 2 x 4.19 MB and 413 tokens x 5 x 74 KB
    "kernel.state_chunk_roofline":
        100 * (5 * (3 * 2 * STATE + 413 * TOKEN) / 819e9) / 0.07,
    # 160,000 tokens x 1 layer x 2 x 2 x 128 x 2 B = 0.164 GB: 0.2 ms; their
    # FLOPs (x 32 heads x 4 x 128) 2.6 GFLOP: 0.013 ms. Of 0.05 s
    "kernel.paged_attn_roofline": 100 * (160000 * 1024 / 819e9) / 0.05,
    "moe.rows_per_touched_expert": 25720 / 2572,
    "moe.expert_load_imbalance": (300 * 128 / 12800 + 220 * 128 / 12920) / 2,
    "state.rows_peak_share": 100.0,
    # 62 rows x 21.59 MB over 8960 blocks x 16,384 B
    "state.bytes_over_cache_bytes": 62 * ROW / (8960 * BLOCK),
    "kv.blocks_peak_share": 25.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _reader(name)(RUN) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(WANT))
def test_no_share_of_the_made_up_run_passes_its_peak(name):
    if name.endswith("_roofline") or name.endswith("_busy"):
        assert 0.0 < WANT[name] < 100.0


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """A program that states these sizes and runs none of it: no grouped
    product, no kernel of the recurrence or the read and no sort in its
    trace, no counter on its spans, no `moe` group, no state pool and no
    sample of a block pool; and a run with no trace. A reader returns None
    and does not raise."""
    run = dict(RUN, trace=dict(RUN["trace"], op_seconds={
        "%fusion f32[64,261120]": 0.4}))
    run["spans"] = {"gateway": [], "worker_1": [_tick(100.5, 50, width=256)]}
    run["stats_before"] = {"worker_1": {}}
    run["stats_after"] = {"worker_1": {"mixed": {"ticks": 9}}}
    run["pool_samples"] = [{"t": 1.0, "kv_pool": {"worker_1": None}}]
    assert _reader(name)(run) is None
    run["trace"] = run["slice"] = run["peaks"] = None
    assert _reader(name)(run) is None


def test_the_readers_take_this_configuration_s_sizes_not_another_s():
    """The same spans and trace under the configuration whose EVERY layer
    has both mixers and no experts: the recurrence is counted in 6 layers
    of 32 heads of (128, 256) where this one has 5 of 128 of (64, 128), the
    paged read at 4 KV heads in 6 layers where this one has 2 in 1, and the
    experts' readers find no experts stated."""
    other = dict(RUN, config=OTHER)
    assert _reader("kernel.moe_experts_roofline")(other) is None
    assert _reader("moe.expert_load_imbalance")(other) is None
    assert _reader("kernel.paged_attn_roofline")(other) == pytest.approx(
        WANT["kernel.paged_attn_roofline"] * (6 * 4) / (1 * 2), rel=1e-9)
    state, token = 32 * 128 * 256 * 4, (32 * 257 + 2 * 2 * 256) * 4
    assert _reader("kernel.state_step_roofline")(other) == pytest.approx(
        100 * (6 * 83 * (2 * state + token) / 819e9) / 0.3, rel=1e-9)


SIZES = sizes(CONFIG)


def test_sizes_of_the_configuration_as_run():
    assert SIZES == {
        "attention": {"kernel": "paged", "layers": 1, "heads": 32,
                      "kv_heads": 2, "head_dim": 128, "lanes": 512,
                      "bytes_per_element": 2},
        "experts": {"kernel": "ragged-dot", "matrices": 2, "rows": 1024,
                    "cols": 2688, "held": (0, 128),
                    "bytes_per_element": 2},
        "recurrence": {"kind": "ssd", "layers": 5, "heads": 128,
                       "state": (64, 128), "groups": 8, "step": "ssd_step",
                       "chunk": "ssd_chunk"}}


def test_a_state_is_4_19_mb_a_token_of_k_v_1024_bytes_an_expert_11_mb():
    """ISSUE 50's figures: 128 x 64 x 128 float32 a row and M layer; 2 x 2 x
    128 x 2 B a token in the one * layer; 2 x 1024 x 2688 x 2 B an expert."""
    assert roofline_gated_delta.state_bytes(
        SIZES["recurrence"]) == STATE == 4194304
    assert roofline.attention_bytes(1, 1, 2, 128, 2) == 1024
    assert roofline_moe_mla.expert_bytes(1, 1024, 2688, 2, 2) == EXPERT \
        == 11010048
    assert ROW == 21585920 and BLOCK == 16 * 1024


def test_a_tick_s_experts_are_bound_by_their_matrices():
    """A tick touches all 128 held experts in 5 layers: 640 x 11.0 MB = 7.05
    GB, 8.6 ms at the HBM peak (ISSUE 50's three fifths of a tick's bytes);
    its ~7,040 held pairs are 2 x 2 x 1024 x 2688 operations each, 77.5
    GFLOP, 0.39 ms: ~11 rows an expert against a ridge near 240."""
    size = SIZES["experts"]
    n_bytes = roofline_moe_mla.expert_bytes(
        640, size["rows"], size["cols"], size["bytes_per_element"],
        size["matrices"])
    flops = roofline_moe_mla.expert_flops(7040, size["rows"], size["cols"],
                                          size["matrices"])
    assert n_bytes == 640 * EXPERT and 7.04e9 < n_bytes < 7.06e9
    assert flops == 7040 * 4 * 1024 * 2688
    assert roofline.floor_seconds(n_bytes, flops, V5E) == pytest.approx(
        n_bytes / 819e9)
    assert 8.5e-3 < n_bytes / 819e9 < 8.7e-3
    assert flops / V5E["bf16_flops_per_s"] < 0.4e-3


def test_a_tick_s_steps_are_bound_by_their_states_in_the_m_layers_alone():
    """43 rows x 5 M layers (not 11): 2 x 4.19 MB of state each and 74 KB of
    x, dt, B, C and read: 1.82 GB, 2.2 ms at the HBM peak."""
    mamba = SIZES["recurrence"]
    n_bytes = roofline_falcon_h1.recurrence_bytes(43, 43, mamba)
    assert n_bytes == 43 * 5 * (2 * STATE + TOKEN)
    assert TOKEN == (128 * 129 + 2048) * 4
    assert roofline_falcon_h1.recurrence_flops(43, mamba) == (
        43 * 5 * 128 * 4 * 64 * 128)
    assert 2.2e-3 < n_bytes / 819e9 < 2.3e-3


def test_the_imbalance_is_read_over_the_held_experts_alone():
    """Over all 512 experts the 384 that are another chip's read 0 and the
    busiest held expert would read 4 times as uneven: what the reader reads
    under a configuration that states the same experts all held."""
    held = _reader("moe.expert_load_imbalance")(RUN)
    assert held == pytest.approx(WANT["moe.expert_load_imbalance"])
    whole = dict(CONFIG, kwargs=dict(CONFIG["kwargs"], held_count=0))
    assert _reader("moe.expert_load_imbalance")(
        dict(RUN, config=whole)) == pytest.approx(4 * held)
