"""The eleven merged per-layer readers `kimi-linear-48b-a3b-5l.reason` is
listed on since PR 68 (`kernel.state_step_*`, `kernel.paged_attn_*`,
`kernel.moe_experts_*`, `moe.rows_per_touched_expert`,
`state.rows_peak_share`, `state.bytes_over_cache_bytes`,
`kv.blocks_peak_share`, `step.decode_ms`) and the chunked form's pair, which
the cell is not listed on (its traced slice holds no chunk tick), on the
made-up run and at the hand-computed values that pinned PR 44's copies of
them (`kernel.kda_*`, `kernel.mla_nope_attn_*`, `kernel.moe_held2304_*`,
`moe.held_rows_per_touched_expert`, `state.kda_*`,
`kv.latent_state_blocks_peak_share`, `step.kda_decode_ms`): the merged
readers at THIS configuration's sizes (a delta rule gated by channel, a
latent pool, half the experts held). And the counting of a channel-gated
state by hand-computed cases.

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

from lib import roofline, roofline_gated_delta, roofline_moe_mla  # noqa: E402
from lib.roofline_sizes import sizes  # noqa: E402

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL = "kimi-linear-48b-a3b-5l.reason"


def _reader(metric):
    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


with open(os.path.join(BENCH, "configs", "kimi-linear-48b-a3b-5l.json")) as f:
    CONFIG = json.load(f)


def _tick(start, ms, **attrs):
    return {"op": "mixed_step", "start_ts": start, "ts": start + ms / 1e3,
            "duration_us": ms * 1e3, "attrs": attrs}


ROW = 4 * (32 * 128 * 128 + 3 * 12288) * 4      # a row's state: 8.98 MB
BLOCK = 16 * 640 * 2                            # a latent block: 20,480 B


def _pool(t, blocks, rows, lanes=(128, 512)):
    return {"t": t, "kv_pool": {"worker_1": {
        "blocks_total": 90112, "blocks_free": 90112 - blocks,
        "kv_bytes_held": blocks * BLOCK, "state_bytes_held": rows * ROW,
        "block_lanes": list(lanes)}}}


STATE = 32 * 128 * 128 * 4              # a row's state, one layer: 2.10 MB
TOKEN = 32 * (2 * (128 + 128) + 128) * 4    # q, k, v, read and 128 gates a head
EXPERT = 3 * 2304 * 1024 * 2            # an expert's three matrices: 14.2 MB
# A 3 s slice of which the device was busy 2.5 s: 0.2 s in the `kda_chunk`
# calls, 0.4 s in `kda_step`'s, 0.1 s in the latent reads, 1.0 s in the
# grouped products; two ticks wholly inside it (a chunk tick and a decode
# tick), two cut by its edges and left out whole, and three decode-only
# ticks (width 1) of 24, 26 and 31 ms outside it.
RUN = {
    "trace": {"busy_s": 2.5, "window_s": 3.0, "planes": 1, "op_seconds": {
        "%mla_latent_read bf16[224,128,512]": 0.06,
        "%mla_latent_read bf16[128,32,512]": 0.04,
        "%kda_chunk (tuple)": 0.2, "%kda_step (tuple)": 0.4,
        "%ragged-dot-none": 0.9, "%ragged-dot-metadata": 0.1,
        "%fusion bf16[384,9216]": 0.8}},
    "slice": {"begin": 100.0, "end": 103.0},
    "peaks": V5E, "config": CONFIG, "cell": {"name": CELL},
    "spans": {"gateway": [], "worker_1": [
        _tick(99.99, 50, kda_chunk_tokens=10 ** 6, kda_chunk_rows=100,
              kda_step_rows=1000, ctx_tokens_latent=10 ** 7,
              moe_experts_touched=10 ** 5, moe_assignments_held=10 ** 6),
        _tick(100.5, 40, kda_chunk_tokens=150, kda_chunk_rows=2,
              kda_step_rows=100, ctx_tokens_latent=200000,
              moe_experts_touched=500, moe_assignments_held=4000),
        _tick(101.0, 30, width=1, kda_chunk_tokens=0, kda_chunk_rows=0,
              kda_step_rows=120, ctx_tokens_latent=240000,
              moe_experts_touched=480, moe_assignments_held=1920),
        _tick(102.99, 50, kda_chunk_tokens=10 ** 6, kda_chunk_rows=100,
              kda_step_rows=1000, ctx_tokens_latent=10 ** 7,
              moe_experts_touched=10 ** 5, moe_assignments_held=10 ** 6),
        _tick(104.0, 24, width=1, kda_step_rows=128),
        _tick(104.1, 31, width=1, kda_step_rows=128),
        _tick(104.2, 26, width=1, kda_step_rows=127)]},
    "stats_before": {"worker_1": {"moe": {
        "assignments": 1000, "assignments_held": 500,
        "experts_touched": 100}}},
    "stats_after": {"worker_1": {
        "moe": {"assignments": 9000, "assignments_held": 4500,
                "experts_touched": 1100},
        "state_pool": {"rows_total": 128, "rows_peak": 120, "rows_held": 3},
        "kv_pool": {"block_lanes": [128, 512]}}},
    # the latent pool held most in the second sample
    "pool_samples": [_pool(1.0, 10000, 128), _pool(1.5, 22528, 120),
                     _pool(2.0, 20000, 128)],
}
WANT = {
    "kernel.state_step_busy": 16.0,
    "kernel.state_chunk_busy": 8.0,
    "kernel.paged_attn_busy": 4.0,
    "kernel.moe_experts_busy": 40.0,
    # 220 rows x 4 layers x (2 x 2.10 MB + 80 KB) = 3.76 GB: 4.59 ms at the
    # HBM peak (the recurrence's 0.09 TFLOP take 0.4 ms), of 0.4 s
    "kernel.state_step_roofline":
        100 * (4 * 220 * (2 * STATE + TOKEN) / 819e9) / 0.4,
    # 2 rows x 4 layers x 2 x 2.10 MB and 150 tokens x 4 x 80 KB
    "kernel.state_chunk_roofline":
        100 * (4 * (2 * 2 * STATE + 150 * TOKEN) / 819e9) / 0.2,
    # 440 000 tokens x 1 MLA layer x 1152 B = 0.51 GB: 0.62 ms; their FLOPs
    # (x 32 heads x 2 x 1088) 30.6 GFLOP: 0.16 ms. Of 0.1 s
    "kernel.paged_attn_roofline":
        100 * (440000 * 1152 / 819e9) / 0.1,
    # 980 touched experts x 14.2 MB = 13.9 GB: 16.9 ms (5920 held pairs x
    # 14.2 MFLOP = 84 GFLOP: 0.43 ms), of 1.0 s
    "kernel.moe_experts_roofline": 100 * (980 * EXPERT / 819e9) / 1.0,
    "moe.rows_per_touched_expert": 4.0,
    "state.rows_peak_share": 93.75,
    # 120 rows x 8.98 MB over 22 528 blocks x 20 480 B
    "state.bytes_over_cache_bytes": 120 * ROW / (22528 * BLOCK),
    "kv.blocks_peak_share": 25.0,
    "step.decode_ms": 26.0,
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
    """A program that states these sizes and runs none of it: no kernel of
    the recurrence, no latent read and no grouped product in its trace, no
    counter on its spans, no `moe` group, no state pool and no sample of a
    block pool; and a run with no trace. A reader returns None and does not
    raise."""
    run = dict(RUN, trace=dict(RUN["trace"], op_seconds={
        "%fusion bf16[384,9216]": 0.8}))
    run["spans"] = {"gateway": [], "worker_1": [_tick(100.5, 50, width=256)]}
    run["stats_before"] = {"worker_1": {}}
    run["stats_after"] = {"worker_1": {"mixed": {"ticks": 9}}}
    run["pool_samples"] = [{"t": 1.0, "kv_pool": {"worker_1": None}}]
    assert _reader(name)(run) is None
    run["trace"] = run["slice"] = run["peaks"] = None
    assert _reader(name)(run) is None


SIZES = sizes(CONFIG)
SIZE = SIZES["recurrence"]


def test_sizes_of_the_configuration_as_run():
    assert SIZES == {
        "attention": {"kernel": "mla_latent", "layers": 1, "heads": 32,
                      "latent": 512, "rope": 64, "lanes": 576,
                      "bytes_per_element": 2},
        "experts": {"kernel": "ragged-dot", "matrices": 3, "rows": 2304,
                    "cols": 1024, "held": (0, 128),
                    "bytes_per_element": 2},
        "recurrence": {"kind": "kda", "layers": 4, "heads": 32,
                       "state": (128, 128), "gate_lanes": 128,
                       "step": "kda_step", "chunk": "kda_chunk"}}


def test_a_state_is_2_10_mb_and_a_token_of_latent_1152_bytes():
    """ISSUE 44's figures: 32 x 128 x 128 float32 a row and KDA layer; 576
    used lanes x 2 B a token and MLA layer (1,280 B are stored)."""
    assert roofline_gated_delta.state_bytes(SIZE) == STATE == 2097152
    assert roofline_moe_mla.latent_bytes(1, 1, 512, 64, 2) == 1152
    assert roofline_moe_mla.expert_bytes(1, 2304, 1024, 2) == EXPERT
    assert round(EXPERT / 1e6, 1) == 14.2


def test_a_decode_tick_s_steps_are_bound_by_their_states():
    """128 rows x 4 layers: 2 x 2.10 MB of state each and 80 KB of q, k, v,
    gates and read, 2.19 GB, 2.67 ms at the HBM peak; 3 x 2 x 32 x 128 x
    128 operations a row and layer, 1.6 GFLOP, 8 us."""
    n_bytes = roofline_gated_delta.recurrence_bytes(128, 128, SIZE)
    assert n_bytes == 128 * 4 * (2 * STATE + TOKEN)
    assert TOKEN == 32 * 640 * 4
    flops = roofline_gated_delta.recurrence_flops(128, SIZE)
    assert flops == 128 * 4 * 32 * 3 * 2 * 128 * 128
    assert roofline.floor_seconds(n_bytes, flops, V5E) == pytest.approx(
        n_bytes / 819e9)
    assert 2.6e-3 < n_bytes / 819e9 < 2.8e-3
    assert flops / V5E["bf16_flops_per_s"] < 1e-5


def test_a_chunk_s_state_is_read_once_a_row_not_once_a_token():
    one = roofline_gated_delta.recurrence_bytes(1, 200, SIZE)
    assert one == 4 * (2 * STATE + 200 * TOKEN)
    assert one < roofline_gated_delta.recurrence_bytes(200, 200, SIZE) / 5
