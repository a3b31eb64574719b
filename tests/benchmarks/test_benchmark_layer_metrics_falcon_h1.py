"""The ten merged per-layer readers `falcon-h1-34b-6l.converse` is listed on
since PR 68 (`kernel.state_step_*`, `kernel.state_chunk_*`,
`kernel.paged_attn_*`, `state.rows_peak_share`,
`state.bytes_over_cache_bytes`, `kv.blocks_peak_share`, `step.decode_ms`),
on the made-up run and at the hand-computed values that pinned PR 46's
copies of them (`kernel.ssd_*`, `kernel.gqa5_attn_*`, `state.ssd_*`,
`kv.ssd_blocks_peak_share`, `step.ssd_decode_ms`): the merged readers at
THIS configuration's sizes. And the counting of lib/roofline_falcon_h1.py by
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

from lib import roofline, roofline_falcon_h1, roofline_gated_delta  # noqa: E402
from lib.roofline_sizes import sizes  # noqa: E402

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL = "falcon-h1-34b-6l.converse"


def _reader(metric):
    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


with open(os.path.join(BENCH, "configs", "falcon-h1-34b-6l.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b-12l.json")) as f:
    OTHER = json.load(f)


def _tick(start, ms, **attrs):
    return {"op": "mixed_step", "start_ts": start, "ts": start + ms / 1e3,
            "duration_us": ms * 1e3, "attrs": attrs}


ROW = 6 * (32 * 128 * 256 + 3 * 5120) * 4       # a row's state: 25.5 MB
BLOCK = 6 * 16 * 2 * 512 * 2                    # a K/V block: 196,608 B


def _pool(t, blocks, rows, lanes=(512, 512)):
    return {"t": t, "kv_pool": {"worker_1": {
        "blocks_total": 6144, "blocks_free": 6144 - blocks,
        "kv_bytes_held": blocks * BLOCK, "state_bytes_held": rows * ROW,
        "block_lanes": list(lanes)}}}


STATE = 32 * 128 * 256 * 4              # a row's state, one layer: 4.19 MB
TOKEN = (32 * (2 * 128 + 1) + 2 * 2 * 256) * 4   # x, dt, B, C in, read out
# A 3 s slice of which the device was busy 2.5 s: 0.05 s in the `ssd_chunk`
# calls, 0.6 s in `ssd_step`'s, 0.2 s in the paged reads (the short and the
# tall call); two ticks wholly inside it (a chunk tick and a decode tick),
# two cut by its edges and left out whole, and three decode-only ticks
# (width 1) of 16, 17 and 21 ms outside it (and the one of 19 ms inside).
RUN = {
    "trace": {"busy_s": 2.5, "window_s": 3.0, "planes": 1, "op_seconds": {
        "%ssd_step (tuple)": 0.6, "%ssd_chunk (tuple)": 0.05,
        "%_paged_call bf16[64,4,5,128]": 0.15,
        "%_paged_call bf16[67,4,640,128]": 0.05,
        "%fusion f32[64,261120]": 0.4}},
    "slice": {"begin": 100.0, "end": 103.0},
    "peaks": V5E, "config": CONFIG, "cell": {"name": CELL},
    "spans": {"gateway": [], "worker_1": [
        _tick(99.99, 50, ssd_chunk_tokens=10 ** 6, ssd_chunk_rows=100,
              ssd_step_rows=1000, ctx_tokens_full=10 ** 7),
        _tick(100.5, 30, ssd_chunk_tokens=300, ssd_chunk_rows=2,
              ssd_step_rows=58, ctx_tokens_full=30000),
        _tick(101.0, 19, width=1, ssd_chunk_tokens=0, ssd_chunk_rows=0,
              ssd_step_rows=62, ctx_tokens_full=31000),
        _tick(102.99, 50, ssd_chunk_tokens=10 ** 6, ssd_chunk_rows=100,
              ssd_step_rows=1000, ctx_tokens_full=10 ** 7),
        _tick(104.0, 16, width=1, ssd_step_rows=64),
        _tick(104.1, 21, width=1, ssd_step_rows=64),
        _tick(104.2, 17, width=1, ssd_step_rows=63)]},
    "stats_before": {"worker_1": {}},
    "stats_after": {"worker_1": {
        "state_pool": {"rows_total": 64, "rows_peak": 60, "rows_held": 3},
        "kv_pool": {"block_lanes": [512, 512], "state_bytes_held": 3 * ROW}}},
    # the K/V pool held most in the second sample
    "pool_samples": [_pool(1.0, 1000, 64), _pool(1.5, 1536, 60),
                     _pool(2.0, 1200, 64)],
}
WANT = {
    "kernel.state_step_busy": 24.0,
    "kernel.state_chunk_busy": 2.0,
    "kernel.paged_attn_busy": 8.0,
    # 120 rows x 6 layers x (2 x 4.19 MB + 37 KB) = 6.07 GB: 7.41 ms at the
    # HBM peak (the recurrence's 0.09 TFLOP take 0.5 ms), of 0.6 s
    "kernel.state_step_roofline":
        100 * (6 * 120 * (2 * STATE + TOKEN) / 819e9) / 0.6,
    # 2 rows x 6 layers x 2 x 4.19 MB and 300 tokens x 6 x 37 KB
    "kernel.state_chunk_roofline":
        100 * (6 * (2 * 2 * STATE + 300 * TOKEN) / 819e9) / 0.05,
    # 61 000 tokens x 6 layers x 2 x 4 x 128 x 2 B = 0.75 GB: 0.92 ms; their
    # FLOPs (x 20 heads x 4 x 128) 3.7 GFLOP: 0.02 ms. Of 0.2 s
    "kernel.paged_attn_roofline":
        100 * (61000 * 6 * 2048 / 819e9) / 0.2,
    "state.rows_peak_share": 93.75,
    # 60 rows x 25.5 MB over 1536 blocks x 196,608 B
    "state.bytes_over_cache_bytes": 60 * ROW / (1536 * BLOCK),
    "kv.blocks_peak_share": 25.0,
    "step.decode_ms": 17.0,
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
    the recurrence or the read in its trace, no counter on its spans, no
    state pool and no sample of a block pool; and a run with no trace. A
    reader returns None and does not raise."""
    run = dict(RUN, trace=dict(RUN["trace"], op_seconds={
        "%fusion f32[64,261120]": 0.4}))
    run["spans"] = {"gateway": [], "worker_1": [_tick(100.5, 50, width=256)]}
    run["stats_after"] = {"worker_1": {"mixed": {"ticks": 9}}}
    run["pool_samples"] = [{"t": 1.0, "kv_pool": {"worker_1": None}}]
    assert _reader(name)(run) is None
    run["trace"] = run["slice"] = run["peaks"] = None
    assert _reader(name)(run) is None


def test_the_readers_take_this_configuration_s_sizes_not_another_s():
    """The same spans and trace under the configuration whose recurrence is
    a delta rule: the step readers look for `gdn_step`, which this trace
    does not hold, and the paged read is counted at 30 KV heads in 3 layers
    where this configuration has 4 in 6."""
    other = dict(RUN, config=OTHER)
    assert _reader("kernel.state_step_busy")(other) is None
    assert _reader("kernel.state_step_roofline")(other) is None
    assert _reader("kernel.paged_attn_roofline")(other) == pytest.approx(
        WANT["kernel.paged_attn_roofline"] * (3 * 30) / (6 * 4), rel=1e-9)


SIZE = sizes(CONFIG)["recurrence"]


def test_sizes_of_the_configuration_as_run():
    assert sizes(CONFIG) == {
        "attention": {"kernel": "paged", "layers": 6, "heads": 20,
                      "kv_heads": 4, "head_dim": 128, "lanes": 1024,
                      "bytes_per_element": 2},
        "experts": None,
        "recurrence": {"kind": "ssd", "layers": 6, "heads": 32,
                       "state": (128, 256), "groups": 2, "step": "ssd_step",
                       "chunk": "ssd_chunk"}}


def test_a_state_is_4_19_mb_and_a_token_of_k_v_12_288_bytes():
    """ISSUE 46's figures: 32 x 128 x 256 float32 a row and layer; 2 x 4 x
    128 x 2 B a token and layer, six layers."""
    assert roofline_gated_delta.state_bytes(SIZE) == STATE == 4194304
    assert roofline.attention_bytes(1, 6, 4, 128, 2) == 12288
    assert ROW == 25534464 and BLOCK == 16 * 12288


def test_a_decode_tick_s_steps_are_bound_by_their_states():
    """64 rows x 6 layers: 2 x 4.19 MB of state each and 37 KB of x, dt, B,
    C and read, 3.24 GB, 3.95 ms at the HBM peak (ISSUE 46's 3.9 ms); 2 x 2
    x 32 x 128 x 256 operations a row and layer, 1.6 GFLOP, 8 us."""
    n_bytes = roofline_falcon_h1.recurrence_bytes(64, 64, SIZE)
    assert n_bytes == 64 * 6 * (2 * STATE + TOKEN)
    assert TOKEN == (32 * 257 + 1024) * 4
    flops = roofline_falcon_h1.recurrence_flops(64, SIZE)
    assert flops == 64 * 6 * 32 * 4 * 128 * 256
    assert roofline.floor_seconds(n_bytes, flops, V5E) == pytest.approx(
        n_bytes / 819e9)
    assert 3.9e-3 < n_bytes / 819e9 < 4.0e-3
    assert flops / V5E["bf16_flops_per_s"] < 1e-5


def test_a_chunk_s_state_is_read_once_a_row_not_once_a_token():
    one = roofline_falcon_h1.recurrence_bytes(1, 200, SIZE)
    assert one == 6 * (2 * STATE + 200 * TOKEN)
    assert one < roofline_falcon_h1.recurrence_bytes(200, 200, SIZE) / 5


def test_a_tick_s_context_is_read_under_the_name_the_lane_notes_it_by():
    """A lane with state rows notes what its attending layers read as
    `ctx_tokens_full` beside every tick's `ctx_tokens`: the same number,
    read once."""
    run = dict(RUN, spans={"gateway": [], "worker_1": [
        dict(s, attrs=dict(s["attrs"], ctx_tokens=s["attrs"].get(
            "ctx_tokens_full", 0))) for s in RUN["spans"]["worker_1"]]})
    assert _reader("kernel.paged_attn_roofline")(run) == pytest.approx(
        WANT["kernel.paged_attn_roofline"], rel=1e-9)
