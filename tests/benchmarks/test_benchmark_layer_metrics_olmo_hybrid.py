"""The ten merged per-layer readers `olmo-hybrid-7b-12l.digest` is listed on
since PR 68 (`kernel.state_chunk_*`, `kernel.state_step_*`,
`kernel.paged_attn_*`, `state.rows_peak_share`,
`state.bytes_over_cache_bytes`, `kv.blocks_peak_share`, `step.decode_ms`),
on the made-up run and at the hand-computed values that pinned PR 39's
copies of them (`kernel.gdn_*`, `kernel.mha128_attn_*`,
`state.bytes_over_kv_bytes`, `kv.hybrid_blocks_peak_share`,
`step.hybrid_decode_ms`): the merged readers at THIS configuration's sizes
(a delta rule gated by head in nine layers, MHA at 30 heads in three).
(`step.hybrid_decode_device_ms`, the time the host was blocked on a lane
that runs ahead, went with PR 68: `step.decode_run_ms` has the device's.)

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
CELL = "olmo-hybrid-7b-12l.digest"


def _reader(metric):
    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b-12l.json")) as f:
    CONFIG = json.load(f)


def _tick(start, ms, **attrs):
    return {"op": "mixed_step", "start_ts": start, "ts": start + ms / 1e3,
            "duration_us": ms * 1e3, "attrs": attrs}


def _pool(t, blocks, rows):
    return {"t": t, "kv_pool": {"worker_1": {
        "blocks_total": 8704, "blocks_free": 8704 - blocks,
        "kv_bytes_held": blocks * 737280,
        "state_bytes_held": rows * 21150720}}}


STATE = 30 * 192 * 96 * 4               # a row's state, one layer: 2.21 MB
# A 3 s slice of which the device was busy 2 s: 0.2 s in the `gdn_chunk`
# calls, 0.3 s in `gdn_step`'s, 0.4 s in the paged calls; four ticks that
# carry a chunk, the first and the last cut by the slice's edges and left
# out whole, and three decode-only ticks (width 1) of 24, 26 and 31 ms, of
# which the step program had 15 + 3, 16 + 4 and 20 + 5.
RUN = {
    "trace": {"busy_s": 2.0, "window_s": 3.0, "planes": 1, "op_seconds": {
        "%_paged_call bf16[16,30,256,128]": 0.3,
        "%_paged_call bf16[16,30,1,128]": 0.1,
        "%gdn_chunk (tuple)": 0.2, "%gdn_step (tuple)": 0.3,
        "%fusion bf16[288,11008]": 1.0}},
    "slice": {"begin": 100.0, "end": 103.0},
    "peaks": V5E, "config": CONFIG, "cell": {"name": CELL},
    "spans": {"gateway": [], "worker_1": [
        _tick(99.99, 50, gdn_chunk_tokens=10 ** 6, gdn_chunk_rows=100,
              gdn_step_rows=1000, ctx_tokens_full=10 ** 7),
        _tick(100.5, 40, gdn_chunk_tokens=241, gdn_chunk_rows=1,
              gdn_step_rows=15, ctx_tokens_full=60000),
        _tick(101.0, 30, gdn_chunk_tokens=300, gdn_chunk_rows=2,
              gdn_step_rows=14, ctx_tokens_full=64000),
        _tick(102.99, 50, gdn_chunk_tokens=10 ** 6, gdn_chunk_rows=100,
              gdn_step_rows=1000, ctx_tokens_full=10 ** 7),
        _tick(104.0, 24, width=1, dispatch_us=15000, wait_us=3000,
              gdn_chunk_tokens=0, gdn_chunk_rows=0, gdn_step_rows=16),
        _tick(104.1, 31, width=1, dispatch_us=20000, wait_us=5000,
              gdn_chunk_tokens=0, gdn_chunk_rows=0, gdn_step_rows=16),
        _tick(104.2, 26, width=1, dispatch_us=16000, wait_us=4000,
              gdn_chunk_tokens=0, gdn_chunk_rows=0, gdn_step_rows=15)]},
    "stats_before": {"worker_1": {}},
    "stats_after": {"worker_1": {"state_pool": {
        "rows_total": 16, "rows_peak": 12, "rows_held": 3}}},
    # the K/V pool held most in the second sample
    "pool_samples": [_pool(1.0, 3000, 16), _pool(1.5, 4352, 15),
                     _pool(2.0, 4000, 16)],
}
WANT = {
    "kernel.state_chunk_busy": 10.0,
    "kernel.state_step_busy": 15.0,
    "kernel.paged_attn_busy": 20.0,
    # 3 rows x 9 layers x 2 x 2.21 MB and 541 tokens x 9 x 30 x 576 lanes x
    # 4 B = 0.46 GB: 0.56 ms at the HBM peak (the recurrence's 16 GFLOP
    # take 0.08 ms), of 0.2 s
    "kernel.state_chunk_roofline":
        100 * (9 * (3 * 2 * STATE + 541 * 30 * 576 * 4) / 819e9) / 0.2,
    # 29 rows x 9 layers x (2 x 2.21 MB + 69 KB) = 1.17 GB: 1.43 ms, of 0.3 s
    "kernel.state_step_roofline":
        100 * (9 * 29 * (2 * STATE + 30 * 576 * 4) / 819e9) / 0.3,
    # 124 000 tokens x 3 full layers x 15 360 B = 5.71 GB: 6.98 ms, of 0.4 s
    "kernel.paged_attn_roofline":
        100 * (124000 * 3 * 15360 / 819e9) / 0.4,
    "state.rows_peak_share": 75.0,
    # 15 rows x 21.15 MB over 4352 blocks x 737 280 B
    "state.bytes_over_cache_bytes": 15 * 21150720 / (4352 * 737280),
    "kv.blocks_peak_share": 50.0,
    "step.decode_ms": 26.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _reader(name)(RUN) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """A program that states these sizes and runs none of it: no kernel of
    the recurrence and no paged call in its trace, no counter on its spans,
    no state pool and no sample of a block pool; and a run with no trace. A
    reader returns None and does not raise."""
    run = dict(RUN, trace=dict(RUN["trace"], op_seconds={
        "%fusion bf16[288,11008]": 1.0}))
    run["spans"] = {"gateway": [], "worker_1": [_tick(100.5, 50, width=256)]}
    run["stats_after"] = {"worker_1": {"mixed": {"ticks": 9}}}
    run["pool_samples"] = [{"t": 1.0, "kv_pool": {"worker_1": None}}]
    assert _reader(name)(run) is None
    run["trace"] = run["slice"] = run["peaks"] = None
    assert _reader(name)(run) is None
