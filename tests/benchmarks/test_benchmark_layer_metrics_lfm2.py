"""The cell's metrics: `lfm2-24b-a2b-9l.assist` arrived when the per-layer
list was FULL (128 of 128 entries: PR 60), so it brought no reader of its
own and was appended to the lists of the nine accepted readers by part and
by run that find something in its step (not `step.decode_run_ms`: nearly
every tick of the cell carries a chunk, and a traced slice holds no width-1
run; not `step.mixer_chunk_busy`: nothing runs under that part). Since PR 68
it is on the nine merged readers of a kind of kernel, pool and counter its
lane feeds (`MERGED`), pinned here on a made-up run at values computed by
hand from THIS configuration's sizes: two attention layers of 32 query heads
over 8 KV heads of 64, every one of 64 experts of three 2048 x 1536 matrices
held, a short convolution's tail a row where a hybrid has a recurrent state.
The cell's small double is rehearsed through run.py on the CPU, and the
readers by part are held on a step that opens no op under a part
(tests/benchmarks/test_benchmark_reference_lfm2.py holds the lists)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import (  # noqa: E402
    BENCH,
    ROOT,
    listed as metrics_listed,
    load_benchmark,
    read_without_a_device,
    rehearsal_cells,
)

from lib import roofline, roofline_moe_mla, xplane_scopes  # noqa: E402
from lib.roofline_sizes import sizes  # noqa: E402

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL = "lfm2-24b-a2b-9l.assist"
LISTED = ["step.attn_busy", "step.attn_read_busy", "step.ffn_busy",
          "step.moe_experts_busy", "step.mixer_busy", "step.head_busy",
          "step.sample_busy", "step.unscoped_busy", "step.chunk_run_ms"]
with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b-9l.json")) as f:
    CONFIG = json.load(f)


def test_the_rehearsal_lists_every_metric_of_the_new_cell(tmp_path):
    """run.py --trace 1 on the CPU at the small size, a cell list of its own
    with the keyless per-layer metrics, the nine accepted readers by part
    and by run and the nine merged readers: the span and counter metrics
    print, what only a device trace gives is left out."""
    cells = rehearsal_cells(tmp_path, "lfm2", CELL)
    real = load_benchmark()
    want = [m["name"] for m in metrics_listed(real, CELL)]
    assert set(LISTED + MERGED) <= set(want)
    assert len(want) >= 24                  # ISSUE 68; 26 at PR 68
    assert [m["name"] for m in metrics_listed(real, CELL, "end_to_end")] \
        == ["itl_p95_ms", "tokens_per_s", "setup_s"]
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"),
         "--benchmark-file", cells, "--workload", "lfm2.closed",
         "--seed", str(2**31 + 60), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=dict(os.environ, TPU_ENGINE_PLATFORM="cpu"),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert set(got) == read_without_a_device(real, CELL)
    assert not set(got) & {*LISTED, *(n for n in MERGED
                                      if n.startswith("kernel."))}
    assert got["step.compiles"] == {"value": 0, "unit": "compilations"}


def _reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "under_test_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


def _tick(start, ms, **attrs):
    return {"op": "mixed_step", "start_ts": start, "ts": start + ms / 1e3,
            "duration_us": ms * 1e3, "attrs": attrs}


EXPERT = 3 * 2048 * 1536 * 2            # an expert's three matrices: 18.9 MB
ROW = 7 * 2 * 2048 * 4                  # a row's conv tails: 114,688 B
BLOCK = 2 * 16 * 2 * 512 * 2            # a K/V block: 65,536 B


def _pool(t, blocks, rows):
    return {"t": t, "kv_pool": {"worker_1": {
        "blocks_total": 40960, "blocks_free": 40960 - blocks,
        "kv_bytes_held": blocks * BLOCK, "state_bytes_held": rows * ROW,
        "block_lanes": [512, 512]}}}


def _moe(pairs, touched, rows):
    """Every expert is held: each routed pair forms a row here."""
    return {"assignments": pairs, "assignments_held": pairs,
            "experts_touched": touched, "rows_by_expert": rows}


# Two of the eight expert layers' rows over the window. Layer 0: expert 9
# took 120 of 2,640 rows (mean 41.25: 2.91); layer 1: even.
ROWS_0 = [40] * 64
ROWS_0[9] = 120
ROWS_1 = [40] * 64
# A 3 s slice of which the device was busy 2.0 s: 1.2 s in the grouped
# products, 0.1 s in the paged reads; two ticks wholly inside it (300 and
# 128 tokens, top 4 of 64 in 8 expert layers), two cut by its edges and left
# out whole.
RUN = {
    "trace": {"busy_s": 2.0, "window_s": 3.0, "planes": 1, "op_seconds": {
        "%ragged-dot-none f32[1536,3072]": 0.7,
        "%ragged-dot-none f32[1536,2048]": 0.45,
        "%ragged-dot-metadata (tuple)": 0.05,
        "%_paged_call bf16[128,8,4,64]": 0.06,
        "%_paged_call bf16[130,8,512,64]": 0.04,
        "%fusion f32[384,65536]": 0.7}},
    "slice": {"begin": 100.0, "end": 103.0},
    "peaks": V5E, "config": CONFIG, "cell": {"name": CELL},
    "spans": {"gateway": [], "worker_1": [
        _tick(99.99, 50, ctx_tokens=10 ** 8, ctx_tokens_full=10 ** 8,
              moe_assignments=10 ** 7, moe_assignments_held=10 ** 7,
              moe_experts_touched=10 ** 4),
        _tick(100.5, 30, width=256, conv_chunk_tokens=180, conv_chunk_rows=1,
              conv_step_rows=120, conv_step_slots=128, ctx_tokens=250000,
              ctx_tokens_full=250000, moe_assignments=9600,
              moe_assignments_held=9600, moe_experts_touched=512),
        _tick(101.0, 24, width=256, conv_chunk_tokens=0, conv_chunk_rows=0,
              conv_step_rows=128, conv_step_slots=128, ctx_tokens=262000,
              ctx_tokens_full=262000, moe_assignments=4096,
              moe_assignments_held=4096, moe_experts_touched=500),
        _tick(102.99, 50, ctx_tokens=10 ** 8, ctx_tokens_full=10 ** 8,
              moe_assignments=10 ** 7, moe_assignments_held=10 ** 7,
              moe_experts_touched=10 ** 4)]},
    "stats_before": {"worker_1": {"moe": _moe(1000, 100,
                                              [[0] * 64, [0] * 64])}},
    "stats_after": {"worker_1": {
        "moe": _moe(1000 + 54784, 100 + 6848, [ROWS_0, ROWS_1]),
        "state_pool": {"rows_total": 128, "rows_peak": 112, "rows_held": 90},
        "kv_pool": {"block_lanes": [512, 512],
                    "state_bytes_held": 90 * ROW}}},
    # the K/V pool held most in the second sample
    "pool_samples": [_pool(1.0, 9000, 112), _pool(1.5, 10240, 100),
                     _pool(2.0, 9500, 112)],
}
WANT = {
    "kernel.moe_experts_busy": 60.0,
    "kernel.paged_attn_busy": 5.0,
    # 1012 touched experts x 18.9 MB = 19.1 GB: 23.3 ms at the HBM peak (the
    # 13,696 pairs' 0.26 TFLOP take 1.3 ms), of 1.2 s
    "kernel.moe_experts_roofline": 100 * (1012 * EXPERT / 819e9) / 1.2,
    # 512,000 tokens x 2 layers x 2 x 8 x 64 x 2 B = 2.10 GB: 2.56 ms; their
    # FLOPs (x 32 heads x 4 x 64) 8.4 GFLOP: 0.04 ms. Of 0.1 s
    "kernel.paged_attn_roofline": 100 * (512000 * 4096 / 819e9) / 0.1,
    "moe.rows_per_touched_expert": 54784 / 6848,
    "moe.expert_load_imbalance": (120 * 64 / 2640 + 1.0) / 2,
    "state.rows_peak_share": 87.5,
    # 100 rows' tails over 10,240 blocks x 65,536 B
    "state.bytes_over_cache_bytes": 100 * ROW / (10240 * BLOCK),
    "kv.blocks_peak_share": 25.0,
}
MERGED = sorted(WANT)


@pytest.mark.parametrize("name", MERGED)
def test_reader_arithmetic(name):
    assert _reader(name)(RUN) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", MERGED)
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """A program that states these sizes and runs none of it; and a run with
    no trace. A reader returns None and does not raise."""
    run = dict(RUN, trace=dict(RUN["trace"], op_seconds={
        "%fusion f32[384,65536]": 0.7}))
    run["spans"] = {"gateway": [], "worker_1": [_tick(100.5, 50, width=256)]}
    run["stats_before"] = {"worker_1": {}}
    run["stats_after"] = {"worker_1": {"mixed": {"ticks": 9}}}
    run["pool_samples"] = [{"t": 1.0, "kv_pool": {"worker_1": None}}]
    assert _reader(name)(run) is None
    run["trace"] = run["slice"] = run["peaks"] = None
    assert _reader(name)(run) is None


def test_the_figures_pr_60_s_entry_counted_by_hand():
    """PERF.md, 'Open since PR 60' (a) and (b): an expert is 3 x 2048 x 1536
    x 2 B, a token's cache 4,096 B in the two attention layers (head size 64
    is d_model / n_heads: the file states none); a convolution keeps a tail
    and has no kernel of a recurrence, so the state's readers of a kernel
    find no sizes."""
    size = sizes(CONFIG)
    assert size["recurrence"] is None
    assert roofline_moe_mla.expert_bytes(1, 2048, 1536, 2) == EXPERT \
        == 18874368
    assert roofline.attention_bytes(1, 2, 8, 64, 2) == 4096
    assert size["attention"]["lanes"] * size["attention"]["layers"] \
        * size["attention"]["bytes_per_element"] == 4096
    assert _reader("kernel.state_step_busy")(RUN) is None
    assert _reader("kernel.state_step_roofline")(RUN) is None


def _run(parts, modules):
    """A run object whose trace was reduced to `parts` (self seconds) and
    `modules` (runs in ms), one second busy of a two-second slice."""
    every = {part: {"self_s": 0.0, "flops": 0.0, "bytes": 0.0, "ops": 0}
             for part in (*xplane_scopes.STEP_PARTS, xplane_scopes.UNSCOPED)}
    for part, seconds in parts.items():
        every[part] = dict(every[part], self_s=seconds, ops=1)
    return {"trace": {"busy_s": 1.0, "window_s": 2.0},
            "scopes": {"busy_s": 1.0, "window_s": 2.0, "parts": every,
                       "modules": modules}}


def test_the_listed_readers_read_the_cell_s_step_on_a_hand_made_trace():
    """The parts this family's step opens (tests/test_lfm2.py holds them):
    the experts 0.78 s of one busy second, the conv operators' three parts
    0.04 together, attention 0.07 of which the read 0.03; `mixer/chunk`
    holds no op, so `step.mixer_chunk_busy` reads 0.0 and the cell is NOT on
    its list."""
    run = _run({"moe/experts": 0.78, "moe/route": 0.02, "mlp": 0.01,
                "mixer/in": 0.02, "mixer/step": 0.01, "mixer/out": 0.01,
                "attn/qkv": 0.02, "attn/write": 0.01, "attn/read": 0.03,
                "attn/out": 0.01, "head": 0.05, "sample": 0.01,
                xplane_scopes.UNSCOPED: 0.02},
               {"tick_w1": [17.0, 19.0, 18.0],
                "tick_w256": [30.0, 34.0, 32.0]})
    want = {"step.attn_busy": 7.0, "step.attn_read_busy": 3.0,
            "step.ffn_busy": 81.0, "step.moe_experts_busy": 78.0,
            "step.mixer_busy": 4.0, "step.head_busy": 5.0,
            "step.sample_busy": 1.0, "step.unscoped_busy": 2.0,
            "step.chunk_run_ms": 32.0}
    assert sorted(want) == sorted(LISTED)
    for name, value in want.items():
        assert abs(_reader(name)(run) - value) < 1e-9, name
    assert _reader("step.mixer_chunk_busy")(run) == 0.0


def test_the_listed_readers_are_null_proof():
    """No op under a part reads 0.0 and not an error; no trace at all, and a
    slice in which no tick program ran, read nothing."""
    empty = _run({}, {})
    for name in LISTED:
        got = _reader(name)(empty)
        assert got is None if name.endswith("_run_ms") else got == 0.0, name
    untraced = {"trace": None}
    assert all(_reader(name)(untraced) is None for name in LISTED)
