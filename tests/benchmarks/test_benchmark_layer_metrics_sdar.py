"""The four per-layer readers of a block-decoding lane's passes that PR 53
lists for `sdar-30b-a3b-chat-7l.reply` (`sched.passes_per_block`,
`sched.tokens_per_row_tick`, `sched.commit_pass_share`,
`step.block_decode_ms`) and the seven merged readers the cell is listed on
since PR 68 (`kernel.paged_attn_*`, `kernel.moe_experts_*`,
`moe.rows_per_touched_expert`, `moe.expert_load_imbalance`,
`kv.blocks_peak_share`), on the made-up run and at the hand-computed values
that pinned PR 53's copies of them (`kernel.block_attn_*`,
`kernel.moe_e128_*`, `moe.e128_*`, `kv.block_pool_peak_share`): the merged
readers at THIS configuration's sizes (the read under a block-causal mask,
whose pairs the lane counts; 128 whole experts). The counting by
hand-computed cases, and the rehearsal of a small cell through
benchmarks/run.py.

`WANT` is this file's part of the table of pins: the hook in
tests/conftest.py joins every `test_benchmark_layer_metrics_*.py`'s `WANT`
to the table test_benchmark_layer_metrics.py holds the `per_layer` list to."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH, rehearsal_cells  # noqa: E402

from lib import roofline, roofline_moe_mla, roofline_sdar  # noqa: E402
from lib.roofline_sizes import sizes  # noqa: E402

ROOT = os.path.dirname(BENCH)
V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL = "sdar-30b-a3b-chat-7l.reply"


def _reader(metric):
    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat-7l.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "configs", "laguna-s-2.1-5l.json")) as f:
    OTHER = json.load(f)


def _tick(start, ms, **attrs):
    return {"op": "mixed_step", "start_ts": start, "ts": start + ms / 1e3,
            "duration_us": ms * 1e3, "attrs": attrs}


def _runs(start, ms, denoise, commit, **attrs):
    return _tick(start, ms, width=1, run_width=4, denoise_rows=denoise,
                 commit_rows=commit, **attrs)


TOKEN = 7 * 2 * 4 * 128 * 2             # a token's K and V, 7 layers: 14,336 B
EXPERT = 3 * 2048 * 768 * 2             # an expert's three matrices: 9.4 MB
# Two layers' rows over the window: layer 0's expert 5 took 400 of 12,800
# rows (mean 100: 4.0); layer 1 even but for expert 9's 250 of 12,950.
ROWS_0 = [100] * 128
ROWS_0[5], ROWS_0[6], ROWS_0[7], ROWS_0[8] = 400, 0, 0, 0
ROWS_1 = [100] * 128
ROWS_1[9] = 250


def _mixed(ticks, denoise, commit, blocks, tokens):
    return {"ticks": ticks, "denoise_passes": denoise,
            "commit_passes": commit, "blocks_finished": blocks,
            "decode_tokens": tokens}


def _moe(assignments, touched, rows):
    return {"assignments": assignments, "experts_touched": touched,
            "rows_by_expert": rows}


def _pool(t, held):
    return {"t": t, "kv_pool": {"worker_1": {
        "blocks_total": 9216, "blocks_free": 9216 - held}}}


# A 3 s slice of which the device was busy 2.9 s: 1.5 s in the grouped
# products, 0.4 s in the block-mask reads (the run call and the tall call);
# two ticks wholly inside it (one of runs alone, one with a chunk), two cut
# by its edges and left out whole.
RUN = {
    "trace": {"busy_s": 2.9, "window_s": 3.0, "planes": 1, "op_seconds": {
        "%ragged-dot-none f32[4224,1536]": 0.9,
        "%ragged-dot-none f32[4224,2048]": 0.6,
        "%block_mask_read bf16[64,4,32,128]": 0.3,
        "%block_mask_read bf16[97,4,128,128]": 0.1,
        "%fusion f32[256,151936]": 0.2}},
    "slice": {"begin": 100.0, "end": 103.0},
    "peaks": V5E, "config": CONFIG, "cell": {"name": CELL},
    "spans": {"gateway": [], "worker_1": [
        _runs(99.99, 20, 64, 0, ctx_tokens_full=10 ** 8, attn_pairs=10 ** 9,
              moe_assignments=10 ** 7, moe_experts_touched=10 ** 4),
        _runs(100.5, 16, 51, 13, ctx_tokens_full=64000, attn_pairs=256000,
              moe_assignments=14336, moe_experts_touched=896),
        dict(_runs(101.0, 30, 50, 12, prefill_tokens=256,
                   ctx_tokens_full=63000, attn_pairs=310000,
                   moe_assignments=28224, moe_experts_touched=896),
             attrs=dict(_runs(0, 0, 50, 12)["attrs"], width=256,
                        prefill_tokens=256, ctx_tokens_full=63000,
                        attn_pairs=310000, moe_assignments=28224,
                        moe_experts_touched=896)),
        _runs(101.5, 18, 52, 12),
        _runs(102.99, 20, 64, 0, ctx_tokens_full=10 ** 8,
              attn_pairs=10 ** 9, moe_assignments=10 ** 7,
              moe_experts_touched=10 ** 4)]},
    "stats_before": {"worker_1": {
        "mixed": _mixed(100, 400, 90, 100, 400),
        "moe": _moe(1000, 100, [[0] * 128, [0] * 128])}},
    "stats_after": {"worker_1": {
        "mixed": _mixed(100 + 3000, 400 + 153600, 90 + 37800, 100 + 38400,
                        400 + 153600),
        "moe": _moe(1000 + 102400, 100 + 5120, [ROWS_0, ROWS_1]),
        "kv_pool": {"blocks_total": 9216, "blocks_free": 9216}}},
    # the pool held most in the second sample
    "pool_samples": [_pool(1.0, 3000), _pool(1.5, 3686.4), _pool(2.0, 3500)],
}
WANT = {
    # (153,600 + 37,800) passes over 38,400 blocks: every block but a row's
    # last commits
    "sched.passes_per_block": (153600 + 37800) / 38400,
    "sched.tokens_per_row_tick": 153600 / (153600 + 37800),
    # 13 + 12 + 12 commit rows of 64 + 62 + 64 + 2 x 64 run rows
    "sched.commit_pass_share": 100 * 37 / (64 * 2 + 64 + 62 + 64),
    # the ticks with no chunk aboard: 16, 18, 20, 20 ms
    "step.block_decode_ms": 18.0,
    "kernel.paged_attn_busy": 100 * 0.4 / 2.9,
    "kernel.moe_experts_busy": 100 * 1.5 / 2.9,
    # 127,000 tokens x 14,336 B = 1.82 GB: 2.22 ms at the HBM peak (566,000
    # pairs x 7 x 32 x 512 = 65 GFLOP: 0.33 ms), of 0.4 s
    "kernel.paged_attn_roofline": 100 * (127000 * TOKEN / 819e9) / 0.4,
    # 1792 touched experts x 9.4 MB = 16.9 GB: 20.6 ms at the HBM peak
    # (42,560 pairs' 0.4 TFLOP take 2 ms), of 1.5 s
    "kernel.moe_experts_roofline": 100 * (1792 * EXPERT / 819e9) / 1.5,
    "moe.rows_per_touched_expert": 102400 / 5120,
    "moe.expert_load_imbalance": (400 * 128 / 12800 + 250 * 128 / 12950) / 2,
    "kv.blocks_peak_share": 40.0,
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
    """This configuration under the PARENT's program (the driver lays the
    benchmark's files over the parent's checkout): no counter, no span
    attr, no kernel of that name, no sample of a pool; and a run with no
    trace. A reader returns None and does not raise."""
    bare = dict(RUN, trace=dict(RUN["trace"], op_seconds={
        "%fusion f32[64,151936]": 0.4}))
    bare["spans"] = {"gateway": [], "worker_1": [
        _tick(100.5, 30, width=256, prefill_tokens=200)]}
    bare["pool_samples"] = []
    bare["stats_before"] = {"worker_1": {"mixed": {"ticks": 1,
                                                   "decode_tokens": 5}}}
    bare["stats_after"] = {"worker_1": {"mixed": {"ticks": 9,
                                                  "decode_tokens": 500}}}
    assert _reader(name)(bare) is None
    bare["trace"] = bare["slice"] = bare["peaks"] = None
    assert _reader(name)(bare) is None


def test_sizes_of_the_configuration_as_run():
    assert sizes(CONFIG) == {
        "attention": {"kernel": "block_mask_read", "layers": 7, "heads": 32,
                      "kv_heads": 4, "head_dim": 128, "lanes": 1024,
                      "bytes_per_element": 2},
        "experts": {"kernel": "ragged-dot", "matrices": 3, "rows": 2048,
                    "cols": 768, "held": (0, 128),
                    "bytes_per_element": 2},
        "recurrence": None}
    assert roofline_sdar.decodes_by_blocks({"config": CONFIG})
    assert not roofline_sdar.decodes_by_blocks({"config": OTHER})


def test_a_token_of_k_and_v_is_14_336_bytes_an_expert_9_4_mb():
    """ISSUE 53's figures: 7 layers x 2 x 4 heads x 128 x 2 B a token; 3 x
    2048 x 768 x 2 B an expert."""
    assert roofline.attention_bytes(1, 7, 4, 128, 2) == TOKEN == 14336
    assert roofline_moe_mla.expert_bytes(1, 2048, 768, 2) == EXPERT
    assert EXPERT == 9437184


@pytest.mark.parametrize("pos0,qlen,pairs", [
    (0, 4, 16),                 # a first block: 4 queries see 4 positions
    (1000, 4, 4 * 1004),        # a run: 4 queries see the context and it
    (8, 8, 4 * 12 + 4 * 16),    # two blocks of a chunk
    (0, 256, 4 * 4 * 64 * 65 // 2),     # a first chunk of 64 blocks
    (512, 0, 0),
])
def test_the_pairs_the_block_mask_keeps(pos0, qlen, pairs):
    assert roofline_sdar.block_pairs(pos0, qlen, 4) == pairs
    brute = sum(((pos0 + i) // 4 + 1) * 4 for i in range(qlen))
    assert brute == pairs


def test_a_tick_of_runs_is_bound_by_its_experts_matrices_then_its_context():
    """64 runs of 4 tokens touch all 128 experts of 7 layers: 896 x 9.4 MB
    = 8.46 GB, 10.3 ms at the HBM peak (ISSUE 53: a tick reads 9.35 GB); its
    14,336 pairs are 3 x 2 x 2048 x 768 operations each, 135 GFLOP, 0.7 ms:
    16 rows an expert against a ridge near 240. The rows' contexts (64 x
    ~1000 tokens x 14,336 B = 0.92 GB) are 1.1 ms."""
    n_bytes = roofline_moe_mla.expert_bytes(896, 2048, 768, 2)
    flops = roofline_moe_mla.expert_flops(14336, 2048, 768)
    assert 8.4e9 < n_bytes < 8.5e9 and flops == 14336 * 6 * 2048 * 768
    assert roofline.floor_seconds(n_bytes, flops, V5E) == pytest.approx(
        n_bytes / 819e9)
    assert flops / V5E["bf16_flops_per_s"] < 0.7e-3
    ctx = roofline.attention_bytes(64 * 1000, 7, 4, 128, 2)
    assert 1.1e-3 < ctx / 819e9 < 1.2e-3


# -- the rehearsal ------------------------------------------------------------------

def test_a_small_cell_reads_every_reader_through_the_harness(
        tmp_path):
    """benchmarks/run.py on tests/benchmarks/data/BENCHMARK.sdar.test.json
    with what BENCHMARK.json lists for the cell today
    (sdar-small-test behind the HTTP front, a closed loop): `correct` is
    true against references/sdar.py's replay, and every reader that needs
    no device reads a number."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark-file",
         rehearsal_cells(tmp_path, "sdar", CELL),
         "--workload", "sdar.closed", "--seed", "5", "--seconds", "2",
         "--trace", "1"],
        env=dict(os.environ, TPU_ENGINE_PLATFORM="cpu", JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = {name: m["value"] for name, m in line["metrics"].items()}
    for name in ("sched.passes_per_block", "sched.tokens_per_row_tick",
                 "sched.commit_pass_share", "step.block_decode_ms",
                 "moe.rows_per_touched_expert",
                 "moe.expert_load_imbalance", "kv.blocks_peak_share",
                 "sched.decode_rows_per_tick", "step.prefill_ms",
                 "sched.prefill_tick_share", "sched.itl_prefill_share"):
        assert name in got, name
    # 8 new tokens a request are 2 blocks: 8 denoise passes and 1 commit.
    assert 4.0 < got["sched.passes_per_block"] <= 5.0 or \
        3.5 < got["sched.passes_per_block"] <= 5.0
    assert 0 < got["sched.prefill_tick_share"] < 100
    assert "lane worker_1 decodes by blocks of 4 (sequential, 1 a pass): " \
        "ticks run one ahead" in out.stdout
