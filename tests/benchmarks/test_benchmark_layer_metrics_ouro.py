"""`step.loop_decode_hbm_roofline`, which PR 58 lists for `ouro-2.6b.think`,
and the three merged readers the cell is listed on since PR 68
(`kernel.paged_attn_busy`, `kernel.paged_attn_roofline`,
`kv.blocks_peak_share`), on the made-up run and at the hand-computed values
that pinned PR 58's copies of them (`kernel.mha16_attn_*`,
`kv.loop_planes_peak_share`): the merged readers at THIS configuration's
sizes (a cache plane a (pass, layer), 192 of them, counted as layers). The
counting of lib/roofline_ouro.py by hand-computed cases, and the rehearsal
of a small cell through benchmarks/run.py.

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

from lib import roofline, roofline_ouro  # noqa: E402
from lib.roofline_sizes import sizes  # noqa: E402

ROOT = os.path.dirname(BENCH)
V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL = "ouro-2.6b.think"


def _reader(metric):
    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "configs", "mistral-7b-v0.2-8l.json")) as f:
    OTHER = json.load(f)


def _tick(start, ms, **attrs):
    return {"op": "mixed_step", "start_ts": start, "ts": start + ms / 1e3,
            "duration_us": ms * 1e3, "attrs": attrs}


def _looped(start, ms, width, ctx):
    return _tick(start, ms, width=width, ctx_tokens=ctx, ut_steps=4,
                 kv_planes=192)


PLANE_TOKEN = 2 * 16 * 128 * 2          # a token's K and V in one plane: 8,192 B
LAYER = (4 * 2048 * 2048 + 3 * 2048 * 5632) * 2     # a layer's matrices
HEAD = 2048 * 49152 * 2


def _pool(t, held):
    return {"t": t, "kv_pool": {"worker_1": {
        "blocks_total": 320, "blocks_free": 320 - held}}}


# A 3 s slice of which the device was busy 2.95 s, 0.5 s of it in the paged
# reads (the short call and a chunk's tall call); three ticks wholly inside
# it (two of decode rows alone at contexts of 2800 and 3000 tokens, one with
# a chunk), two cut by its edges and left out whole. The width-1 program ran
# 36, 37 and 38 ms on the device.
RUN = {
    "trace": {"busy_s": 2.95, "window_s": 3.0, "planes": 1, "op_seconds": {
        "%_paged_call bf16[8,16,1,128]": 0.45,
        "%_paged_call bf16[10,16,128,128]": 0.05,
        "%multiply_reduce_fusion (tuple)": 1.1,
        "%fusion f32[8,5632]": 0.5}},
    "scopes": {"busy_s": 2.95, "window_s": 3.0, "parts": {},
               "modules": {"tick_w1": [36.0, 37.0, 38.0],
                           "tick_w256": [80.0]}},
    "slice": {"begin": 100.0, "end": 103.0},
    "peaks": V5E, "config": CONFIG, "cell": {"name": CELL},
    "spans": {"gateway": [], "worker_1": [
        _looped(99.99, 37, 1, 10 ** 7),
        _looped(100.5, 37, 1, 2800),
        _looped(101.0, 37, 1, 3000),
        _looped(101.5, 80, 256, 2900),
        _looped(102.99, 37, 1, 10 ** 7)]},
    "stats_before": {"worker_1": {"mixed": {"ticks": 10, "kv_planes": 192}}},
    "stats_after": {"worker_1": {"mixed": {"ticks": 1400, "kv_planes": 192,
                                           "ut_steps": 4},
                                 "kv_pool": {"blocks_total": 320,
                                             "blocks_free": 320}}},
    # the pool held most in the second sample
    "pool_samples": [_pool(1.0, 150), _pool(1.5, 200), _pool(2.0, 180)],
}
WANT = {
    "kernel.paged_attn_busy": 100 * 0.5 / 2.95,
    # 8,700 context tokens x 192 planes x 8,192 B = 13.7 GB: 16.7 ms at the
    # HBM peak (their 8,700 x 192 x 16 x 512 = 13.7 GFLOP take 0.07 ms), of
    # 0.5 s
    "kernel.paged_attn_roofline":
        100 * (8700 * 192 * PLANE_TOKEN / 819e9) / 0.5,
    # the two width-1 ticks' bytes: 4 x 48 layers, the planes of 2800 and
    # 3000 tokens, the head; nearest-rank median: the first; over 37 ms
    "step.loop_decode_hbm_roofline":
        100 * ((4 * 48 * LAYER + 2800 * 192 * PLANE_TOKEN + HEAD) / 819e9)
        / 37e-3,
    "kv.blocks_peak_share": 62.5,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _reader(name)(RUN) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(WANT))
def test_no_share_of_the_made_up_run_passes_its_peak(name):
    assert 0.0 < WANT[name] < 100.0


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """This configuration's file over a program that notes no pass, reads
    through no paged call and samples no pool; and a run with no trace. A
    reader returns None and does not raise."""
    run = dict(RUN, trace=dict(RUN["trace"], op_seconds={
        "%fusion f32[8,5632]": 0.5}))
    run["spans"] = {"gateway": [], "worker_1": [
        _tick(100.5, 30, width=256, prefill_tokens=200),
        _tick(100.6, 20, width=1)]}
    run["stats_before"] = {"worker_1": {"mixed": {"ticks": 1}}}
    run["stats_after"] = {"worker_1": {"mixed": {"ticks": 9}}}
    run["pool_samples"] = []
    assert _reader(name)(run) is None
    run["trace"] = run["slice"] = run["peaks"] = None
    run["scopes"] = {}
    assert _reader(name)(run) is None


def test_sizes_of_the_configuration_as_run():
    assert roofline_ouro.sizes(CONFIG) == {
        "layers": 48, "passes": 4, "heads": 16, "head_dim": 128,
        "d_model": 2048, "d_ff": 5632, "vocab": 49152,
        "bytes_per_element": 2}
    assert sizes(CONFIG) == {
        "attention": {"kernel": "paged", "layers": 192, "heads": 16,
                      "kv_heads": 16, "head_dim": 128, "lanes": 4096,
                      "bytes_per_element": 2},
        "experts": None, "recurrence": None}
    # Another configuration's uniform step has a plane a layer.
    assert sizes(OTHER)["attention"]["layers"] == 8


def test_issue_58_s_figures_by_hand():
    """A token takes 8,192 B a plane and 1,572,864 B in all 192; a layer's
    matrices are 51,380,224 numbers; a decode tick streams them four times:
    19.7 GB, 24.1 ms at 819 GB/s whatever the batch."""
    size = roofline_ouro.sizes(CONFIG)
    assert roofline_ouro.plane_token_bytes(size) == PLANE_TOKEN == 8192
    assert roofline_ouro.read_bytes(1, 192, size) == 1_572_864
    assert roofline_ouro.layer_bytes(size) == LAYER == 2 * 51_380_224
    assert roofline_ouro.head_bytes(size) == HEAD == 201_326_592
    weights = roofline_ouro.decode_tick_bytes(0, 4, 192, size) - HEAD
    assert weights == 4 * 48 * LAYER
    assert 19.7e9 < weights < 19.8e9
    assert 24.0e-3 < weights / 819e9 < 24.2e-3
    # 8 rows of ~350 tokens: at most 5.4 ms of planes
    planes = roofline_ouro.read_bytes(8 * 350, 192, size)
    assert 5.3e-3 < planes / 819e9 < 5.4e-3
    # the read is bound by its bytes: a pair is 512 operations a head
    flops = roofline.attention_flops(2800, 192, 16, 128)
    assert flops == 2800 * 192 * 16 * 512
    assert flops / 197e12 < planes / 819e9 / 50
    # a plane counted as a layer is the same bytes
    assert roofline.attention_bytes(8 * 350, 192, 16, 128, 2) == planes


# -- the rehearsal ------------------------------------------------------------------

def test_a_small_cell_reads_every_reader_through_the_harness(
        tmp_path):
    """benchmarks/run.py on tests/benchmarks/data/BENCHMARK.ouro.test.json
    with what BENCHMARK.json lists for the cell today
    (ouro-small-test behind the HTTP front, a closed loop): `correct` is
    true against references/ouro.py, the start-up line states the passes
    and the planes, and the reader that needs no device reads a number."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark-file",
         rehearsal_cells(tmp_path, "ouro", CELL),
         "--workload", "ouro.closed", "--seed", "5", "--seconds", "2",
         "--trace", "1"],
        env=dict(os.environ, TPU_ENGINE_PLATFORM="cpu", JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = {name: m["value"] for name, m in line["metrics"].items()}
    for name in ("kv.blocks_peak_share", "sched.decode_rows_per_tick",
                 "step.prefill_ms", "sched.prefill_tick_share",
                 "sched.itl_prefill_share"):
        assert name in got, name
    assert 0 < got["kv.blocks_peak_share"] <= 100
    assert "lane worker_1 3 passes x 3 layers, 9 planes, " in out.stdout
