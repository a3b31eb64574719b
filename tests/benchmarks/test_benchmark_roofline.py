"""lib/roofline.py pinned by cases computed by hand, and the traced slice
as the sampler hands it to the readers."""

import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH  # noqa: E402

from lib import roofline  # noqa: E402

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, want", [
    ("gpt2-large", {"layers": 36, "heads": 20, "kv_heads": 20,
                    "head_dim": 64, "bytes_per_element": 2}),
    ("mistral-7b-v0.2-8l", {"layers": 8, "heads": 32, "kv_heads": 8,
                            "head_dim": 128, "bytes_per_element": 2}),
])
def test_attention_sizes_of_the_configurations_as_run(name, want):
    assert roofline.attention_sizes(_config(name)) == want


def test_attention_sizes_takes_a_stated_head_size_and_a_quantized_pool():
    config = {"kwargs": {"n_layers": 16, "d_model": 2048, "n_heads": 16,
                         "d_head": 96},
              "serving": {"dtype": "bfloat16", "gen_kv_quantize": "int8"}}
    assert roofline.attention_sizes(config) == {
        "layers": 16, "heads": 16, "kv_heads": 16, "head_dim": 96,
        "bytes_per_element": 1}


@pytest.mark.parametrize("args, want", [
    # one token, one layer, one KV head of 64 in bf16: K and V, 128 each
    ((1, 1, 1, 64, 2), 256),
    # 32 rows x 176 tokens, 36 layers, 20 x 64 in bf16: 5120 B a token
    # and layer, 1.04 GB a tick
    ((32 * 176, 36, 20, 64, 2), 1038090240),
    # a 1024-token context at 8 KV heads x 128, 8 layers, int8
    ((1024, 8, 8, 128, 1), 16777216),
])
def test_attention_bytes(args, want):
    assert roofline.attention_bytes(*args) == want


@pytest.mark.parametrize("args, want", [
    # one pair, one layer, one head of 64: 64 multiply-adds for the score
    # and 64 for the value
    ((1, 1, 1, 64), 256),
    # a decode row at context 1000, 8 layers, 32 heads of 128
    ((1000, 8, 32, 128), 131072000),
    # a causal chunk of 256 queries after 768 tokens: 256 * 768 +
    # 256 * 257 / 2 = 229 504 pairs; 32 heads of 128, one layer: 3.76 GFLOP
    ((256 * 768 + 256 * 257 // 2, 1, 32, 128), 3760193536),
])
def test_attention_flops(args, want):
    assert roofline.attention_flops(*args) == want


def test_floor_seconds_is_the_slower_of_memory_and_arithmetic():
    # 819 MB is a millisecond of memory; 197 GFLOP a millisecond of MXU.
    assert roofline.floor_seconds(819e6, 0, V5E) == pytest.approx(1e-3)
    assert roofline.floor_seconds(0, 197e9, V5E) == pytest.approx(1e-3)
    assert roofline.floor_seconds(819e6, 394e9, V5E) == pytest.approx(2e-3)
    assert roofline.floor_seconds(1638e6, 197e9, V5E) == pytest.approx(2e-3)
    # batch's decode tick: memory-bound, 1.27 ms.
    assert roofline.floor_seconds(1038090240, 1038090240, V5E) \
        == pytest.approx(1.2675e-3, rel=1e-4)


def test_the_peaks_table_has_what_the_floor_reads():
    with open(os.path.join(BENCH, "lib", "peaks.json")) as f:
        for kind, peaks in json.load(f).items():
            assert roofline.floor_seconds(1.0, 1.0, peaks) > 0, kind


def test_the_sampler_says_which_slice_it_traced(tmp_path):
    """`traced` is on time.time(), the clock of the spans' `start_ts`, and
    lies inside the calls of the profiler's start and stop."""
    from lib.sut import Sampler

    class Idle:
        def generator_stats(self):
            return {"worker_1": {}}

    t0 = time.time()
    begin = time.monotonic() + 0.2
    sampler = Sampler(Idle(), str(tmp_path), begin, begin + 0.4,
                      period_s=0.05)
    sampler.start()
    time.sleep(1.2)
    sampler.stop()
    traced = sampler.traced
    assert t0 + 0.2 <= traced["begin"] < traced["end"] <= time.time()
    assert 0.2 < traced["end"] - traced["begin"] < 1.0
    assert len(sampler.samples) >= 5
