"""lib/roofline.py and lib/roofline_sizes.py pinned by cases computed by
hand, and the traced slice as the sampler hands it to the readers."""

import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH  # noqa: E402

from lib import roofline  # noqa: E402
from lib.roofline_sizes import sizes  # noqa: E402

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _kv(layers, heads, kv_heads, head_dim, kernel="paged"):
    return {"kernel": kernel, "layers": layers, "heads": heads,
            "kv_heads": kv_heads, "head_dim": head_dim,
            "lanes": 2 * kv_heads * head_dim, "bytes_per_element": 2}


def _latent(layers, heads):
    return {"kernel": "mla_latent", "layers": layers, "heads": heads,
            "latent": 512, "rope": 64, "lanes": 576, "bytes_per_element": 2}


def _experts(rows, cols, held, matrices=3):
    return {"kernel": "ragged-dot", "matrices": matrices, "rows": rows,
            "cols": cols, "held": held, "bytes_per_element": 2}


def _ssd(layers, heads, state, groups):
    return {"kind": "ssd", "layers": layers, "heads": heads, "state": state,
            "groups": groups, "step": "ssd_step", "chunk": "ssd_chunk"}


def _delta(kind, layers, heads, state, gate_lanes):
    return {"kind": kind, "layers": layers, "heads": heads, "state": state,
            "gate_lanes": gate_lanes, "step": kind + "_step",
            "chunk": kind + "_chunk"}


# Every committed configuration: the sizes its family's library of PRs
# 27-64 read from it (lib/roofline.py `attention_sizes`, the `sizes` of
# lib/roofline_<family>.py), and for the two configurations that came when
# `per_layer` was full, the sizes their PRs' PERF.md entries counted by hand.
SIZES = {
    "gpt2-large": (_kv(36, 20, 20, 64), None, None),
    "mistral-7b-v0.2-8l": (_kv(8, 32, 8, 128), None, None),
    "moonlight-16b-a3b-7l": (_latent(7, 16),
                             _experts(2048, 1408, (0, 64)), None),
    # two attention classes: lib/roofline_laguna.py has their sizes
    "laguna-s-2.1-5l": (None, _experts(3072, 1024, (0, 128)), None),
    "olmo-hybrid-7b-12l": (_kv(3, 30, 30, 128), None,
                           _delta("gdn", 9, 30, (192, 96), 0)),
    "kimi-linear-48b-a3b-5l": (_latent(1, 32),
                               _experts(2304, 1024, (0, 128)),
                               _delta("kda", 4, 32, (128, 128), 128)),
    "falcon-h1-34b-6l": (_kv(6, 20, 4, 128), None,
                         _ssd(6, 32, (128, 256), 2)),
    "nemotron-3-super-120b-a12b-11l": (
        _kv(1, 32, 2, 128), _experts(1024, 2688, (0, 128), matrices=2),
        _ssd(5, 128, (64, 128), 8)),
    "sdar-30b-a3b-chat-7l": (_kv(7, 32, 4, 128, kernel="block_mask_read"),
                             _experts(2048, 768, (0, 128)), None),
    # a cache plane a (pass, layer): 4 x 48
    "ouro-2.6b": (_kv(192, 16, 16, 128), None, None),
    # 2 attention layers of the 9 as run (the 7 conv layers keep a tail,
    # no recurrence with a kernel of its own); every expert held
    "lfm2-24b-a2b-9l": (_kv(2, 32, 8, 64),
                        _experts(2048, 1536, (0, 64)), None),
    # 9 mamba layers and 1 attention layer of the 10 as run; half held
    "granite-4.0-h-small-10l": (_kv(1, 32, 8, 128),
                                _experts(4096, 768, (0, 36)),
                                _ssd(9, 128, (64, 128), 1)),
}


def test_every_configuration_pinned_here_is_committed():
    """The twelve of PR 68. A configuration that comes later pins its sizes
    in its own test file (test_benchmark_layer_metrics_merged.py demands a
    made-up run at them of every cell on a merged list)."""
    committed = {name[:-len(".json")]
                 for name in os.listdir(os.path.join(BENCH, "configs"))}
    assert set(SIZES) <= committed


@pytest.mark.parametrize("name", sorted(SIZES))
def test_sizes_of_the_configurations_as_run(name):
    attention, experts, recurrence = SIZES[name]
    assert sizes(_config(name)) == {"attention": attention,
                                    "experts": experts,
                                    "recurrence": recurrence}


def test_sizes_take_a_stated_head_size_and_a_quantized_pool():
    config = {"kwargs": {"n_layers": 16, "d_model": 2048, "n_heads": 16,
                         "d_head": 96},
              "serving": {"dtype": "bfloat16", "gen_kv_quantize": "int8"}}
    assert sizes(config)["attention"] == {
        "kernel": "paged", "layers": 16, "heads": 16, "kv_heads": 16,
        "head_dim": 96, "lanes": 3072, "bytes_per_element": 1}
    # A run object that carries no configuration states nothing.
    assert sizes({}) == {"attention": None, "experts": None,
                         "recurrence": None}


def test_sizes_of_a_family_in_words_it_does_not_know_are_none_by_part():
    """The next family edits no file: a part `sizes` has no word for reads
    None (the family brings that kernel's reader and counts), and the parts
    it does state are still read, so its cell can join those merged lists."""
    config = {"kwargs": {"n_layers": 4, "d_model": 512, "n_heads": 8,
                         "layer_types": ["attention", "retention",
                                         "retention", "retention"],
                         "retention_heads": 8, "n_experts": 16,
                         "expert_shape": [512, 256]},
              "serving": {"dtype": "bfloat16"}}
    got = sizes(config)
    assert got["experts"] is None and got["recurrence"] is None
    assert got["attention"] == _kv(1, 8, 8, 64)
    # Layers of a kind it does not know, with a recurrence it does not know.
    config["kwargs"]["layer_types"][1:] = ["mamba"] * 3
    assert sizes(config)["recurrence"] is None
    # No depth stated at all: nothing attends as far as it can tell.
    assert sizes({"kwargs": {"d_model": 512}, "serving": {
        "dtype": "bfloat16"}}) == {"attention": None, "experts": None,
                                   "recurrence": None}


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
