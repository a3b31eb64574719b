"""The Ouro configuration of the benchmark (benchmarks/configs/
ouro-2.6b.json): nothing of it cut but the lane's context, its arithmetic
re-derived from the catalog's keys (parameters a layer, bytes, pool), its
files held to BENCHMARK.json, and its plain reference, dialect "ouro"
(benchmarks/references/ouro.py): each control the configuration names
changes the logits, and the exit rule is the published one."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH  # noqa: E402

ROOT = os.path.dirname(BENCH)
CELL = "ouro-2.6b.think"

with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
with open(os.path.join(ROOT, "tests", "benchmarks", "data", "configs",
                       "ouro-small-test.json")) as f:
    SMALL = json.load(f)
# The catalog row's keys (model-configs guide, Ouro-2.6B).
PUBLISHED = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
             "intermediate_size": 5632,
             "layer_types": ["full_attention"] * 48,
             "max_position_embeddings": 65536, "max_window_layers": 48,
             "model_type": "ouro", "num_attention_heads": 16,
             "num_hidden_layers": 48, "num_key_value_heads": 16,
             "rms_norm_eps": 1e-06, "rope_scaling": None,
             "rope_theta": 1000000, "sliding_window": None,
             "tie_word_embeddings": False, "total_ut_steps": 4,
             "early_exit_threshold": 1, "use_sliding_window": False,
             "vocab_size": 49152}


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(BENCH, "references", "ouro.py")
    spec = importlib.util.spec_from_file_location("ouro_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def small():
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )

    _ensure_builtin_models_imported()
    spec = create_model(SMALL["factory"], **SMALL["kwargs"])
    return spec, jax.jit(spec.init)(jax.random.PRNGKey(11))


def _sizes(**more):
    return tuple(sorted(dict(SMALL["reference"], **more).items()))


# -- the cut ------------------------------------------------------------------------

def test_every_published_key_is_kept_but_the_lane_s_context():
    entry, = [c for c in BENCHMARK["configs"] if c["name"] == CONFIG["name"]]
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == list(CONFIG["reduced"]) == [
        "max_position_embeddings"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CONFIG[key] != value
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["max_position_embeddings"] == 640


def test_the_kwargs_are_the_published_widths_at_full_depth():
    kw = CONFIG["kwargs"]
    assert (kw["n_layers"], kw["d_model"], kw["n_heads"], kw["head_dim"],
            kw["d_ff"], kw["vocab"], kw["ut_steps"]) == (
        48, 2048, 16, 128, 5632, 49152, 4)
    assert kw["exit_threshold"] == PUBLISHED["early_exit_threshold"] == 1
    assert kw["max_seq"] == CONFIG["max_position_embeddings"]
    assert (kw["rope_theta"], kw["ln_eps"]) == (1e6, 1e-6)
    for key in ("branch_norms", "pass_norm", "cache", "exit", "rope",
                "biases", "weights"):
        assert key in CONFIG["assumed"], key
    ref = CONFIG["reference"]
    assert (ref["ut_steps"], ref["exit_threshold"], ref["n_heads"]) == (
        4, 1.0, 16)


def test_the_bytes_re_derived_from_the_published_keys():
    """ISSUE 58's arithmetic from the config's keys, and the program's own
    tree at those widths (shapes only)."""
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )

    d, h, dh, f, vocab, layers, passes = 2048, 16, 128, 5632, 49152, 48, 4
    attention, swiglu, norms = 4 * d * h * dh, 3 * d * f, 4 * d
    assert (attention, swiglu, norms) == (16777216, 34603008, 8192)
    layer = attention + swiglu + norms
    assert layer == 51388416 and layers * layer == 2466643968
    ends = 2 * vocab * d
    assert ends == 201326592
    total = layers * layer + ends + d + d + 1       # final norm and gate
    assert total == 2667974657
    _ensure_builtin_models_imported()
    spec = create_model(CONFIG["factory"], **CONFIG["kwargs"])
    tree = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    kernels = sum(int(np.prod(x.shape)) for path, x
                  in jax.tree_util.tree_flatten_with_path(tree)[0]
                  if "bias" not in str(path[-1]))   # the source has none
    assert kernels == total - 1                     # but the gate's
    assert 5.33e9 < 2 * total < 5.34e9              # bfloat16
    # K and V of 16 heads x 128 in 4 x 48 planes, 2 B each.
    token = passes * layers * 2 * h * dh * 2
    assert token == 1572864
    (kind,) = spec.config.kv_block_kinds
    assert kind.n_layers == 192 and kind.kv_lanes == (2048, 2048)
    serving = CONFIG["serving"]
    bs = serving["gen_kv_block_size"]
    assert bs * token == 25165824
    assert serving["gen_kv_blocks"] == 8 * 640 // bs + 1 == 321
    assert serving["gen_max_batch_size"] == 8
    pool = 321 * bs * token
    assert 8.07e9 < pool < 8.08e9
    assert 0.85 < (2 * total + pool) / 15.75e9 < 0.86
    # One tensor of the pool stays under 2^31 elements; 341 blocks do not.
    assert 192 * 321 * bs * 2048 < 2 ** 31 < 192 * 342 * bs * 2048
    assert serving["gen_prefix_sharing"] is False
    # 19.7 GFLOP a token: the layers four times, two operations a weight.
    assert 19.7e9 < passes * 2 * layers * (attention + swiglu) < 19.8e9


def test_the_cell_and_its_files():
    cell, = [w for w in BENCHMARK["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG["name"], traffic="think",
                        chips=1)
    with open(os.path.join(BENCH, "traffic", "think.json")) as f:
        traffic = json.load(f)
    assert traffic["loop"] == "closed" and traffic["clients"] == 8
    assert traffic["block"] * traffic["pool"] == 1024
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 192,
                                        "max": 448}
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 96,
                                        "sigma": 0.6, "min": 32, "max": 192}
    # The longest context is the lane's limit, and the pool holds it.
    assert (traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
            == CONFIG["kwargs"]["max_seq"])
    correct = CONFIG["correct"]
    assert correct["prompt_lens"] == [32, 96, 192, 384]
    assert max(correct["prompt_lens"]) > CONFIG["serving"][
        "gen_prefill_chunk"]                        # crosses two chunks
    assert (max(correct["prompt_lens"]) + correct["new_tokens"]
            <= correct["pad_to"] == 640)
    # PR 58's four readers: the whole decode tick's is the cell's own; the
    # paged read's pair and the pool's share are the merged readers' since
    # PR 68 (`kernel.mha16_attn_*`, `kv.loop_planes_peak_share` before).
    listed = [m["name"] for m in BENCHMARK["per_layer"]
              if m.get("workloads") == [CELL]]
    assert "step.loop_decode_hbm_roofline" in listed
    for name in ("step.loop_decode_hbm_roofline", "kernel.paged_attn_busy",
                 "kernel.paged_attn_roofline", "kv.blocks_peak_share"):
        metric, = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
        assert CELL in metric["workloads"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    # The readers by part that were there list the cell.
    for name in ("step.attn_busy", "step.attn_read_busy", "step.ffn_busy",
                 "step.decode_run_ms"):
        metric, = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
        assert CELL in metric["workloads"]
    assert len(BENCHMARK["per_layer"]) <= 128
    assert os.path.exists(os.path.join(BENCH, "references", "ouro.py"))


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "ouro.py")) as f:
        source = f.read()
    assert "tpu_engine" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source


# -- the reference ------------------------------------------------------------------

@pytest.mark.parametrize("control,changes", [
    ({"drop": "branch_norms"}, True),
    ({"drop": "pass_norm"}, True),
    ({"drop": "own_cache"}, True),
    ({"weights_as": "float8_e4m3fn"}, True),
    ({"ut_steps": 2}, True),
    ({}, False),
])
def test_each_control_changes_the_logits(reference, small, control, changes):
    _, params = small
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(1, 250, 64), jnp.int32)
    forward = jax.jit(reference.forward, static_argnums=(2,))
    plain = np.asarray(forward(params, tokens, _sizes()))
    other = np.asarray(forward(params, tokens, _sizes(**control)))
    moved = np.abs(plain - other)[11:59].max()
    assert (moved > 1e-3) == changes, moved


def test_the_exit_rule_is_the_published_one(reference):
    """p_t = lam_t prod_{j<t}(1 - lam_j), the last pass takes the rest; a
    token exits at the first t whose cumulative p reaches the threshold. By
    hand on four passes: lam = (0.1, 0.5, 0.9, .) gives p = (0.1, 0.45,
    0.405, 0.045), cumulative (0.1, 0.55, 0.955, 1)."""
    lam = np.array([0.1, 0.5, 0.9, 0.3])
    logit = np.log(lam / (1 - lam))
    # streams (T, S, d) whose first lane is the gate's logit; the gate reads
    # that lane alone.
    streams = np.zeros((4, 5, 8), np.float32)
    streams[:, :, 0] = logit[:, None]
    gate = {"kernel": jnp.zeros((8, 1)).at[0, 0].set(1.0),
            "bias": jnp.zeros((1,))}
    for threshold, want in ((0.05, 0), (0.3, 1), (0.55, 1), (0.9, 2),
                            (0.99, 3), (1.0, 3)):
        got = np.asarray(reference.exit_pass(gate, jnp.asarray(streams),
                                             threshold))
        assert (got == want).all(), (threshold, got)


def test_the_program_s_exit_rule_is_the_reference_s(reference, small):
    from tpu_engine.models.ouro import exit_pass

    _, params = small
    streams = jax.random.normal(jax.random.PRNGKey(5), (3, 40, 64)) * 3.0
    for threshold in (0.2, 0.5, 0.8):
        np.testing.assert_array_equal(
            np.asarray(exit_pass(params["gate"], streams, threshold)),
            np.asarray(reference.exit_pass(params["gate"], streams,
                                           threshold)))
