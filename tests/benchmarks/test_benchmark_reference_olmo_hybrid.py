"""The "olmo_hybrid" reference (benchmarks/references/olmo_hybrid.py)
against the program's float32 forward at the small test size, `check_served`
telling the served path from a reference with one term changed, the
configuration's widths against the source's and ISSUE 39's cut arithmetic
(6.54 GB of weights, 46,080 B of K/V a token, 21.2 MB of state a row)
against the tree and the pools it builds, the counting of
lib/roofline_gated_delta.py by hand-computed cases, and the rehearsal of the
new cell's metrics through run.py on the CPU (the eleven readers:
test_benchmark_layer_metrics_olmo_hybrid.py)."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import (  # noqa: E402
    BENCH,
    DATA,
    ROOT,
    listed as metrics_listed,
    load_benchmark,
    read_without_a_device,
    rehearsal_cells,
)

from lib import reference, roofline, roofline_gated_delta  # noqa: E402
from lib.roofline_sizes import sizes  # noqa: E402

CELL = "olmo-hybrid-7b-12l.digest"
V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# PR 39's readers, under the names of the merged readers that took their
# place in PR 68 (`step.hybrid_decode_device_ms` went with no successor).
NEW = ["kernel.state_chunk_busy", "kernel.state_chunk_roofline",
       "kernel.state_step_busy", "kernel.state_step_roofline",
       "kernel.paged_attn_busy", "kernel.paged_attn_roofline",
       "state.rows_peak_share", "state.bytes_over_cache_bytes",
       "kv.blocks_peak_share", "step.decode_ms"]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        "under_test_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, name)


@pytest.fixture(scope="module")
def small():
    import jax
    import jax.numpy as jnp

    from tpu_engine.models import registry
    from tpu_engine.models.olmo_hybrid import olmo_hybrid_apply

    with open(os.path.join(DATA, "configs",
                           "olmo-hybrid-small-test.json")) as f:
        config = json.load(f)
    registry._ensure_builtin_models_imported()
    spec = registry.create_model(config["factory"], **config["kwargs"])
    params = spec.init(jax.random.PRNGKey(3))
    forward = jax.jit(lambda tokens: olmo_hybrid_apply(
        params, tokens, spec.config, dtype=jnp.float32))

    def program(tokens):
        """Causal: one program over 64 right-padded columns."""
        padded = np.zeros((1, 64), np.int32)
        padded[0, :len(tokens)] = tokens
        return np.asarray(forward(padded)[0, :len(tokens)])

    return (config, spec, params, program,
            _load(os.path.join(BENCH, "references", "olmo_hybrid.py"),
                  "forward"))


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b-12l.json")) as f:
        return json.load(f)


# -- the reference -------------------------------------------------------------

def test_reference_logits_equal_the_program_s_in_float32(small):
    """The dialect "olmo_hybrid": 56 tokens, more than three prefill chunks
    of the test lane, within 2e-4 of the largest logit. The reference scans
    the recurrence a token at a time; the program's forward runs the WY
    form over a sub-chunk."""
    import jax.numpy as jnp

    config, spec, params, program, forward = small
    tokens = np.random.default_rng(0).integers(
        0, spec.config.vocab, size=56).astype(np.int32)
    ours = np.asarray(forward(params, jnp.asarray(tokens),
                              reference.sizes_of(config["reference"])))
    theirs = program(tokens)
    assert ours.shape == theirs.shape == (56, spec.config.vocab)
    assert ours.dtype == np.float32
    assert np.abs(ours - theirs).max() < 2e-4 * np.abs(theirs).max()


@pytest.fixture(scope="module")
def served(small):
    """Three prompts and the program's eight greedy tokens after each,
    decoded once for all the controls."""
    _, spec, _, program, _ = small
    rng = np.random.default_rng(1)
    samples = []
    for length in (5, 20, 50):
        prompt = [int(t) for t in rng.integers(0, spec.config.vocab, length)]
        seq = list(prompt)
        for _ in range(8):
            seq.append(int(program(np.asarray(seq, np.int32))[-1].argmax()))
        samples.append((prompt, seq[length:]))
    return samples


@pytest.mark.parametrize("control", [
    {"drop": "decay"}, {"drop": "double"}, {"drop": "conv_tail"},
    {"drop": "state"}])
def test_check_served_accepts_greedy_tokens_and_refuses_a_control(
        small, served, control):
    """The served tokens against the reference, then against the reference
    with the decay left out, b not doubled, the conv tail or the state
    dropped at every chunk boundary: each reads NOT correct. (The controls
    one precision down, `drop: state_bf16` and `weights_as: float8_e4m3fn`,
    are read on the chip at the published widths.)"""
    config, spec, params, program, forward = small
    samples = served
    ok, details = reference.check_served(forward, params, config["reference"],
                                         samples, 0.05, 0.9, pad_to=64)
    assert ok, details
    assert details["exact_share"] == 1.0 and details["positions"] == 24
    ok, details = reference.check_served(
        forward, params, dict(config["reference"], **control), samples,
        0.05, 0.9, pad_to=64)
    assert not ok, details


def test_the_test_configuration_is_the_registry_s_small_model(small):
    from tpu_engine.models import registry

    config, spec, _, _, _ = small
    assert spec.config == registry.create_model("olmo_hybrid_small").config
    ref, cfg = config["reference"], spec.config
    assert ref["dialect"] == "olmo_hybrid"
    assert ref["linear"] == ",".join(str(int(x)) for x in cfg.linear)
    assert (ref["n_heads"], ref["lin_heads"], ref["lin_key_dim"],
            ref["neg_eigval"], ref["chunk"]) == (
        cfg.n_heads, cfg.lin_heads, cfg.lin_key_dim, int(cfg.neg_eigval),
        config["serving"]["gen_prefill_chunk"])


# -- the published configuration -------------------------------------------------

def test_every_source_key_is_there_and_only_depth_and_positions_are_reduced(
        published):
    """The catalog's `config` for Olmo-Hybrid-7B, key for key; the two keys
    of `reduced` alone differ, and neither is a width."""
    with open(CATALOG) as f:
        source = next(row for row in map(json.loads, f)
                      if row["name"] == "Olmo-Hybrid-7B")
    p = published
    assert p["source"] == source["source_url"]
    assert list(p["reduced"]) == ["num_hidden_layers",
                                  "max_position_embeddings"]
    for key, value in source["config"].items():
        if key not in p["reduced"]:
            assert p[key] == value, key
    assert (p["num_hidden_layers"], p["max_position_embeddings"]) == (
        12, 16384)
    for key in ("norm_placement", "qk_norm", "rope", "linear_layer",
                "biases", "weights"):
        assert "lternative" in p["assumed"][key] or key in ("biases",
                                                            "weights")
    assert "every layer kept and the whole vocabulary" in p["deployment"]


def test_the_kwargs_are_the_published_widths(published):
    p, k = published, published["kwargs"]
    assert (k["d_model"], k["n_heads"], k["d_ff"], k["vocab"],
            k["lin_heads"], k["lin_key_dim"], k["lin_value_dim"],
            k["conv_width"], k["neg_eigval"], k["ln_eps"]) == (
        p["hidden_size"], p["num_attention_heads"], p["intermediate_size"],
        p["vocab_size"], p["linear_num_key_heads"],
        p["linear_key_head_dim"], p["linear_value_head_dim"],
        p["linear_conv_kernel_dim"], p["linear_allow_neg_eigval"],
        p["rms_norm_eps"])
    assert p["num_key_value_heads"] == p["num_attention_heads"]
    assert p["linear_num_value_heads"] == p["linear_num_key_heads"]
    assert k["head_dim"] * k["n_heads"] == p["hidden_size"]
    assert p["rope_parameters"] == {"rope_theta": None}
    depth = p["num_hidden_layers"]
    assert k["layer_types"] == p["layer_types"][:depth]
    assert k["layer_types"] == (["linear_attention"] * 3
                                + ["full_attention"]) * 3
    assert (k["max_seq"], k["param_dtype"]) == (
        p["max_position_embeddings"], "bfloat16")
    r = p["reference"]
    assert (r["linear"], r["n_heads"], r["lin_heads"], r["lin_key_dim"],
            r["neg_eigval"], r["chunk"]) == (
        "1,1,1,0,1,1,1,0,1,1,1,0", 30, 30, 96, 1, 256)
    s = p["serving"]
    assert (s["dtype"], s["gen_max_batch_size"], s["gen_kv_block_size"],
            s["gen_kv_blocks"], s["gen_mixed_step"], s["gen_prefill_chunk"],
            s["gen_prefix_sharing"]) == ("bfloat16", 16, 16, 8705, True,
                                         256, False)
    # Prompts that cross many chunk boundaries, then decode.
    assert max(p["correct"]["prompt_lens"]) >= 7 * s["gen_prefill_chunk"]
    assert p["correct"]["pad_to"] >= (max(p["correct"]["prompt_lens"])
                                      + p["correct"]["new_tokens"] - 1)


def test_the_configuration_builds_the_model_the_arithmetic_describes(
        published):
    """Shapes only, ISSUE 39's cut: 6.54 GB of bfloat16 weights (a linear
    layer 215.5 M parameters, its mixer 88.7 M; a full one 185.8 M, its
    mixer 59.0 M; embedding and head 770.7 M), a block pool over the 3 full
    layers at 46,080 B a token (6.42 GB), 21.2 MB of state a row."""
    import jax

    from tpu_engine.models import registry
    from tpu_engine.runtime.kv_blocks import dense_block_bytes

    registry._ensure_builtin_models_imported()
    spec = registry.create_model(published["factory"], **published["kwargs"])
    cfg = spec.config
    tree = jax.eval_shape(spec.init, jax.random.PRNGKey(0))

    def count(sub):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(sub))

    n_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(tree))
    assert 6.53e9 < n_bytes < 6.55e9
    layers = tree["layers"]
    # The issue's matrices; zero biases and norm scales add < 0.1 M.
    assert 215.5 < count(layers[0]) / 1e6 < 215.65          # linear
    assert 88.7 < count(layers[0]["lin"]) / 1e6 < 88.8
    assert 185.8 < count(layers[3]) / 1e6 < 185.9           # full
    assert 58.98 < count(layers[3]["attn"]) / 1e6 < 59.05
    assert 126.8 < count(layers[0]["mlp"]) / 1e6 < 126.85
    assert 770.7 < (count(tree["tok_embed"]) + count(tree["head"])) / 1e6 \
        < 770.85
    (full,) = cfg.kv_block_kinds
    assert (full.n_layers, cfg.n_linear_layers) == (3, 9)
    assert dense_block_bytes(full, 16, "bfloat16") == 16 * 46080
    serving = published["serving"]
    assert serving["gen_kv_blocks"] == 16 * (8192 + 512) // 16 + 1
    assert 6.41e9 < serving["gen_kv_blocks"] * 16 * 46080 < 6.43e9
    row = cfg.n_linear_layers * 4 * sum(int(np.prod(s))
                                        for s in cfg.state_row_shapes)
    assert cfg.state_row_shapes == ((30, 192, 96), (3, 11520))
    assert 21.1e6 < row < 21.2e6
    assert 0.35e9 < (serving["gen_max_batch_size"] + 1) * row < 0.37e9


def test_the_benchmark_lists_the_cell_and_its_eleven_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # Found by name: later PRs append after it.
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "olmo-hybrid-7b-12l",
                    "traffic": "digest", "chips": 1, "why": cell["why"]}
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings"]
    assert config["source"].endswith("allenai/Olmo-Hybrid-7B/blob/main/"
                                     "config.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    mine = [by_name[name] for name in NEW]
    assert all(CELL in m["workloads"] for m in mine)
    assert {m["layer"] for m in mine} == {"kernels", "state pool", "KV pool",
                                       "step function"}
    assert all(m["moves"] == "tokens_per_s" for m in mine)
    with open(os.path.join(BENCH, "traffic", "digest.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], traffic["block"],
            traffic["pool"]) == ("closed", 16, 32, 32)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 3072, "sigma": 0.8, "min": 512,
        "max": 8192}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 128,
                                        "max": 512}
    assert traffic["sharing"] == {"share": 0.0}


# -- the counting ----------------------------------------------------------------

def test_sizes_of_the_configuration_as_run(published):
    assert sizes(published) == {
        "attention": {"kernel": "paged", "layers": 3, "heads": 30,
                      "kv_heads": 30, "head_dim": 128, "lanes": 7680,
                      "bytes_per_element": 2},
        "experts": None,
        "recurrence": {"kind": "gdn", "layers": 9, "heads": 30,
                       "state": (192, 96), "gate_lanes": 0,
                       "step": "gdn_step", "chunk": "gdn_chunk"}}


def test_a_state_is_2_21_mb_and_a_token_of_k_v_15_360_bytes(published):
    """ISSUE 39's figures: 30 x 192 x 96 float32 a row and linear layer;
    2 x 30 heads x 128 lanes x 2 B a token and full layer."""
    size = sizes(published)["recurrence"]
    assert roofline_gated_delta.state_bytes(size) == 2211840
    assert roofline.attention_bytes(1, 1, 30, 128, 2) == 15360
    assert roofline.attention_bytes(1, 3, 30, 128, 2) == 46080


def test_a_decode_tick_s_steps_are_bound_by_their_states(published):
    """15 rows x 9 layers: 2 x 2.21 MB of state each and 69 KB of q, k, v
    and read, 0.61 GB, 0.74 ms at the HBM peak; 3 x 2 x 30 x 96 x 192
    operations a row and layer, 0.45 GFLOP, 2 us."""
    size = sizes(published)["recurrence"]
    n_bytes = roofline_gated_delta.recurrence_bytes(15, 15, size)
    assert n_bytes == 15 * 9 * (2 * 2211840 + 30 * 576 * 4)
    flops = roofline_gated_delta.recurrence_flops(15, size)
    assert flops == 15 * 9 * 3 * 2 * 30 * 96 * 192
    assert roofline.floor_seconds(n_bytes, flops, V5E) == pytest.approx(
        n_bytes / 819e9)
    assert flops / V5E["bf16_flops_per_s"] < 3e-6


def test_a_chunk_s_state_is_read_once_a_row_not_once_a_token(published):
    size = sizes(published)["recurrence"]
    one = roofline_gated_delta.recurrence_bytes(1, 241, size)
    assert one == 9 * (2 * 2211840 + 241 * 30 * 576 * 4)
    assert one < roofline_gated_delta.recurrence_bytes(241, 241, size) / 5


# -- the rehearsal -------------------------------------------------------------------

def test_the_rehearsal_lists_every_metric_of_the_new_cell(tmp_path):
    """run.py --trace 1 on the CPU at the small size, a cell list of its
    own with the ten keyless per-layer metrics and the cell's own eleven: the
    span and counter metrics print, what only a device trace gives is left
    out and said so; the untraced run prints the three end-to-end ones."""
    cells = rehearsal_cells(tmp_path, "olmo", CELL)
    real = load_benchmark()
    want = [m["name"] for m in metrics_listed(real, CELL)]
    assert set(NEW) <= set(want)
    assert [m["name"] for m in metrics_listed(real, CELL, "end_to_end")] \
        == ["itl_p95_ms", "tokens_per_s", "setup_s"]
    env = dict(os.environ, TPU_ENGINE_PLATFORM="cpu")
    lines = {}
    for trace in ("1", "0"):
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"),
             "--benchmark-file", cells, "--workload", "olmo.closed",
             "--seed", str(2**31 + 39), "--seconds", "2", "--trace", trace],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=420)
        assert proc.returncode == 0, proc.stderr[-3000:]
        lines[trace] = json.loads(proc.stdout.strip().splitlines()[-1])
        assert lines[trace]["correct"] is True
        assert lines[trace]["failed"] == 0
    assert set(lines["0"]["metrics"]) == {"itl_p95_ms", "tokens_per_s",
                                          "setup_s"}
    got = lines["1"]["metrics"]
    assert set(got) == read_without_a_device(real, CELL)
    assert not {name for name in NEW if name.startswith("kernel.")} \
        & set(got)
    assert got["step.compiles"] == {"value": 0, "unit": "compilations"}
    # Three clients of four slots; states and blocks of the same rows.
    assert got["state.rows_peak_share"]["value"] == 75.0
    assert 0.2 < got["state.bytes_over_cache_bytes"]["value"] < 2.0
    assert 5.0 < got["kv.blocks_peak_share"]["value"] < 40.0
