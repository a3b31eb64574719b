"""The "laguna" reference (benchmarks/references/laguna.py) against the
program's float32 forward at the small test size, `check_served` telling
the served path from a reference with one term changed, the configuration's
widths against the source's and its arithmetic against the tree it builds,
the counting of lib/roofline_laguna.py by hand-computed cases, and the
rehearsal of the new cell's metrics through run.py on the CPU (the nine
readers: test_benchmark_layer_metrics_laguna.py)."""

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

from lib import reference, roofline, roofline_laguna, roofline_moe_mla  # noqa: E402,E501

CELL = "laguna-s-2.1-5l.repo"
# PR 36's readers; its `kernel.moe_held_*` under the merged pair's names
# since PR 68.
MINE = ["kernel.swa_attn_busy", "kernel.swa_attn_roofline",
        "kernel.full_attn_busy", "kernel.full_attn_roofline",
        "kernel.moe_experts_busy", "kernel.moe_experts_roofline",
        "moe.held_assignment_share", "kv.window_over_full_tokens",
        "kv.full_blocks_peak_share"]
V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    from tpu_engine.models.laguna import laguna_apply

    with open(os.path.join(DATA, "configs", "laguna-small-test.json")) as f:
        config = json.load(f)
    registry._ensure_builtin_models_imported()
    spec = registry.create_model(config["factory"], **config["kwargs"])
    params = spec.init(jax.random.PRNGKey(3))
    forward = jax.jit(lambda tokens: laguna_apply(
        params, tokens, spec.config, dtype=jnp.float32))

    def program(tokens):
        """Causal: one program over 48 right-padded columns."""
        padded = np.zeros((1, 48), np.int32)
        padded[0, :len(tokens)] = tokens
        return np.asarray(forward(padded)[0, :len(tokens)])

    return (config, spec, params, program,
            _load(os.path.join(BENCH, "references", "laguna.py"), "forward"))


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "laguna-s-2.1-5l.json")) as f:
        return json.load(f)


# -- the reference -------------------------------------------------------------

def test_reference_logits_equal_the_program_s_in_float32(small):
    """The dialect "laguna": 40 tokens, five windows of the test model and
    more than two prefill chunks of the test lane, within 2e-4 of the
    largest logit. The reference masks a whole sequence and applies every
    held expert; the program's forward sorts pairs into a grouped
    product."""
    import jax.numpy as jnp

    config, spec, params, program, forward = small
    tokens = np.random.default_rng(0).integers(
        0, spec.config.vocab, size=40).astype(np.int32)
    ours = np.asarray(forward(params, jnp.asarray(tokens),
                              reference.sizes_of(config["reference"])))
    theirs = program(tokens)
    assert ours.shape == theirs.shape == (40, spec.config.vocab)
    assert ours.dtype == np.float32
    assert np.abs(ours - theirs).max() < 2e-4 * np.abs(theirs).max()


@pytest.mark.parametrize("control", [
    {"drop": "window"}, {"drop": "gate"}, {"drop": "partial_rope"},
    {"drop": "bias"}, {"drop": "shared"}])
def test_check_served_accepts_greedy_tokens_and_refuses_a_control(small,
                                                                  control):
    """The served tokens against the reference, then against the reference
    with the window layers attending everything, the heads' gate left out,
    the full layers' rope over all lanes, the selection bias or the shared
    expert left out: each reads NOT correct. (The control one precision
    down, `experts_as: float8_e4m3fn`, is read on the chip at the
    published widths.)"""
    config, spec, params, program, forward = small
    rng = np.random.default_rng(1)
    samples = []
    for length in (5, 19, 33):
        prompt = [int(t) for t in rng.integers(0, spec.config.vocab, length)]
        seq = list(prompt)
        for _ in range(8):
            seq.append(int(program(np.asarray(seq, np.int32))[-1].argmax()))
        samples.append((prompt, seq[length:]))
    ok, details = reference.check_served(forward, params, config["reference"],
                                         samples, 0.05, 0.9, pad_to=48)
    assert ok, details
    assert details["exact_share"] == 1.0 and details["positions"] == 24
    ok, details = reference.check_served(
        forward, params, dict(config["reference"], **control), samples,
        0.05, 0.9, pad_to=48)
    assert not ok, details


def test_the_test_configuration_is_the_registry_s_small_laguna(small):
    from tpu_engine.models import registry

    config, spec, _, _, _ = small
    assert spec.config == registry.create_model("laguna-small-test").config
    assert config["reference"]["dialect"] == "laguna"
    ref, cfg = config["reference"], spec.config
    assert ref["heads_per_layer"] == ",".join(map(str, cfg.heads_per_layer))
    assert ref["windowed"] == ",".join(str(int(w)) for w in cfg.windowed)
    assert (ref["window"], ref["top_k"], ref["held_first"]) == (
        cfg.window, cfg.top_k, cfg.held[0])


# -- the published configuration -------------------------------------------------

def test_every_source_key_is_there_and_only_the_four_are_reduced(published):
    """The catalog's `config` for Laguna-S-2.1, key for key; the four keys
    of `reduced` alone differ, and none of them is a width."""
    with open(CATALOG) as f:
        source = next(row for row in map(json.loads, f)
                      if row["name"] == "Laguna-S-2.1")
    p = published
    assert p["source"] == source["source_url"]
    assert list(p["reduced"]) == ["num_hidden_layers", "num_experts",
                                  "vocab_size", "max_position_embeddings"]
    for key, value in source["config"].items():
        if key not in p["reduced"]:
            assert p[key] == value, key
    assert (p["num_hidden_layers"], p["num_experts"], p["vocab_size"],
            p["max_position_embeddings"]) == (5, 128, 50176, 16384)
    assert len(p["assumed"]) >= 5
    for key in ("qk_norm", "attention_gate", "router", "shared_expert",
                "rope"):
        assert p["assumed"][key]
    assert "one of 2" in p["deployment"] and "HALF" in p["deployment"]


def test_the_kwargs_are_the_published_widths(published):
    p, k = published, published["kwargs"]
    assert (k["d_model"], k["n_kv_heads"], k["head_dim"], k["d_ff_dense"],
            k["d_ff_expert"], k["d_ff_shared"], k["n_experts"], k["top_k"],
            k["routed_scale"], k["window"], k["rope_theta"],
            k["window_rope_theta"], k["partial_rotary"], k["yarn_factor"],
            k["yarn_original_max"], k["yarn_beta_fast"],
            k["yarn_beta_slow"], k["yarn_attention_factor"], k["ln_eps"]) == (
        p["hidden_size"], p["num_key_value_heads"], p["head_dim"],
        p["intermediate_size"], p["moe_intermediate_size"],
        p["shared_expert_intermediate_size"], 256,
        p["num_experts_per_tok"], p["moe_routed_scaling_factor"],
        p["sliding_window"],
        p["rope_parameters"]["full_attention"]["rope_theta"],
        p["rope_parameters"]["sliding_attention"]["rope_theta"],
        p["rope_parameters"]["full_attention"]["partial_rotary_factor"],
        p["rope_parameters"]["full_attention"]["factor"],
        p["rope_parameters"]["full_attention"][
            "original_max_position_embeddings"],
        p["rope_parameters"]["full_attention"]["beta_fast"],
        p["rope_parameters"]["full_attention"]["beta_slow"],
        p["rope_parameters"]["full_attention"]["attention_factor"],
        p["rms_norm_eps"])
    depth = p["num_hidden_layers"]
    assert k["layer_types"] == p["layer_types"][:depth]
    assert k["heads_per_layer"] == p["num_attention_heads_per_layer"][:depth]
    assert (k["held_first"], k["held_count"], k["vocab"], k["max_seq"],
            k["n_dense_layers"], k["param_dtype"]) == (
        0, p["num_experts"], p["vocab_size"], p["max_position_embeddings"],
        len(p["mlp_only_layers"]), "bfloat16")
    r = p["reference"]
    assert (r["heads_per_layer"], r["windowed"], r["n_kv_heads"],
            r["window"], r["top_k"], r["routed_scale"], r["held_first"]) == (
        "48,72,72,72,48", "0,1,1,1,0", 8, 512, 10, 2.5, 0)
    s = p["serving"]
    assert (s["dtype"], s["gen_max_batch_size"], s["gen_kv_block_size"],
            s["gen_kv_blocks"], s["gen_mixed_step"], s["gen_prefill_chunk"],
            s["gen_prefix_sharing"]) == ("bfloat16", 32, 16, 17409, True,
                                         256, False)
    assert max(p["correct"]["prompt_lens"]) >= 2048
    assert p["correct"]["pad_to"] >= 2048 + p["correct"]["new_tokens"] - 1


def test_the_configuration_builds_the_model_the_arithmetic_describes(
        published):
    """Shapes only: 11.15 GB of bfloat16 weights (ISSUE 36 reckoned 11.14),
    a window layer 73.3 M parameters outside its experts and a full one
    54.4 M, 1.208 B held routed parameters a layer; the two pools 2.28 and
    0.31 GB."""
    import jax

    from tpu_engine.models import registry
    from tpu_engine.runtime.kv_blocks import dense_block_bytes

    registry._ensure_builtin_models_imported()
    spec = registry.create_model(published["factory"], **published["kwargs"])
    tree = jax.eval_shape(spec.init, jax.random.PRNGKey(0))

    def count(sub):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(sub))

    n_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(tree))
    assert 11.14e9 < n_bytes < 11.16e9
    layers = tree["layers"]
    held = count(layers[1]["mlp"]["experts"])
    assert held == 128 * 3 * 3072 * 1024
    # The issue's matrices; zero biases and norm scales add < 0.1 M.
    assert 73.3 < (count(layers[1]) - held) / 1e6 < 73.45     # window
    assert 54.4 < (count(layers[4]) - held) / 1e6 < 54.55     # full
    assert 157.4 < count(layers[0]) / 1e6 < 157.55      # dense, full
    full, window = spec.config.kv_block_kinds
    serving = published["serving"]
    assert dense_block_bytes(full, 16, "bfloat16") == 2 * 16 * 4096
    assert dense_block_bytes(window, 16, "bfloat16") == 3 * 16 * 4096
    assert serving["gen_kv_blocks"] == 32 * (8192 + 512) // 16 + 1
    assert 2.28e9 < serving["gen_kv_blocks"] * 2 * 16 * 4096 < 2.29e9
    per_row = -(-(512 + serving["gen_prefill_chunk"]) // 16) + 1
    assert per_row == 49
    assert 0.30e9 < (32 * per_row + 1) * 3 * 16 * 4096 < 0.31e9


def test_the_benchmark_lists_the_cell_and_its_nine_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # Found by name: later PRs append after it.
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "laguna-s-2.1-5l",
                    "traffic": "repo", "chips": 1, "why": cell["why"]}
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size", "max_position_embeddings"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    mine = [by_name[name] for name in MINE]
    assert all(CELL in m["workloads"] for m in mine)
    assert {m["layer"] for m in mine} == {"kernels", "expert layer",
                                          "KV pool"}
    assert all(m["moves"] == "tokens_per_s" for m in mine)
    with open(os.path.join(BENCH, "traffic", "repo.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], traffic["block"],
            traffic["pool"]) == ("closed", 32, 32, 32)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 2048, "sigma": 0.9, "min": 256,
        "max": 8192}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 128,
                                        "max": 512}
    assert traffic["sharing"] == {"share": 0.0}


# -- the counting ----------------------------------------------------------------

def test_sizes_of_the_configuration_as_run(published):
    assert roofline_laguna.sizes(published) == {
        "layers": (2, 3), "heads": (48, 72), "kv_heads": 8, "head_dim": 128,
        "d_model": 3072, "d_expert": 1024, "bytes_per_element": 2}


def test_layers_of_one_kind_must_agree_on_their_heads(published):
    kwargs = dict(published["kwargs"], heads_per_layer=[48, 72, 64, 72, 48])
    with pytest.raises(ValueError, match="differ in their head count"):
        roofline_laguna.sizes(dict(published, kwargs=kwargs))


@pytest.mark.parametrize("attrs, key, want", [
    ([{"ctx_tokens_window": 5}, {"ctx_tokens_window": 7}, {}],
     "ctx_tokens_window", 12),
    ([{"ctx_tokens": 9}], "ctx_tokens_full", 0),
    ([], "moe_assignments_held", 0),
])
def test_span_sum(attrs, key, want):
    assert roofline_laguna.span_sum(attrs, key) == want


def test_a_token_is_4096_bytes_a_layer():
    """2 x 8 KV heads x 128 lanes x 2 B: ISSUE 36's figure."""
    assert roofline.attention_bytes(1, 1, 8, 128, 2) == 4096
    # a decode tick: 32 rows see 512 tokens on 3 window layers, ~3 k on 2
    assert roofline.attention_bytes(32 * 512, 3, 8, 128, 2) == 201326592
    assert roofline.attention_bytes(32 * 3000, 2, 8, 128, 2) == 786432000


def test_a_chunk_tick_s_held_experts_are_bound_by_their_weights():
    """All 4 x 128 held experts touched by a chunk tick's ~5760 held
    pairs: 9.66 GB, 11.8 ms at the HBM peak, against 0.55 ms of
    arithmetic."""
    n_bytes = roofline_moe_mla.expert_bytes(512, 3072, 1024, 2)
    assert n_bytes == 9663676416
    flops = roofline_moe_mla.expert_flops(5760, 3072, 1024)
    assert roofline.floor_seconds(n_bytes, flops, V5E) == pytest.approx(
        9663676416 / 819e9)
    assert flops / V5E["bf16_flops_per_s"] < 0.6e-3


def test_the_full_layers_seconds_leave_the_window_call_out():
    run = {"trace": {"busy_s": 1.0, "op_seconds": {
        "%swa_window_read bf16[1]": 0.3, "%_paged_call bf16[1]": 0.2,
        "%fusion": 0.5}}}
    assert roofline_laguna.full_attention_seconds(run) == 0.2
    assert roofline_laguna.full_attention_seconds({"trace": None}) is None
    run["trace"]["op_seconds"] = {"%swa_window_read bf16[1]": 0.3}
    assert roofline_laguna.full_attention_seconds(run) is None


# -- the rehearsal -------------------------------------------------------------------

def test_the_rehearsal_lists_every_metric_of_the_new_cell(tmp_path):
    """run.py --trace 1 on the CPU at the small size, a cell list of its
    own with the ten keyless per-layer metrics and the cell's own nine: the
    span and counter metrics print, what only a device trace gives is left
    out and said so; the untraced run prints the three end-to-end ones."""
    cells = rehearsal_cells(tmp_path, "laguna", CELL)
    real = load_benchmark()
    want = [m["name"] for m in metrics_listed(real, CELL)]
    assert set(MINE) <= set(want)
    assert [m["name"] for m in metrics_listed(real, CELL, "end_to_end")] \
        == ["itl_p95_ms", "tokens_per_s", "setup_s"]
    env = dict(os.environ, TPU_ENGINE_PLATFORM="cpu")
    lines, said = {}, {}
    for trace in ("1", "0"):
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"),
             "--benchmark-file", cells, "--workload", "laguna.closed",
             "--seed", str(2**31 + 36), "--seconds", "2", "--trace", trace],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=420)
        assert proc.returncode == 0, proc.stderr[-3000:]
        lines[trace] = json.loads(proc.stdout.strip().splitlines()[-1])
        said[trace] = proc.stderr
        assert lines[trace]["correct"] is True
        assert lines[trace]["failed"] == 0
    assert set(lines["0"]["metrics"]) == {"itl_p95_ms", "tokens_per_s",
                                          "setup_s"}
    got = lines["1"]["metrics"]
    assert set(got) == read_without_a_device(real, CELL)
    device_only = set(want) - set(got)
    assert {name for name in MINE if name.startswith("kernel.")} \
        <= device_only
    assert got["step.compiles"] == {"value": 0, "unit": "compilations"}
    assert 35.0 < got["moe.held_assignment_share"]["value"] < 65.0
    assert 0.0 < got["kv.window_over_full_tokens"]["value"] <= 1.0
    assert 0.0 < got["kv.full_blocks_peak_share"]["value"] <= 100.0
    for name in device_only:
        assert f"{name} found nothing to read" in said["1"]
