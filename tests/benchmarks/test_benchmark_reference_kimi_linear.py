"""The "kimi_linear" reference (benchmarks/references/kimi_linear.py) against
the program's float32 forward at the small test size, `check_served` telling
the served path from a reference with one term changed (the channel gate
replaced by its head's mean among them: the scalar rule under this name),
the configuration's widths against the catalog's and ISSUE 44's cut
arithmetic (8.57 GB of weights, 1.16 GB of state rows, 1.85 GB of latent
blocks) re-derived from the tree and the pools the configuration builds, and
the rehearsal of the new cell's metrics through run.py on the CPU (the
eleven readers listed, and two more that no cell lists yet:
test_benchmark_layer_metrics_kimi_linear.py). The cell is
found BY NAME and only the metrics that list it are counted: a cell or a
metric appended later changes nothing here."""

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

from lib import reference  # noqa: E402

CELL = "kimi-linear-48b-a3b-5l.reason"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# PR 44's readers, under the names of the merged readers that took their
# place in PR 68.
NEW = ["kernel.state_step_busy", "kernel.state_step_roofline",
       "kernel.paged_attn_busy", "kernel.paged_attn_roofline",
       "kernel.moe_experts_busy", "kernel.moe_experts_roofline",
       "moe.rows_per_touched_expert", "state.rows_peak_share",
       "state.bytes_over_cache_bytes", "kv.blocks_peak_share",
       "step.decode_ms"]
KEYLESS = ["sched.decode_rows_per_tick", "sched.prefill_tick_share",
           "step.prefill_ms", "device.idle", "device.hbm_peak_gb",
           "step.compiles", "device.idle_host", "sched.itl_prefill_share"]
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "model_max_length"]


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
    from tpu_engine.models.kimi_linear import kimi_linear_apply

    with open(os.path.join(DATA, "configs",
                           "kimi-linear-small-test.json")) as f:
        config = json.load(f)
    registry._ensure_builtin_models_imported()
    spec = registry.create_model(config["factory"], **config["kwargs"])
    params = spec.init(jax.random.PRNGKey(3))
    forward = jax.jit(lambda tokens: kimi_linear_apply(
        params, tokens, spec.config, dtype=jnp.float32))

    def program(tokens):
        """Causal: one program over 64 right-padded columns."""
        padded = np.zeros((1, 64), np.int32)
        padded[0, :len(tokens)] = tokens
        return np.asarray(forward(padded)[0, :len(tokens)])

    return (config, spec, params, program,
            _load(os.path.join(BENCH, "references", "kimi_linear.py"),
                  "forward"))


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs",
                           "kimi-linear-48b-a3b-5l.json")) as f:
        return json.load(f)


# -- the reference -------------------------------------------------------------

def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "kimi_linear.py")) as f:
        source = f.read()
    assert "tpu_engine" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source


def test_reference_logits_equal_the_program_s_in_float32(small):
    """The dialect "kimi_linear": 56 tokens, more than three prefill chunks
    of the test lane, within 2e-4 of the largest logit. The reference scans
    the recurrence a token at a time, expands the MLA layer and applies
    every held expert to every token; the program's forward runs the
    chunked form and the grouped product."""
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
    {"drop": "decay"}, {"drop": "gate_mean"}, {"drop": "conv_tail"},
    {"drop": "state"}, {"drop": "other_half"}, {"drop": "shared"}])
def test_check_served_accepts_greedy_tokens_and_refuses_a_control(
        small, served, control):
    """The served tokens against the reference, then against the reference
    with the decay left out, the channel gate replaced by its head's mean,
    the conv tail or the state dropped at every chunk boundary, the other
    chip's half of the experts, no shared expert: each reads NOT correct.
    (A rotation of 8 lanes over 58 positions moves too little to tell at
    this size, and the controls one precision down need bfloat16: both are
    read on the chip at the published widths; tests/test_kimi_linear.py
    holds that each moves the reference's logits.)"""
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
    assert spec.config == registry.create_model("kimi_linear_small").config
    ref, cfg = config["reference"], spec.config
    assert ref["dialect"] == "kimi_linear"
    assert ref["linear"] == ",".join(str(int(x)) for x in cfg.linear)
    assert (ref["n_heads"], ref["qk_nope"], ref["qk_rope"], ref["lin_heads"],
            ref["lin_key_dim"], ref["n_dense_layers"], ref["top_k"],
            ref["routed_scale"], ref["held_first"], ref["chunk"]) == (
        cfg.n_heads, cfg.qk_nope, cfg.qk_rope, cfg.lin_heads,
        cfg.lin_key_dim, cfg.n_dense_layers, cfg.top_k, cfg.routed_scale,
        cfg.held[0], config["serving"]["gen_prefill_chunk"])


# -- the published configuration -------------------------------------------------

def test_every_source_key_is_there_and_only_four_are_reduced(published):
    """The catalog's `config` for Kimi-Linear-48B-A3B-Instruct, key for key
    (the nested `linear_attn_config` whole); the four keys of `reduced`
    alone differ, and none is a width."""
    with open(CATALOG) as f:
        source = next(row for row in map(json.loads, f)
                      if row["name"] == "Kimi-Linear-48B-A3B-Instruct")
    p = published
    assert p["source"] == source["source_url"]
    assert list(p["reduced"]) == REDUCED
    for key, value in source["config"].items():
        if key not in p["reduced"]:
            assert p[key] == value, key
    assert (p["num_hidden_layers"], p["num_experts"], p["vocab_size"],
            p["model_max_length"]) == (5, 128, 81920, 12288)
    assert (source["config"]["num_hidden_layers"],
            source["config"]["num_experts"], source["config"]["vocab_size"],
            source["config"]["model_max_length"]) == (27, 256, 163840,
                                                      1048576)
    for key in REDUCED:
        assert not (key.endswith("_dim") or key.endswith("_rank")
                    or "size" in key.replace("vocab_size", "")), key
    for key in ("gates", "writing_strength", "linear_layer",
                "norm_placement", "unrotated_lanes", "kv_a_layernorm",
                "router"):
        assert "lternative" in p["assumed"][key], key
    assert {"biases", "weights"} <= set(p["assumed"])
    assert "(0.55, 1)" in p["assumed"]["weights"]
    assert "one of 2 chips that share each layer" in p["deployment"]


def test_the_kwargs_are_the_published_widths(published):
    p, k = published, published["kwargs"]
    lin = p["linear_attn_config"]
    assert (k["d_model"], k["n_heads"], k["d_ff_dense"], k["d_ff_expert"],
            k["kv_lora_rank"], k["qk_nope"], k["qk_rope"], k["v_head"],
            k["top_k"], k["n_shared"], k["routed_scale"],
            k["n_dense_layers"], k["ln_eps"]) == (
        p["hidden_size"], p["num_attention_heads"], p["intermediate_size"],
        p["moe_intermediate_size"], p["kv_lora_rank"],
        p["qk_nope_head_dim"], p["qk_rope_head_dim"], p["v_head_dim"],
        p["num_experts_per_token"], p["num_shared_experts"],
        p["routed_scaling_factor"], p["first_k_dense_replace"],
        p["rms_norm_eps"])
    assert (k["lin_heads"], k["lin_head_dim"], k["conv_width"]) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    assert (k["kda_layers"], k["full_attn_layers"]) == (
        lin["kda_layers"], lin["full_attn_layers"])
    assert p["q_lora_rank"] is None and p["mla_use_nope"] is True
    # The router keeps every output; this chip holds half of the experts
    # and half of the vocabulary's rows.
    assert (k["n_experts"], k["held_first"], k["held_count"]) == (
        2 * p["num_experts"], 0, p["num_experts"])
    assert (k["n_layers"], k["vocab"], k["max_seq"], k["param_dtype"]) == (
        p["num_hidden_layers"], p["vocab_size"], p["model_max_length"],
        "bfloat16")
    r = p["reference"]
    assert (r["dialect"], r["linear"], r["n_heads"], r["qk_nope"],
            r["qk_rope"], r["lin_heads"], r["lin_key_dim"],
            r["n_dense_layers"], r["top_k"], r["routed_scale"],
            r["held_first"], r["chunk"]) == (
        "kimi_linear", "1,1,1,0,1", 32, 128, 64, 32, 128, 1, 8, 2.446, 0,
        256)
    s = p["serving"]
    assert (s["dtype"], s["gen_max_batch_size"], s["gen_kv_block_size"],
            s["gen_kv_blocks"], s["gen_mixed_step"], s["gen_prefill_chunk"],
            s["gen_prefix_sharing"]) == ("bfloat16", 128, 16, 90113, True,
                                         256, False)
    # Prompts that cross many chunk boundaries, then decode across more.
    assert max(p["correct"]["prompt_lens"]) >= 7 * s["gen_prefill_chunk"]
    assert p["correct"]["new_tokens"] == 256
    assert p["correct"]["pad_to"] >= (max(p["correct"]["prompt_lens"])
                                      + p["correct"]["new_tokens"] - 1)


def test_the_configuration_builds_the_model_the_arithmetic_describes(
        published):
    """Shapes only, ISSUE 44's cut re-derived: 8.57 GB of bfloat16 weights
    (a KDA mixer 39.5 M parameters, the MLA mixer 29.1 M, the dense FFN
    63.7 M, an expert 7.08 M and 128 of them 906 M a layer, embedding and
    head 377.5 M), a latent pool over the one MLA layer at 1,280 B a token
    (1.85 GB), 8.98 MB of state a row (1.16 GB for 129 rows): 11.6 GB, 74 %
    of the chip."""
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
    assert 8.56e9 < n_bytes < 8.58e9
    assert cfg.linear == (True, True, True, False, True)
    layers = tree["layers"]
    # The issue's matrices; zero biases and norm scales add < 0.1 M.
    assert 39.5 < count(layers[0]["lin"]) / 1e6 < 39.6
    assert 29.1 < count(layers[3]["attn"]) / 1e6 < 29.2
    assert 63.7 < count(layers[0]["mlp"]) / 1e6 < 63.75
    bank = layers[1]["mlp"]["experts"]
    assert bank["gate_up"].shape == (128, 2304, 2048)
    assert bank["down"].shape == (128, 1024, 2304)
    assert 905.9 < count(bank) / 1e6 < 906.0
    assert round(count(bank) / 128 / 1e6, 2) == 7.08
    assert layers[1]["mlp"]["router"]["kernel"].shape == (2304, 256)
    assert 7.07 < count(layers[1]["mlp"]["shared"]) / 1e6 < 7.09
    assert 103.2 < count(layers[0]) / 1e6 < 103.3           # KDA + dense
    assert 953.1 < count(layers[1]) / 1e6 < 953.3           # KDA + experts
    assert 942.7 < count(layers[3]) / 1e6 < 942.9           # MLA + experts
    assert 377.4 < (count(tree["tok_embed"]) + count(tree["head"])) / 1e6 \
        < 377.6
    (latent,) = cfg.kv_block_kinds
    assert (latent.n_layers, latent.kv_lanes, cfg.n_linear_layers) == (
        1, (128, 512), 4)
    assert dense_block_bytes(latent, 16, "bfloat16") == 16 * 1280
    serving = published["serving"]
    rows = serving["gen_max_batch_size"]
    assert serving["gen_kv_blocks"] == rows * (8192 + 3072) // 16 + 1
    pool = serving["gen_kv_blocks"] * 16 * 1280
    assert 1.84e9 < pool < 1.85e9
    row = cfg.n_linear_layers * 4 * sum(int(np.prod(s))
                                        for s in cfg.state_row_shapes)
    assert [int(np.prod(s)) for s in cfg.state_row_shapes] == [
        32 * 128 * 128, 3 * 12288]
    assert 8.97e6 < row < 8.99e6
    states = (rows + 1) * row
    assert 1.15e9 < states < 1.17e9
    held = n_bytes + pool + states
    assert 11.5e9 < held < 11.7e9 and 0.73 < held / 15.75e9 < 0.75


def test_the_benchmark_lists_the_cell_and_its_eleven_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # Found by name: later PRs append after it.
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "kimi-linear-48b-a3b-5l",
                    "traffic": "reason", "chips": 1, "why": cell["why"]}
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == REDUCED
    assert config["source"].endswith(
        "moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert config["file"] == "benchmarks/configs/kimi-linear-48b-a3b-5l.json"
    by_name = {m["name"]: m for m in bench["per_layer"]}
    mine = [by_name[name] for name in NEW]
    assert all(CELL in m["workloads"] for m in mine)
    # The chunked form's merged pair does NOT list the cell: the traced
    # slice (seconds 24-27 of the window) falls between the first prompts'
    # chunk ticks and the first completions' successors, so it holds decode
    # ticks alone and the readers find nothing there; a line that lacks a
    # listed metric is refused (PERF.md section 7).
    for name in ("kernel.state_chunk_busy", "kernel.state_chunk_roofline"):
        assert CELL not in by_name[name]["workloads"]
    assert {m["layer"] for m in mine} == {"kernels", "expert layer",
                                       "state pool", "KV pool",
                                       "step function"}
    assert all(m["moves"] == "tokens_per_s" for m in mine)
    assert [m["name"] for m in bench["per_layer"]
            if "workloads" not in m] == KEYLESS
    assert [m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])] == [
        "itl_p95_ms", "tokens_per_s", "setup_s"]
    with open(os.path.join(BENCH, "traffic", "reason.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], traffic["block"],
            traffic["pool"], traffic["warmup_s"]) == ("closed", 128, 128, 8,
                                                      2)
    assert traffic["clients"] == next(
        json.load(open(os.path.join(ROOT, c["file"])))
        for c in bench["configs"] if c["name"] == cell["config"]
    )["serving"]["gen_max_batch_size"]
    assert traffic["block"] * traffic["pool"] >= 1024
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 1.0, "min": 64,
        "max": 8192}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 1024,
                                        "max": 3072}
    assert traffic["sharing"] == {"share": 0.0}


# -- the rehearsal -------------------------------------------------------------------

def test_the_rehearsal_lists_every_metric_of_the_new_cell(tmp_path):
    """run.py --trace 1 on the CPU at the small size, a cell list of its
    own with the ten keyless per-layer metrics and the cell's own eleven:
    the span and counter metrics print, what only a device trace gives is
    left out and said so; the untraced run prints the three end-to-end
    ones."""
    cells = rehearsal_cells(tmp_path, "kimi", CELL)
    real = load_benchmark()
    names = [m["name"] for m in metrics_listed(real, CELL)]
    assert set(KEYLESS + NEW) <= set(names)
    assert [m["name"] for m in metrics_listed(real, CELL, "end_to_end")] \
        == ["itl_p95_ms", "tokens_per_s", "setup_s"]
    env = dict(os.environ, TPU_ENGINE_PLATFORM="cpu")
    lines = {}
    for trace in ("1", "0"):
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"),
             "--benchmark-file", cells, "--workload", "kimi.closed",
             "--seed", str(2**31 + 44), "--seconds", "2", "--trace", trace],
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
    # Three clients of four slots; states and latent blocks of the same
    # rows; about half of 4 pairs a token over 8 held experts.
    assert got["state.rows_peak_share"]["value"] == 75.0
    assert 0.1 < got["state.bytes_over_cache_bytes"]["value"] < 2.0
    assert 5.0 < got["kv.blocks_peak_share"]["value"] < 40.0
    assert 1.0 <= got["moe.rows_per_touched_expert"]["value"] < 8.0
    assert got["step.decode_ms"]["value"] > 0.0
