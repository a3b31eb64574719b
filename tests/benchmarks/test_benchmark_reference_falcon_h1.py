"""The "falcon_h1" reference (benchmarks/references/falcon_h1.py) against
the program's float32 forward at the small test size, `check_served` telling
the served path from a reference with one term changed, the configuration's
keys against the catalog's and ISSUE 46's cut arithmetic (10.51 GB of
weights, 12,288 B of K/V a token, 25.5 MB of state a row) against the tree
and the pools it builds, and the rehearsal of the new cell's metrics through
run.py on the CPU (the ten readers and the counting:
test_benchmark_layer_metrics_falcon_h1.py)."""

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

CELL = "falcon-h1-34b-6l.converse"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# PR 46's readers, under the names of the merged readers that took their
# place in PR 68.
NEW = ["kernel.state_step_busy", "kernel.state_step_roofline",
       "kernel.state_chunk_busy", "kernel.state_chunk_roofline",
       "kernel.paged_attn_busy", "kernel.paged_attn_roofline",
       "state.rows_peak_share", "state.bytes_over_cache_bytes",
       "kv.blocks_peak_share", "step.decode_ms"]
MULTIPLIERS = ("embedding_multiplier", "attention_in_multiplier",
               "key_multiplier", "attention_out_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier",
               "lm_head_multiplier")


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
    from tpu_engine.models.falcon_h1 import falcon_h1_apply

    with open(os.path.join(DATA, "configs",
                           "falcon-h1-small-test.json")) as f:
        config = json.load(f)
    registry._ensure_builtin_models_imported()
    spec = registry.create_model(config["factory"], **config["kwargs"])
    params = spec.init(jax.random.PRNGKey(3))
    forward = jax.jit(lambda tokens: falcon_h1_apply(
        params, tokens, spec.config, dtype=jnp.float32))

    def program(tokens):
        """Causal: one program over 64 right-padded columns."""
        padded = np.zeros((1, 64), np.int32)
        padded[0, :len(tokens)] = tokens
        return np.asarray(forward(padded)[0, :len(tokens)])

    return (config, spec, params, program,
            _load(os.path.join(BENCH, "references", "falcon_h1.py"),
                  "forward"))


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "falcon-h1-34b-6l.json")) as f:
        return json.load(f)


# -- the reference -------------------------------------------------------------

def test_reference_logits_equal_the_program_s_in_float32(small):
    """The dialect "falcon_h1": 56 tokens, more than three prefill chunks of
    the test lane, within 2e-4 of the largest logit. The reference scans the
    recurrence a token at a time and attends under a mask; the program's
    forward runs the chunked form."""
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


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "falcon_h1.py")) as f:
        text = f.read()
    assert "tpu_engine" not in text.split('"""', 2)[2]
    assert "mamba2" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text


@pytest.mark.parametrize("control", [
    {"drop": "ssm"}, {"drop": "attention"}, {"drop": "decay"},
    {"drop": "group"}, {"drop": "ssm_out_multiplier"},
    {"drop": "key_multiplier"}, {"drop": "state"}, {"drop": "conv_tail"}])
def test_check_served_accepts_greedy_tokens_and_refuses_a_control(small,
                                                                  control):
    """The served tokens against the reference, then against the reference
    with a branch dropped, the decay left out, one group's B and C for every
    head, a multiplier dropped, the state or the conv tail dropped at every
    chunk boundary: each reads NOT correct. (The controls one precision
    down, `drop: state_bf16` and `weights_as: float8_e4m3fn`, are read on
    the chip at the published widths.)"""
    config, spec, params, program, forward = small
    rng = np.random.default_rng(1)
    samples = []
    for length in (5, 20, 50):
        prompt = [int(t) for t in rng.integers(0, spec.config.vocab, length)]
        seq = list(prompt)
        for _ in range(8):
            seq.append(int(program(np.asarray(seq, np.int32))[-1].argmax()))
        samples.append((prompt, seq[length:]))
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
    assert spec.config == registry.create_model("falcon_h1_small").config
    ref, cfg = config["reference"], spec.config
    assert ref["dialect"] == "falcon_h1"
    assert (ref["n_heads"], ref["n_kv_heads"], ref["ssm_heads"],
            ref["n_groups"], ref["d_state"], ref["rope_theta"],
            ref["chunk"]) == (
        cfg.n_heads, cfg.kv_heads, cfg.lin_heads, cfg.n_groups, cfg.d_state,
        cfg.rope_theta, config["serving"]["gen_prefill_chunk"])
    for name in MULTIPLIERS:
        assert ref[name] == getattr(cfg, name), name
    for name in ("ssm_multipliers", "mlp_multipliers"):
        assert tuple(float(v) for v in ref[name].split(",")) == getattr(
            cfg, name)


# -- the published configuration -------------------------------------------------

def test_every_source_key_is_there_and_only_depth_and_positions_are_reduced(
        published):
    """The catalog's `config` for Falcon-H1-34B-Instruct, key for key; the
    two keys of `reduced` alone differ, and neither is a width."""
    with open(CATALOG) as f:
        source = next(row for row in map(json.loads, f)
                      if row["name"] == "Falcon-H1-34B-Instruct")
    p = published
    assert p["source"] == source["source_url"]
    assert list(p["reduced"]) == ["num_hidden_layers",
                                  "max_position_embeddings"]
    for key, value in source["config"].items():
        if key not in p["reduced"]:
            assert p[key] == value, key
    assert (p["num_hidden_layers"], p["max_position_embeddings"]) == (6, 2048)
    for key in ("w_in_order", "grouped_norm", "groups_to_heads",
                "multipliers"):
        assert "lternative" in p["assumed"][key], key
    assert "after the multipliers" in p["assumed"]["weights"].lower()
    assert "sub-chunks of 64" in p["assumed"]["mamba_chunk_size"]
    assert "WHOLE vocabulary" in p["deployment"]
    assert "no share of a layer is taken" in p["deployment"]


def test_the_kwargs_are_the_published_widths(published):
    p, k = published, published["kwargs"]
    assert (k["d_model"], k["n_heads"], k["n_kv_heads"], k["head_dim"],
            k["d_ff"], k["vocab"], k["ssm_heads"], k["ssm_head_dim"],
            k["d_state"], k["n_groups"], k["conv_width"], k["rope_theta"],
            k["ln_eps"]) == (
        p["hidden_size"], p["num_attention_heads"],
        p["num_key_value_heads"], p["head_dim"], p["intermediate_size"],
        p["vocab_size"], p["mamba_n_heads"], p["mamba_d_head"],
        p["mamba_d_state"], p["mamba_n_groups"], p["mamba_d_conv"],
        p["rope_theta"], p["rms_norm_eps"])
    assert k["ssm_heads"] * k["ssm_head_dim"] == p["mamba_d_ssm"]
    assert p["mamba_d_ssm"] != p["mamba_expand"] * p["hidden_size"]
    assert p["intermediate_size"] != (p["mlp_expansion_factor"]
                                      * p["hidden_size"])
    for name in MULTIPLIERS + ("ssm_multipliers", "mlp_multipliers"):
        assert k[name] == p[name], name
    assert (k["n_layers"], k["max_seq"], k["param_dtype"]) == (
        p["num_hidden_layers"], p["max_position_embeddings"], "bfloat16")
    r = p["reference"]
    assert (r["n_heads"], r["n_kv_heads"], r["ssm_heads"], r["n_groups"],
            r["d_state"], r["chunk"]) == (20, 4, 32, 2, 256, 256)
    for name in MULTIPLIERS:
        assert r[name] == p[name], name
    assert [float(v) for v in r["ssm_multipliers"].split(",")] == \
        p["ssm_multipliers"]
    assert [float(v) for v in r["mlp_multipliers"].split(",")] == \
        p["mlp_multipliers"]
    s = p["serving"]
    assert (s["dtype"], s["gen_max_batch_size"], s["gen_kv_block_size"],
            s["gen_kv_blocks"], s["gen_mixed_step"], s["gen_prefill_chunk"],
            s["gen_prefix_sharing"]) == ("bfloat16", 64, 16, 6145, True, 256,
                                         False)
    # One row crosses three chunk boundaries; rows decode across a multiple
    # of 256; the reference's logits fit (pad_to x 261,120 x 4 B = 1.07 GB).
    c = p["correct"]
    assert max(c["prompt_lens"]) > 3 * s["gen_prefill_chunk"]
    assert any(n // 256 != (n + c["new_tokens"] - 1) // 256
               for n in c["prompt_lens"])
    assert c["pad_to"] >= max(c["prompt_lens"]) + c["new_tokens"] - 1
    assert c["pad_to"] * p["vocab_size"] * 4 < 1.1e9


def test_the_configuration_builds_the_model_the_arithmetic_describes(
        published):
    """Shapes only, ISSUE 46's cut: 10.51 GB of bfloat16 weights (a layer
    430.2 M parameters: attention 31.5 M, Mamba-2 68.4 M, SwiGLU 330.3 M;
    embedding and head 2.674 B), a block pool over ALL six layers at 12,288
    B a token (1.21 GB), 25.5 MB of state a row (1.66 GB)."""
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
    assert 10.50e9 < n_bytes < 10.53e9
    layer = tree["layers"][0]
    # The issue's matrices; zero biases and norm scales add < 0.1 M.
    assert 430.1 < count(layer) / 1e6 < 430.4
    assert 31.4 < count(layer["attn"]) / 1e6 < 31.5
    assert 68.3 < count(layer["ssm"]) / 1e6 < 68.45
    assert 330.3 < count(layer["mlp"]) / 1e6 < 330.4
    assert layer["ssm"]["w_in"]["kernel"].shape == (5120, 9248)
    assert layer["ssm"]["conv"].shape == (4, 5120)
    assert 2.6738 < (count(tree["tok_embed"]) + count(tree["head"])) / 1e9 \
        < 2.6745
    (kind,) = cfg.kv_block_kinds
    assert kind.n_layers == cfg.n_linear_layers == cfg.n_layers == 6
    assert dense_block_bytes(kind, 16, "bfloat16") == 16 * 12288
    serving = published["serving"]
    assert serving["gen_kv_blocks"] == 64 * (1024 + 512) // 16 + 1
    assert 1.20e9 < serving["gen_kv_blocks"] * 16 * 12288 < 1.22e9
    row = cfg.n_layers * 4 * sum(int(np.prod(s))
                                 for s in cfg.state_row_shapes)
    assert cfg.state_row_shapes == ((32, 128, 256), (8, 1920))
    assert 25.5e6 < row < 25.6e6
    assert 1.65e9 < (serving["gen_max_batch_size"] + 1) * row < 1.67e9


def test_the_benchmark_lists_the_cell_and_its_ten_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # Found by name: later PRs append after it.
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "falcon-h1-34b-6l",
                    "traffic": "converse", "chips": 1, "why": cell["why"]}
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings"]
    assert config["source"].endswith("tiiuae/Falcon-H1-34B-Instruct/blob/"
                                     "main/config.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    mine = [by_name[name] for name in NEW]
    assert all(CELL in m["workloads"] for m in mine)
    assert {m["layer"] for m in mine} == {"kernels", "state pool", "KV pool",
                                       "step function"}
    assert all(m["moves"] == "tokens_per_s" for m in mine)
    with open(os.path.join(BENCH, "traffic", "converse.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], traffic["block"],
            traffic["pool"]) == ("closed", 64, 64, 16)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32,
        "max": 1024}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 128,
                                        "max": 512}
    assert traffic["sharing"] == {"share": 0.0}


def test_the_rehearsal_lists_every_metric_of_the_new_cell(tmp_path):
    """run.py --trace 1 on the CPU at the small size, a cell list of its own
    with the ten keyless per-layer metrics and the cell's own ten: the span
    and counter metrics print, what only a device trace gives is left out
    and said so."""
    cells = rehearsal_cells(tmp_path, "falcon", CELL)
    real = load_benchmark()
    want = [m["name"] for m in metrics_listed(real, CELL)]
    assert set(NEW) <= set(want)
    assert [m["name"] for m in metrics_listed(real, CELL, "end_to_end")] \
        == ["itl_p95_ms", "tokens_per_s", "setup_s"]
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"),
         "--benchmark-file", cells, "--workload", "falcon.closed",
         "--seed", str(2**31 + 46), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=dict(os.environ, TPU_ENGINE_PLATFORM="cpu"),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert set(got) == read_without_a_device(real, CELL)
    assert not {name for name in NEW if name.startswith("kernel.")} \
        & set(got)
    assert got["step.compiles"] == {"value": 0, "unit": "compilations"}
    # Three clients of four slots; states and blocks of the same rows.
    assert got["state.rows_peak_share"]["value"] == 75.0
    assert 0.2 < got["state.bytes_over_cache_bytes"]["value"] < 3.0
    assert 5.0 < got["kv.blocks_peak_share"]["value"] < 40.0
    assert got["step.decode_ms"]["value"] > 0
