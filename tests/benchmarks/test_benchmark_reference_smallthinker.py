"""The "smallthinker" reference (benchmarks/references/smallthinker.py)
against the program's float32 forward at the small test size, the head's
rows around a sequence's end (`tail_rows`) against the whole array,
`check_served` telling the served path from a reference with one term
changed (every control of `correct` reads NOT correct), the configuration's
widths against the catalog's and its arithmetic against the tree it builds,
and the rehearsal of the new cell's metrics through run.py on the CPU (the
readers' pins: test_benchmark_layer_metrics_smallthinker.py)."""

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

CELL = "smallthinker-21b-a3b-8l.history"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PAD = 128


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
    from tpu_engine.models.smallthinker import smallthinker_apply

    with open(os.path.join(DATA, "configs",
                           "smallthinker-small-test.json")) as f:
        config = json.load(f)
    registry._ensure_builtin_models_imported()
    spec = registry.create_model(config["factory"], **config["kwargs"])
    params = spec.init(jax.random.PRNGKey(3))
    forward = jax.jit(lambda tokens: smallthinker_apply(
        params, tokens, spec.config, dtype=jnp.float32))

    def program(tokens):
        """Causal: one program over 128 right-padded columns."""
        padded = np.zeros((1, PAD), np.int32)
        padded[0, :len(tokens)] = tokens
        return np.asarray(forward(padded)[0, :len(tokens)])

    return (config, spec, params, program,
            _load(os.path.join(BENCH, "references", "smallthinker.py"),
                  "forward"))


@pytest.fixture(scope="module")
def served(small):
    """Greedy tokens of the program's own forward: prompts short of the
    window (48), past it, and past window + chunk; one ENDS in token 0."""
    _, spec, _, program, _ = small
    rng = np.random.default_rng(1)
    samples = []
    for length in (5, 70, 100):
        prompt = [int(t) for t in rng.integers(1, spec.config.vocab, length)]
        seq = list(prompt)
        for _ in range(8):
            seq.append(int(program(np.asarray(seq, np.int32))[-1].argmax()))
        samples.append((prompt, seq[length:]))
    return samples


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs",
                           "smallthinker-21b-a3b-8l.json")) as f:
        return json.load(f)


# -- the reference ------------------------------------------------------------

def test_reference_logits_equal_the_program_s_in_float32(small):
    """The dialect "smallthinker": 100 tokens, two windows of the test
    model, within 2e-4 of the largest logit; and the head's rows around the
    sequence's end are the whole array's rows, where asked for. The
    sequence ENDS in three tokens 0, which the reference takes for padding:
    its rows reach far enough past the end it finds to hold them."""
    import jax.numpy as jnp

    config, spec, params, program, forward = small
    tokens = np.zeros((100,), np.int32)
    tokens[:97] = np.random.default_rng(0).integers(
        1, spec.config.vocab, size=97)
    block = dict(config["reference"])
    rows = block.pop("tail_rows")
    padded = np.zeros((PAD,), np.int32)
    padded[:100] = tokens
    ours = np.asarray(forward(params, jnp.asarray(padded),
                              reference.sizes_of(block)))[:100]
    theirs = program(tokens)
    assert ours.shape == theirs.shape == (100, spec.config.vocab)
    assert ours.dtype == np.float32
    assert np.abs(ours - theirs).max() < 2e-4 * np.abs(theirs).max()
    tail = forward(params, jnp.asarray(padded),
                   reference.sizes_of(config["reference"]))
    assert tail.rows.shape == (rows, spec.config.vocab)
    # The end it finds is 97; 97 + 32 - 48 rows, held inside the 128.
    assert int(tail.start) == 80
    assert np.array_equal(tail[91:100], ours[91:100])
    with pytest.raises(IndexError, match="computed 80:128"):
        tail[60:100]


@pytest.mark.parametrize("control", [
    {}, {"control": "window"}, {"control": "rotate_full"},
    {"control": "late_router"}, {"control": "silu"}])
def test_check_served_accepts_greedy_tokens_and_refuses_a_control(
        small, served, control):
    """The served tokens against the reference, then against the reference
    with the window layers attending everything, the full layers rotated,
    the router read from RMS(h1; ln2) or SiLU for ReLU in the experts: each
    reads NOT correct. (The control one precision down, `experts_as:
    float8_e4m3fn`, is read on the chip at the published widths.)"""
    config, _, params, _, forward = small
    ok, details = reference.check_served(
        forward, params, dict(config["reference"], **control), served,
        0.05, 0.9, pad_to=PAD)
    assert details["positions"] == 24
    if control:
        assert not ok, details
    else:
        assert ok and details["exact_share"] == 1.0, details


def test_the_test_configuration_is_the_registry_s_small_smallthinker(small):
    from tpu_engine.models import registry

    config, spec, _, _, _ = small
    assert spec.config == registry.create_model(
        "smallthinker-small-test").config
    ref, cfg = config["reference"], spec.config
    assert ref["dialect"] == "smallthinker"
    assert ref["windowed"] == ",".join(str(int(w)) for w in cfg.windowed)
    assert ref["rotated"] == ",".join(str(int(r)) for r in cfg.rotated)
    assert (ref["n_heads"], ref["n_kv_heads"], ref["window"], ref["top_k"],
            ref["held_first"]) == (cfg.n_heads, cfg.kv_heads, cfg.window,
                                   cfg.top_k, cfg.held[0])


# -- the published configuration ----------------------------------------------

def test_every_source_key_is_there_and_only_the_depth_is_reduced(published):
    """The catalog's `config` for SmallThinker-21BA3B-Instruct, key for key,
    the two layout lists whole; `num_hidden_layers` alone differs."""
    with open(CATALOG) as f:
        source = next(row for row in map(json.loads, f)
                      if row["name"] == "SmallThinker-21BA3B-Instruct")
    p = published
    assert p["source"] == source["source_url"]
    assert list(p["reduced"]) == ["num_hidden_layers"]
    for key, value in source["config"].items():
        if key != "num_hidden_layers":
            assert p[key] == value, key
    assert (source["config"]["num_hidden_layers"],
            p["num_hidden_layers"]) == (52, 8)
    assert len(p["rope_layout"]) == len(p["sliding_window_layout"]) == 52
    for key in ("router_input", "router", "rope", "expert", "not_modelled",
                "weights"):
        assert p["assumed"][key]
    assert "pipeline" in p["deployment"] and "HIGH" in p["deployment"]


def test_the_kwargs_are_the_published_widths(published):
    p, k = published, published["kwargs"]
    depth = p["num_hidden_layers"]
    assert (k["d_model"], k["n_kv_heads"], k["head_dim"], k["d_ff_expert"],
            k["n_experts"], k["top_k"], k["window"], k["rope_theta"],
            k["ln_eps"], k["vocab"], k["max_seq"]) == (
        p["hidden_size"], p["num_key_value_heads"], p["head_dim"],
        p["moe_ffn_hidden_size"], p["moe_num_primary_experts"],
        p["moe_num_active_primary_experts"], p["sliding_window_size"],
        p["rope_theta"], p["rms_norm_eps"], p["vocab_size"],
        p["max_position_embeddings"])
    assert k["heads_per_layer"] == [p["num_attention_heads"]] * depth
    assert k["rope_layout"] == p["rope_layout"][:depth]
    assert [t == "sliding_attention" for t in k["layer_types"]] == [
        bool(w) for w in p["sliding_window_layout"][:depth]]
    assert (k["held_first"], k["held_count"], k["param_dtype"]) == (
        0, 0, "bfloat16")
    r = p["reference"]
    assert (r["n_heads"], r["n_kv_heads"], r["windowed"], r["rotated"],
            r["window"], r["top_k"], r["held_first"]) == (
        28, 4, "0,1,1,1,0,1,1,1", "0,1,1,1,0,1,1,1", 4096, 6, 0)
    s = p["serving"]
    assert (s["dtype"], s["gen_max_batch_size"], s["gen_kv_block_size"],
            s["gen_kv_blocks"], s["gen_mixed_step"], s["gen_prefill_chunk"],
            s["gen_prefix_sharing"]) == ("bfloat16", 32, 16, 27137, True,
                                         256, False)
    # `correct`: two prompts past window + chunk, one near 8192, 128 steps;
    # the head's rows reach the generated positions and the slack past them.
    c = p["correct"]
    assert len(c["prompt_lens"]) >= 6 and c["new_tokens"] >= 128
    assert sum(n > 4096 + 256 for n in c["prompt_lens"]) >= 2
    assert max(c["prompt_lens"]) == 8192
    assert c["pad_to"] >= 8192 + c["new_tokens"] - 1
    assert r["tail_rows"] >= c["new_tokens"] + 2 * 32


def test_the_configuration_builds_the_model_the_arithmetic_describes(
        published):
    """Shapes only: 7.93 GB of bfloat16 weights (ISSUE 70's arithmetic), a
    layer 398.6 M parameters of which 377.5 M in its 64 experts, 777.9 M in
    the embedding and the head; the two pools 1.78 and 1.72 GB."""
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
    assert 7.93e9 < n_bytes < 7.95e9
    layer = tree["layers"][1]
    assert count(layer["mlp"]["experts"]) == 64 * 3 * 2560 * 768
    assert 398.6 < count(layer) / 1e6 < 398.7
    assert count(tree["tok_embed"]) + 2560 * 151936 == 2 * 151936 * 2560
    assert layer["mlp"]["router"]["kernel"].dtype == np.float32
    full, window = spec.config.kv_block_kinds
    serving = published["serving"]
    assert dense_block_bytes(full, 16, "bfloat16") == 2 * 16 * 2048
    assert dense_block_bytes(window, 16, "bfloat16") == 6 * 16 * 2048
    assert serving["gen_kv_blocks"] == 32 * (12288 + 1024 + 256) // 16 + 1
    assert 1.77e9 < serving["gen_kv_blocks"] * 2 * 16 * 2048 < 1.79e9
    per_row = -(-(4096 + serving["gen_prefill_chunk"]) // 16) + 1
    assert per_row == 273
    assert 1.71e9 < (32 * per_row + 1) * 6 * 16 * 2048 < 1.73e9


def test_the_benchmark_lists_the_cell_and_its_traffic():
    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "smallthinker-21b-a3b-8l",
                    "traffic": "history", "chips": 1, "why": cell["why"]}
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers"]
    with open(os.path.join(BENCH, "traffic", "history.json")) as f:
        traffic = json.load(f)
    # A plan of 8 x 32 requests (ISSUE 70 said 32 x 32): the load generator
    # builds the whole plan before its first request, a prompt token a
    # call, and 1,024 prompts of ~6.7 k tokens take longer than the
    # warm-up's three seconds, so no request was ever sent (CHANGES.md, PR
    # 70). A window sends ~110.
    assert (traffic["loop"], traffic["clients"], traffic["block"],
            traffic["pool"]) == ("closed", 32, 32, 8)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.5, "min": 1024,
        "max": 12288}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 256,
                                        "max": 1024}
    assert traffic["sharing"] == {"share": 0.0}
    assert (traffic["warmup_s"], traffic["drain_s"],
            traffic["warmup_max_new_tokens"]) == (2, 30, 8)


def test_the_rehearsal_lists_every_metric_of_the_new_cell(tmp_path):
    """run.py --trace 1 on the CPU at the small size, a cell list of its
    own with every per-layer metric BENCHMARK.json lists for the cell: the
    span and counter metrics print, the two new ones among them, what only
    a device trace gives is left out; `correct` is decided through the
    head's rows around each sample's end."""
    cells = rehearsal_cells(tmp_path, "smallthinker", CELL)
    real = load_benchmark()
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"),
         "--benchmark-file", cells, "--workload", "smallthinker.closed",
         "--seed", str(2**31 + 70), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=dict(os.environ, TPU_ENGINE_PLATFORM="cpu"),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert set(got) == read_without_a_device(real, CELL)
    assert {"kv.window_blocks_peak_share", "kv.window_bound_row_share",
            "kv.window_over_full_tokens"} <= set(got)
    assert got["step.compiles"] == {"value": 0, "unit": "compilations"}
    assert len(metrics_listed(real, CELL)) == 29
