"""The "nemotron_h" reference (benchmarks/references/nemotron_h.py) against
the program's float32 forward at the small test size, `check_served` telling
the served path from a reference with one term changed, the configuration's
keys against the catalog's and ISSUE 50's cut arithmetic (9.30 GB of weights
at 5 : 5 : 1, 1,024 B of K/V a token, 21.59 MB of state a row) against the
tree and the pools it builds, and the rehearsal of the new cell's metrics
through run.py on the CPU (the fourteen readers and the counting:
test_benchmark_layer_metrics_nemotron_h.py)."""

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

CELL = "nemotron-3-super-120b-a12b-11l.agents"
CONFIG = "nemotron-3-super-120b-a12b-11l"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "max_position_embeddings"]
# PR 50's readers, under the names of the merged readers that took their
# place in PR 68 (`moe.route_sort_busy` is the cell's own still).
NEW = ["kernel.moe_experts_busy", "kernel.moe_experts_roofline",
       "moe.rows_per_touched_expert", "moe.expert_load_imbalance",
       "moe.route_sort_busy", "kernel.state_step_busy",
       "kernel.state_step_roofline", "kernel.state_chunk_busy",
       "kernel.state_chunk_roofline", "kernel.paged_attn_busy",
       "kernel.paged_attn_roofline", "state.rows_peak_share",
       "kv.blocks_peak_share", "state.bytes_over_cache_bytes"]


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
    from tpu_engine.models.nemotron_h import nemotron_h_apply

    with open(os.path.join(DATA, "configs",
                           "nemotron-h-small-test.json")) as f:
        config = json.load(f)
    registry._ensure_builtin_models_imported()
    spec = registry.create_model(config["factory"], **config["kwargs"])
    params = spec.init(jax.random.PRNGKey(3))
    forward = jax.jit(lambda tokens: nemotron_h_apply(
        params, tokens, spec.config, dtype=jnp.float32))

    def program(tokens):
        """Causal: one program over 64 right-padded columns."""
        padded = np.zeros((1, 64), np.int32)
        padded[0, :len(tokens)] = tokens
        return np.asarray(forward(padded)[0, :len(tokens)])

    return (config, spec, params, program,
            _load(os.path.join(BENCH, "references", "nemotron_h.py"),
                  "forward"))


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def source():
    with open(CATALOG) as f:
        return next(row for row in map(json.loads, f) if row["name"]
                    == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")


# -- the reference -------------------------------------------------------------

def test_reference_logits_equal_the_program_s_in_float32(small):
    """The dialect "nemotron_h": 56 tokens, more than three prefill chunks
    of the test lane, within 2e-4 of the largest logit. The reference scans
    the recurrence a token at a time, attends under a mask and applies every
    held expert to every token; the program's forward runs the chunked form
    and the sorted pair list."""
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
    with open(os.path.join(BENCH, "references", "nemotron_h.py")) as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "tpu_engine" not in code and "falcon" not in code
    assert "ragged_dot" not in code and "import math" not in code
    assert 'default_matmul_precision("highest")' in text


@pytest.mark.parametrize("control", [
    {"drop": "mamba"}, {"drop": "attention"}, {"drop": "experts"},
    {"drop": "latent"}, {"drop": "silu"}, {"top_k": 3},
    {"drop": "other_share"}, {"drop": "state"}])
def test_check_served_accepts_greedy_tokens_and_refuses_a_control(small,
                                                                  control):
    """The served tokens against the reference, then against the reference
    with a kind of layer dropped, the latent projection skipped, SiLU in
    place of relu^2, the top 6 cut to 3, another chip's experts, the state
    dropped at every chunk boundary: each reads NOT correct. (The controls
    one precision down and the rotation are read on the chip at the
    published widths.)"""
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
    assert spec.config == registry.create_model("nemotron_h_small").config
    ref, cfg = config["reference"], spec.config
    assert ref["dialect"] == "nemotron_h"
    assert (ref["pattern"], ref["n_heads"], ref["n_kv_heads"],
            ref["ssm_heads"], ref["n_groups"], ref["d_state"], ref["top_k"],
            ref["routed_scale"], ref["held_first"], ref["ln_eps"],
            ref["chunk"]) == (
        cfg.pattern, cfg.n_heads, cfg.kv_heads, cfg.lin_heads, cfg.n_groups,
        cfg.d_state, cfg.top_k, cfg.routed_scale, cfg.held[0], cfg.ln_eps,
        config["serving"]["gen_prefill_chunk"])


# -- the published configuration -------------------------------------------------

def test_every_source_key_is_there_and_only_the_four_cuts_are_reduced(
        published, source):
    """The catalog's `config` for Nemotron-3-Super-120B-A12B, key for key;
    the four keys of `reduced` alone differ, none is a width, and the file
    states the published value beside each."""
    p = published
    assert p["source"] == source["source_url"]
    assert list(p["reduced"]) == REDUCED
    for key, value in source["config"].items():
        if key not in p["reduced"]:
            assert p[key] == value, key
        else:
            assert p["published"][key] == value, key
    assert [p[key] for key in REDUCED] == [11, 128, 32768, 9216]
    assert not [key for key in REDUCED
                if key.endswith(("_dim", "_rank", "_size")) and
                key != "vocab_size"]
    # The pattern is kept whole and the program runs its first 11 layers.
    assert p["hybrid_override_pattern"] == \
        source["config"]["hybrid_override_pattern"]
    assert p["kwargs"]["pattern"] == p["hybrid_override_pattern"]
    assert p["kwargs"]["pattern"][:p["kwargs"]["n_layers"]] == "MEMEMEM*EME"
    assert p["reference"]["pattern"] == "MEMEMEM*EME"
    assumed = ("no_rotary", "w_in_order", "grouped_norm", "groups_to_heads",
               "latent_projections", "router_input", "selection_bias",
               "n_group_vs_n_groups", "chunk_size",
               "rescale_prenorm_residual")
    for key in assumed:
        assert key in p["assumed"], key
    for key in assumed[:8]:
        assert "lternative" in p["assumed"][key], key
    assert "sub-chunks of 64" in p["assumed"]["chunk_size"]
    assert "num_nextn_predict_layers" in p["omitted"]["mtp"]
    assert "state rollback" in p["omitted"]["mtp"]
    assert (p["num_nextn_predict_layers"], p["mtp_hybrid_override_pattern"]
            ) == (1, "*E")
    for said in ("one of 4 v5e chips", "8 stages", "32 chips",
                 "A QUARTER OF THE ROWS", "nothing stands in for it"):
        assert said in p["deployment"], said


def test_the_kwargs_are_the_published_widths(published, source):
    p, k, s = published, published["kwargs"], source["config"]
    assert (k["d_model"], k["n_heads"], k["n_kv_heads"], k["head_dim"],
            k["ssm_heads"], k["ssm_head_dim"], k["d_state"], k["n_groups"],
            k["conv_width"], k["d_latent"], k["d_ff_expert"],
            k["d_ff_shared"], k["n_experts"], k["top_k"], k["routed_scale"],
            k["ln_eps"]) == (
        s["hidden_size"], s["num_attention_heads"],
        s["num_key_value_heads"], s["head_dim"], s["mamba_num_heads"],
        s["mamba_head_dim"], s["ssm_state_size"], s["n_groups"],
        s["conv_kernel"], s["moe_latent_size"], s["moe_intermediate_size"],
        s["moe_shared_expert_intermediate_size"], s["n_routed_experts"],
        s["num_experts_per_tok"], s["routed_scaling_factor"],
        s["layer_norm_epsilon"])
    assert k["ssm_heads"] * k["ssm_head_dim"] == (s["expand"]
                                                  * s["hidden_size"])
    # The cuts: depth, the share of experts held, the vocabulary's rows,
    # the lane's limit.
    assert (k["n_layers"], k["held_first"], k["held_count"], k["vocab"],
            k["max_seq"], k["param_dtype"]) == (
        p["num_hidden_layers"], 0, p["n_routed_experts"], p["vocab_size"],
        p["max_position_embeddings"], "bfloat16")
    # The guide's floors: a whole period and four layers, 8 experts a
    # layer, an eighth of the vocabulary.
    assert k["held_count"] >= 8 and k["vocab"] * 8 >= s["vocab_size"]
    assert k["held_count"] * 4 == s["n_routed_experts"]
    r = p["reference"]
    assert (r["n_heads"], r["n_kv_heads"], r["ssm_heads"], r["n_groups"],
            r["d_state"], r["top_k"], r["routed_scale"], r["held_first"],
            r["chunk"]) == (32, 2, 128, 8, 128, 22, 5.0, 0, 256)
    sv = p["serving"]
    assert (sv["dtype"], sv["gen_max_batch_size"], sv["gen_kv_block_size"],
            sv["gen_kv_blocks"], sv["gen_mixed_step"],
            sv["gen_prefill_chunk"], sv["gen_prefix_sharing"]) == (
        "bfloat16", 64, 16, 35841, True, 256, False)
    with open(os.path.join(BENCH, "configs", "falcon-h1-34b-6l.json")) as f:
        assert set(sv) == set(json.load(f)["serving"])
    # Three rows cross three chunk boundaries or more; five of the six
    # decode across a multiple of 256; the reference's logits fit beside
    # the server.
    c = p["correct"]
    assert sum(n > 3 * sv["gen_prefill_chunk"]
               for n in c["prompt_lens"]) >= 2
    assert sum(n // 256 != (n + c["new_tokens"] - 1) // 256
               for n in c["prompt_lens"]) == 5
    assert c["pad_to"] >= max(c["prompt_lens"]) + c["new_tokens"] - 1
    assert c["pad_to"] % 256 == 0
    assert c["pad_to"] * p["vocab_size"] * 4 < 0.4e9


def test_the_configuration_builds_the_model_the_arithmetic_describes(
        published):
    """Shapes only, ISSUE 50's cut: 9.30 GB of bfloat16 weights at 5 : 5 : 1
    (a Mamba-2 layer 109.64 M parameters, the attention layer 35.66 M, an
    expert layer 54.53 M outside its 128 held experts of 5.505 M each; the
    embedding and the head 2 x 32,768 x 4096), a block pool over the ONE
    attention layer at 1,024 B a token (0.59 GB), 21.59 MB of state a row
    over the five Mamba-2 layers (1.40 GB)."""
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
    assert cfg.pattern == "MEMEMEM*EME" and spec.held == (0, 128)
    assert (cfg.n_linear_layers, cfg.n_moe_layers, cfg.n_full_layers) == (
        5, 5, 1)
    # The issue's matrices; zero biases add < 0.1 M a layer.
    by_kind = {kind: tree["layers"][cfg.pattern.index(kind)]
               for kind in "ME*"}
    assert 109.6 < count(by_kind["M"]) / 1e6 < 109.75
    assert 35.6 < count(by_kind["*"]) / 1e6 < 35.7
    bank = by_kind["E"]["mlp"]["experts"]
    assert (bank["up"].shape, bank["down"].shape) == (
        (128, 1024, 2688), (128, 2688, 1024))
    assert count(bank) == 128 * 5505024
    assert 54.5 < (count(by_kind["E"]) - count(bank)) / 1e6 < 54.6
    assert by_kind["M"]["ssm"]["w_in"]["kernel"].shape == (4096, 18560)
    assert by_kind["M"]["ssm"]["conv"].shape == (4, 10240)
    assert by_kind["E"]["mlp"]["router"]["kernel"].shape == (4096, 512)
    assert (count(tree["tok_embed"]) + count(tree["head"])
            - 32768) == 2 * 32768 * 4096
    assert 4.64e9 < count(tree) < 4.66e9
    # The router, the conv, the norms and the biases are float32: 9.30 GB
    # of bfloat16 and 0.02 GB more.
    assert 9.29e9 < n_bytes < 9.34e9
    (kind,) = cfg.kv_block_kinds
    assert kind.n_layers == 1
    assert dense_block_bytes(kind, 16, "bfloat16") == 16 * 1024
    serving = published["serving"]
    assert serving["gen_kv_blocks"] == 64 * (8192 + 512 + 256) // 16 + 1
    assert 0.58e9 < serving["gen_kv_blocks"] * 16 * 1024 < 0.60e9
    assert cfg.max_seq == 8192 + 512 + 512
    row = cfg.n_linear_layers * 4 * sum(int(np.prod(s))
                                        for s in cfg.state_row_shapes)
    assert cfg.state_row_shapes == ((128, 64, 128), (8, 3840))
    assert 21.58e6 < row < 21.60e6
    assert 1.40e9 < (serving["gen_max_batch_size"] + 1) * row < 1.41e9
    # Reckoned: 11.3 GB = 72 % of the chip's 15.75 GB.
    total = (n_bytes + serving["gen_kv_blocks"] * 16 * 1024
             + (serving["gen_max_batch_size"] + 1) * row)
    assert 0.71 < total / 15.75e9 < 0.73


def test_the_benchmark_lists_the_cell_and_its_fourteen_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # Found by name: later PRs append after it.
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "agents",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "1/4 of a deployment" in cell["why"]
    assert "host share" in cell["why"]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == REDUCED and len(config["why"]) <= 200
    assert config["source"].endswith(
        "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json")
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    by_name = {m["name"]: m for m in bench["per_layer"]}
    mine = [by_name[name] for name in NEW]
    assert all(CELL in m["workloads"] for m in mine)
    assert {m["layer"] for m in mine} == {"kernels", "state pool", "KV pool",
                                       "expert layer"}
    assert all(m["moves"] == "tokens_per_s" for m in mine)
    assert all(m["unit"] == "%" for m in mine
               if m["name"].endswith(("_roofline", "_busy")))
    with open(os.path.join(BENCH, "traffic", "agents.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], traffic["block"],
            traffic["pool"], traffic["warmup_s"], traffic["drain_s"]) == (
        "closed", 64, 64, 16, 2, 30)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 1.0, "min": 128,
        "max": 8192}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 128,
                                        "max": 512}
    assert traffic["sharing"] == {"share": 0.0}


def test_the_rehearsal_lists_every_metric_of_the_new_cell(tmp_path):
    """run.py --trace 1 on the CPU at the small size, a cell list of its own
    with the ten keyless per-layer metrics and the cell's own fourteen: the
    span and counter metrics print, what only a device trace gives is left
    out and said so."""
    cells = rehearsal_cells(tmp_path, "nemotron", CELL)
    real = load_benchmark()
    want = [m["name"] for m in metrics_listed(real, CELL)]
    assert set(NEW) <= set(want)
    assert [m["name"] for m in metrics_listed(real, CELL, "end_to_end")] \
        == ["itl_p95_ms", "tokens_per_s", "setup_s"]
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"),
         "--benchmark-file", cells, "--workload", "nemotron.closed",
         "--seed", str(2**31 + 50), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=dict(os.environ, TPU_ENGINE_PLATFORM="cpu"),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert set(got) == read_without_a_device(real, CELL)
    assert not {name for name in NEW if name.startswith("kernel.")} \
        & set(got)
    assert "moe.route_sort_busy" not in got
    assert got["step.compiles"] == {"value": 0, "unit": "compilations"}
    # Three clients of four slots; states and blocks of the same rows; a
    # quarter of the 16 experts held, top 6.
    assert got["state.rows_peak_share"]["value"] == 75.0
    assert 1.0 < got["state.bytes_over_cache_bytes"]["value"] < 40.0
    assert 3.0 < got["kv.blocks_peak_share"]["value"] < 40.0
    assert 1.0 < got["moe.rows_per_touched_expert"]["value"] < 20.0
    assert 1.0 <= got["moe.expert_load_imbalance"]["value"] < 3.0
