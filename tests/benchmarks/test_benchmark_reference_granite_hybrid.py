"""The "granite_hybrid" reference (benchmarks/references/granite_hybrid.py):
`check_served` telling the program's greedy tokens from a reference with one
term changed (the controls of the cell's `correct` that 24 positions can see,
at the small test size; the others are held on logits in
tests/test_granite_hybrid.py), the configuration's keys
against the catalog's and ISSUE 64's cut arithmetic (9.93 GB of weights at 9
mamba : 1 attention, 4,096 B of K/V a token, 38.66 MB of state a row) against
the tree and the pools it builds, what BENCHMARK.json gained, and the
rehearsal of the new cell's small double through run.py on the CPU. The model
against the reference on logits, the served step and the shares:
tests/test_granite_hybrid.py."""

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

CELL = "granite-4.0-h-small-10l.sessions"
CONFIG = "granite-4.0-h-small-10l"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_local_experts", "vocab_size",
           "max_position_embeddings"]
# The accepted readers by part and by run whose lists gained the cell: the
# per-layer list was FULL (128 of 128) when the cell arrived.
LISTED = ["kernel.state_step_live_share", "step.attn_busy",
          "step.attn_read_busy", "step.ffn_busy", "step.moe_experts_busy",
          "step.mixer_busy", "step.mixer_chunk_busy", "step.head_busy",
          "step.sample_busy", "step.unscoped_busy", "step.decode_run_ms",
          "step.chunk_run_ms"]
# The merged readers of a kind of kernel, pool and counter that list the
# cell since PR 68 made room (test_benchmark_layer_metrics_granite_hybrid.py
# pins them at this configuration's sizes).
MERGED = ["kv.blocks_peak_share", "kernel.paged_attn_busy",
          "kernel.paged_attn_roofline", "kernel.moe_experts_busy",
          "kernel.moe_experts_roofline", "moe.expert_load_imbalance",
          "moe.rows_per_touched_expert", "kernel.state_chunk_busy",
          "kernel.state_chunk_roofline", "kernel.state_step_busy",
          "kernel.state_step_roofline", "state.rows_peak_share",
          "state.bytes_over_cache_bytes"]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        "under_test_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, name)


@pytest.fixture(scope="module")
def small():
    """The small configuration, its weights, and the program's own greedy
    tokens after three prompts (its one-shot float32 forward)."""
    import jax
    import jax.numpy as jnp

    from tpu_engine.models import registry
    from tpu_engine.models.granite_hybrid import granite_hybrid_apply

    with open(os.path.join(DATA, "configs",
                           "granite-hybrid-small-test.json")) as f:
        config = json.load(f)
    registry._ensure_builtin_models_imported()
    spec = registry.create_model(config["factory"], **config["kwargs"])
    params = spec.init(jax.random.PRNGKey(3))
    forward = jax.jit(lambda tokens: granite_hybrid_apply(
        params, tokens, spec.config, dtype=jnp.float32))

    def program(tokens):
        """Causal: one program over 64 right-padded columns."""
        padded = np.zeros((1, 64), np.int32)
        padded[0, :len(tokens)] = tokens
        return np.asarray(forward(padded)[0, :len(tokens)])

    rng = np.random.default_rng(1)
    samples = []
    for length in (5, 20, 50):
        prompt = [int(t) for t in rng.integers(0, spec.config.vocab, length)]
        seq = list(prompt)
        for _ in range(8):
            seq.append(int(program(np.asarray(seq, np.int32))[-1].argmax()))
        samples.append((prompt, seq[length:]))
    return (config, spec, params, samples,
            _load(os.path.join(BENCH, "references", "granite_hybrid.py"),
                  "forward"))


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def source():
    with open(CATALOG) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "granite-4.0-h-small")


# -- the reference -------------------------------------------------------------

def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "granite_hybrid.py")) as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "tpu_engine" not in code and "ragged_dot" not in code
    assert "import math" not in code
    assert 'default_matmul_precision("highest")' in text


@pytest.mark.parametrize("control", [
    None, {"drop": "residual"}, {"drop": "embedding"}, {"drop": "rotate"},
    {"drop": "shared"}, {"drop": "mamba"}, {"drop": "attention"},
    {"drop": "decay"}, {"drop": "skip"}, {"drop": "other_share"},
    {"drop": "conv_tail"}, {"drop": "state"}],
    ids=lambda c: "served" if c is None else "-".join(map(str, c.values())))
def test_check_served_accepts_greedy_tokens_and_refuses_a_control(small,
                                                                  control):
    """The program's tokens against the reference, then against the
    reference with one term changed: two of the four scalars at 1, rotation
    applied, the shared expert or a kind of mixer dropped, no decay, no
    skip, the other chip's experts, the tail or the state lost at every
    chunk boundary: each reads NOT correct, at the test cell's own limits.
    (24 positions cannot tell the score scale, a top 4 cut to 2, the routed
    experts dropped or in float8: tests/test_granite_hybrid.py holds those
    on logits, the chip at the published widths.)"""
    config, spec, params, samples, forward = small
    ok, details = reference.check_served(
        forward, params, dict(config["reference"], **(control or {})),
        samples, 0.05, 0.9, pad_to=64)
    if control is None:
        assert ok, details
        assert details["exact_share"] == 1.0 and details["positions"] == 24
        assert all(len(set(g)) > 4 for _, g in samples)
    else:
        assert not ok, details


def test_the_weights_over_the_chosen_are_the_program_s_renormalised_ones():
    """ISSUE 64: this model's soft-max over the ten chosen logits EQUALS
    `ops.moe.softmax_topk_route`'s soft-max over all, top k, renormalised."""
    import jax
    import jax.numpy as jnp

    from tpu_engine.ops.moe import softmax_topk_route

    x = jax.random.normal(jax.random.PRNGKey(0), (50, 48))
    router = {"kernel": jax.random.normal(jax.random.PRNGKey(1), (48, 72))}
    experts, weights = softmax_topk_route(x, router, 10)
    logits = x @ router["kernel"]
    picked, chosen = jax.lax.top_k(logits, 10)
    assert bool((experts == chosen).all())
    np.testing.assert_allclose(weights, jax.nn.softmax(picked, axis=-1),
                               atol=1e-6)
    assert float(jnp.abs(weights.sum(-1) - 1).max()) < 1e-6


def test_the_test_configuration_is_the_registry_s_small_model(small):
    from tpu_engine.models import registry

    config, spec, _, _, _ = small
    assert spec.config == registry.create_model(
        "granite_hybrid-small-test").config
    ref, cfg = config["reference"], spec.config
    assert ref["dialect"] == "granite_hybrid"
    assert (ref["layers"], ref["n_heads"], ref["n_kv_heads"],
            ref["ssm_heads"], ref["n_groups"], ref["d_state"], ref["top_k"],
            ref["held_first"], ref["ln_eps"], ref["embedding_multiplier"],
            ref["residual_multiplier"], ref["attention_multiplier"],
            ref["logits_scaling"], ref["chunk"]) == (
        "".join("M" if kind == "mamba" else "A" for kind in cfg.layer_types),
        cfg.n_heads, cfg.kv_heads, cfg.lin_heads, cfg.n_groups, cfg.d_state,
        cfg.top_k, cfg.held[0], cfg.ln_eps, cfg.embedding_multiplier,
        cfg.residual_multiplier, cfg.attention_multiplier,
        cfg.logits_scaling, config["serving"]["gen_prefill_chunk"])


# -- the published configuration -------------------------------------------------

def test_every_source_key_is_there_and_only_the_four_cuts_are_reduced(
        published, source):
    """The catalog's `config` for granite-4.0-h-small, key for key; the four
    keys of `reduced` alone differ, none is a width, and the file states the
    published value beside each."""
    p = published
    assert p["source"] == source["source_url"]
    assert list(p["reduced"]) == REDUCED == list(p["published"])
    for key, value in source["config"].items():
        if key not in p["reduced"]:
            assert p[key] == value, key
        else:
            assert p["published"][key] == value, key
    assert [p[key] for key in REDUCED] == [10, 36, 50176, 5376]
    assert not [key for key in REDUCED
                if key.endswith(("_dim", "_rank", "_size"))
                and key != "vocab_size"]
    # Every published width, unchanged.
    assert (p["hidden_size"], p["num_attention_heads"],
            p["num_key_value_heads"], p["mamba_n_heads"], p["mamba_d_head"],
            p["mamba_d_state"], p["mamba_n_groups"], p["mamba_d_conv"],
            p["num_experts_per_tok"], p["intermediate_size"],
            p["shared_intermediate_size"]) == (
        4096, 32, 8, 128, 64, 128, 1, 4, 10, 768, 1536)
    assert (p["embedding_multiplier"], p["residual_multiplier"],
            p["attention_multiplier"], p["logits_scaling"]) == (
        12, 0.22, 0.0078125, 16)
    # One whole period of the published list: 9 mamba to 1 attention.
    first = p["layer_types"][:10]
    assert (first.count("mamba"), first.index("attention")) == (9, 5)
    for key in ("w_in_order", "swiglu_halves", "gated_norm", "one_group",
                "dt", "no_rotary", "attention_scale", "routing",
                "untied_head", "weights"):
        assert key in p["assumed"], key
    assert p["tie_word_embeddings"] is True
    assert "tie changes bytes" in p["assumed"]["untied_head"]
    for said in ("one of 2 v5e chips", "EXPERT parallelism", "8 chips",
                 "three further pairs", "HALF THE ROWS",
                 "nothing stands in for it"):
        assert said in p["deployment"], said
    assert list(p["omitted"]) == ["nothing"]


def test_the_kwargs_are_the_published_widths(published, source):
    p, k, s = published, published["kwargs"], source["config"]
    assert (k["d_model"], k["n_heads"], k["n_kv_heads"], k["head_dim"],
            k["ssm_heads"], k["ssm_head_dim"], k["d_state"], k["n_groups"],
            k["conv_width"], k["d_ff_expert"], k["d_ff_shared"],
            k["n_experts"], k["top_k"], k["embedding_multiplier"],
            k["residual_multiplier"], k["attention_multiplier"],
            k["logits_scaling"], k["ln_eps"], k["layer_types"]) == (
        s["hidden_size"], s["num_attention_heads"],
        s["num_key_value_heads"],
        s["hidden_size"] // s["num_attention_heads"], s["mamba_n_heads"],
        s["mamba_d_head"], s["mamba_d_state"], s["mamba_n_groups"],
        s["mamba_d_conv"], s["intermediate_size"],
        s["shared_intermediate_size"], s["num_local_experts"],
        s["num_experts_per_tok"], s["embedding_multiplier"],
        s["residual_multiplier"], s["attention_multiplier"],
        s["logits_scaling"], s["rms_norm_eps"], s["layer_types"])
    assert s["mamba_expand"] * s["hidden_size"] == (
        k["ssm_heads"] * k["ssm_head_dim"])
    assert (k["n_layers"], k["held_first"], k["held_count"], k["vocab"],
            k["max_seq"], k["param_dtype"]) == (
        p["num_hidden_layers"], 0, p["num_local_experts"], p["vocab_size"],
        p["max_position_embeddings"], "bfloat16")
    r = p["reference"]
    assert (r["layers"], r["n_heads"], r["n_kv_heads"], r["ssm_heads"],
            r["n_groups"], r["d_state"], r["top_k"], r["held_first"],
            r["embedding_multiplier"], r["residual_multiplier"],
            r["attention_multiplier"], r["logits_scaling"],
            r["chunk"]) == ("MMMMMAMMMM", 32, 8, 128, 1, 128, 10, 0, 12.0,
                            0.22, 0.0078125, 16.0, 256)
    sv = p["serving"]
    assert (sv["dtype"], sv["gen_max_batch_size"], sv["gen_kv_block_size"],
            sv["gen_kv_blocks"], sv["gen_mixed_step"],
            sv["gen_prefill_chunk"], sv["gen_prefix_sharing"]) == (
        "bfloat16", 64, 16, 21505, True, 256, False)
    with open(os.path.join(BENCH, "configs", "falcon-h1-34b-6l.json")) as f:
        assert set(sv) == set(json.load(f)["serving"])
    # Six prompts of 40-1,900 tokens: the longest is eight chunks, three of
    # the six cross three chunk boundaries or more; the reference's logits
    # fit beside the server.
    c = p["correct"]
    assert len(c["prompt_lens"]) == 6 and c["new_tokens"] == 200
    assert (min(c["prompt_lens"]), max(c["prompt_lens"])) == (40, 1900)
    assert sum(n > 3 * sv["gen_prefill_chunk"]
               for n in c["prompt_lens"]) >= 3
    assert c["pad_to"] >= max(c["prompt_lens"]) + c["new_tokens"] - 1
    assert c["pad_to"] % 256 == 0
    assert c["pad_to"] * p["vocab_size"] * 4 < 0.5e9


def test_the_configuration_builds_the_model_the_arithmetic_describes(
        published):
    """Shapes only, ISSUE 64's cut: a Mamba-2 mixer 102,286,976 parameters,
    the attention mixer 41,943,040, a layer's expert block outside its
    routed experts 19,169,280 and its two norms, a layer's bank 339,738,624
    in two tensors made in bfloat16; a block pool over the ONE attention
    layer at 4,096 B a token (1.41 GB), 38.66 MB of state a row over the
    nine mamba layers (2.51 GB with the null row)."""
    import jax

    from tpu_engine.models import registry
    from tpu_engine.runtime.kv_blocks import dense_block_bytes

    registry._ensure_builtin_models_imported()
    spec = registry.create_model(published["factory"], **published["kwargs"])
    cfg = spec.config
    tree = jax.eval_shape(spec.init, jax.random.PRNGKey(0))

    def count(sub):
        """Matrices and scales; the program's zero biases left out."""
        return sum(int(np.prod(x.shape)) for path, x in
                   jax.tree_util.tree_leaves_with_path(sub)
                   if str(path[-1]) != "['bias']")

    n_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(tree))
    layers = tree["layers"]
    assert ["ssm" in bp for bp in layers] == [True] * 5 + [False] + [True] * 4
    assert count(layers[0]["ssm"]) == 102286976
    assert count(layers[5]["attn"]) == 41943040
    bank = layers[0]["mlp"]["experts"]
    assert (bank["gate_up"].shape, bank["down"].shape) == (
        (36, 4096, 1536), (36, 768, 4096))
    assert {x.dtype.name for x in jax.tree.leaves(bank)} == {"bfloat16"}
    assert count(bank) == 339738624
    assert count(layers[0]["mlp"]) - count(bank) == 19169280
    assert layers[0]["mlp"]["router"]["kernel"].shape == (4096, 72)
    assert "bias" not in layers[0]["mlp"]["router"]
    assert count(tree) == 4962732672
    assert 9.92e9 < n_bytes < 9.94e9
    (kind,) = cfg.kv_block_kinds
    assert kind.n_layers == 1
    assert dense_block_bytes(kind, 16, "bfloat16") == 16 * 4096
    serving = published["serving"]
    assert serving["gen_kv_blocks"] == 64 * 5376 // 16 + 1
    assert 1.40e9 < serving["gen_kv_blocks"] * 16 * 4096 < 1.42e9
    row = cfg.n_linear_layers * 4 * sum(int(np.prod(s))
                                        for s in cfg.state_row_shapes)
    assert row == 38661120
    assert 2.51e9 < (serving["gen_max_batch_size"] + 1) * row < 2.52e9
    # Reckoned: 13.85 GB = 88 % of the chip's 15.75 GB.
    total = (n_bytes + serving["gen_kv_blocks"] * 16 * 4096
             + (serving["gen_max_batch_size"] + 1) * row)
    assert 0.87 < total / 15.75e9 < 0.89


def test_the_benchmark_lists_the_cell_on_the_accepted_readers_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # Found by name: later PRs append after it.
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "sessions",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "360 expert banks" in cell["why"]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == REDUCED and len(config["why"]) <= 200
    assert config["source"].endswith(
        "ibm-granite/granite-4.0-h-small/blob/main/config.json")
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])]
    assert sorted(listed) == sorted(LISTED + MERGED)
    assert len(bench["per_layer"]) <= 84           # ISSUE 68: room again
    assert not [m["name"] for m in bench["end_to_end"]
                if CELL in m.get("workloads", [])]       # no TTFT
    with open(os.path.join(BENCH, "traffic", "sessions.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], traffic["block"],
            traffic["pool"], traffic["warmup_s"], traffic["drain_s"],
            traffic["warmup_max_new_tokens"]) == (
        "closed", 64, 64, 16, 2, 30, 8)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 1.0, "min": 128,
        "max": 4096}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 256,
                                        "max": 1024}
    assert traffic["sharing"] == {"share": 0.0}


def test_the_rehearsal_lists_every_metric_of_the_new_cell(tmp_path):
    """run.py --trace 1 on the CPU at the small size, a cell list of its own
    with the ten keyless per-layer metrics and the accepted readers the cell
    was appended to: the span and counter metrics print (the live share of
    the state step's slots among them), what only a device trace gives is
    left out."""
    cells = rehearsal_cells(tmp_path, "granite", CELL)
    real = load_benchmark()
    want = [m["name"] for m in metrics_listed(real, CELL)]
    assert set(LISTED + MERGED) <= set(want)
    assert len(want) >= 29                  # ISSUE 68; 33 at PR 68
    assert [m["name"] for m in metrics_listed(real, CELL, "end_to_end")] \
        == ["itl_p95_ms", "tokens_per_s", "setup_s"]
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"),
         "--benchmark-file", cells, "--workload", "granite.closed",
         "--seed", str(2**31 + 64), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=dict(os.environ, TPU_ENGINE_PLATFORM="cpu"),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert set(got) == read_without_a_device(real, CELL)
    # the readers by part and of a kernel's seconds need a device trace
    assert not set(got) & {
        name for name in LISTED + MERGED
        if name.startswith(("step.", "kernel."))
        and name != "kernel.state_step_live_share"}
    assert 0 < got["kernel.state_step_live_share"]["value"] <= 100
    assert got["step.compiles"] == {"value": 0, "unit": "compilations"}
