"""The "lfm2" reference (benchmarks/references/lfm2.py): `check_served`
telling the program's greedy tokens from a reference with one term changed
(every control of the cell's `correct`, at the small test size), the
configuration's keys against the catalog's and ISSUE 60's cut arithmetic
(10.63 GB of weights at 7 conv : 2 attention, 4,096 B of K/V a token,
114,688 B of state a row) against the tree and the pools it builds, what
BENCHMARK.json gained (the rehearsal of the new cell through run.py on the
CPU: test_benchmark_layer_metrics_lfm2.py). The model against the reference on logits, the served step and the
shares: tests/test_lfm2.py."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH, DATA, ROOT  # noqa: E402

from lib import reference  # noqa: E402

CELL = "lfm2-24b-a2b-9l.assist"
CONFIG = "lfm2-24b-a2b-9l"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "max_position_embeddings"]
# The accepted readers by part and by run whose lists gained the cell: the
# per-layer list was FULL (128 of 128) when the cell arrived.
LISTED = ["step.attn_busy", "step.attn_read_busy", "step.ffn_busy",
          "step.moe_experts_busy", "step.mixer_busy", "step.head_busy",
          "step.sample_busy", "step.unscoped_busy", "step.chunk_run_ms"]
# The merged readers of a kind of kernel, pool and counter that list the
# cell since PR 68 made room (test_benchmark_layer_metrics_lfm2.py pins them
# at this configuration's sizes).
MERGED = ["kv.blocks_peak_share", "kernel.paged_attn_busy",
          "kernel.paged_attn_roofline", "kernel.moe_experts_busy",
          "kernel.moe_experts_roofline", "moe.expert_load_imbalance",
          "moe.rows_per_touched_expert", "state.rows_peak_share",
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
    from tpu_engine.models.lfm2 import lfm2_apply

    with open(os.path.join(DATA, "configs", "lfm2-small-test.json")) as f:
        config = json.load(f)
    registry._ensure_builtin_models_imported()
    spec = registry.create_model(config["factory"], **config["kwargs"])
    params = spec.init(jax.random.PRNGKey(3))
    forward = jax.jit(lambda tokens: lfm2_apply(
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
            _load(os.path.join(BENCH, "references", "lfm2.py"), "forward"))


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def source():
    with open(CATALOG) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "LFM2-24B-A2B")


# -- the reference -------------------------------------------------------------

def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "lfm2.py")) as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "tpu_engine" not in code and "ragged_dot" not in code
    assert "import math" not in code
    assert 'default_matmul_precision("highest")' in text


@pytest.mark.parametrize("control", [
    None, {"drop": "conv_tail"}, {"drop": "taps"}, {"drop": "qk_norm"}, {"rope_theta": 1e4}, {"drop": "gate"},
    {"drop": "conv"}, {"drop": "attention"}, {"drop": "experts"},
    {"top_k": 2}, {"weights_as": "float8_e4m3fn"}],
    ids=lambda c: "served" if c is None else "-".join(map(str, c.values())))
def test_check_served_accepts_greedy_tokens_and_refuses_a_control(small,
                                                                  control):
    """The program's tokens against the reference, then against the
    reference with one term changed: the tail zeroed at every chunk
    boundary, the taps' order reversed, q and k not normalised, theta 1e4, the gate C left out, a kind of layer
    dropped, the top 4 cut to 2, every matrix in float8: each reads NOT
    correct, at the test cell's own limits. (24 positions cannot tell the
    marginal expert the bias swaps, nor float8 in the experts alone: the
    test below holds those on logits, the chip at the published widths.)"""
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


@pytest.mark.parametrize("control", [{"drop": "bias"},
                                     {"experts_as": "float8_e4m3fn"}],
                         ids=["bias", "experts_float8"])
def test_a_control_in_the_experts_moves_the_logits_past_the_lane_s_1e_4(
        small, control):
    """The float32 lane equals the reference within 1e-4 on logits
    (tests/test_lfm2.py); a choice made without the bias, or the experts'
    matrices rounded to float8, moves them by a hundred times that."""
    import jax.numpy as jnp

    config, _, params, samples, forward = small
    tokens = jnp.asarray(samples[2][0] + samples[2][1], jnp.int32)
    want = forward(params, tokens, reference.sizes_of(config["reference"]))
    got = forward(params, tokens, reference.sizes_of(
        dict(config["reference"], **control)))
    assert float(jnp.abs(got - want).max()) > 1e-2


def test_the_bias_changes_more_than_one_choice_in_twenty():
    """The draw's selection bias (0.02 x normal beside sigmoid scores of
    spread 0.2) decides a share of the router's choices at the published 64
    experts top 4, so that a program that chose on the scores alone is
    told: over random unit rows, in the first expert layer."""
    import jax

    from tpu_engine.models import registry

    spec = registry.create_model("lfm2-small-test", n_experts=64)
    params = spec.init(jax.random.PRNGKey(3))
    router = params["layers"][1]["mlp"]["router"]
    z = jax.random.normal(jax.random.PRNGKey(5), (4000, spec.config.d_model))
    scores = np.asarray(jax.nn.sigmoid(z @ router["kernel"]))
    k = spec.config.top_k
    with_bias = np.argsort(-(scores + np.asarray(router["bias"])), -1)[:, :k]
    without = np.argsort(-scores, -1)[:, :k]
    moved = np.mean([len(set(a) - set(b)) for a, b in zip(with_bias,
                                                          without)]) / k
    assert moved > 0.05


def test_the_test_configuration_is_the_registry_s_small_model(small):
    from tpu_engine.models import registry

    config, spec, _, _, _ = small
    assert spec.config == registry.create_model("lfm2-small-test").config
    ref, cfg = config["reference"], spec.config
    assert ref["dialect"] == "lfm2"
    assert (ref["layers"], ref["n_heads"], ref["n_kv_heads"],
            ref["n_dense_layers"], ref["top_k"], ref["routed_scale"],
            ref["held_first"], ref["ln_eps"], ref["rope_theta"],
            ref["chunk"]) == (
        "".join("C" if kind == "conv" else "A" for kind in cfg.layer_types),
        cfg.n_heads, cfg.kv_heads, cfg.n_dense_layers, cfg.top_k,
        cfg.routed_scale, cfg.held[0], cfg.ln_eps, cfg.rope_theta,
        config["serving"]["gen_prefill_chunk"])


# -- the published configuration -------------------------------------------------

def test_every_source_key_is_there_and_only_the_four_cuts_are_reduced(
        published, source):
    """The catalog's `config` for LFM2-24B-A2B, key for key; the four keys
    of `reduced` alone differ, none is a width, and the file states the
    published value beside each."""
    p = published
    assert p["source"] == source["source_url"]
    assert list(p["reduced"]) == REDUCED
    for key, value in source["config"].items():
        if key not in p["reduced"]:
            assert p[key] == value, key
    assert (p["published"]["num_hidden_layers"],
            p["published"]["num_dense_layers"],
            p["published"]["max_position_embeddings"]) == (
        source["config"]["num_hidden_layers"],
        source["config"]["num_dense_layers"],
        source["config"]["max_position_embeddings"])
    layers = source["config"]["layer_types"]
    # Published layer 0 and layers 2-9: two whole periods behind the dense
    # layer.
    assert p["layer_types"] == [layers[0]] + layers[2:10]
    assert [p[key] for key in REDUCED if key != "layer_types"] == [
        9, 1, 5120]
    assert not [key for key in REDUCED
                if key.endswith(("_dim", "_rank", "_size"))]
    assert (p["num_experts"], p["vocab_size"]) == (64, 65536)   # NOT cut
    for key in ("qk_norm", "head_dim", "final_norm", "untied_head", "rope",
                "conv", "expert_bias", "weights"):
        assert key in p["assumed"], key
    for key in ("qk_norm", "final_norm", "untied_head", "expert_bias"):
        assert "lternative" in p["assumed"][key] or "tie" in p["assumed"][key]
    for said in ("PIPELINE stages", "five v5e chips", "no exchange",
                 "a half to a quarter", "leading dense layer kept once"):
        assert said in p["deployment"], said


def test_the_kwargs_are_the_published_widths(published, source):
    p, k, s = published, published["kwargs"], source["config"]
    assert (k["d_model"], k["n_heads"], k["n_kv_heads"], k["d_ff_dense"],
            k["d_ff_expert"], k["n_experts"], k["top_k"], k["routed_scale"],
            k["conv_width"], k["ln_eps"], k["rope_theta"], k["vocab"]) == (
        s["hidden_size"], s["num_attention_heads"],
        s["num_key_value_heads"], s["intermediate_size"],
        s["moe_intermediate_size"], s["num_experts"],
        s["num_experts_per_tok"], s["routed_scaling_factor"],
        s["conv_L_cache"], s["norm_eps"],
        s["rope_parameters"]["rope_theta"], s["vocab_size"])
    assert (k["n_layers"], k["n_dense_layers"], k["layer_types"],
            k["held_first"], k["held_count"], k["max_seq"],
            k["param_dtype"]) == (
        p["num_hidden_layers"], p["num_dense_layers"], p["layer_types"], 0,
        64, p["max_position_embeddings"], "bfloat16")
    r = p["reference"]
    assert (r["layers"], r["n_heads"], r["n_kv_heads"], r["n_dense_layers"],
            r["top_k"], r["routed_scale"], r["held_first"], r["rope_theta"],
            r["chunk"]) == ("CACCCACCC", 32, 8, 1, 4, 1.0, 0, 1e6, 256)
    sv = p["serving"]
    assert (sv["dtype"], sv["gen_max_batch_size"], sv["gen_kv_block_size"],
            sv["gen_kv_blocks"], sv["gen_mixed_step"],
            sv["gen_prefill_chunk"], sv["gen_prefix_sharing"]) == (
        "bfloat16", 128, 16, 40961, True, 256, False)
    with open(os.path.join(BENCH, "configs", "falcon-h1-34b-6l.json")) as f:
        assert set(sv) == set(json.load(f)["serving"])
    # Six prompts of 48-2,000 tokens: the longest is eight chunks, so a tail
    # crosses seven chunk boundaries; the reference's logits fit beside the
    # server.
    c = p["correct"]
    assert len(c["prompt_lens"]) == 6 and c["new_tokens"] == 256
    assert (min(c["prompt_lens"]), max(c["prompt_lens"])) == (48, 2000)
    assert -(-max(c["prompt_lens"]) // sv["gen_prefill_chunk"]) == 8
    assert c["pad_to"] >= max(c["prompt_lens"]) + c["new_tokens"] - 1
    assert c["pad_to"] % 256 == 0
    assert c["pad_to"] * p["vocab_size"] * 4 < 0.7e9


def test_the_configuration_builds_the_model_the_arithmetic_describes(
        published):
    """Shapes only, ISSUE 60's cut: a conv operator 16,783,360 parameters, a
    GQA operator 10,485,888, an expert layer's bank 603,979,776 in two
    tensors made in bfloat16, the dense SwiGLU 72,351,744; a block pool over
    the TWO attention layers at 4,096 B a token (2.68 GB), 114,688 B of
    state a row over the seven conv layers."""
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
                   if "bias" not in str(path[-1]) or "router" in str(path))

    n_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(tree))
    layers = tree["layers"]
    assert count(layers[0]["conv"]) == 16783360
    assert count(layers[1]["attn"]) == 10485888
    assert count(layers[0]["mlp"]) == 72351744
    bank = layers[1]["mlp"]["experts"]
    assert (bank["gate_up"].shape, bank["down"].shape) == (
        (64, 2048, 3072), (64, 1536, 2048))
    assert {x.dtype.name for x in jax.tree.leaves(bank)} == {"bfloat16"}
    assert count(bank) == 603979776
    router = layers[1]["mlp"]["router"]
    assert (router["kernel"].shape, router["bias"].shape) == ((2048, 64),
                                                            (64,))
    assert count(tree) == 5312168704
    assert 10.62e9 < n_bytes < 10.64e9
    (kind,) = cfg.kv_block_kinds
    assert kind.n_layers == 2
    assert dense_block_bytes(kind, 16, "bfloat16") == 16 * 4096
    serving = published["serving"]
    assert serving["gen_kv_blocks"] == 128 * 5120 // 16 + 1
    assert 2.68e9 < serving["gen_kv_blocks"] * 16 * 4096 < 2.69e9
    row = cfg.n_linear_layers * 4 * sum(int(np.prod(s))
                                        for s in cfg.state_row_shapes)
    assert row == 114688
    # Reckoned: 13.3 GB = 85 % of the chip's 15.75 GB.
    total = (n_bytes + serving["gen_kv_blocks"] * 16 * 4096
             + (serving["gen_max_batch_size"] + 1) * row)
    assert 0.84 < total / 15.75e9 < 0.86


def test_the_benchmark_lists_the_cell_on_the_accepted_readers_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # Found by name: later PRs append after it.
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "assist",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "8 rows an expert" in cell["why"]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == REDUCED and len(config["why"]) <= 200
    assert config["source"].endswith(
        "LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])]
    assert sorted(listed) == sorted(LISTED + MERGED)
    # Nothing runs under `mixer/chunk` here: a listed metric that reads
    # nothing refuses the line.
    assert "step.mixer_chunk_busy" not in listed
    assert not [m["name"] for m in bench["end_to_end"]
                if CELL in m.get("workloads", [])]       # no TTFT
    with open(os.path.join(BENCH, "traffic", "assist.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], traffic["block"],
            traffic["pool"], traffic["warmup_s"], traffic["drain_s"],
            traffic["warmup_max_new_tokens"]) == (
        "closed", 128, 128, 16, 2, 30, 8)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.9, "min": 64,
        "max": 4096}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 256,
                                        "max": 1024}
    assert traffic["sharing"] == {"share": 0.0}
