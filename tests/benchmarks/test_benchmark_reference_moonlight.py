"""The "moonlight" reference (benchmarks/references/moonlight.py) against the
program's float32 forward at the small test size, `check_served` telling the
served path from a reference with one term dropped, the configuration's
widths against the source's, the counting of lib/roofline_moe_mla.py by
hand-computed cases, and the rehearsal of the new cell's metrics through
run.py on the CPU (the six readers: test_benchmark_layer_metrics_moonlight.py)."""

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

from lib import reference, roofline, roofline_moe_mla  # noqa: E402
from lib.roofline_sizes import sizes  # noqa: E402

CELL = "moonlight-16b-a3b-7l.solve"
MINE = ["kernel.paged_attn_busy", "kernel.moe_experts_busy",
        "kernel.paged_attn_roofline", "kernel.moe_experts_roofline",
        "moe.expert_load_imbalance", "moe.rows_per_touched_expert"]
V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


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
    from tpu_engine.models.moonlight import moonlight_apply

    with open(os.path.join(DATA, "configs",
                           "moonlight-small-test.json")) as f:
        config = json.load(f)
    registry._ensure_builtin_models_imported()
    spec = registry.create_model(config["factory"], **config["kwargs"])
    params = spec.init(jax.random.PRNGKey(3))

    def program(tokens):
        return np.asarray(moonlight_apply(
            params, jnp.asarray(tokens)[None], spec.config,
            dtype=jnp.float32)[0])

    forward = _load(os.path.join(BENCH, "references", "moonlight.py"),
                    "forward")
    return config, spec, params, program, forward


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs",
                           "moonlight-16b-a3b-7l.json")) as f:
        return json.load(f)


# -- the reference -------------------------------------------------------------

def test_reference_logits_equal_the_program_s_in_float32(small):
    """The dialect "moonlight": 40 tokens, more than one prefill chunk of
    the test lane (16), within 2e-4 of the largest logit. The reference is
    the expanded attention with every expert applied and masked; the
    program's forward sorts pairs and runs a grouped product."""
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


@pytest.fixture(scope="module")
def served(small):
    """Three prompts and the program's eight greedy tokens after each,
    decoded once for the three controls (24 lengths, a compilation each)."""
    _, spec, _, program, _ = small
    rng = np.random.default_rng(1)
    samples = []
    for length in (5, 19, 33):
        prompt = [int(t) for t in rng.integers(0, spec.config.vocab, length)]
        seq = list(prompt)
        for _ in range(8):
            seq.append(int(program(np.asarray(seq, np.int32))[-1].argmax()))
        samples.append((prompt, seq[length:]))
    return samples


@pytest.mark.parametrize("control", [{"drop": "shared"}, {"drop": "bias"},
                                     {"drop": "k_pe"}])
def test_check_served_accepts_greedy_tokens_and_refuses_a_control(
        small, served, control):
    """The served tokens against the reference, then against the reference
    with the shared expert, the selection bias or the rope-key term left
    out: each reads NOT correct, by the exact share or by the gap. (The
    control one precision down, `experts_as: float8_e4m3fn`, is read on the
    chip at the published widths: three layers of 32-wide experts in
    float32 do not flip a router's choice.)"""
    config, spec, params, program, forward = small
    samples = served
    ok, details = reference.check_served(forward, params, config["reference"],
                                         samples, 0.05, 0.9, pad_to=48)
    assert ok, details
    assert details["exact_share"] == 1.0 and details["positions"] == 24
    ok, details = reference.check_served(
        forward, params, dict(config["reference"], **control), samples,
        0.05, 0.9, pad_to=48)
    assert not ok, details


def test_the_test_configuration_is_the_registry_s_small_moonlight(small):
    from tpu_engine.models import registry

    config, spec, _, _, _ = small
    assert spec.config == registry.create_model("moonlight-small-test").config
    assert config["reference"]["dialect"] == "moonlight"


# -- the published configuration -------------------------------------------------

def test_widths_are_the_sources(published):
    """Every published width is as the source has it; only depth is cut."""
    p = published
    assert (p["hidden_size"], p["kv_lora_rank"], p["qk_nope_head_dim"],
            p["qk_rope_head_dim"], p["v_head_dim"],
            p["moe_intermediate_size"], p["intermediate_size"],
            p["n_routed_experts"], p["num_experts_per_tok"],
            p["n_shared_experts"], p["vocab_size"],
            p["num_attention_heads"], p["first_k_dense_replace"],
            p["routed_scaling_factor"], p["rope_theta"], p["q_lora_rank"],
            p["max_position_embeddings"], p["rms_norm_eps"]) == (
        2048, 512, 128, 64, 128, 1408, 11264, 64, 6, 2, 163840, 16, 1,
        2.446, 50000, None, 8192, 1e-05)
    assert list(p["reduced"]) == ["num_hidden_layers"]
    assert p["num_hidden_layers"] == p["kwargs"]["n_layers"] == 7
    k = p["kwargs"]
    assert (k["d_model"], k["kv_lora_rank"], k["qk_nope"], k["qk_rope"],
            k["v_head"], k["d_ff_expert"], k["d_ff_dense"], k["n_experts"],
            k["top_k"], k["n_shared"], k["vocab"], k["n_heads"],
            k["n_dense_layers"], k["routed_scale"], k["rope_theta"],
            k["max_seq"], k["param_dtype"]) == (
        2048, 512, 128, 64, 128, 1408, 11264, 64, 6, 2, 163840, 16, 1,
        2.446, 50000.0, 8192, "bfloat16")
    for key in ("assumed", "deployment", "source"):
        assert p[key]
    r = p["reference"]
    assert (r["n_heads"], r["qk_nope"], r["qk_rope"], r["v_head"],
            r["top_k"], r["routed_scale"]) == (16, 128, 64, 128, 6, 2.446)
    s = p["serving"]
    assert (s["dtype"], s["gen_max_batch_size"], s["gen_kv_block_size"],
            s["gen_kv_blocks"], s["gen_mixed_step"],
            s["gen_prefill_chunk"]) == ("bfloat16", 32, 16, 5121, True, 256)


def test_the_configuration_builds_the_model_the_arithmetic_describes(
        published):
    """Shapes only: 8.53 GB of bfloat16 weights, the latent pool as sized."""
    import jax

    from tpu_engine.models import registry
    from tpu_engine.runtime.kv_blocks import dense_block_bytes

    registry._ensure_builtin_models_imported()
    spec = registry.create_model(published["factory"], **published["kwargs"])
    leaves = jax.tree.leaves(jax.eval_shape(spec.init,
                                            jax.random.PRNGKey(0)))
    n_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
    assert 8.52e9 < n_bytes < 8.54e9
    block = dense_block_bytes(spec.config, 16, "bfloat16")
    assert block == 7 * 16 * 640 * 2
    assert 0.73e9 < published["serving"]["gen_kv_blocks"] * block < 0.74e9


def test_the_benchmark_lists_the_cell_and_its_six_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {
        "name": CELL, "config": "moonlight-16b-a3b-7l", "traffic": "solve",
        "chips": 1, "why": cell["why"]}
    # PR 28's six readers, under the names of the merged readers that took
    # the two latent-attention copies' place in PR 68.
    by_name = {m["name"]: m for m in bench["per_layer"]}
    mine = [by_name[name] for name in MINE]
    assert all(CELL in m["workloads"] for m in mine)
    assert {m["layer"] for m in mine} == {"kernels", "expert layer"}
    assert all(m["moves"] == "tokens_per_s" for m in mine)
    with open(os.path.join(BENCH, "traffic", "solve.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], traffic["block"],
            traffic["pool"]) == ("closed", 32, 32, 32)
    assert traffic["prompt_tokens"] == {"dist": "uniform", "min": 512,
                                        "max": 2048}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 128,
                                        "max": 512}
    assert traffic["sharing"] == {"share": 0.0}


# -- the counting ----------------------------------------------------------------

def test_sizes_of_the_configuration_as_run(published):
    assert sizes(published) == {
        "attention": {"kernel": "mla_latent", "layers": 7, "heads": 16,
                      "latent": 512, "rope": 64, "lanes": 576,
                      "bytes_per_element": 2},
        "experts": {"kernel": "ragged-dot", "matrices": 3, "rows": 2048,
                    "cols": 1408, "held": (0, 64),
                    "bytes_per_element": 2},
        "recurrence": None}


@pytest.mark.parametrize("args, want", [
    # one token, one layer: 576 lanes of bf16
    ((1, 1, 512, 64, 2), 1152),
    # 32 rows x 1500 tokens, 7 layers: 1152 B a token and layer, 0.39 GB
    ((32 * 1500, 7, 512, 64, 2), 387072000),
])
def test_latent_bytes(args, want):
    assert roofline_moe_mla.latent_bytes(*args) == want


@pytest.mark.parametrize("args, want", [
    # one pair, one layer, one head: 576 multiply-adds for the score and
    # 512 for the value
    ((1, 1, 1, 512, 64), 2176),
    # a decode row at context 1000, 7 layers, 16 heads
    ((1000, 7, 16, 512, 64), 243712000),
])
def test_latent_flops(args, want):
    assert roofline_moe_mla.latent_flops(*args) == want


@pytest.mark.parametrize("args, want", [
    # one expert: three matrices of 2048 x 1408 in bf16, 17.3 MB
    ((1, 2048, 1408, 2), 17301504),
    # every expert of six layers: 6.64 GB
    ((6 * 64, 2048, 1408, 2), 6643777536),
])
def test_expert_bytes(args, want):
    assert roofline_moe_mla.expert_bytes(*args) == want


@pytest.mark.parametrize("args, want", [
    ((1, 2048, 1408), 17301504),
    # 32 decode rows x 6 experts x 6 layers
    ((32 * 6 * 6, 2048, 1408), 19931332608),
])
def test_expert_flops(args, want):
    assert roofline_moe_mla.expert_flops(*args) == want


def test_a_decode_tick_s_experts_are_bound_by_their_weights():
    """All 384 experts touched by 1152 assignments: 8.1 ms of weights
    against 0.1 ms of arithmetic."""
    floor = roofline.floor_seconds(
        roofline_moe_mla.expert_bytes(384, 2048, 1408, 2),
        roofline_moe_mla.expert_flops(1152, 2048, 1408), V5E)
    assert floor == pytest.approx(6643777536 / 819e9)


# -- the rehearsal -------------------------------------------------------------------

def test_the_rehearsal_lists_every_metric_of_the_new_cell(tmp_path):
    """run.py --trace 1 on the CPU at the small size, a cell list of its
    own with the ten keyless per-layer metrics and the cell's own six: the
    span and counter metrics print, what only a device trace gives is left
    out and said so; the untraced run prints the three end-to-end ones."""
    cells = rehearsal_cells(tmp_path, "moonlight", CELL)
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
             "--benchmark-file", cells, "--workload", "moonlight.closed",
             "--seed", str(2**31 + 28), "--seconds", "2", "--trace", trace],
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
    assert got["moe.expert_load_imbalance"]["value"] >= 1.0
    assert 1.0 <= got["moe.rows_per_touched_expert"]["value"] <= 4 * 16 * 2
    for name in device_only:
        assert f"{name} found nothing to read" in said["1"]
