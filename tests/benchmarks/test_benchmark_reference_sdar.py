"""The SDAR configuration of the benchmark (benchmarks/configs/
sdar-30b-a3b-chat-7l.json): its cut re-derived from the catalog's keys
(parameters a layer, bytes, pool), its files held to BENCHMARK.json, and its
plain reference, dialect "sdar" (benchmarks/references/sdar.py): the replay of
`forward` equals a pass-by-pass loop over `body` on a small case, and each
control the configuration names changes the logits."""

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
CELL = "sdar-30b-a3b-chat-7l.reply"
RUN, MASK = 4, 255

with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat-7l.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
with open(os.path.join(ROOT, "tests", "benchmarks", "data", "configs",
                       "sdar-small-test.json")) as f:
    SMALL = json.load(f)
# The catalog row's keys (model-configs guide, SDAR-30B-A3B-Chat).
PUBLISHED = {"attention_bias": False, "decoder_sparse_step": 1,
             "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
             "intermediate_size": 6144, "max_position_embeddings": 32768,
             "max_window_layers": 48, "mlp_only_layers": [],
             "model_type": "sdar_moe", "moe_intermediate_size": 768,
             "norm_topk_prob": True, "num_attention_heads": 32,
             "num_experts": 128, "num_experts_per_tok": 8,
             "num_hidden_layers": 48, "num_key_value_heads": 4,
             "rms_norm_eps": 1e-06, "rope_scaling": None,
             "rope_theta": 1000000, "sliding_window": None,
             "tie_word_embeddings": False, "use_sliding_window": False,
             "vocab_size": 151936}


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(BENCH, "references", "sdar.py")
    spec = importlib.util.spec_from_file_location("sdar_reference", path)
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

def test_every_published_key_is_kept_but_the_two_listed():
    entry, = [c for c in BENCHMARK["configs"] if c["name"] == CONFIG["name"]]
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == sorted(CONFIG["reduced"], reverse=True)
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CONFIG[key] != value
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 7
    assert CONFIG["max_position_embeddings"] == 4096


def test_the_kwargs_are_the_published_widths():
    kw = CONFIG["kwargs"]
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"],
            kw["d_ff_expert"], kw["n_experts"], kw["top_k"], kw["vocab"]) == (
        2048, 32, 4, 128, 768, 128, 8, 151936)
    assert kw["n_layers"] == CONFIG["num_hidden_layers"]
    assert kw["max_seq"] == CONFIG["max_position_embeddings"]
    assert (kw["rope_theta"], kw["ln_eps"]) == (1e6, 1e-6)
    assert (kw["block_length"], kw["denoising_steps"], kw["reveal"]) == (
        4, 4, "sequential")
    for key in ("qk_norm", "logits", "block_length", "denoising_steps",
                "reveal", "mask_token_id", "confidence_threshold", "rope",
                "weights"):
        assert key in CONFIG["assumed"], key
    ref = CONFIG["reference"]
    assert ref["tokens_per_pass"] == kw["block_length"] // kw[
        "denoising_steps"]
    assert ref["mask_token_id"] == kw["mask_token_id"] == 151669


def test_the_bytes_re_derived_from_the_published_keys():
    """ISSUE 53's arithmetic from the config's keys, and the program's own
    tree at those widths (shapes only)."""
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )

    d, h, kv, dh = 2048, 32, 4, 128
    attention = d * h * dh + 2 * d * kv * dh + h * dh * d
    norms = 2 * d + 2 * dh
    experts = 128 * 3 * d * 768
    layer = attention + norms + d * 128 + experts
    assert (attention, norms, experts) == (18874368, 4352, 603979776)
    assert layer == 623120640
    ends = 2 * 151936 * d
    assert ends == 622329856
    total = 7 * layer + ends + d                    # and the final norm
    _ensure_builtin_models_imported()
    spec = create_model(CONFIG["factory"], **CONFIG["kwargs"])
    tree = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    kernels = sum(int(np.prod(x.shape)) for path, x
                  in jax.tree_util.tree_flatten_with_path(tree)[0]
                  if "bias" not in str(path[-1]))   # the source has none
    assert kernels == total
    assert 9.96e9 < 2 * total < 9.98e9              # bfloat16
    # K and V: 7 layers x 2 x 4 heads x 128 x 2 B a token; 64 rows x (2048 +
    # 256) tokens in blocks of 16, and the null block.
    serving = CONFIG["serving"]
    assert serving["gen_kv_blocks"] == 64 * (2048 + 256) // 16 + 1 == 9217
    pool = 9217 * 16 * 7 * 2 * kv * dh * 2
    assert 2.11e9 < pool < 2.12e9
    assert (2 * total + pool) / 15.75e9 > 0.76
    assert serving["gen_mixed_token_budget"] == 64 * 4 + serving[
        "gen_prefill_chunk"]
    assert serving["gen_prefix_sharing"] is False


def test_the_cell_and_its_files():
    cell, = [w for w in BENCHMARK["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG["name"], traffic="reply",
                        chips=1)
    with open(os.path.join(BENCH, "traffic", "reply.json")) as f:
        traffic = json.load(f)
    assert traffic["loop"] == "closed" and traffic["clients"] == 64
    assert traffic["block"] * traffic["pool"] >= 2048
    assert traffic["output_tokens"] == {"dist": "fixed", "value": 256}
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                        "sigma": 0.8, "min": 64, "max": 2048}
    correct = CONFIG["correct"]
    sampled = correct["prompt_lens"] + [correct["new_tokens"],
                                        correct["repeat_prompt_len"],
                                        correct["repeat_new_tokens"]]
    assert all(n % RUN == 0 for n in sampled)       # no block holds a tail
    assert max(correct["prompt_lens"]) >= 1024
    assert len(correct["prompt_lens"]) >= 4
    assert max(correct["prompt_lens"]) + correct["new_tokens"] <= correct[
        "pad_to"]
    # PR 53's eleven readers: the four of a block-decoding lane's passes
    # are the cell's own; its two kernels' pairs, its experts' two counters
    # and its pool's share are the merged readers' since PR 68.
    listed = [m["name"] for m in BENCHMARK["per_layer"]
              if m.get("workloads") == [CELL]]
    own = ["sched.passes_per_block", "sched.tokens_per_row_tick",
           "sched.commit_pass_share", "step.block_decode_ms"]
    assert set(own) <= set(listed)
    for name in own + ["kernel.paged_attn_busy",
                          "kernel.paged_attn_roofline",
                          "kernel.moe_experts_busy",
                          "kernel.moe_experts_roofline",
                          "moe.rows_per_touched_expert",
                          "moe.expert_load_imbalance",
                          "kv.blocks_peak_share"]:
        metric, = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
        assert CELL in metric["workloads"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert os.path.exists(os.path.join(BENCH, "references", "sdar.py"))


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "sdar.py")) as f:
        source = f.read()
    assert "tpu_engine" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source


# -- the replay ---------------------------------------------------------------------

def _loop(reference, params, tokens, prompt_len, per_pass, sizes):
    """Pass by pass over `body`: row p - 1 = the logits, at position p, of
    the pass that reveals p, the block's earlier positions final and the
    rest MASK, every earlier block clean."""
    body = jax.jit(reference.body, static_argnums=(2,))
    rows = np.zeros((len(tokens), 256), np.float32)
    for start in range(prompt_len, len(tokens), RUN):
        for first in range(0, RUN, per_pass):
            shown = (tokens[:start + first]
                     + [MASK] * (RUN - first))
            lg = np.asarray(body(params, jnp.asarray(shown, jnp.int32),
                                 sizes))
            for p in range(start + first, start + first + per_pass):
                rows[p - 1] = lg[p]
    return rows


@pytest.mark.parametrize("per_pass", [1, 2])
def test_the_two_stream_replay_equals_a_pass_by_pass_loop(reference, small,
                                                          per_pass):
    _, params = small
    rng = np.random.default_rng(per_pass)
    prompt_len, new = 12, 16
    tokens = [int(t) for t in rng.integers(1, 250, prompt_len + new)]
    sizes = _sizes(tokens_per_pass=per_pass)
    padded = np.zeros((64,), np.int32)
    padded[:len(tokens)] = tokens
    got = np.asarray(jax.jit(reference.forward, static_argnums=(2,))(
        params, jnp.asarray(padded), sizes))
    want = _loop(reference, params, tokens, prompt_len, per_pass, sizes)
    np.testing.assert_allclose(got[prompt_len - 1:len(tokens) - 1],
                               want[prompt_len - 1:len(tokens) - 1],
                               atol=2e-4)


@pytest.mark.parametrize("control,changes", [
    ({"drop": "block_mask"}, True),
    ({"drop": "commit"}, True),
    ({"drop": "qk_norm"}, True),
    ({"drop": "norm_topk"}, True),
    ({"router_as": "bfloat16"}, True),
    ({"experts_as": "float8_e4m3fn"}, True),
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


def test_a_soft_max_after_the_choice_gives_the_same_weights():
    """Why "soft-max after the top 8" is no control: with the chosen
    probabilities normalised it is the same numbers."""
    from tpu_engine.ops.moe import softmax_topk_route

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    router = {"kernel": jax.random.normal(jax.random.PRNGKey(1), (32, 16))}
    experts, weights = softmax_topk_route(x, router, 4)
    logits = np.asarray(x @ router["kernel"])
    chosen = np.take_along_axis(logits, np.asarray(experts), axis=-1)
    after = np.exp(chosen - chosen.max(-1, keepdims=True))
    after /= after.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weights), after, atol=1e-5)


def test_the_experts_drop_no_token_however_uneven_the_load(reference,
                                                           small):
    """Every token to one expert (what the masked positions of the noisy
    streams do to the first layers): each still gets that expert's output,
    weighted 1."""
    _, params = small
    mlp = params["layers"][0]["mlp"]
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (64, 64)))
    sizes = dict(SMALL["reference"], top_k=1)
    one = dict(mlp, router={"kernel": jnp.zeros_like(
        mlp["router"]["kernel"]).at[:, 0].set(1.0)})
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference._experts(one, x, sizes))
        gate, up = jnp.split(x @ mlp["experts"]["gate_up"][0], 2, axis=-1)
        want = (jax.nn.silu(gate) * up) @ mlp["experts"]["down"][0]
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)
