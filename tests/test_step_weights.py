"""The step's weights are cast to the step's dtype once, not every tick
(models.transformer.step_weights, ModelSpec.step_weights, the continuous
scheduler's `_step_params`).

Contracts under test:
- SAME WORK DONE ONCE: the paged step functions give bit-equal logits and
  pool with the float32 master tree and with the step tree, for a gpt2-
  and a llama-dialect model, at width 1 and at the chunk width;
- the lowered mixed step handed the step tree holds no float32 -> bfloat16
  convert of a kernel's shape (the master tree's lowering holds one per
  kernel: the control);
- the copy holds ONLY the kernels the step casts: every other leaf is the
  master's array, a kernel already in the step's dtype and a
  weight-quantized leaf are left alone;
- the family decides through the registry: dense transformers declare
  `step_weights`; the MoE transformer, Moonlight and the slab family's
  lanes hand their steps the master tree itself (`step_bytes` 0);
- `set_params` rebuilds the copy; a `--tp` lane's copy is sharded like
  its master; `stats()["weights"]` adds up to the leaves' bytes.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    create_model,
    tp_shardings,
)
from tpu_engine.models.transformer import (
    step_weights,
    transformer_step_rows_ragged,
)
from tpu_engine.ops.quant import quantize_params
from tpu_engine.parallel.mesh import tp_mesh
from tpu_engine.runtime.kv_blocks import BlockPool
from tpu_engine.runtime.scheduler import ContinuousGenerator

_ensure_builtin_models_imported()

DENSE = ["gpt2-small-test", "llama-small-test"]
CHUNK = 16
PROMPTS = [[5, 9, 3, 17], [2, 4, 6, 8, 10, 12], [1] * 20]


@functools.lru_cache(maxsize=None)
def _model(name):
    spec = create_model(name, max_seq=64)
    return spec, spec.init(jax.random.PRNGKey(0))


def _leaves(tree):
    return {jax.tree_util.keystr(path, simple=True, separator="/"): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _cast_names(params, step):
    master = _leaves(params)
    return sorted(name for name, leaf in _leaves(step).items()
                  if leaf is not master[name])


def _lane(name, params=None, dtype="bfloat16", **kw):
    spec, init = _model(name)
    kw.setdefault("n_slots", 4)
    if spec.state_family != "state_slab":
        kw.setdefault("kv_block_size", 16)
    kw.setdefault("mixed_step", True)
    kw.setdefault("prefill_chunk", CHUNK)
    return ContinuousGenerator(spec, params=init if params is None else params,
                               dtype=dtype, **kw)


def _step_inputs(cfg, width, quantize=""):
    """Two rows mid-stream over a pool of recognisable bytes: row 0
    consumes `width` tokens from column 17, row 1 one token at column 3
    (its other slots are padding). An int8 pool's scales ride as the
    caches' second half."""
    pool = BlockPool(cfg, 6, 16, jnp.bfloat16, quantize=quantize)
    rng = np.random.default_rng(0)

    def noise(x):
        if x.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-100, 100, x.shape), x.dtype)
        return jnp.asarray(rng.normal(size=x.shape), x.dtype)

    caches = jax.tree.map(noise, pool.caches)
    if quantize:
        caches = (caches, jax.tree.map(lambda x: jnp.abs(noise(x)) + 0.1,
                                       pool.scales))
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (2, width)), jnp.int32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    pos0 = jnp.asarray([17, 3], jnp.int32)
    qlen = jnp.asarray([width, 1], jnp.int32)
    return tokens, caches, tables, pos0, qlen


@pytest.mark.parametrize("step", ["ragged-1", f"ragged-{CHUNK}", "int8-1"])
@pytest.mark.parametrize("name", DENSE)
def test_step_tree_gives_bit_equal_logits_and_pool(name, step):
    spec, params = _model(name)
    cfg = spec.config
    tree = step_weights(params, jnp.bfloat16)
    assert tree is not params
    if step == "int8-1":
        # A decode-only tick over an int8 pool: the new token quantizes
        # at its write and the scales come back with the pool.
        tokens, (caches, scales), tables, pos0, qlen = _step_inputs(
            cfg, 1, quantize="int8")
        run = jax.jit(lambda p: transformer_step_rows_ragged(
            p, tokens, caches, tables, pos0, qlen, cfg,
            sample_slot=qlen - 1, scales=scales))
    else:
        tokens, caches, tables, pos0, qlen = _step_inputs(
            cfg, int(step.split("-")[1]))
        run = jax.jit(lambda p: transformer_step_rows_ragged(
            p, tokens, caches, tables, pos0, qlen, cfg,
            sample_slot=qlen - 1))
    want, got = run(params), run(tree)
    assert np.asarray(want[0]).any()
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


_TO_BF16 = re.compile(r"convert.*\(tensor<([\dx]+)xf32>\) -> tensor<\1xbf16>")


@pytest.mark.parametrize("width", [1, CHUNK])
@pytest.mark.parametrize("name", DENSE)
def test_lowered_mixed_step_casts_no_kernel(name, width):
    """The mixed step as lowered: with the master tree every kernel is
    converted float32 -> bfloat16 inside the program (a layer's slice in
    the scan's body, the head outside it); with the step tree no convert
    of a kernel's shape, stacked or a layer's, is left."""
    spec, params = _model(name)
    cfg = spec.config
    tokens, caches, tables, pos0, qlen = _step_inputs(cfg, width)
    shapes = set()
    for leaf_name, leaf in _leaves(params).items():
        if leaf_name.endswith("/kernel"):
            shapes.add("x".join(map(str, leaf.shape)))
            shapes.add("x".join(map(str, leaf.shape[-2:])))

    def converted(tree):
        text = jax.jit(lambda p: transformer_step_rows_ragged(
            p, tokens, caches, tables, pos0, qlen, cfg,
            sample_slot=qlen - 1)).lower(tree).as_text()
        return shapes & set(_TO_BF16.findall(text))

    assert converted(params)
    assert not converted(step_weights(params, jnp.bfloat16))


@pytest.mark.parametrize("name", DENSE)
def test_only_the_steps_kernels_are_copies(name):
    spec, params = _model(name)
    mlp = (["gate", "up", "proj"] if spec.config.mlp_act == "swiglu"
           else ["fc", "proj"])
    want = sorted([f"blocks/attn/{w}/kernel" for w in ("wq", "wk", "wv", "wo")]
                  + [f"blocks/mlp/{m}/kernel" for m in mlp]
                  + ["head/kernel"])
    tree = step_weights(params, jnp.bfloat16)
    assert _cast_names(params, tree) == want
    master, step = _leaves(params), _leaves(tree)
    assert master.keys() == step.keys()
    for leaf_name in want:
        assert step[leaf_name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(step[leaf_name]),
            np.asarray(master[leaf_name].astype(jnp.bfloat16)))
    # Nothing to cast: the master tree itself, not an equal one.
    assert step_weights(params, jnp.float32) is params
    assert step_weights(tree, jnp.bfloat16) is tree


def test_a_leaf_already_cast_or_quantized_is_left_alone():
    spec, params = _model("gpt2-small-test")
    half = dict(params, head=dict(
        params["head"], kernel=params["head"]["kernel"].astype(jnp.bfloat16)))
    tree = step_weights(half, jnp.bfloat16)
    assert tree["head"]["kernel"] is half["head"]["kernel"]
    assert "head/kernel" not in _cast_names(half, tree)
    quant = quantize_params(params)
    assert "kernel_q" in quant["head"]
    assert step_weights(quant, jnp.bfloat16) is quant


@pytest.mark.parametrize("name", DENSE)
def test_dense_lanes_hand_their_steps_the_copy(name):
    spec, params = _model(name)
    assert spec.step_weights is step_weights
    gen = _lane(name)
    try:
        weights = gen.stats()["weights"]
        assert gen.params is params
        step = gen._step_params
        assert step is not params
        copies = [leaf for leaf_name, leaf in _leaves(step).items()
                  if leaf_name in _cast_names(params, step)]
        assert weights == {
            "master_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
            "step_bytes": sum(x.nbytes for x in copies),
            "step_dtype": "bfloat16",
        }
        assert 0 < weights["step_bytes"] < weights["master_bytes"] / 2
        assert gen.generate([PROMPTS[0]], max_new_tokens=4)[0]
    finally:
        gen.stop()


@pytest.mark.parametrize("name,dtype", [
    ("gpt2-small-test", "float32"),     # nothing to cast at this dtype
    ("gpt2-moe-test", "bfloat16"),      # ops.moe casts its own banks
    ("moonlight-small-test", "bfloat16"),
    ("ssd-small-test", "bfloat16"),
])
def test_other_lanes_hand_their_steps_the_master_tree(name, dtype):
    spec, params = _model(name)
    if name != "gpt2-small-test":
        assert spec.step_weights is None
    gen = _lane(name, dtype=dtype)
    try:
        assert gen._step_params is gen.params
        weights = gen.stats()["weights"]
        assert weights["step_bytes"] == 0
        assert weights["step_dtype"] == dtype
        assert weights["master_bytes"] == sum(
            x.nbytes for x in jax.tree.leaves(params))
    finally:
        gen.stop()


def test_stream_after_set_params_equals_a_fresh_lanes():
    spec, params = _model("gpt2-small-test")
    new = spec.init(jax.random.PRNGKey(7))
    fresh = _lane("gpt2-small-test", params=new)
    try:
        want = fresh.generate(PROMPTS, max_new_tokens=8)
    finally:
        fresh.stop()
    gen = _lane("gpt2-small-test")
    try:
        before = gen.generate(PROMPTS, max_new_tokens=8)
        old_copy = gen._step_params
        gen.set_params(new)
        assert gen.params is new
        assert _cast_names(new, gen._step_params) == _cast_names(params,
                                                                 old_copy)
        assert gen.generate(PROMPTS, max_new_tokens=8) == want
    finally:
        gen.stop()
    assert before != want


def test_a_tp_lanes_copy_is_sharded_like_its_master():
    spec, params = _model("gpt2-small-test")
    gen = _lane("gpt2-small-test", tp=2)
    try:
        want = _leaves(tp_shardings(spec, params, tp_mesh(2, None)))
        master, step = _leaves(gen.params), _leaves(gen._step_params)
        cast = _cast_names(gen.params, gen._step_params)
        assert "blocks/attn/wq/kernel" in cast and "head/kernel" in cast
        for leaf_name in cast:
            assert step[leaf_name].dtype == jnp.bfloat16
            for sharding in (master[leaf_name].sharding, want[leaf_name]):
                assert step[leaf_name].sharding.is_equivalent_to(
                    sharding, step[leaf_name].ndim)
        assert not step["blocks/attn/wq/kernel"].sharding.is_fully_replicated
        assert gen.generate([PROMPTS[0]], max_new_tokens=4)[0]
    finally:
        gen.stop()
