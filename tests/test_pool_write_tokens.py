"""The uniform step's pool write over the tick's TOKENS against the write
over all of its slots (`models.transformer.transformer_step_rows_ragged`,
`max_tokens`): the same values at the same places of the pool, the null
block apart, and so the same logits to the bit.

One random tick a case: rows of 0, 1 and a chunk of tokens; a tick AT the
bound; the int8 pool's tuple; a GQA model with rotation; bfloat16; and the
two cases in which the step keeps the parent's graph (a step a slot wide,
a caller that states no bound). Then the scheduler's side: a tick over the
bound is a counted fault (what the spans say of the write is held beside
its reader, tests/benchmarks/test_benchmark_layer_metrics_poolwrite.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.models.transformer import (
    pool_write_slots,
    transformer_step_rows_ragged,
)
from tpu_engine.runtime.kv_blocks import BlockPool
from tpu_engine.runtime.scheduler import ContinuousGenerator

_ensure_builtin_models_imported()

BS, WIDTH, N_BLOCKS = 16, 16, 16

# case: (model, q_lens, pos0, width, max_tokens, quantize, dtype)
CASES = {
    "rows-of-0-1-and-a-chunk": (
        "gpt2-small-test", (0, 1, 16, 5), (0, 21, 16, 3), WIDTH, 24, "",
        "float32"),
    "at-the-bound": (
        "gpt2-small-test", (0, 1, 16, 3), (0, 40, 0, 30), WIDTH, 20, "",
        "float32"),
    "every-row-a-token-in-a-wide-step": (
        "gpt2-small-test", (1, 1, 1, 1), (5, 0, 33, 17), WIDTH, 20, "",
        "float32"),
    "no-row-holds-a-token": (
        "gpt2-small-test", (0, 0, 0, 0), (0, 0, 0, 0), WIDTH, 20, "",
        "float32"),
    "int8-pool": (
        "gpt2-small-test", (0, 1, 16, 5), (0, 21, 16, 3), WIDTH, 24, "int8",
        "float32"),
    "int8-pool-at-the-bound": (
        "gpt2-small-test", (2, 1, 16, 1), (7, 40, 0, 30), WIDTH, 20, "int8",
        "float32"),
    "grouped-heads-rotated": (
        "llama-small-test", (0, 1, 16, 5), (0, 21, 16, 3), WIDTH, 24, "",
        "float32"),
    "bfloat16": (
        "gpt2-small-test", (3, 1, 16, 0), (2, 21, 16, 0), WIDTH, 24, "",
        "bfloat16"),
    # The parent's graph: nothing to leave out at one slot a row, and no
    # bound stated.
    "a-slot-wide": (
        "gpt2-small-test", (1, 0, 1, 1), (5, 0, 33, 17), 1, 20, "",
        "float32"),
    "no-bound-stated": (
        "gpt2-small-test", (0, 1, 16, 5), (0, 21, 16, 3), WIDTH, None, "",
        "float32"),
    "a-bound-past-the-slots": (
        "gpt2-small-test", (0, 1, 16, 5), (0, 21, 16, 3), WIDTH, 64, "",
        "float32"),
}
KEEPS_THE_GRAPH = {"a-slot-wide", "no-bound-stated", "a-bound-past-the-slots"}


@pytest.fixture(scope="module")
def models():
    made = {}
    for name in ("gpt2-small-test", "llama-small-test"):
        spec = create_model(name, max_seq=128)
        made[name] = (spec, spec.init(jax.random.PRNGKey(0)))
    return made


def _tick(models, case):
    """(call(max_tokens) -> the step's outputs, valid (B, W) bool)."""
    model, q_lens, pos0, width, _, quant, dtype = CASES[case]
    spec, params = models[model]
    cfg, dtype = spec.config, jnp.dtype(dtype)
    rng = np.random.default_rng(sorted(CASES).index(case))
    pool = BlockPool(cfg, N_BLOCKS, BS, dtype, quantize=quant)

    def noise(x, lo, hi):
        return jnp.asarray(rng.integers(lo, hi, x.shape), x.dtype)

    caches = jax.tree.map(lambda x: noise(x, -100, 100), pool.caches)
    scales = (jax.tree.map(lambda x: noise(x, 1, 9) / 64, pool.scales)
              if quant else None)
    rows = len(q_lens)
    # Row b holds blocks 1 + 3 b .. 3 + 3 b: 48 columns, a chunk past the
    # deepest `pos0` here.
    tables = jnp.asarray(1 + 3 * np.arange(rows)[:, None] + np.arange(3),
                         jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (rows, width)),
                         jnp.int32)
    qlen = jnp.asarray(q_lens, jnp.int32)

    def call(max_tokens):
        return jax.jit(
            lambda c, s: transformer_step_rows_ragged(
                params, tokens, c, tables, jnp.asarray(pos0, jnp.int32),
                qlen, cfg, dtype=dtype, scales=s, max_tokens=max_tokens)
        )(caches, scales)

    return call, np.arange(width)[None, :] < np.asarray(q_lens)[:, None]


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_listed_write_is_the_all_slot_write(models, case):
    """Logits bit-equal at every valid slot, and every block of every pool
    array but the null one bit-equal, layer by layer."""
    max_tokens = CASES[case][4]
    call, valid = _tick(models, case)
    want, got = call(None), call(max_tokens)
    assert want[0].shape == got[0].shape == valid.shape + want[0].shape[2:]
    np.testing.assert_array_equal(np.asarray(got[0])[valid],
                                  np.asarray(want[0])[valid])
    arrays = [(a, b) for old, new in zip(want[1:], got[1:])
              for a, b in zip(old, new)]
    assert len(arrays) == (4 if CASES[case][5] else 2)
    for old, new in arrays:
        np.testing.assert_array_equal(np.asarray(new)[:, 1:],
                                      np.asarray(old)[:, 1:])


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_step_lists_its_tokens_only_where_that_leaves_slots_out(
        models, case):
    """The graph is the parent's at one slot a row, with no bound and with
    a bound no shorter than the slots; elsewhere each layer's scatter
    takes `max_tokens` indices, not B x W."""
    model, q_lens, _, width, max_tokens, _, _ = CASES[case]
    spec, params = models[model]
    rows = len(q_lens)
    pool = BlockPool(spec.config, N_BLOCKS, BS, jnp.float32)

    def graph(bound):
        return str(jax.make_jaxpr(
            lambda c, tokens, tables, pos0, qlen:
            transformer_step_rows_ragged(
                params, tokens, c, tables, pos0, qlen, spec.config,
                dtype=jnp.float32, max_tokens=bound))(
            pool.caches, jnp.zeros((rows, width), jnp.int32),
            jnp.zeros((rows, 3), jnp.int32), jnp.zeros((rows,), jnp.int32),
            jnp.zeros((rows,), jnp.int32)))

    slots = pool_write_slots(rows, width, max_tokens)
    lanes = spec.config.kv_heads * spec.config.d_head
    if case in KEEPS_THE_GRAPH:
        assert slots == rows * width
        assert graph(max_tokens) == graph(None)
    else:
        assert slots == max_tokens < rows * width
        assert f"f32[{slots},{lanes}]" in graph(max_tokens)
        assert f"f32[{slots},{lanes}]" not in graph(None)


@pytest.fixture()
def lane(models):
    spec, params = models["gpt2-small-test"]
    gen = ContinuousGenerator(spec, params=params, dtype="float32",
                              n_slots=4, max_seq=128, kv_block_size=16,
                              prefill_chunk=16, mixed_token_budget=16)
    yield gen
    gen.stop()


def test_a_tick_over_the_bound_is_a_counted_fault(lane):
    """A tick that held more tokens than the step's list would lose K/V
    without a sign. It never reaches the device: the tick raises where it
    is formed, the loop counts a failure and fails the rows as after any
    lost step, and the lane serves on."""
    assert lane._tick_max_tokens == 16 + 4
    prompt = [5, 9, 3, 7, 2]
    want = lane.submit(prompt=prompt, max_new_tokens=4).result(timeout=120)
    assert lane.stats().get("failures", 0) == 0
    lane._tick_max_tokens = 2   # what no configuration can state
    with pytest.raises(RuntimeError, match="over the step's bound of 2"):
        lane.submit(prompt=prompt, max_new_tokens=4).result(timeout=120)
    stats = lane.stats()
    assert stats["failures"] == 1
    assert stats["kv_pool"]["blocks_free"] == stats["kv_pool"]["blocks_total"]
    lane._tick_max_tokens = 20
    got = lane.submit(prompt=prompt, max_new_tokens=4).result(timeout=120)
    assert got[0] == want[0]
    assert lane.stats()["failures"] == 1
