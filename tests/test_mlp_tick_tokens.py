"""The uniform step's second half over the tick's TOKENS against the
second half over all of its slots (`models.transformer`
`transformer_step_rows_ragged`, `max_tokens`; `second_half_slots`): the
output projection and its residual add, the norm, the MLP and its add are
row-wise, so a valid slot's logits and the K/V every later layer writes for
it are the all-slot step's whichever rows sit beside it in the operand.

One random tick a case, over gelu and SwiGLU, learned positions over all
heads and rotated grouped ones, the plain pool and the int8 one: decode
rows alone, beside one chunk and beside several, a chunk whose last token
the list's tail repeats, a tick with no live token, a tick at exactly the
bound, a chunk's tail of 1 to 3 tokens. Then which products the step holds
and over how many rows, where it keeps the all-slot form (a slot wide, no
bound stated, routed experts), a lane's served tokens with the bound and
without, and what the tick's span says the two parts computed (its
reader-side twin for the pool write:
tests/benchmarks/test_benchmark_layer_metrics_poolwrite.py).
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
    second_half_slots,
    transformer_step_rows_ragged,
)
from tpu_engine.runtime.kv_blocks import BlockPool
from tpu_engine.runtime.scheduler import ContinuousGenerator
from tpu_engine.utils.tracing import SpanRecorder

_ensure_builtin_models_imported()

BS, WIDTH, N_BLOCKS = 16, 16, 16
# No other side of the test models is 96 or 48 long: a shape names its op.
MAX_SEQ, GRAPH_ROWS = 96, 3
# gelu over all heads; SwiGLU over grouped heads, rotated.
MODELS = ("gpt2-small-test", "llama-small-test")

# mix: (q_lens, pos0, max_tokens)
MIXES = {
    "decode-rows-alone": ((1, 1, 1, 1), (21, 16, 3, 40), 20),
    "decode-rows-beside-one-chunk": ((1, 16, 1, 0), (21, 16, 3, 0), 24),
    # the list's tail repeats the CHUNK's last token, 9 times
    "a-chunk-last-in-the-list": ((1, 0, 1, 13), (21, 0, 3, 16), 24),
    "decode-rows-beside-several-chunks": ((1, 9, 1, 7), (30, 16, 3, 0), 24),
    "no-live-token": ((0, 0, 0, 0), (0, 0, 0, 0), 20),
    "at-exactly-the-bound": ((1, 16, 2, 1), (40, 0, 30, 7), 20),
    "a-tail-of-one": ((1, 1, 0, 1), (5, 32, 0, 17), 20),
    "a-tail-of-two": ((0, 2, 1, 1), (0, 32, 9, 17), 20),
    "a-tail-of-three": ((3, 0, 0, 1), (16, 0, 0, 2), 20),
}
# The repo's parity tolerance for a float32 forward (tests/test_llama.py
# holds the cached forward to the plain one at this); the pool's payload
# is held to it too, an int8 one to a level.
TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.fixture(scope="module")
def models():
    made = {}
    for name in MODELS + ("gpt2-moe-test",):
        spec = create_model(name, max_seq=MAX_SEQ)
        made[name] = (spec, spec.init(jax.random.PRNGKey(0)))
    return made


_STEPS = {}


def _step(spec, quant, max_tokens):
    """The step jitted once a (model, pool, bound): a mix is data."""
    key = (spec.name, quant, max_tokens)
    if key not in _STEPS:
        _STEPS[key] = jax.jit(
            lambda params, tokens, caches, scales, tables, pos0, qlen:
            transformer_step_rows_ragged(
                params, tokens, caches, tables, pos0, qlen, spec.config,
                dtype=jnp.float32, scales=scales, max_tokens=max_tokens))
    return _STEPS[key]


def _tick(spec, params, q_lens, pos0, width, quant, seed):
    """(call(max_tokens) -> the step's outputs, valid (B, W) bool) on a
    pool of noise: what a tick does not write is held as it lay."""
    cfg = spec.config
    rng = np.random.default_rng(seed)
    pool = BlockPool(cfg, N_BLOCKS, BS, jnp.float32, quantize=quant)

    def noise(x, lo, hi):
        return jnp.asarray(rng.integers(lo, hi, x.shape), x.dtype)

    caches = jax.tree.map(lambda x: noise(x, -100, 100), pool.caches)
    if not quant:
        caches = jax.tree.map(lambda x: x / 64, caches)
    scales = (jax.tree.map(lambda x: noise(x, 1, 9) / 64, pool.scales)
              if quant else None)
    rows = len(q_lens)
    tables = jnp.asarray(1 + 3 * np.arange(rows)[:, None] + np.arange(3),
                         jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (rows, width)),
                         jnp.int32)

    def call(max_tokens):
        return _step(spec, quant, max_tokens)(
            params, tokens, caches, scales, tables,
            jnp.asarray(pos0, jnp.int32), jnp.asarray(q_lens, jnp.int32))

    return call, np.arange(width)[None, :] < np.asarray(q_lens)[:, None]


@pytest.mark.parametrize("quant", ["", "int8"])
@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("model", MODELS)
def test_the_listed_second_half_is_the_all_slot_one(models, model, mix,
                                                    quant):
    """Logits at every valid slot, and every block of every pool array but
    the null one, layer by layer: a later layer's K/V is made from what the
    layers under it left in the residual, so the pool holds every layer's
    second half to account, not the last one's alone."""
    q_lens, pos0, max_tokens = MIXES[mix]
    assert sum(q_lens) <= max_tokens < len(q_lens) * WIDTH
    spec, params = models[model]
    call, valid = _tick(spec, params, q_lens, pos0, WIDTH, quant,
                        sorted(MIXES).index(mix))
    want, got = call(None), call(max_tokens)
    assert want[0].shape == got[0].shape == valid.shape + want[0].shape[2:]
    np.testing.assert_allclose(np.asarray(got[0])[valid],
                               np.asarray(want[0])[valid], **TOL)
    assert np.isfinite(np.asarray(got[0])[valid]).all()
    arrays = [(a, b) for old, new in zip(want[1:], got[1:])
              for a, b in zip(old, new)]
    assert len(arrays) == (4 if quant else 2)
    for old, new in arrays:
        old, new = np.asarray(old)[:, 1:], np.asarray(new)[:, 1:]
        if old.dtype == np.int8:
            # A value on a rounding edge may land a level apart.
            assert np.abs(old.astype(np.int32) - new).max() <= 1
        else:
            np.testing.assert_allclose(new, old, **TOL)


def _jaxpr(spec, params, rows, width, bound):
    pool = BlockPool(spec.config, N_BLOCKS, BS, jnp.float32)
    return jax.make_jaxpr(
        lambda c, tokens, tables, pos0, qlen:
        transformer_step_rows_ragged(
            params, tokens, c, tables, pos0, qlen, spec.config,
            dtype=jnp.float32, max_tokens=bound))(
        pool.caches, jnp.zeros((rows, width), jnp.int32),
        jnp.zeros((rows, 3), jnp.int32), jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows,), jnp.int32))


def _graph(spec, params, rows, width, bound):
    return str(_jaxpr(spec, params, rows, width, bound))


def _products(closed):
    """part of the step -> the shapes of the left operands of its
    `dot_general`s, the layer scan's body included."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                part = str(eqn.source_info.name_stack)
                found.setdefault(part, []).append(eqn.invars[0].aval.shape)
            for inner in jax.core.jaxprs_in_params(eqn.params):
                walk(inner)

    walk(closed.jaxpr)
    return found


@pytest.mark.parametrize("model", MODELS)
def test_a_chunk_tick_multiplies_the_list_and_no_slot_more(models, model):
    """With the bound stated and a width above 1 no product of the step
    has a (rows x width, d_ff) or (rows, width, d_ff) side: the
    feed-forward's are (max_tokens, d_ff). `wo`'s product, the one
    `dot_general` under `attn/out`, has max_tokens rows too, and none of
    the second half's has rows x width."""
    spec, params = models[model]
    cfg, rows, bound = spec.config, GRAPH_ROWS, 20
    assert second_half_slots(cfg, rows, WIDTH, bound) == bound
    traced = [_jaxpr(spec, params, rows, WIDTH, b) for b in (bound, None)]
    listed, every = map(str, traced)
    wide = (f"f32[{rows},{WIDTH},{cfg.d_ff}]", f"f32[{rows * WIDTH},"
            f"{cfg.d_ff}]")
    assert f"f32[{bound},{cfg.d_ff}]" in listed
    assert not any(shape in listed for shape in wide)
    assert f"f32[{bound},{cfg.d_ff}]" not in every
    assert any(shape in every for shape in wide)
    listed, every = map(_products, traced)
    heads = cfg.n_heads * cfg.d_head
    assert listed["attn/out"] == [(bound, heads)]
    assert every["attn/out"] == [(rows, WIDTH, heads)]
    assert all(shape[0] == bound and len(shape) == 2
               for shape in listed["mlp"])
    # The first half keeps its slots: q, k and v are products over all.
    assert listed["attn/qkv"] == every["attn/qkv"]
    assert all(shape[:2] == (rows, WIDTH) for shape in listed["attn/qkv"])


@pytest.mark.parametrize("model,width,bound", [
    ("gpt2-small-test", 1, 20),          # a slot wide: a token a slot
    ("llama-small-test", 1, 20),
    ("gpt2-small-test", WIDTH, None),    # no bound stated
    ("llama-small-test", WIDTH, None),
    ("gpt2-small-test", WIDTH, 64),      # a bound no shorter than the slots
    ("llama-small-test", WIDTH, 64),
    ("gpt2-moe-test", 1, 20),
])
def test_the_step_keeps_the_all_slot_program(models, model, width, bound):
    """The width-1 program, the program of a caller that states no bound
    (`_tick_spec`) and the one whose bound leaves no slot out are the
    step's with no list at all, to the letter."""
    spec, params = models[model]
    rows = 4
    assert second_half_slots(spec.config, rows, width, bound) == rows * width
    stated = _jaxpr(spec, params, rows, width, bound)
    assert str(stated) == _graph(spec, params, rows, width, None)
    cfg = spec.config
    assert _products(stated)["attn/out"] == [
        (rows, width, cfg.n_heads * cfg.d_head)]


def test_routed_experts_keep_every_slot(models):
    """An expert's capacity counts the rows of the operand
    (`ops.moe.moe_apply`), so a row's value is NOT its own there: the
    routed feed-forward sees all rows x width slots with the bound stated
    as without, and so does `wo` (listed alone it would be written back
    once more a layer), while the pool write takes the list all the
    same."""
    spec, params = models["gpt2-moe-test"]
    cfg, rows, bound = spec.config, 4, 20
    assert cfg.n_experts > 0
    assert second_half_slots(cfg, rows, WIDTH, bound) == rows * WIDTH
    assert pool_write_slots(rows, WIDTH, bound) == bound
    stated = _jaxpr(spec, params, rows, WIDTH, bound)
    listed, every = str(stated), _graph(spec, params, rows, WIDTH, None)
    # K and V are gathered at the list; nothing is scattered but the pool.
    lanes = cfg.kv_heads * cfg.d_head
    assert listed.count(f"f32[{bound},{lanes}] = gather") == 2
    assert listed.count(" scatter[") == every.count(" scatter[") > 0
    assert _products(stated)["attn/out"] == [
        (rows, WIDTH, cfg.n_heads * cfg.d_head)]
    call, valid = _tick(spec, params, (1, 16, 1, 0), (21, 16, 3, 0), WIDTH,
                        "", 0)
    want, got = call(None), call(bound)
    np.testing.assert_array_equal(np.asarray(got[0])[valid],
                                  np.asarray(want[0])[valid])


def _lane(spec, params, **options):
    return ContinuousGenerator(spec, params=params, dtype="float32",
                               n_slots=4, max_seq=MAX_SEQ, kv_block_size=16,
                               prefill_chunk=16, mixed_token_budget=16,
                               **options)


PROMPTS = [[5, 9, 3, 7, 2] * 7, [11, 4, 8], [2] * 19, [7, 1] * 16]


@pytest.mark.parametrize("quant", ["", "int8"])
@pytest.mark.parametrize("model", MODELS)
def test_a_lane_serves_the_same_tokens_with_the_bound_and_without(
        models, model, quant):
    """Greedy tokens of four requests of unlike lengths served at once
    (chunks of 16, tails of 3, 1 and 0, decode rows beside them): a lane
    that states its bound against one that states none, whose every tick
    is the all-slot step."""
    spec, params = models[model]
    served = []
    for bound_stated in (True, False):
        gen = _lane(spec, params, kv_quantize=quant)
        try:
            assert gen._tick_max_tokens == 16 + 4
            if not bound_stated:
                gen._tick_max_tokens = None
            futures = [gen.submit(prompt=p, max_new_tokens=6)
                       for p in PROMPTS]
            served.append([list(f.result(timeout=120)) for f in futures])
            assert gen.stats().get("failures", 0) == 0
        finally:
            gen.stop()
    assert served[0] == served[1]
    assert all(len(tokens) == 6 for tokens in served[0])


@pytest.mark.parametrize("model,bound_stated,listed", [
    ("gpt2-small-test", True, 16 + 4),
    ("llama-small-test", True, 16 + 4),
    ("gpt2-moe-test", True, 4 * 16),      # routed experts: every slot
    ("gpt2-small-test", False, 4 * 16),   # no bound: every slot
])
def test_the_span_says_what_the_feed_forward_computed(models, model,
                                                      bound_stated, listed):
    """`out_slots` and `mlp_slots` on a uniform lane's `mixed_step` spans,
    the rows a layer's `wo` and its feed-forward multiplied: the list's
    length (token budget + rows) on a chunk tick, rows x width under
    routed experts or with no bound stated, a row a slot on a width-1
    tick; the same function the step asks
    (`models.transformer.second_half_slots`)."""
    spec, params = models[model]
    gen = _lane(spec, params)
    gen.tracer = SpanRecorder(256)
    try:
        assert gen._tick_max_tokens == 20
        if not bound_stated:
            gen._tick_max_tokens = None
        gen.submit(prompt=[5, 9, 3, 7, 2] * 5,
                   max_new_tokens=5).result(timeout=120)
        spans = [s["attrs"] for s in gen.tracer.snapshot()
                 if s["op"] == "mixed_step"]
    finally:
        gen.stop()
    chunk = [a for a in spans if a["width"] > 1]
    decode = [a for a in spans if a["width"] == 1]
    assert chunk and decode
    for attrs in chunk:
        assert attrs["out_slots"] == attrs["mlp_slots"] == listed
        assert attrs["write_slots"] == (20 if bound_stated else 64)
        assert attrs["write_tokens"] <= attrs["mlp_slots"]
    for attrs in decode:
        assert attrs["out_slots"] == attrs["mlp_slots"] == gen.n_slots == 4
