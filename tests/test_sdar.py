"""The SDAR family (models/sdar.py) on the served path: a generating row is
a RUN of `block_length` tokens denoised over several ticks under a
block-causal mask and committed by one more, the first row kind that yields
no single token a tick. `sdar-small-test` (2 layers, 4 query heads over 2
KV heads of 16 lanes, 8 experts top 2 of width 32, blocks of 4) against the
plain reference benchmarks/references/sdar.py: logits of prefill chunks, of
every denoise pass and of what the commit stored; the three reveal rules,
replayed pass by pass; a prompt's tail, EOS inside a block, a budget that
is no multiple of 4, a prompt that holds the mask id; both tick orders."""

import functools
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.tick_pipeline import in_order, mixed_counters, serve, wait_idle
from tpu_engine.models.registry import (
    FAMILY_CAPABILITIES,
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.models.sdar import sdar_apply, sdar_step_rows_ragged
from tpu_engine.ops.attention import KVCache
from tpu_engine.runtime.scheduler import ContinuousGenerator
from tpu_engine.utils.tracing import SpanRecorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS, RUN, MASK, PAD = 16, 4, 255, 128
LANE = dict(n_slots=4, dtype="float32", kv_block_size=BS,
            prefill_chunk=16, prefix_sharing=False)
# name -> (reveal, denoising_steps, threshold). The threshold of the dynamic
# rule sits inside the test model's confidences (0.02-0.5 over 256 tokens),
# so that some passes reveal by it and some by count.
RULES = {"sequential-1": ("sequential", 4, 0.9),
         "sequential-2": ("sequential", 2, 0.9),
         "static": ("low_confidence_static", 4, 0.9),
         "dynamic": ("low_confidence_dynamic", 4, 0.08)}
# float32 on both sides, summation order apart.
LOGIT_TOL = 2e-4


def _model(rule="sequential-1"):
    _ensure_builtin_models_imported()
    reveal, steps, threshold = RULES[rule]
    return create_model("sdar-small-test", reveal=reveal,
                        denoising_steps=steps,
                        confidence_threshold=threshold)


@pytest.fixture(scope="module")
def spec():
    return _model()


@pytest.fixture(scope="module")
def params(spec):
    return jax.jit(spec.init)(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def reference():
    """benchmarks/references/sdar.py's `body`, jitted over sequences padded
    to PAD, and the test configuration's `reference` block."""
    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    path = os.path.join(bench, "references", "sdar.py")
    module_spec = importlib.util.spec_from_file_location(
        "sdar_reference_under_test", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    with open(os.path.join(ROOT, "tests", "benchmarks", "data", "configs",
                           "sdar-small-test.json")) as f:
        sizes = tuple(sorted(json.load(f)["reference"].items()))
    body = jax.jit(module.body, static_argnums=(2,))

    def logits(params, tokens):
        padded = np.zeros((PAD,), np.int32)
        padded[:len(tokens)] = tokens
        return np.asarray(body(params, jnp.asarray(padded),
                               sizes))[:len(tokens)]

    return logits


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 250, n)]


def _lane(params, rule="sequential-1", **more):
    return ContinuousGenerator(_model(rule), params=params,
                               **dict(LANE, **more))


def replay(logits_of, params, prompt, max_new, rule, eos=-1, stops=(),
           max_seq=128):
    """SDAR's block_diffusion_generate over the reference's `body`, one
    whole forward a pass: (tokens, passes: [(block before, block after)])."""
    reveal, steps, threshold = RULES[rule]
    per_pass = RUN // steps
    head = len(prompt) // RUN * RUN
    seq, tail = list(prompt[:head]), len(prompt) - head
    block = list(prompt[head:]) + [-1] * (RUN - tail)
    out, passes = [], []
    while True:
        while any(b < 0 for b in block):
            shown = [MASK if b < 0 else b for b in block]
            lg = logits_of(params, seq + shown)[len(seq):]
            x0 = lg.argmax(-1)
            z = lg - lg.max(-1, keepdims=True)
            conf = (np.exp(z) / np.exp(z).sum(-1, keepdims=True))[
                np.arange(RUN), x0]
            masked = [i for i in range(RUN) if block[i] < 0]
            n = min(per_pass, len(masked))
            if reveal == "sequential":
                take = masked[:n]
            else:
                take = sorted(masked, key=lambda i: (-conf[i], i))[:n]
                sure = [i for i in masked if conf[i] > threshold]
                if reveal == "low_confidence_dynamic" and len(sure) >= n:
                    take = sure
            before = list(block)
            for i in take:
                block[i] = int(x0[i])
            passes.append((before, list(block)))
        fresh = block[tail:]
        out += fresh
        if (any(t == eos or t in stops for t in fresh)
                or len(out) >= max_new
                or len(seq) + 2 * RUN > max_seq):
            break
        seq, block, tail = seq + block, [-1] * RUN, 0
    out = out[:max_new]
    for k, t in enumerate(out):
        if t == eos or t in stops:
            return out[:k], passes
    return out, passes


# -- registry and fences --------------------------------------------------------------

def test_family_declaration_and_capabilities(spec):
    cfg, block = spec.config, spec.block_decode
    assert spec.state_family == "kv_block_decode"
    assert spec.capabilities == FAMILY_CAPABILITIES["kv_block_decode"]
    assert block == (RUN, MASK, 1, "sequential", 0.9)
    assert cfg.n_moe_layers == cfg.n_layers == 2 and cfg.n_dense_layers == 0
    for absent in ("prefix_sharing", "kv_host_tier", "kv_quantize",
                   "spec_decode", "tensor_parallel", "migration", "handoff"):
        assert not spec.supports(absent)
    _ensure_builtin_models_imported()
    assert create_model("gpt2-small-test").block_decode is None


@pytest.mark.parametrize("flag,value,said", [
    ("spec_k", 2, "spec_decode"),
    ("kv_quantize", "int8", "kv_quantize"),
    ("kv_host_blocks", 8, "kv_host_tier"),
    ("prefix_sharing", True, "prefix_sharing"),
    ("kv_block_size", 0, "block pool only"),
    ("tp", 2, "tensor-parallel"),
    ("kv_block_size", 2, "whole blocks"),
    ("prefill_chunk", 2, "hold a block"),
])
def test_what_the_lane_cannot_do_is_refused_at_start_up(spec, params, flag,
                                                        value, said):
    kwargs = dict(LANE, **{flag: value})
    if flag == "kv_host_blocks":
        kwargs["prefix_sharing"] = True
    with pytest.raises((ValueError, RuntimeError)) as err:
        ContinuousGenerator(spec, params=params, **kwargs).stop()
    assert said in str(err.value)


def test_requests_the_lane_cannot_serve_are_refused(spec, params):
    gen = _lane(params)
    try:
        with pytest.raises(ValueError, match="repetition_penalty"):
            gen.submit(_prompt(1, 8), 4, repetition_penalty=1.3)
        with pytest.raises((ValueError, RuntimeError)):
            gen.submit_score(_prompt(1, 8), _prompt(2, 3))
        assert gen.export_row("nobody")["ok"] is False
    finally:
        gen.stop()


# -- the step against the reference, pass by pass -------------------------------------

def _pool(cfg, n_blocks=33):
    shape = (cfg.n_layers, n_blocks, BS, cfg.kv_heads * cfg.d_head)
    return KVCache(jnp.zeros(shape, jnp.float32),
                   jnp.zeros(shape, jnp.float32))


@functools.lru_cache(maxsize=None)
def _jitted_step(cfg, max_tokens):
    return jax.jit(functools.partial(
        sdar_step_rows_ragged, cfg=cfg, dtype=jnp.float32,
        max_tokens=max_tokens))


def _step(spec, params, caches, tables, rows, width):
    """One call of the family's step: rows = [(pos0, tokens)], a row each,
    the others empty. Returns (logits (B, width, vocab), caches)."""
    b = tables.shape[0]
    tokens = np.zeros((b, width), np.int32)
    pos0, qlen = np.zeros((b,), np.int32), np.zeros((b,), np.int32)
    for r, (at, toks) in enumerate(rows):
        tokens[r, :len(toks)], pos0[r], qlen[r] = toks, at, len(toks)
    logits, caches, _ = _jitted_step(spec.config, b * width)(
        params, jnp.asarray(tokens), caches, jnp.asarray(tables),
        jnp.asarray(pos0), jnp.asarray(qlen))
    return np.asarray(logits), caches


@pytest.mark.parametrize("prompt_len", [4, 16, 24, 36])
def test_chunks_passes_and_the_commit_equal_the_reference(spec, params,
                                                          reference,
                                                          prompt_len):
    """Prefill in chunks of 16, then two blocks: every denoise pass's
    logits equal the reference's forward over [context ; block as the pass
    sees it], and the second block's equal it only if the first block's
    COMMIT stored the final tokens' K and V (its denoise passes wrote
    MASKed ones into the same slots)."""
    prompt = _prompt(prompt_len, prompt_len)
    tables = np.zeros((2, 8), np.int32)
    tables[0], tables[1] = np.arange(1, 9), np.arange(9, 17)
    caches = _pool(spec.config)
    for w0 in range(0, prompt_len, 16):
        chunk = prompt[w0:w0 + 16]
        logits, caches = _step(spec, params, caches, tables,
                               [(w0, chunk)], 16)
        want = reference(params, prompt[:w0 + len(chunk)])[w0:]
        np.testing.assert_allclose(logits[0, :len(chunk)], want,
                                   atol=LOGIT_TOL)
    seq = list(prompt)
    for _ in range(2):
        block = [-1] * RUN
        for s in range(RUN):
            shown = [MASK if t < 0 else t for t in block]
            logits, caches = _step(spec, params, caches, tables,
                                   [(len(seq), shown)], RUN)
            want = reference(params, seq + shown)[len(seq):]
            np.testing.assert_allclose(logits[0], want, atol=LOGIT_TOL)
            block[s] = int(want[s].argmax())
        logits, caches = _step(spec, params, caches, tables,
                               [(len(seq), block)], RUN)      # the commit
        np.testing.assert_allclose(
            logits[0], reference(params, seq + block)[len(seq):],
            atol=LOGIT_TOL)
        seq += block


def test_a_run_beside_a_chunk_in_one_tick(spec, params, reference):
    """A tick 16 slots wide that holds a run of 4 (the short class) and a
    chunk of 12 (a tall tile): each row's logits are its own reference's."""
    a, b = _prompt(5, 8), _prompt(6, 28)
    tables = np.zeros((2, 8), np.int32)
    tables[0], tables[1] = np.arange(1, 9), np.arange(9, 17)
    caches = _pool(spec.config)
    _, caches = _step(spec, params, caches, tables, [(0, a), (0, b[:16])],
                      16)
    shown = [MASK, 7, MASK, MASK]
    logits, _ = _step(spec, params, caches, tables,
                      [(8, shown), (16, b[16:])], 16)
    np.testing.assert_allclose(logits[0, :RUN],
                               reference(params, a + shown)[8:],
                               atol=LOGIT_TOL)
    np.testing.assert_allclose(logits[1, :12], reference(params, b)[16:],
                               atol=LOGIT_TOL)


def test_one_shot_forward_equals_the_reference_body(spec, params, reference):
    tokens = _prompt(9, 40)
    got = sdar_apply(params, jnp.asarray([tokens]), spec.config,
                     dtype=jnp.float32)[0]
    np.testing.assert_allclose(np.asarray(got), reference(params, tokens),
                               atol=LOGIT_TOL)


# -- the lane against the pass-by-pass replay ------------------------------------------

@pytest.fixture(scope="module")
def lanes(params):
    made = {}

    def lane(rule):
        if rule not in made:
            made[rule] = _lane(params, rule)
        return made[rule]

    yield lane
    for gen in made.values():
        gen.stop()


# (rule, prompt, budget): every shape of prompt under the cell's rule, the
# ones that differ by rule under the others.
SHAPES = [(16, 12),     # whole blocks
          (17, 9), (18, 8), (19, 10),   # a tail of 1, 2, 3 opens a block
          (3, 6),       # shorter than a block: no prefill at all
          (40, 7)]      # three chunks; a budget that is no multiple of 4
CASES = ([("sequential-1",) + shape for shape in SHAPES]
         + [(rule,) + shape for rule in list(RULES)[1:]
            for shape in (SHAPES[0], SHAPES[2], SHAPES[5])])


@pytest.mark.parametrize("rule,prompt_len,max_new", CASES)
def test_greedy_tokens_equal_the_replay(lanes, params, reference, rule,
                                        prompt_len, max_new):
    prompt = _prompt(100 + prompt_len, prompt_len)
    want, _ = replay(reference, params, prompt, max_new, rule)
    got = lanes(rule).submit(prompt, max_new_tokens=max_new).result(300)
    assert got == want and len(got) == max_new


@pytest.mark.parametrize("rule", ["sequential-1", "static", "dynamic"])
def test_rows_of_one_tick_do_not_see_each_other(lanes, params, reference,
                                                rule):
    requests = [dict(prompt=_prompt(200 + k, n), max_new_tokens=m)
                for k, (n, m) in enumerate([(9, 14), (33, 8), (16, 11),
                                            (2, 9), (21, 16), (50, 6)])]
    got = serve(lanes(rule), requests)
    for kw, tokens in zip(requests, got):
        assert tokens == replay(reference, params, kw["prompt"],
                                kw["max_new_tokens"], rule)[0]


@pytest.mark.parametrize("rule", ["sequential-1", "static", "dynamic"])
def test_an_end_inside_a_block_cuts_the_output_after_it(lanes, params,
                                                        reference, rule):
    """EOS, then a stop token, put where the row's own greedy stream meets
    it inside a block: the output is cut there, the row's blocks go back."""
    prompt = _prompt(300, 18)
    free, _ = replay(reference, params, prompt, 24, rule)
    k = next(k for k in range(5, len(free))
             if free[k] not in free[:k] and k % RUN != RUN - 1)
    gen = lanes(rule)
    assert gen.submit(prompt, 24, eos_id=free[k]).result(300) == free[:k]
    assert gen.submit(prompt, 24, stop_tokens=[free[k]]).result(
        300) == free[:k]
    wait_idle(gen)
    pool = gen.stats()["kv_pool"]
    assert pool["blocks_free"] == pool["blocks_total"]


def test_a_prompt_may_hold_the_mask_id(lanes, params, reference):
    """Maskedness is state, not a token's value: a prompt that holds the
    mask token's id, in a prefilled block and in the tail that opens the
    first block, is served as any other."""
    prompt = _prompt(400, 18)
    prompt[5] = prompt[16] = MASK
    want, _ = replay(reference, params, prompt, 10, "sequential-1")
    assert lanes("sequential-1").submit(prompt, 10).result(300) == want


def test_greedy_twice_and_streamed_are_identical(lanes):
    import queue

    gen, prompt = lanes("static"), _prompt(500, 21)
    first = gen.submit(prompt, 13).result(300)
    assert gen.submit(prompt, 13).result(300) == first
    stream = queue.Queue()
    gen.submit(prompt, 13, stream=stream).result(300)
    events = []
    while True:
        item = stream.get(timeout=10)
        if item is None:
            break
        events.append(list(item))
    assert sum(events, []) == first
    # A block goes out as ONE event: 3 + 4 + 4 + 2 of 13 (a tail of 1).
    assert [len(e) for e in events] == [3, 4, 4, 2]


def test_sampled_tokens_follow_the_seed_and_the_position(lanes):
    gen, prompt = lanes("sequential-1"), _prompt(600, 12)
    kw = dict(max_new_tokens=12, temperature=0.9, seed=7)
    one = gen.submit(prompt, **kw).result(300)
    assert gen.submit(prompt, **kw).result(300) == one
    assert gen.submit(prompt, **dict(kw, seed=8)).result(300) != one


# -- both tick orders -------------------------------------------------------------------

@pytest.mark.parametrize("rule", ["sequential-2", "static"])
def test_run_ahead_and_drained_orders_token_for_token(params, rule):
    requests = [dict(prompt=_prompt(700 + k, n), max_new_tokens=m, eos_id=e)
                for k, (n, m, e) in enumerate([(9, 14, -1), (33, 8, -1),
                                               (16, 24, 101), (2, 9, -1),
                                               (21, 16, 41), (50, 6, -1)])]
    ahead, order = _lane(params, rule), in_order(_lane(params, rule))
    try:
        got, want = serve(ahead, requests), serve(order, requests)
        assert got == want
        a, o = mixed_counters(ahead), mixed_counters(order)
        assert a["overlapped_ticks"] > 0 and o["overlapped_ticks"] == 0
        assert a["ticks"] == a["dispatches"] and o["ticks"] == o["dispatches"]
        for gen in (ahead, order):
            wait_idle(gen)
            pool = gen.stats()["kv_pool"]
            assert pool["blocks_free"] == pool["blocks_total"]
    finally:
        ahead.stop()
        order.stop()


def test_the_dynamic_rule_ticks_in_the_drained_order(lanes):
    gen = lanes("dynamic")
    gen.submit(_prompt(800, 10), 12).result(300)
    m = mixed_counters(gen)
    assert m["overlapped_ticks"] == 0
    assert m["block_decode"]["runs_ahead"] is False
    assert mixed_counters(lanes("static"))["block_decode"]["runs_ahead"]


# -- counters and spans -------------------------------------------------------------------

def test_counters_and_spans_count_in_blocks(params):
    gen = _lane(params)
    gen.tracer, gen.trace_node = SpanRecorder(4096), "lane"
    try:
        before = mixed_counters(gen)
        out = serve(gen, [dict(prompt=_prompt(900, 36), max_new_tokens=16),
                          dict(prompt=_prompt(901, 8), max_new_tokens=12)])
        wait_idle(gen)
        m = mixed_counters(gen)
        got = {k: m[k] - before[k] for k in (
            "denoise_passes", "commit_passes", "blocks_finished",
            "decode_tokens", "prefill_tokens", "ticks")}
        # 4 + 3 blocks of 4 passes; every block but a row's last commits.
        assert got["blocks_finished"] == 7 and got["denoise_passes"] == 28
        assert got["commit_passes"] == 5
        assert got["decode_tokens"] == sum(map(len, out)) == 28
        assert got["prefill_tokens"] == 44
        spans = [s for s in gen.tracer.snapshot() if s["op"] == "mixed_step"]
        attrs = [s["attrs"] for s in spans]
        assert len(spans) == got["ticks"]
        assert all(a["run_width"] == RUN for a in attrs)
        assert sum(a["denoise_rows"] for a in attrs) == 28
        assert sum(a["commit_rows"] for a in attrs) == 5
        assert sum(a["blocks_finished"] for a in attrs) == 7
        # `width` keeps its meaning: a chunk's compiled width, 1 without.
        assert {a["width"] for a in attrs} == {1, 16}
        assert all((a["width"] == 16) == (a["prefill_tokens"] > 0)
                   for a in attrs)
        moe = gen.stats()["moe"]
        assert moe["assignments"] == 2 * 2 * (44 + 4 * (28 + 5))
    finally:
        gen.stop()


def test_the_budget_counts_a_generating_row_as_its_run(params):
    """Four rows' runs fill a budget of 16 tokens: a fifth request's prompt
    still gets one block a tick (never starved), cut to whole blocks."""
    gen = _lane(params, mixed_token_budget=16)
    seen = []
    real = gen._tick_formed

    def formed(width, prefill_rows, chunk, qlen, *rest, **kw):
        seen.append((int(qlen.sum()), [int(c) for c in chunk if c]))
        return real(width, prefill_rows, chunk, qlen, *rest, **kw)

    gen._tick_formed = formed
    try:
        serve(gen, [dict(prompt=_prompt(950 + k, 4), max_new_tokens=24)
                    for k in range(3)]
              + [dict(prompt=_prompt(960, 30), max_new_tokens=8)])
        assert all(total <= gen._tick_max_tokens for total, _ in seen)
        assert all(c % RUN == 0 for _, chunks in seen for c in chunks)
        assert any(chunks == [4] for _, chunks in seen)
    finally:
        gen.stop()
