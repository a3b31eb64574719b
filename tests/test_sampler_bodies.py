"""The sampler does only what a call's kept rows ask for
(runtime.generator `_sample`): one of three bodies runs, chosen on the
device from the rows' own controls.

Contracts under test:
- a row's token does not depend on the body its call took: the same
  alone, beside a greedy row, beside an unfiltered sampling row and beside
  a filtering row, at a vocabulary where the float32 cumulative sum
  reaches 1.0 before the last token (so `top_p >= 1` must keep the whole
  vocabulary by rule, not by arithmetic);
- greedy and filtering rows read what the parent's `_sample` read
  (`_parent_sample`, the body every call ran until PR 38, kept here);
- the choice stays OUTSIDE the `vmap` over rows: the jaxpr has one `cond`
  at top level and a `sort` only under its third branch;
- the scheduler counts each tick's body from the same predicate and puts
  it on the `mixed_step` span; a released slot's stale controls choose
  nothing;
- a decode-chunk scan and a speculative step with a sampling row emit
  the parent's tokens.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.runtime import generator as generator_module
from tpu_engine.runtime import scheduler as scheduler_module
from tpu_engine.runtime.generator import (
    SAMPLER_BODIES,
    _sample,
    sampler_body,
)
from tpu_engine.runtime.scheduler import ContinuousGenerator
from tpu_engine.utils.tracing import SpanRecorder

_ensure_builtin_models_imported()

VOCAB = 32768
# Seeds on whose logits the cumulative sum reaches 1.0 early (it rounds
# the other way, staying under 1.0 to the end, on about two seeds in five:
# the first test below holds every seed listed to it).
SEEDS = (0, 2, 3, 6, 7, 8, 13, 2147483002)
GREEDY, PLAIN, FILTERED = range(3)

# (temperature, top_p, top_k, min_p) and the body such a row asks for.
CONTROLS = {
    "greedy": ((0.0, 1.0, 0, 0.0), GREEDY),
    "t0.7": ((0.7, 1.0, 0, 0.0), PLAIN),
    "t1.0_top_p0.9": ((1.0, 0.9, 0, 0.0), FILTERED),
    "top_k40": ((0.8, 1.0, 40, 0.0), FILTERED),
    "min_p0.05": ((0.8, 1.0, 0, 0.05), FILTERED),
    "top_p0.9_top_k40": ((0.9, 0.9, 40, 0.0), FILTERED),
}
# The rows a row under test is put beside, and the body each asks for.
COMPANIONS = {
    "a greedy row": CONTROLS["greedy"],
    "an unfiltered sampling row": ((0.9, 1.0, 0, 0.0), PLAIN),
    "a filtering row": ((1.1, 0.8, 7, 0.0), FILTERED),
}


def _parent_sample(logits, seeds, positions, temperature, top_p=None,
                   top_k=None, min_p=None, kept=None):
    """`_sample` as it stood at 45cb15a (PR 36): every row's whole
    vocabulary sorted in every call. `kept` is taken and ignored."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if top_p is None:
        top_p = jnp.ones(logits.shape[:1], jnp.float32)
    if top_k is None:
        top_k = jnp.zeros(logits.shape[:1], jnp.int32)
    if min_p is None:
        min_p = jnp.zeros(logits.shape[:1], jnp.float32)

    def row(key_seed, pos, lg, t, p, k_limit, p_min):
        key = jax.random.fold_in(jax.random.PRNGKey(key_seed), pos)
        lg = lg / jnp.maximum(t, 1e-6)
        sorted_lg = jnp.sort(lg)[::-1]
        cum = jnp.cumsum(jax.nn.softmax(sorted_lg))
        k = jnp.minimum(jnp.sum(cum < p) + 1, lg.shape[-1])
        k = jnp.where(k_limit > 0, jnp.minimum(k, k_limit), k)
        thresh = sorted_lg[k - 1]
        lg = jnp.where(lg >= thresh, lg, -jnp.inf)
        min_thresh = jnp.where(
            p_min > 0, jnp.max(lg) + jnp.log(jnp.maximum(p_min, 1e-30)),
            -jnp.inf)
        lg = jnp.where(lg >= min_thresh, lg, -jnp.inf)
        return jax.random.categorical(key, lg)

    sampled = jax.vmap(row)(seeds, positions, logits, temperature,
                            top_p, top_k, min_p).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


_new = jax.jit(_sample)
_parent = jax.jit(_parent_sample)


def _logits(seed, rows=1):
    """Peaked logits over a real vocabulary: a few hundred tokens hold
    the mass and the float32 cumulative sum of the sorted probabilities
    reaches 1.0 thousands of tokens before the last."""
    return 4.0 * jax.random.normal(jax.random.PRNGKey(seed), (rows, VOCAB))


def _args(logits, seed, controls):
    """`_sample`'s arguments for `logits`' rows under `controls`, one
    (temperature, top_p, top_k, min_p) a row; row r draws with seed + r
    at position 17 + r."""
    t, p, k, m = zip(*controls)
    n = len(controls)
    return (logits, (seed + jnp.arange(n)).astype(jnp.int32) & 0x7FFFFFFF,
            17 + jnp.arange(n, dtype=jnp.int32),
            jnp.asarray(t, jnp.float32), jnp.asarray(p, jnp.float32),
            jnp.asarray(k, jnp.int32), jnp.asarray(m, jnp.float32))


def _body(args, kept=None):
    return int(sampler_body(*(np.asarray(a) for a in args[3:]), kept))


def _beside(logits, seed, mine, other):
    """The row under test as row 0 of a batch of two whose row 1 holds
    `other`'s controls: the same logits, seed and position as alone."""
    both = jnp.concatenate([logits, _logits(seed + 101)])
    return _args(both, seed, [mine, other])


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cumulative_sum_reaches_one_before_the_last_token(seed):
    """What makes `top_p >= 1` a rule and not arithmetic: on these logits
    the parent's count `sum(cum < 1) + 1` stops short of the vocabulary."""
    lg = jnp.sort(_logits(seed)[0] / 0.7)[::-1]
    cum = jnp.cumsum(jax.nn.softmax(lg))
    assert int(jnp.sum(cum < 1.0)) + 1 < VOCAB - 1000


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_a_rows_token_is_the_same_in_every_body(name, seed):
    mine, own_body = CONTROLS[name]
    logits = _logits(seed)
    alone = _args(logits, seed, [mine])
    assert _body(alone) == own_body
    token = int(_new(*alone)[0])
    bodies = {own_body}
    for who, (other, others_body) in COMPANIONS.items():
        batch = _beside(logits, seed, mine, other)
        assert _body(batch) == max(own_body, others_body), who
        bodies.add(_body(batch))
        assert int(_new(*batch)[0]) == token, who
        # ... and a companion whose sample is not kept chooses nothing.
        kept = np.array([True, False])
        assert _body(batch, kept) == own_body
        assert int(_new(*batch, kept)[0]) == token, who
    assert FILTERED in bodies and len(bodies) == 3 - own_body
    if own_body != PLAIN:
        # Greedy and filtering rows read what the parent read. (An
        # unfiltered sampling row may differ where the parent masked the
        # tail its cumulative sum rounded away, ~1e-7 of the mass.)
        assert int(_parent(*alone)[0]) == token
    if own_body == GREEDY:
        assert token == int(jnp.argmax(logits[0]))


@pytest.mark.parametrize("seed", SEEDS[:4])
def test_an_all_greedy_batch_is_the_argmax_and_the_parents(seed):
    logits = _logits(seed, rows=8)
    args = _args(logits, seed, [CONTROLS["greedy"][0]] * 8)
    assert _body(args) == GREEDY
    got = np.asarray(_new(*args))
    assert got.dtype == np.int32
    assert (got == np.asarray(jnp.argmax(logits, axis=-1))).all()
    assert (got == np.asarray(_parent(*args))).all()
    # The optional controls left out: every row greedy or plain.
    assert (np.asarray(_new(*args[:4])) == got).all()


@pytest.mark.parametrize("seed", SEEDS[:4])
def test_a_mixed_batch_is_the_parents_row_for_row(seed):
    """All six kinds of row in one call (the filtered body), against the
    parent: every row but the unfiltered sampling row must agree, and on
    these seeds that one does too."""
    names = sorted(CONTROLS)
    args = _args(_logits(seed, rows=len(names)), seed,
                 [CONTROLS[n][0] for n in names])
    assert _body(args) == FILTERED
    assert (np.asarray(_new(*args)) == np.asarray(_parent(*args))).all()


@pytest.mark.parametrize("seed", SEEDS)
def test_top_p_one_keeps_the_whole_vocabulary_beside_a_filtering_row(
        seed, monkeypatch):
    """The repair inside the filtered body, seen where tokens cannot show
    it (the tail is ~1e-7 of the mass): with the one draw both bodies
    share replaced by a count of the logits that reach it, an unfiltered
    row's count is the vocabulary in the plain body and in the filtered
    one, where the parent's arithmetic kept thousands fewer."""
    monkeypatch.setattr(generator_module, "_draw",
                        lambda key_seed, pos, lg: jnp.sum(jnp.isfinite(lg)))
    logits = _logits(seed)
    mine = CONTROLS["t0.7"][0]
    # The bodies themselves, not through the switch: it keeps the
    # branches it traced, and those hold the real draw.
    alone = _args(logits, seed, [mine])
    assert int(generator_module._plain_body(
        None, *alone[1:3], logits, *alone[3:])[0]) == VOCAB
    batch = _beside(logits, seed, mine, COMPANIONS["a filtering row"][0])
    assert _body(batch) == FILTERED
    reached = np.asarray(generator_module._filtered_body(
        None, *batch[1:3], batch[0], *batch[3:]))
    assert reached[0] == VOCAB and reached[1] == 7


# -- the choice is outside the vmap --------------------------------------------

def _primitives(jaxpr, skip=()):
    """Names of the primitives of `jaxpr` and of every jaxpr under it,
    but for the equations in `skip`."""
    names = []
    for eqn in jaxpr.eqns:
        if any(eqn is s for s in skip):
            continue
        names.append(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += _primitives(sub)
    return names


@pytest.mark.parametrize("with_kept", [False, True], ids=["all", "kept"])
def test_the_sort_lives_under_one_branch_of_a_top_level_cond(with_kept):
    """Under the `vmap` a `cond` is a select and every body runs, which
    is what every tick paid until PR 38."""
    args = _args(_logits(0, rows=4), 0, [CONTROLS["greedy"][0]] * 4)
    if with_kept:
        args += (jnp.ones((4,), bool),)
    jaxpr = jax.make_jaxpr(_sample)(*args).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    outside = _primitives(jaxpr, skip=conds)
    assert "argmax" in outside
    for dear in ("sort", "cumsum", "random_bits", "threefry2x32"):
        assert dear not in outside, dear
    greedy, plain, filtered = (
        _primitives(b.jaxpr) for b in conds[0].params["branches"])
    assert greedy == [] or set(greedy) <= {"convert_element_type"}
    assert "sort" not in plain and "cumsum" not in plain
    assert "sort" in filtered and "cumsum" in filtered
    draws = {"random_bits", "threefry2x32"}
    assert draws & set(plain) and draws & set(filtered)


def test_a_scan_keeps_the_cond_a_cond():
    """The decode-chunk scans and the speculative loop call `_sample`
    inside a `lax.scan`: legal, and still a conditional there."""
    args = _args(_logits(0, rows=2), 0, [CONTROLS["greedy"][0]] * 2)

    def chunk(*a):
        return jax.lax.scan(lambda c, _: (c, _sample(*a)), 0, None,
                            length=3)[1]

    (scan,) = [e for e in jax.make_jaxpr(chunk)(*args).jaxpr.eqns
               if e.primitive.name == "scan"]
    body = scan.params["jaxpr"].jaxpr
    conds = [e for e in body.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    assert "sort" not in _primitives(body, skip=conds)


# -- the scheduler counts what the step chose -----------------------------------

@pytest.fixture(scope="module")
def spec():
    return create_model("gpt2-small-test", max_seq=128)


@pytest.fixture(scope="module")
def params(spec):
    return spec.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def lane(spec, params):
    gen = ContinuousGenerator(spec, params=params, dtype="float32",
                              n_slots=4, step_chunk=4, max_seq=128,
                              kv_block_size=16, prefill_chunk=16,
                              mixed_token_budget=16)
    gen.tracer = SpanRecorder(8192)
    gen.trace_node = "lane"
    yield gen
    gen.stop()


def _wait_idle(gen):
    limit = time.monotonic() + 30
    while gen.stats()["active"] and time.monotonic() < limit:
        time.sleep(0.005)
    time.sleep(0.06)   # past the idle loop's 20 ms admission wait


def _window(gen, run):
    """(difference of the lane's three counters and `ticks`, the
    `sampler` attrs of the `mixed_step` spans) over `run()`."""
    _wait_idle(gen)
    before = dict(gen.stats()["mixed"])
    seq = gen._clock.seq
    run()
    _wait_idle(gen)
    after = gen.stats()["mixed"]
    counted = {body: after[f"sample_{body}_ticks"]
               - before[f"sample_{body}_ticks"] for body in SAMPLER_BODIES}
    spans = [s["attrs"]["sampler"] for s in gen.tracer.snapshot()
             if s["op"] == "mixed_step" and s["attrs"]["seq"] > seq]
    assert sum(counted.values()) == after["ticks"] - before["ticks"]
    assert counted == {body: spans.count(body) for body in SAMPLER_BODIES}
    return counted


def test_a_finished_sampling_requests_slot_sends_no_later_tick_dear(lane):
    """Nothing resets a row's controls at release: the predicate reads
    kept rows only, on the host and (the step's `live`) on the device."""
    counted = _window(lane, lambda: lane.generate(
        [[5, 9, 3]], max_new_tokens=5, temperature=0.8, seed=3))
    assert counted["plain"] >= 5 and not counted["filtered"]
    assert not counted["greedy"]
    assert (lane._temps > 0).any()    # the slot still holds 0.8
    counted = _window(lane, lambda: lane.generate(
        [[5, 9, 3], [7, 7], [1, 2, 3, 4], [9]], max_new_tokens=6))
    assert counted["greedy"] >= 6
    assert not counted["plain"] and not counted["filtered"]


def test_a_mixed_batch_counts_filtered(lane):
    counted = _window(lane, lambda: lane.generate(
        [[5, 9, 3], [7, 7], [1, 2, 3, 4]], max_new_tokens=6,
        temperature=[0.0, 0.7, 0.9], seed=[0, 1, 2],
        top_p=[1.0, 1.0, 0.9]))
    assert counted["filtered"] >= 6


def test_the_counters_are_exported():
    from tpu_engine.utils.metrics import render_prometheus

    text = render_prometheus([{
        "node_id": "w", "healthy": True,
        "generator": {"mixed": {"ticks": 9, "sample_greedy_ticks": 5,
                                "sample_plain_ticks": 3,
                                "sample_filtered_ticks": 1}}}]).decode()
    for body, n in (("greedy", 5), ("plain", 3), ("filtered", 1)):
        assert (f'tpu_engine_mixed_sample_ticks_total{{node="w",'
                f'body="{body}"}} {n}') in text


# -- other callers: a chunk scan, a speculative step ----------------------------

REQUESTS = dict(max_new_tokens=8, temperature=[0.0, 0.8, 0.9],
                seed=[1, 2, 3], top_p=[1.0, 1.0, 0.85], top_k=[0, 0, 6])
PROMPTS = [[5, 9, 3], [7, 7, 7, 7, 7, 7], [1, 2, 3, 4]]


def _tokens(spec, params, **lane_kwargs):
    gen = ContinuousGenerator(spec, params=params, dtype="float32",
                              n_slots=4, step_chunk=4, max_seq=128,
                              prefill_chunk=16, **lane_kwargs)
    try:
        return gen.generate(PROMPTS, **REQUESTS)
    finally:
        gen.stop()


@pytest.mark.parametrize("lane_kwargs", [
    {}, {"kv_block_size": 16, "mixed_token_budget": 16, "spec_k": 2}],
    ids=["decode_chunk_scan", "speculative_step"])
def test_another_caller_emits_the_parents_tokens(spec, params, monkeypatch,
                                                 lane_kwargs):
    """A greedy, an unfiltered and a filtering request through a lane
    whose steps call `_sample` inside a scan (the dense cache's decode
    chunk) and inside the speculative verify loop, against the same lane
    built over the parent's `_sample`."""
    got = _tokens(spec, params, **lane_kwargs)
    monkeypatch.setattr(scheduler_module, "_sample", _parent_sample)
    monkeypatch.setattr(generator_module, "_sample", _parent_sample)
    assert got == _tokens(spec, params, **lane_kwargs)
    assert len({tuple(t) for t in got}) == 3
