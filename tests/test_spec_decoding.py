"""Continuous speculative decoding (runtime.scheduler spec_k > 0):
draft-verified multi-token ragged ticks in the paged scheduler.

Contracts under test:
- greedy streams (penalties, stop lists, filter knobs included) are
  byte-identical to the plain paged/mixed schedulers for ANY draft —
  the n-gram default, a perfect oracle, and an always-wrong rejector;
- exactly one compiled dispatch per tick (counted at separate sites),
  with a perfect draft advancing rows k+1 tokens per dispatch;
- temperature>0 rows take the rejection-sampling path: deterministic
  per seed, valid tokens, NOT asserted byte-equal (MIGRATION.md);
  rows carrying top_p/top_k/penalty at temp>0 are not drafted and stay
  byte-identical;
- rejected draft tails crossing a block boundary never leak blocks or
  corrupt radix-shared prefixes; over-allocated horizon blocks return
  to the pool as budgets shrink (kv_blocks.release_tail);
- the n-gram drafter is deterministic, empty-history-safe, and the
  scheduler never lets it propose past max_tokens or max_seq;
- serving integration: --spec-k wiring, the /stats//health spec block,
  tpu_engine_spec_* at /metrics, spec_verify trace spans, loud
  misconfiguration;
- the batch SpeculativeGenerator, refactored onto the shared
  acceptance helpers, reproduces its pre-refactor streams exactly
  (golden regression) and exposes its acceptance ratio.

Kept lean per the tier-1 budget: one plain and one spec scheduler are
module fixtures, prompts are short, oracle streams reuse the plain
fixture's output.
"""

import queue as _queue
import random
import time

import jax
import pytest

from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.runtime.scheduler import ContinuousGenerator
from tpu_engine.utils.deadline import Deadline, DeadlineExceeded

_ensure_builtin_models_imported()


@pytest.fixture(scope="module")
def spec():
    return create_model("gpt2-small-test", max_seq=128)


@pytest.fixture(scope="module")
def params(spec):
    return spec.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def plain(spec, params):
    """Two-path paged scheduler, speculation OFF — the identity oracle
    (pinned byte-identical to the dense scheduler in test_paged_kv)."""
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=4, step_chunk=4, max_seq=128,
                            kv_block_size=16, prefill_chunk=16)
    yield s
    s.stop()


@pytest.fixture(scope="module")
def specgen(spec, params):
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=4, step_chunk=4, max_seq=128,
                            kv_block_size=16, prefill_chunk=16, spec_k=3)
    yield s
    s.stop()


class _StubDrafter:
    """Deterministic test drafter driven by a known oracle stream."""

    name = "stub"
    dispatches = 0

    def __init__(self, stream, prompt_len, wrong=False, vocab=256):
        self.stream = list(stream)
        self.plen = prompt_len
        self.wrong = wrong
        self.vocab = vocab

    def propose(self, ctx, k):
        n_emitted = len(ctx) - self.plen
        cont = self.stream[n_emitted:n_emitted + k]
        if self.wrong:
            cont = [(t + 1) % self.vocab for t in cont]
        return cont


def test_spec_requires_paged(spec, params):
    with pytest.raises(ValueError, match="requires the paged KV cache"):
        ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=2, spec_k=2)


def test_greedy_matches_plain(plain, specgen):
    for prompt, mn in (([5, 9, 3], 12), ([3, 3, 3], 16),
                       ([(i * 7) % 90 + 1 for i in range(40)], 6)):
        want = plain.generate([prompt], max_new_tokens=mn)[0]
        got = specgen.generate([prompt], max_new_tokens=mn)[0]
        assert got == want, (prompt, got, want)
    st = specgen.stats()["spec"]
    assert st["ticks"] == st["dispatches"] > 0  # separate count sites


def test_controls_match_plain(plain, specgen):
    kw = dict(max_new_tokens=8, repetition_penalty=3.0)
    assert (specgen.generate([[5, 9, 3]], **kw)[0]
            == plain.generate([[5, 9, 3]], **kw)[0])
    kw = dict(max_new_tokens=10, stop_tokens=[89])
    assert (specgen.generate([[3, 3, 3]], **kw)[0]
            == plain.generate([[3, 3, 3]], **kw)[0])
    # temp>0 with filters: not drafted (q_len 1) -> byte-identical too.
    kw = dict(max_new_tokens=8, temperature=0.8, seed=7, top_p=0.9)
    assert (specgen.generate([[5, 9, 3, 2]], **kw)[0]
            == plain.generate([[5, 9, 3, 2]], **kw)[0])


def test_sampled_deterministic_not_byte_equal_contract(specgen):
    """temp>0 filter-free rows speculate via rejection sampling: unbiased
    and deterministic per seed; byte-equality to plain decode is
    explicitly NOT promised (MIGRATION.md)."""
    kw = dict(max_new_tokens=8, temperature=0.9, seed=11)
    a = specgen.generate([[5, 9, 3]], **kw)[0]
    b = specgen.generate([[5, 9, 3]], **kw)[0]
    assert a == b and len(a) == 8
    assert all(0 <= t < 256 for t in a)
    c = specgen.generate([[5, 9, 3]], max_new_tokens=8, temperature=0.9,
                         seed=12)[0]
    assert c != a  # seed moves the stream


def test_oracle_draft_full_acceptance(spec, params, plain, specgen):
    """A perfect draft accepts everything: identical stream, ~k+1 tokens
    per dispatch — the counter-level statement of the perf win."""
    want = plain.generate([[3, 3, 3]], max_new_tokens=24)[0]
    before = specgen.stats()["spec"]
    old = specgen._drafter
    specgen._drafter = _StubDrafter(want, prompt_len=3)
    try:
        got = specgen.generate([[3, 3, 3]], max_new_tokens=24)[0]
    finally:
        specgen._drafter = old
    assert got == want
    st = specgen.stats()["spec"]
    d_ticks = st["ticks"] - before["ticks"]
    d_emit = st["emitted_tokens"] - before["emitted_tokens"]
    d_prop = st["proposed_tokens"] - before["proposed_tokens"]
    d_acc = st["accepted_tokens"] - before["accepted_tokens"]
    assert d_acc == d_prop > 0
    assert d_emit / d_ticks >= 2.0, (d_emit, d_ticks)


def test_ngram_drafter_on_looping_text_beats_one_token_a_row_tick(
        plain, specgen):
    """The same count with the drafter that ships (prompt-lookup
    n-gram, no stub): on prompts that loop, its proposals are accepted
    often enough that a row advances well over one token a verify
    dispatch it takes part in (plain decode: exactly 1; this fixture at
    k=3 counts 188 tokens in 142 row ticks), and the streams are the
    plain scheduler's."""
    rnd = random.Random(42)
    prompts = [([rnd.randrange(1, 200) for _ in range(6)] * 5)[:24]
               for _ in range(4)]
    want = plain.generate(prompts, max_new_tokens=48)
    before = specgen.stats()["spec"]
    assert before["draft"] == "ngram"
    assert specgen.generate(prompts, max_new_tokens=48) == want
    st = specgen.stats()["spec"]
    d_emit = st["emitted_tokens"] - before["emitted_tokens"]
    d_rows = st["row_ticks"] - before["row_ticks"]
    assert st["accepted_tokens"] > before["accepted_tokens"]
    assert d_emit / d_rows >= 1.25, (d_emit, d_rows)
    assert st["ticks"] == st["dispatches"]


def test_accepted_counter_counts_stop_on_accepted_draft(plain, specgen):
    """A stream that stops ON an accepted draft token has no
    corrected/bonus slot in its window, so accepted tokens cannot be
    inferred host-side as emitted-1 per row tick — the device-counted
    n_acc must include that final accepted slot."""
    want = plain.generate([[5, 9, 3]], max_new_tokens=24)[0]
    # First emitted index that is a drafted slot of the first decode
    # tick (indices 1..3 with k=3) and whose token value appears for
    # the first time there — a valid stop trigger.
    j = next(i for i in (1, 2, 3) if want[i] not in want[:i])
    kw = dict(max_new_tokens=24, stop_tokens=[want[j]])
    want_s = plain.generate([[5, 9, 3]], **kw)[0]
    # The stop token itself is client-invisible (truncate_at_stops).
    assert want_s == want[:j]
    before = specgen.stats()["spec"]
    old = specgen._drafter
    specgen._drafter = _StubDrafter(want, prompt_len=3)
    try:
        got = specgen.generate([[5, 9, 3]], **kw)[0]
    finally:
        specgen._drafter = old
    assert got == want_s
    st = specgen.stats()["spec"]
    d_acc = st["accepted_tokens"] - before["accepted_tokens"]
    # Slots 0..j-1 of the single decode tick all kept their draft token
    # (the last one IS the stop token): j accepted, zero corrected.
    assert d_acc == j, (d_acc, j, want_s)


def test_rejecting_draft_block_boundary_rewind(spec, params, plain,
                                               specgen):
    """An always-wrong draft: every window verifies 1 real token + a
    rejected tail that (with a 15-token prompt on 16-column blocks)
    crosses a block boundary on the first tick. Stream must stay
    byte-identical and every block must come back — stale draft KV in
    retained blocks is position-masked, never attended."""
    prompt = [(i * 3) % 90 + 1 for i in range(15)]
    want = plain.generate([prompt], max_new_tokens=10)[0]
    old = specgen._drafter
    specgen._drafter = _StubDrafter(want, prompt_len=15, wrong=True)
    try:
        got = specgen.generate([prompt], max_new_tokens=10)[0]
    finally:
        specgen._drafter = old
    assert got == want
    deadline = time.time() + 10
    while time.time() < deadline:
        st = specgen.stats()
        pool = st["kv_pool"]
        if (st["active"] == 0 and pool["blocks_free"]
                + pool["radix_nodes"] >= pool["blocks_total"]):
            break
        time.sleep(0.05)
    pool = specgen.stats()["kv_pool"]
    assert pool["blocks_free"] + pool["radix_nodes"] \
        >= pool["blocks_total"], pool
    # Radix-shared prefix blocks survived the rejected-tail writes: a
    # repeat of the same prompt (radix hit) still streams identically.
    assert specgen.generate([prompt], max_new_tokens=10)[0] == want


def test_budget_horizon_trim_and_exact_length(specgen):
    """Near its token budget a row's draft cap shrinks (the drafter must
    never propose past max_tokens) and over-allocated horizon blocks
    return to the pool (kv_blocks.release_tail)."""
    out = specgen.generate([[3, 3, 3]], max_new_tokens=3)[0]
    assert len(out) == 3
    # Long repetitive stream: budget-capped windows near the end.
    out = specgen.generate([[3, 3, 3]], max_new_tokens=30)[0]
    assert len(out) == 30
    assert specgen.stats()["spec"]["tail_blocks_released"] >= 0


def test_deadline_cancel_mid_speculation(specgen):
    """Rows cancelled between verify ticks return every block and later
    requests stream identically."""
    want = specgen.generate([[5, 9, 3]], max_new_tokens=4)[0]
    futs = [specgen.submit([(i * 17 + j) % 90 + 1 for j in range(40)],
                           max_new_tokens=60,
                           deadline=Deadline.after_ms(20))
            for i in range(4)]
    expired = 0
    for f in futs:
        try:
            f.result(60)
        except DeadlineExceeded:
            expired += 1
    deadline = time.time() + 10
    while time.time() < deadline:
        st = specgen.stats()
        pool = st["kv_pool"]
        if (st["active"] == 0 and pool["blocks_free"]
                + pool["radix_nodes"] >= pool["blocks_total"]):
            break
        time.sleep(0.05)
    st = specgen.stats()
    pool = st["kv_pool"]
    assert st["active"] == 0
    assert pool["blocks_free"] + pool["radix_nodes"] \
        >= pool["blocks_total"], pool
    assert st["spec"]["ticks"] == st["spec"]["dispatches"]
    assert specgen.generate([[5, 9, 3]], max_new_tokens=4)[0] == want


def test_mixed_spec_identity_and_coscheduling(spec, params, plain):
    """Speculation composes with mixed stepping: one ragged dispatch per
    tick serves verify windows AND prefill chunks; streams match the
    plain scheduler; a decode row keeps emitting while a long prompt
    admits."""
    ms = ContinuousGenerator(spec, params=params, dtype="float32",
                             n_slots=4, step_chunk=4, max_seq=128,
                             kv_block_size=16, prefill_chunk=16,
                             mixed_token_budget=16,
                             spec_k=3)
    try:
        for prompt, mn in (([5, 9, 3], 10),
                           ([(i * 11) % 90 + 1 for i in range(32)], 5)):
            assert (ms.generate([prompt], max_new_tokens=mn)[0]
                    == plain.generate([prompt], max_new_tokens=mn)[0])
        q = _queue.Queue()
        fa = ms.submit([3, 3, 3], max_new_tokens=30, stream=q)
        got_first = q.get(timeout=30)
        assert got_first  # decode row live before the long prompt lands
        fb = ms.submit([(i * 13) % 90 + 1 for i in range(60)],
                       max_new_tokens=3)
        fa.result(60)
        fb.result(60)
        st = ms.stats()
        assert st["spec"]["ticks"] == st["spec"]["dispatches"]
        m = st["mixed"]
        assert m["ticks"] == m["dispatches"] == st["spec"]["ticks"]
    finally:
        ms.stop()


def test_ngram_drafter_unit():
    from tpu_engine.runtime.speculative import NGramDrafter

    d = NGramDrafter()
    assert d.propose([], 4) == []
    assert d.propose([5], 4) == []
    assert d.propose([1, 2, 3], 0) == []
    # Deterministic, and prefers the match with a FULL continuation.
    ctx = [7] * 10
    assert d.propose(ctx, 3) == [7, 7, 7]
    assert d.propose(ctx, 3) == d.propose(ctx, 3)
    # Longest-tail n-gram wins; continuation may overlap the tail.
    ctx = [1, 2, 3, 9, 9, 1, 2, 3]
    assert d.propose(ctx, 3) == [9, 9, 1]
    # No earlier occurrence -> nothing proposed.
    assert d.propose([1, 2, 3, 4, 5], 3) == []
    with pytest.raises(ValueError):
        NGramDrafter(max_ngram=1, min_ngram=2)


def test_model_drafter_rejects_tiny_max_seq(spec):
    """A draft model whose max_seq cannot hold a context window beside
    the k proposals must fail loudly at construction, not emit silent
    garbage from a context[-0:] mis-slice."""
    from tpu_engine.runtime.speculative import ModelDrafter

    tiny = create_model("gpt2-small-test", max_seq=4)
    with pytest.raises(ValueError, match="cannot hold a context window"):
        ModelDrafter(tiny, k=3, dtype="float32")
    # A draft that fits but is smaller than the 16-token bucket floor must
    # cap its bucket (decode positions pb..pb+k-2 stay < max_seq) instead
    # of feeding positions past its embedding table: proposals stay valid
    # and deterministic.
    small = create_model("gpt2-small-test", max_seq=8)
    d = ModelDrafter(small, k=2, dtype="float32")
    props = d.propose([1, 2, 3, 4, 5], 2)
    assert len(props) == 2
    assert all(0 <= t < small.config.vocab for t in props)
    assert props == d.propose([1, 2, 3, 4, 5], 2)


def test_release_tail_unit(spec):
    from tpu_engine.runtime.kv_blocks import BlockPool

    pool = BlockPool(spec.config, 8, 16, dtype=jax.numpy.float32)
    with pool.lock:
        blocks = pool.alloc(5)
        assert pool.free_blocks == 2
        freed = pool.release_tail(blocks, 2)
    assert freed == 3 and len(blocks) == 2
    assert pool.free_blocks == 5
    # keep >= len is a no-op
    with pool.lock:
        assert pool.release_tail(blocks, 5) == 0
    assert len(blocks) == 2


def test_spec_verify_window_kernel_parity():
    from tpu_engine.ops.paged_attention import spec_verify_parity_check

    # Decode row, two k+1 verify windows, and block-size/boundary chunk
    # rows in ONE ragged batch (the --spec-k dispatch shape). bf16/GQA
    # variants run in diagnostics --spec-parity and the on-chip `spec`
    # stage (tier-1 budget keeps this to one compile).
    assert spec_verify_parity_check(k=3) < 2e-5


def test_worker_spec_serving_and_observability(spec, params):
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig
    from tpu_engine.utils.metrics import render_prometheus

    engine = InferenceEngine(spec, params=params, dtype="float32",
                             batch_buckets=(1, 2))
    w = WorkerNode(WorkerConfig(node_id="sp1", model="gpt2-small-test",
                                dtype="float32",
                                gen_scheduler="continuous",
                                gen_max_batch_size=4,
                                gen_kv_block_size=16,
                                gen_prefill_chunk=16,
                                gen_continuous_spec_k=3),
                   engine=engine)
    try:
        out = w.handle_generate({"request_id": "r1",
                                 "prompt_tokens": [3, 3, 3],
                                 "max_new_tokens": 8})
        assert len(out["tokens"]) == 8
        health = w.get_health()
        sp = health["generator"]["spec"]
        assert sp["ticks"] == sp["dispatches"] > 0
        assert sp["draft"] == "ngram" and sp["k"] == 3
        body = render_prometheus(
            [health], recorders={w.node_id: w.tracer},
            named_hists=w.latency_histograms()).decode()
        for key in ("tpu_engine_spec_dispatches_total",
                    "tpu_engine_spec_accept_ratio",
                    "tpu_engine_spec_tokens_per_dispatch",
                    "tpu_engine_spec_proposed_tokens_total"):
            assert key in body, key
        ops = {s["op"] for s in w.tracer.snapshot()}
        assert "spec_verify" in ops
    finally:
        w.stop()
    # Misconfiguration is loud, never a silently-dropped decode lane.
    with pytest.raises(RuntimeError, match="paged KV cache"):
        WorkerNode(WorkerConfig(node_id="bad", model="gpt2-small-test",
                                dtype="float32",
                                gen_scheduler="continuous",
                                gen_continuous_spec_k=2),
                   engine=InferenceEngine(spec, params=params,
                                          dtype="float32",
                                          batch_buckets=(1,)))
    # A spec-configured worker whose generator can't be built (here: a
    # non-generating target; same path covers a bad draft model) must
    # fail startup, not take the quiet generator=None lane fallback.
    mlp = create_model("mlp", input_dim=8, hidden_dim=32, output_dim=4)
    with pytest.raises(RuntimeError, match="speculative lane misconfig"):
        WorkerNode(WorkerConfig(node_id="bad3", model="mlp",
                                dtype="float32",
                                gen_scheduler="continuous",
                                gen_kv_block_size=16,
                                gen_continuous_spec_k=2),
                   engine=InferenceEngine(
                       mlp, params=mlp.init(jax.random.PRNGKey(0)),
                       dtype="float32", batch_buckets=(1,)))
    # An unknown drafter kind (possible via programmatic WorkerConfig —
    # the CLI's choices= guard doesn't apply) must also fail startup.
    with pytest.raises(RuntimeError, match="spec-draft"):
        WorkerNode(WorkerConfig(node_id="bad2", model="gpt2-small-test",
                                dtype="float32",
                                gen_scheduler="continuous",
                                gen_kv_block_size=16,
                                gen_continuous_spec_k=2,
                                gen_spec_draft="ngrma"),
                   engine=InferenceEngine(spec, params=params,
                                          dtype="float32",
                                          batch_buckets=(1,)))
    # --spec-k under a different gen_scheduler would silently serve
    # without speculation — must be loud, like the misconfigs above.
    with pytest.raises(RuntimeError, match="gen_scheduler=continuous"):
        WorkerNode(WorkerConfig(node_id="bad4", model="gpt2-small-test",
                                dtype="float32",
                                gen_scheduler="batch",
                                gen_kv_block_size=16,
                                gen_continuous_spec_k=2),
                   engine=InferenceEngine(spec, params=params,
                                          dtype="float32",
                                          batch_buckets=(1,)))


# -- batch lane: streams pinned to the paths they claim equality with --------

def test_batch_lane_streams_pinned_to_independent_paths():
    """SpeculativeGenerator's streams, pinned without golden token lists
    (lists captured under one JAX release break on the next: the random
    init itself changes):

    - greedy speculation equals the PLAIN decode path token for token —
      the lane's claim, for any draft — so its golden is runtime.generator's
      `Generator` on the same params;
    - stochastic (rejection-sampling) streams equal no other path by
      design; their contract is that a row's stream is a function of its
      (prompt, seed) alone, so each row of a batch is pinned to the same
      row run solo, and to a repeat — both stochastic temperatures, so
      every acceptance path is exercised."""
    from tpu_engine.runtime.generator import Generator
    from tpu_engine.runtime.speculative import SpeculativeGenerator

    target = create_model("gpt2-small-test")
    sg = SpeculativeGenerator(target, create_model("gpt2-small-test"),
                              rng_seed=0, dtype="float32",
                              batch_buckets=(4,), k=3)
    prompts = [[5, 9, 12, 7], [3, 3, 3], [40, 2, 19, 60, 21, 9], [1]]
    plain = Generator(target, params=sg.params, dtype="float32")
    greedy = sg.generate(prompts, max_new_tokens=10)
    assert greedy == plain.generate(prompts, max_new_tokens=10)
    assert len({tuple(g) for g in greedy}) > 1  # not one degenerate stream

    # Per-row seeds; a scalar seed expands to seed + row.
    for temperature, seeds in ((0.8, [11, 22, 33, 44]), (1.2, [5, 6, 7, 8])):
        seed_arg = seeds if temperature == 0.8 else seeds[0]
        batch = sg.generate(prompts, max_new_tokens=8,
                            temperature=temperature, seed=seed_arg)
        assert batch == sg.generate(prompts, max_new_tokens=8,
                                    temperature=temperature, seed=seed_arg)
        solo = [sg.generate([p], max_new_tokens=8, temperature=temperature,
                            seed=[s])[0] for p, s in zip(prompts, seeds)]
        assert batch == solo
        assert batch != [g[:8] for g in greedy]  # sampling really engaged
    # The satellite: lifetime acceptance is now scrapeable.
    sp = sg.stats()["spec"]
    assert sp["lane"] == "batch" and sp["dispatches"] > 0
    assert sp["accept_ratio"] is not None and 0 <= sp["accept_ratio"] <= 1
    assert sp["emitted_tokens"] > 0
    assert sp["proposed_tokens"] >= sp["accepted_tokens"]
