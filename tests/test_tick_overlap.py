"""The mixed tick's pipeline, one tick deep (`ContinuousGenerator._tick_mixed`,
`_land_tick`): tick N+1 is formed and enqueued before tick N's results are
read, a decode row's token staying on the device between the two.

Contracts under test, on a CPU lane:
- a row's emitted tokens are the synchronous order's, token for token,
  whatever ends it: EOS mid-stream, a stop token, its budget (1 token
  too), the last column of a block, the cache's end; with a repetition
  penalty and with seeded sampling; `lagged_rows` counts exactly the ends
  the host learned one tick late, and such a row's blocks come back once;
- every row the step feeds holds the block of every column it writes;
- a cancel, a deadline expiry and a client that hung up, each while a tick
  is in flight, free the row and disturb no neighbour; a request admitted
  mid-pipeline, and one parked under pool pressure, join it;
- the order itself: the step for N+1 is called before N is landed; the
  lane reads each tick's results first while an export command waits,
  while a row is parked, and always on a speculative or a slab lane;
- a step failure that surfaces at the wait, with a tick enqueued behind
  it, recovers as before: both dropped, `dispatches == ticks`.

The oracle is the same lane kept in order (tests/tick_pipeline.py) and,
for the first contract, the dense two-path scheduler too."""

import queue
import time
from concurrent.futures import Future

import jax
import pytest

from tick_pipeline import (
    check_late_ends,
    first_fresh,
    held_blocks_guard,
    in_order,
    mixed_counters,
    serve,
    wait_idle,
)
from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.runtime.scheduler import ContinuousGenerator
from tpu_engine.utils.deadline import Deadline, DeadlineExceeded
from tpu_engine.utils.tracing import SpanRecorder

_ensure_builtin_models_imported()

BS = 16
MAX_SEQ = 96
LANE = dict(dtype="float32", n_slots=4, max_seq=MAX_SEQ, kv_block_size=BS,
            prefill_chunk=16, mixed_token_budget=16,
            prefix_sharing=False)


def _prompt(seed, n):
    return [(seed * 31 + j * 7) % 90 + 1 for j in range(n)]


@pytest.fixture(scope="module")
def spec():
    return create_model("gpt2-small-test", max_seq=MAX_SEQ)


@pytest.fixture(scope="module")
def params(spec):
    return spec.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def ahead(spec, params):
    gen = ContinuousGenerator(spec, params=params, **LANE)
    gen.tracer = SpanRecorder(16384)
    gen.trace_node = "ahead"
    faults = []
    held_blocks_guard(gen, BS, faults)
    gen.faults = faults
    gen.generate([_prompt(1, 20)], max_new_tokens=3)      # both widths
    yield gen
    gen.stop()


@pytest.fixture(scope="module")
def order(spec, params):
    gen = in_order(ContinuousGenerator(spec, params=params, **LANE))
    gen.generate([_prompt(1, 20)], max_new_tokens=3)
    yield gen
    gen.stop()


@pytest.fixture(scope="module")
def dense(spec, params):
    gen = ContinuousGenerator(spec, params=params, dtype="float32",
                              n_slots=4, max_seq=MAX_SEQ, step_chunk=1)
    yield gen
    gen.stop()


def _pool_whole(gen):
    pool = gen.stats()["kv_pool"]
    return pool["blocks_free"] == pool["blocks_total"]


def _greedy(order, prompt, n):
    return serve(order, [dict(prompt=prompt, max_new_tokens=n)])[0]


# -- (b) the same tokens, whatever ends the row ---------------------------------

def _ended_by(kind, order):
    """(requests, late ends) of one case: three rows served together."""
    prompts = [_prompt(s, n) for s, n in ((3, 9), (4, 21), (5, 34))]
    if kind in ("eos", "stop"):
        requests = []
        for p in prompts:
            toks = _greedy(order, p, 14)
            k = first_fresh(toks)
            assert k is not None, toks
            end = (dict(eos_id=toks[k]) if kind == "eos"
                   else dict(stop_tokens=[toks[k]]))
            requests.append(dict(prompt=p, max_new_tokens=14, **end))
        return requests, 3
    if kind == "eos_at_the_budget":
        # The EOS is the budget's last token: the host knew the end.
        requests = []
        for p in prompts:
            toks = _greedy(order, p, 14)
            k = first_fresh(toks)
            requests.append(dict(prompt=p, max_new_tokens=k + 1,
                                 eos_id=toks[k]))
        return requests, 0
    if kind == "eos_first_token":
        requests = [dict(prompt=p, max_new_tokens=6,
                         eos_id=_greedy(order, p, 1)[0]) for p in prompts]
        return requests, 3
    if kind == "penalty":
        return [dict(prompt=p, max_new_tokens=12, repetition_penalty=1.4)
                for p in prompts], 0
    if kind == "sampled":
        return [dict(prompt=p, max_new_tokens=12, temperature=0.9, top_p=0.9,
                     seed=11 + i) for i, p in enumerate(prompts)], 0
    if kind == "sampled_penalty_stop":
        requests = []
        for i, p in enumerate(prompts):
            kw = dict(prompt=p, max_new_tokens=12, temperature=0.8, top_k=40,
                      seed=5 + i, repetition_penalty=1.2)
            toks = serve(order, [kw])[0]
            k = first_fresh(toks)
            assert k is not None, toks
            requests.append(dict(kw, stop_tokens=[toks[k]]))
        return requests, 3
    if kind == "every_control_in_one_tick":
        # What the control block carries, all in the same ticks: a row
        # that samples through the filters, a penalised row, and a row a
        # stop token ends one tick late (a done row of the tick behind);
        # on the lane that runs ahead every decode row is a `from_prev`
        # row.
        toks = _greedy(order, prompts[2], 14)
        k = first_fresh(toks)
        assert k is not None, toks
        return [dict(prompt=prompts[0], max_new_tokens=12, temperature=0.7,
                     top_p=0.95, min_p=1e-6, seed=3),
                dict(prompt=prompts[1], max_new_tokens=12,
                     repetition_penalty=1.4),
                dict(prompt=prompts[2], max_new_tokens=14,
                     stop_tokens=[toks[k]])], 1
    if kind == "one_token":
        return [dict(prompt=p, max_new_tokens=1) for p in prompts], 0
    if kind == "cache_end":
        # Rows that run into max_seq - 1 before their budget.
        return [dict(prompt=_prompt(6, MAX_SEQ - 10), max_new_tokens=40),
                dict(prompt=_prompt(7, MAX_SEQ - 3), max_new_tokens=40),
                dict(prompt=_prompt(8, 30), max_new_tokens=8)], 0
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", [
    "eos", "stop", "eos_at_the_budget", "eos_first_token", "penalty",
    "sampled", "sampled_penalty_stop", "every_control_in_one_tick",
    "one_token", "cache_end"])
def test_the_stream_is_the_in_order_lane_s_token_for_token(kind, ahead,
                                                           order, dense):
    requests, late = _ended_by(kind, order)
    want = serve(order, requests)
    wait_idle(ahead)
    before = mixed_counters(ahead)
    got = serve(ahead, requests)
    assert got == want
    if kind != "cache_end":     # the dense lane buckets a long prompt
        assert serve(dense, requests) == want
    wait_idle(ahead)
    after = mixed_counters(ahead)
    assert after["lagged_rows"] - before["lagged_rows"] == late
    assert after["overlapped_ticks"] > before["overlapped_ticks"]
    assert after["dispatches"] == after["ticks"]
    # One control block a tick (at most three arrays: ISSUE 47).
    ticks = after["ticks"] - before["ticks"]
    assert ticks <= (after["form_transfers"]
                     - before["form_transfers"]) <= 3 * ticks
    assert _pool_whole(ahead), ahead.stats()["kv_pool"]
    assert ahead.faults == []
    assert mixed_counters(order)["overlapped_ticks"] == 0
    assert mixed_counters(order)["lagged_rows"] == 0


@pytest.mark.parametrize("col", [BS - 1, 2 * BS - 1, 2 * BS])
def test_an_end_at_a_block_s_last_column_rides_into_the_next_block(
        col, ahead, order):
    """The EOS is sampled by the tick that writes column `col`; the tick
    enqueued behind it writes `col + 1`, the next block's first column
    when `col` is a block's last: the row holds that block
    (`_ensure_capacity_paged`'s horizon), and gives all back once."""
    L = 10
    index = col - L + 1       # token i is sampled writing column L + i - 1
    for seed in range(40):
        prompt = _prompt(100 + seed, L)
        toks = _greedy(order, prompt, index + 4)
        if toks[index] not in toks[:index]:
            break
    else:
        pytest.skip("no prompt of forty met a fresh token there")
    request = dict(prompt=prompt, max_new_tokens=index + 4,
                   eos_id=toks[index])
    want = serve(order, [request])
    assert want == [toks[:index]]
    wait_idle(ahead)
    before = mixed_counters(ahead)
    assert serve(ahead, [request]) == want
    wait_idle(ahead)
    after = mixed_counters(ahead)
    assert after["lagged_rows"] - before["lagged_rows"] == 1
    assert _pool_whole(ahead)
    assert ahead.faults == []


def test_late_ends_are_counted_once_each_and_the_lane_ends_empty(spec,
                                                                 params):
    check_late_ends(
        lambda: ContinuousGenerator(spec, params=params, **LANE),
        [_prompt(s, n) for s, n in ((13, 5), (14, 18), (15, 40), (16, 27))],
        _pool_whole)


def test_prefix_sharing_and_int8_lanes_serve_the_same_tokens_ahead(spec,
                                                                  params):
    """Off the benchmark: the radix tree takes a prompt's blocks when its
    first token LANDS, the int8 pool's scales ride the same dispatch."""
    shared = _prompt(21, 32)
    requests = [dict(prompt=shared + _prompt(30 + i, 5 + i),
                     max_new_tokens=8) for i in range(3)]
    for extra in (dict(prefix_sharing=True), dict(kv_quantize="int8")):
        lane = {**LANE, **extra}
        a = ContinuousGenerator(spec, params=params, **lane)
        o = in_order(ContinuousGenerator(spec, params=params, **lane))
        try:
            want = serve(o, requests[:1]) + serve(o, requests[1:])
            got = serve(a, requests[:1]) + serve(a, requests[1:])
            assert got == want
            assert mixed_counters(a)["overlapped_ticks"] > 0
            assert mixed_counters(a)["dispatches"] == \
                mixed_counters(a)["ticks"]
            pool = a.stats()["kv_pool"]
            assert pool["blocks_free"] + pool.get("radix_nodes", 0) \
                >= pool["blocks_total"]
            if "prefix_sharing" in extra:
                assert pool["prefix_hit_tokens"] == \
                    o.stats()["kv_pool"]["prefix_hit_tokens"] > 0
        finally:
            a.stop()
            o.stop()


# -- (c) rows that leave or join while a tick is in flight ----------------------

@pytest.mark.parametrize("how", ["deadline", "cancel", "hang_up"])
def test_a_row_that_leaves_while_a_tick_is_in_flight(how, ahead, order):
    keep = dict(prompt=_prompt(41, 12), max_new_tokens=40)
    want = serve(order, [keep])[0]
    wait_idle(ahead)
    seen = []            # (a tick was in flight, it sampled the leaving row)
    real = ahead._cancel_expired_rows

    def watching():
        flight = ahead._inflight
        doomed = [(r, req) for r, req in enumerate(ahead._row_req)
                  if req is not None and req.deadline is not None
                  and req.deadline.expired()]
        for r, req in doomed:
            seen.append((flight is not None,
                         flight is not None and flight.sampled(r, req)))
        real()

    ahead._cancel_expired_rows = watching
    try:
        stays = ahead.submit(**keep)
        if how == "deadline":
            goes = ahead.submit(_prompt(42, 14), max_new_tokens=80,
                                deadline=Deadline.after_ms(80))
        else:
            cut = Deadline.after_ms(600000)
            stream = queue.Queue() if how == "hang_up" else None
            goes = ahead.submit(_prompt(42, 14), max_new_tokens=80,
                                deadline=cut, stream=stream)
            if how == "hang_up":
                # The client reads three tokens and goes away; its budget
                # ends with it.
                got = []
                while len(got) < 3:
                    got += stream.get(timeout=60)
            else:
                # The caller gives up once the row has emitted a few.
                limit = time.monotonic() + 60
                while time.monotonic() < limit and not any(
                        req is not None and req.deadline is cut
                        and len(ahead._row_emitted[r]) >= 3
                        for r, req in enumerate(ahead._row_req)):
                    time.sleep(0.0005)
            cut.at = 0.0
        with pytest.raises(DeadlineExceeded):
            goes.result(timeout=120)
        assert stays.result(timeout=120) == want
    finally:
        ahead._cancel_expired_rows = real
    wait_idle(ahead)
    assert seen and seen[0] == (True, True), seen
    counters = mixed_counters(ahead)
    assert counters["dispatches"] == counters["ticks"]
    assert _pool_whole(ahead)
    assert ahead.faults == []
    # The freed slot serves the next request whole.
    assert serve(ahead, [keep]) == [want]


def test_a_request_admitted_mid_pipeline_joins_it(ahead, order):
    first = dict(prompt=_prompt(51, 30), max_new_tokens=40)
    later = [dict(prompt=_prompt(52 + i, 7 + 9 * i), max_new_tokens=10)
             for i in range(3)]
    want = [serve(order, [kw])[0] for kw in [first] + later]
    wait_idle(ahead)
    before = mixed_counters(ahead)
    running = ahead.submit(**first)
    limit = time.monotonic() + 60
    while ahead._inflight is None and time.monotonic() < limit:
        time.sleep(0.0005)
    joined = [ahead.submit(**kw) for kw in later]
    got = [f.result(timeout=120) for f in [running] + joined]
    assert got == want
    wait_idle(ahead)
    after = mixed_counters(ahead)
    ticks = after["ticks"] - before["ticks"]
    # All but the first tick after the idle lane ran ahead.
    assert after["overlapped_ticks"] - before["overlapped_ticks"] == ticks - 1
    assert after["coscheduled_ticks"] > before["coscheduled_ticks"]
    assert _pool_whole(ahead) and ahead.faults == []


def test_a_request_parked_under_pool_pressure_joins_when_blocks_come_back(
        spec, params):
    """Blocks for one long row: the second request parks in `_pending`
    until the first ends, while ticks run ahead; both are served whole."""
    lane = {**LANE, "kv_blocks": 7}       # 6 usable blocks of 16
    requests = [dict(prompt=_prompt(61, 50), max_new_tokens=20),
                dict(prompt=_prompt(62, 50), max_new_tokens=20)]
    o = in_order(ContinuousGenerator(spec, params=params, **lane))
    a = ContinuousGenerator(spec, params=params, **lane)
    try:
        want = [serve(o, [kw])[0] for kw in requests]
        futures = [a.submit(**kw) for kw in requests]
        parked = False
        limit = time.monotonic() + 120
        while not all(f.done() for f in futures) \
                and time.monotonic() < limit:
            parked = parked or len(a._pending) > 0
            time.sleep(0.0005)
        assert [f.result(timeout=1) for f in futures] == want
        assert parked
        assert a.stats().get("pool_starved", 0) == 0
        counters = mixed_counters(a)
        assert counters["overlapped_ticks"] > 0
        assert counters["dispatches"] == counters["ticks"]
        assert _pool_whole(a)
    finally:
        a.stop()
        o.stop()


def test_a_row_the_pool_starves_ends_with_every_token_it_was_stepped_for(
        spec, params):
    """Two rows grow into a pool that holds one of them: the lane lands the
    tick in flight before it ends a row early, so the early end is the
    in-order lane's, token for token."""
    lane = {**LANE, "kv_blocks": 7}       # 6 usable blocks for 10
    requests = [dict(prompt=_prompt(71, 14), max_new_tokens=60),
                dict(prompt=_prompt(72, 22), max_new_tokens=60)]
    o = in_order(ContinuousGenerator(spec, params=params, **lane))
    a = ContinuousGenerator(spec, params=params, **lane)
    try:
        want = serve(o, requests)
        assert o.stats().get("pool_starved", 0) >= 1
        assert serve(a, requests) == want
        assert a.stats()["pool_starved"] == o.stats()["pool_starved"]
        assert mixed_counters(a)["overlapped_ticks"] > 0
        assert _pool_whole(a)
    finally:
        a.stop()
        o.stop()


# -- (d) the order, and where the lane stays in order ---------------------------

def test_the_next_step_is_called_before_the_last_one_is_landed(spec, params):
    gen = ContinuousGenerator(spec, params=params, **LANE)
    gen.tracer = SpanRecorder(4096)
    gen.trace_node = "order"
    events = []
    real_exe, real_land = gen._mixed_step_exe, gen._land_tick

    def exe(width, controls):
        compiled = real_exe(width, controls)

        def call(*args, **kwargs):
            events.append("step")
            return compiled(*args, **kwargs)
        return call

    def land(behind=None):
        events.append("land")
        return real_land(behind)

    gen._mixed_step_exe, gen._land_tick = exe, land
    try:
        out = gen.submit(_prompt(81, 40), max_new_tokens=9).result(120)
        assert len(out) == 9
        wait_idle(gen)
        # 3 chunks + 8 decode ticks: the first step alone, then each step
        # before the landing of the one before it, the last landed alone.
        assert events == ["step"] + ["step", "land"] * 10 + ["land"]
        counters = mixed_counters(gen)
        assert counters["ticks"] == counters["dispatches"] == 11
        assert counters["overlapped_ticks"] == 10
        spans = [s["attrs"] for s in gen.tracer.snapshot()
                 if s["op"] == "mixed_step"]
        assert [a["overlapped"] for a in spans] == [0] + [1] * 10
        assert [a["seq"] for a in spans] == list(range(1, 12))
        assert "gap_us" not in spans[0]
        # A tick enqueued behind a running one left the device no gap,
        # or the probe saw the device idle already: never negative.
        assert all(a["gap_us"] >= 0 for a in spans[1:])
        assert [a["width"] for a in spans] == [16] * 3 + [1] * 8
        assert [a["ctx_tokens"] for a in spans[:5]] == [16, 32, 40, 41, 42]
    finally:
        gen.stop()


def _export_mid_stream(a, request, command):
    """Serve `request` on lane `a` under the tag "mover" and, six tokens
    in, export the row by `command(a)`. Returns (snapshot, the request's
    future, the lane's tick in flight at each `_do_export`)."""
    stream = queue.Queue()
    moving = a.submit(**request, stream=stream, tag="mover")
    got = []
    while len(got) < 6:
        got += stream.get(timeout=60)
    flights = []
    real = a._do_export

    def watching(tag, opts=None):
        flights.append(a._inflight)
        return real(tag, opts)

    a._do_export = watching
    return command(a), moving, flights


def _between_the_look_and_the_serve(a):
    """Put the export command where the loop cannot have seen it when it
    chose to run ahead: inside `_may_run_ahead`, as it answers yes. The
    tick is then enqueued with the command waiting, and the next
    `_serve_exports` finds both."""
    real = a._may_run_ahead
    fut = Future()

    def answering():
        ahead = real()
        if ahead and a._inflight is not None and not fut.running():
            fut.set_running_or_notify_cancel()
            a._migrate_q.put(("mover", fut, {}))
        return ahead

    a._may_run_ahead = answering
    return fut.result(timeout=30)


@pytest.mark.parametrize("command", [
    lambda a: a.export_row("mover", timeout_s=30),
    _between_the_look_and_the_serve,
], ids=["from_another_thread", "after_the_loop_chose_to_run_ahead"])
def test_an_export_command_lands_the_tick_in_flight_first(command, spec,
                                                          params):
    """`_serve_exports` ships a row as the last tick left it, whenever the
    command arrived: the row's snapshot and what the importing lane makes
    of it are the in-order lane's."""
    lane = {**LANE}
    a = ContinuousGenerator(spec, params=params, **lane)
    b = ContinuousGenerator(spec, params=params, **lane)
    o = in_order(ContinuousGenerator(spec, params=params, **lane))
    try:
        request = dict(prompt=_prompt(91, 25), max_new_tokens=30)
        want = serve(o, [request])[0]
        snap, moving, flights = _export_mid_stream(a, request, command)
        assert snap["ok"], snap
        assert flights == [None]
        emitted = snap["emitted"]
        assert emitted == want[:len(emitted)] and len(emitted) >= 6
        assert snap["pos"] == 25 + len(emitted) - 1
        assert snap["tok"] == emitted[-1]
        with pytest.raises(Exception, match="migrated"):
            moving.result(timeout=30)
        resumed = b.submit_import(snap).result(timeout=120)
        assert resumed == want
        assert _pool_whole(a)
        counters = mixed_counters(a)
        assert counters["overlapped_ticks"] > 0
        assert counters["dispatches"] == counters["ticks"]
    finally:
        a.stop()
        b.stop()
        o.stop()


def test_an_int8_import_lands_behind_a_tick_in_flight(spec, params):
    """An int8 pool's chain (payload and scales, verbatim) adopted by a
    lane whose tick is in flight for a neighbour: the import waits for no
    drain of its own, the resumed stream and the neighbour's are the
    in-order int8 lane's, and every scale slot goes back with its block."""
    lane = {**LANE, "kv_quantize": "int8"}
    a = ContinuousGenerator(spec, params=params, **lane)
    b = ContinuousGenerator(spec, params=params, **lane)
    o = in_order(ContinuousGenerator(spec, params=params, **lane))
    try:
        request = dict(prompt=_prompt(93, 25), max_new_tokens=30)
        other = dict(prompt=_prompt(94, 9), max_new_tokens=40)
        want, want_other = serve(o, [request])[0], serve(o, [other])[0]
        snap, _, _ = _export_mid_stream(
            a, request, lambda a: a.export_row("mover", timeout_s=30))
        assert snap["ok"], snap
        assert snap["chain"]["quantized"]
        stream = queue.Queue()
        beside = b.submit(**other, stream=stream)
        got = []
        while len(got) < 4:                  # the neighbour is decoding
            got += stream.get(timeout=60)
        flights = []
        real = b._admit_import

        def watching(item, row):
            flights.append(b._inflight)
            return real(item, row)

        b._admit_import = watching
        assert b.submit_import(snap).result(timeout=120) == want
        assert beside.result(timeout=120) == want_other
        assert len(flights) == 1 and flights[0] is not None
        wait_idle(b)
        assert _pool_whole(a) and _pool_whole(b)
        assert b.stats()["migration"]["imported_rows"] == 1
    finally:
        a.stop()
        b.stop()
        o.stop()


def test_a_budget_past_the_cache_ends_at_the_cache_as_in_order(spec, params):
    """An imported snapshot's budget is the source lane's, not clamped to
    this lane's cache: the row ends at `max_seq - 1`, and with the token
    of the tick that reaches it, not of the tick before (`_land_tick`
    hands `_maybe_complete` the position as of the landed tick)."""
    a = ContinuousGenerator(spec, params=params, **LANE)
    b = ContinuousGenerator(spec, params=params, **LANE)
    o = in_order(ContinuousGenerator(spec, params=params, **LANE))
    try:
        L = MAX_SEQ - 20
        request = dict(prompt=_prompt(92, L), max_new_tokens=30)
        clamped = serve(o, [request])[0]
        assert len(clamped) == MAX_SEQ - 1 - L      # `submit`'s clamp
        snap, _, _ = _export_mid_stream(
            a, request, lambda a: a.export_row("mover", timeout_s=30))
        assert snap["ok"], snap
        snap["max_new"] = 60
        want = o.submit_import(dict(snap)).result(timeout=120)
        # One more than the clamp leaves: the token sampled writing the
        # cache's last column.
        assert want[:len(clamped)] == clamped
        assert len(want) == len(clamped) + 1
        before = mixed_counters(b)
        assert b.submit_import(dict(snap)).result(timeout=120) == want
        wait_idle(b)
        after = mixed_counters(b)
        assert after["overlapped_ticks"] > before["overlapped_ticks"]
        assert after["lagged_rows"] == 0
        assert _pool_whole(b) and _pool_whole(o)
    finally:
        a.stop()
        b.stop()
        o.stop()


def test_a_parked_row_rides_beside_a_prefilling_row(spec, params):
    """A handoff row, parked, in the ticks that carry a neighbour's prompt
    chunks: it is fed no token (its `pos` and pending token stand still
    while the neighbour's prompt goes through three ticks), spends none of
    the budget, and both streams are the in-order lane's once the park
    runs out."""
    gen = ContinuousGenerator(spec, params=params, **LANE)
    o = in_order(ContinuousGenerator(spec, params=params, **LANE))
    try:
        request = dict(prompt=_prompt(97, 10), max_new_tokens=10)
        long = dict(prompt=_prompt(98, 48), max_new_tokens=6)
        want, want_long = serve(o, [request])[0], serve(o, [long])[0]
        parked = gen.submit(**request, tag="park", handoff=True,
                            handoff_park_s=1.5)
        limit = time.monotonic() + 60
        while not any(gen._held) and time.monotonic() < limit:
            time.sleep(0.0005)
        row = gen._held.index(True)
        at = (int(gen._pos[row]), int(gen._tok[row]),
              list(gen._row_emitted[row]))
        assert at[2] == want[:1]
        before = mixed_counters(gen)
        beside = gen.submit(**long)
        assert beside.result(timeout=120) == want_long
        after = mixed_counters(gen)
        # 48 prompt tokens at a chunk of 16: three ticks carried them
        # beside the parked row, and none of them stepped it.
        assert after["prefill_tokens"] - before["prefill_tokens"] == 48
        assert after["coscheduled_ticks"] == before["coscheduled_ticks"]
        if gen._held[row]:
            assert at == (int(gen._pos[row]), int(gen._tok[row]),
                          list(gen._row_emitted[row]))
        assert parked.result(timeout=120) == want    # the park ran out
        wait_idle(gen)
        assert _pool_whole(gen)
    finally:
        gen.stop()
        o.stop()


def test_a_parked_row_keeps_the_lane_in_order(spec, params):
    """A handoff row parks when its first token lands: the lane reads every
    tick's results first from the row's admission until the park ends, and
    the parked row is never stepped on."""
    gen = ContinuousGenerator(spec, params=params, **LANE)
    o = in_order(ContinuousGenerator(spec, params=params, **LANE))
    try:
        request = dict(prompt=_prompt(95, 30), max_new_tokens=12)
        other = dict(prompt=_prompt(96, 8), max_new_tokens=25)
        want, want_other = serve(o, [request])[0], serve(o, [other])[0]
        gen.generate([_prompt(1, 20)], max_new_tokens=3)   # both widths
        wait_idle(gen)
        beside = gen.submit(**other)
        parked = gen.submit(**request, tag="park", handoff=True,
                            handoff_park_s=1.0)
        limit = time.monotonic() + 60
        while not any(gen._held) and time.monotonic() < limit:
            time.sleep(0.0005)
        assert any(gen._held)
        held_at = mixed_counters(gen)["overlapped_ticks"]
        time.sleep(0.15)
        assert gen._inflight is None or not any(gen._held)
        assert mixed_counters(gen)["overlapped_ticks"] == held_at
        assert parked.result(timeout=120) == want    # the park ran out
        assert beside.result(timeout=120) == want_other
        assert gen.stats()["handoff"]["park_expired"] == 1
        wait_idle(gen)
        assert mixed_counters(gen)["overlapped_ticks"] > held_at
        assert _pool_whole(gen)
    finally:
        gen.stop()
        o.stop()


@pytest.mark.parametrize("kind", ["spec", "slab"])
def test_a_speculative_and_a_slab_lane_never_run_ahead(kind, spec, params):
    if kind == "spec":
        gen = ContinuousGenerator(spec, params=params, **LANE, spec_k=2)
        prompt = [3, 3, 3, 3, 3, 3]
    else:
        slab = create_model("ssd-small-test")
        gen = ContinuousGenerator(
            slab, params=slab.init(jax.random.PRNGKey(0)), dtype="float32",
            n_slots=2, state_rows=4, prefill_chunk=8,
            mixed_token_budget=16)
        prompt = list(range(1, 12))
    gen.tracer = SpanRecorder(512)
    try:
        assert len(gen.submit(prompt, max_new_tokens=8).result(180)) == 8
        counters = mixed_counters(gen)
        assert counters["ticks"] == counters["dispatches"] > 0
        assert counters["overlapped_ticks"] == counters["lagged_rows"] == 0
        assert gen._inflight is None
        spans = [s["attrs"] for s in gen.tracer.snapshot()
                 if s["op"] == "mixed_step"]
        assert spans and all(a["overlapped"] == 0 for a in spans)
    finally:
        gen.stop()


# -- (e) a failure at the wait, a tick enqueued behind it -----------------------

class _Poisoned:
    """A step result the device failed to produce: reading it raises."""

    def is_ready(self):
        return True

    def __array__(self, *args, **kwargs):
        raise RuntimeError("injected device failure at the wait")


def test_a_failure_at_the_wait_drops_both_ticks_and_recovers(spec, params):
    gen = ContinuousGenerator(spec, params=params, **LANE)
    try:
        request = dict(prompt=_prompt(97, 20), max_new_tokens=6)
        want = serve(gen, [request])[0]
        wait_idle(gen)
        before = mixed_counters(gen)
        real = gen._land_tick
        behind_it = []

        def failing(behind=None):
            if behind is not None and not behind_it:
                behind_it.append(behind)
                gen._inflight.nxt = _Poisoned()
            return real(behind)

        gen._land_tick = failing
        lost = gen.submit(_prompt(98, 20), max_new_tokens=30)
        with pytest.raises(RuntimeError, match="device-step failure") as err:
            lost.result(timeout=120)
        assert err.value.retryable
        gen._land_tick = real
        assert len(behind_it) == 1          # a tick WAS enqueued behind it
        assert gen.stats()["failures"] == 1
        assert gen._inflight is None
        assert serve(gen, [request]) == [want]
        wait_idle(gen)
        after = mixed_counters(gen)
        assert after["dispatches"] == after["ticks"] > before["ticks"]
        # The two dropped ticks were formed, and their blocks sent.
        formed = after["ticks"] - before["ticks"] + 2
        assert formed <= (after["form_transfers"]
                          - before["form_transfers"]) <= 3 * formed
        assert gen.stats().get("recover_invariant_violations", 0) == 0
        assert _pool_whole(gen)
    finally:
        gen.stop()
