"""Helpers of the tests of the mixed tick's pipeline (tests/test_tick_overlap.py
and the families' files): the oracle is the SAME lane kept from running
ahead, because the synchronous order is the drained case of the same code
(`ContinuousGenerator._may_run_ahead`), beside the tests that already hold
the mixed tick to the dense and two-path schedulers."""

import time

import numpy as np


def in_order(gen):
    """`gen`, reading every tick's results before it forms the next."""
    gen._may_run_ahead = lambda: False
    return gen


def serve(gen, requests, timeout=300):
    """Submit every request of `requests` (kwargs of `submit`) at once and
    return their tokens in order."""
    futures = [gen.submit(**kw) for kw in requests]
    return [f.result(timeout=timeout) for f in futures]


def mixed_counters(gen):
    return dict(gen.stats()["mixed"])


def first_fresh(tokens, start=2):
    """The first index >= `start` whose token none before it equals: an
    end put there is met exactly there."""
    for k in range(start, len(tokens)):
        if tokens[k] not in tokens[:k]:
            return k
    return None


def wait_idle(gen, limit_s=30.0):
    limit = time.monotonic() + limit_s
    while gen.stats()["active"] and time.monotonic() < limit:
        time.sleep(0.005)
    time.sleep(0.06)  # past the idle loop's 20 ms admission wait


def held_blocks_guard(gen, bs, faults):
    """Wrap the lane's step so that every dispatch checks what the pipeline
    promises of a row it feeds, late ends included: the row holds the
    block of every column the step writes for it (`pos0 .. pos0 + qlen -
    1`), and no column lies past `max_seq - 1`. Plain block-pool lanes
    (the tick's control block at argument 2)."""
    real = gen._mixed_step_exe

    def guarded(width, controls):
        exe = real(width, controls)

        def call(*args, **kwargs):
            sent = gen._tick_block(width, controls).unpack(
                np.asarray(args[2]))
            tables, = sent["tables"]
            pos0, qlen = sent["pos0"], sent["qlen"]
            for r in np.flatnonzero(qlen > 0):
                last = int(pos0[r] + qlen[r] - 1)
                if last > gen.max_seq - 1:
                    faults.append(("past the cache", int(r), last))
                cols = range(int(pos0[r]) // bs, last // bs + 1)
                if any(tables[r, b] == 0 for b in cols):
                    faults.append(("no block", int(r), last))
            return exe(*args, **kwargs)
        return call

    gen._mixed_step_exe = guarded


def check_late_ends(make_lane, prompts, drained, max_new=12):
    """Serve `prompts` together with an EOS each that its own greedy stream
    meets mid-way, on a lane that runs ahead and on the same lane in order:
    the tokens are the same, every late end is counted once
    (`lagged_rows`), ticks were enqueued ahead, and both lanes end with
    nothing held (`drained(gen)`). Returns the lane's mixed counters."""
    ahead, order = make_lane(), in_order(make_lane())
    try:
        plain = [dict(prompt=p, max_new_tokens=max_new) for p in prompts]
        want = serve(order, plain)
        assert serve(ahead, plain) == want
        wait_idle(ahead)
        before = mixed_counters(ahead)
        assert before["lagged_rows"] == 0     # every end was by the budget
        assert before["overlapped_ticks"] > 0
        cut = [first_fresh(toks) for toks in want]
        assert all(k is not None for k in cut), want
        ended = [dict(prompt=p, max_new_tokens=max_new, eos_id=toks[k])
                 for p, toks, k in zip(prompts, want, cut)]
        short = [toks[:k] for toks, k in zip(want, cut)]
        assert serve(order, ended) == short
        assert serve(ahead, ended) == short
        wait_idle(ahead)
        wait_idle(order)
        after = mixed_counters(ahead)
        assert after["lagged_rows"] - before["lagged_rows"] == len(prompts)
        assert after["dispatches"] == after["ticks"]
        in_order_counters = mixed_counters(order)
        assert in_order_counters["overlapped_ticks"] == 0
        assert in_order_counters["lagged_rows"] == 0
        assert drained(ahead) and drained(order)
        # The slots and what they held serve the next request whole.
        assert serve(ahead, plain[:1]) == want[:1]
        return after
    finally:
        ahead.stop()
        order.stop()
