"""The order a state step's grid takes a tick's rows in (ISSUE 54): the rows
that step first, and no block named for the others.

`ops.gated_delta.gdn_step_rows` (a gate a head, `gdn_step`; a gate a key
channel, `kda_step`) and `ops.ssd.ssd_step_rows` (`ssd_step`, at Falcon-H1's
head shape and at Nemotron-H's) through the Pallas interpreter against their
gather references, over the mixes of live and dead rows a tick can hold; and
the index maps as plain functions: over a grid's steps the (row, block) pair
changes once a live row's block and never again, which is what the chip's
pipeline turns into "no byte moved for a dead row" and no CPU run can time.
The interpreter starts an output from NaN and copies every step's blocks in
and out, so a column no step wrote reads NaN there and a block a dead step
named would still come back as it was: the second is why the index maps are
tested on their own."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.ops import gated_delta as gd
from tpu_engine.ops import ssd

SLOTS = 6          # rows of a call, over a pool of the null row and six more
T, F = True, False
# name -> (live, fresh, each row's pool row). A dead row keeps its own pool
# row, as `models.olmo_hybrid._linear_rows` hands it over: the wrapper sends
# it to the null row.
CASES = {
    "no-live-row": ((F,) * 6, (F,) * 6, (1, 2, 3, 4, 5, 6)),
    "every-row-live": ((T,) * 6, (F, F, T, F, F, F), (1, 2, 3, 4, 5, 6)),
    "one-live-row-first": ((T, F, F, F, F, F), (F,) * 6, (1, 2, 3, 4, 5, 6)),
    "one-live-row-last": ((F, F, F, F, F, T), (F,) * 6, (1, 2, 3, 4, 5, 6)),
    "dead-rows-between-live-rows": ((T, F, T, F, F, T), (F, F, F, T, F, F),
                                    (1, 0, 3, 0, 5, 6)),
    # A prompt's first chunk (position 0: fresh, and dead for the step)
    # beside rows that step from nothing.
    "chunk-row-dead-beside-fresh-live-rows": (
        (T, T, F, T, F, T), (T, F, T, T, F, F), (1, 2, 3, 4, 0, 6)),
    "pool-rows-out-of-order": ((T, T, F, T, T, T), (F, F, F, T, F, F),
                               (5, 2, 6, 1, 4, 3)),
}


def _gdn_operands(channel: bool, h=4, dk=8, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (SLOTS, h, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (SLOTS, h, dk)))
    v = jax.random.normal(ks[2], (SLOTS, h, dv))
    g = jnp.log(jax.random.uniform(
        ks[3], (SLOTS, h, dk) if channel else (SLOTS, h), minval=0.5,
        maxval=1.0))
    beta = jax.random.uniform(ks[4], (SLOTS, h), maxval=2.0)
    return ((q, k, v, g, beta),
            jax.random.normal(ks[5], (2, SLOTS + 1, h, dv, dk)))


def _ssd_operands(h, p, g, n):
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(ks[0], (SLOTS, h, p))
    dt = jax.random.uniform(ks[1], (SLOTS, h), minval=0.1, maxval=2.5)
    a = -jax.random.uniform(ks[2], (h,), minval=0.02, maxval=0.25)
    b = jax.random.normal(ks[3], (SLOTS, g, n)) / n ** 0.5
    c = jax.random.normal(ks[4], (SLOTS, g, n))
    return ((x, dt, a, b, c),
            jax.random.normal(ks[5], (2, SLOTS + 1, h, p, n)))


# name -> (operands, the kernel's wrapper, its reference, the blocks of heads
# a row is at these shapes). The delta rule's four small heads are cut two a
# block by the test (a block's bytes are the module's constant); the state
# space's heads are whole: two of Falcon-H1's (128, 256) a group of two, two
# of Nemotron-H's (64, 128) a group of eight.
KERNELS = {
    "gdn_step": (lambda: _gdn_operands(False), gd.gdn_step_rows,
                 gd.gdn_step_rows_reference, 2),
    "kda_step": (lambda: _gdn_operands(True), gd.gdn_step_rows,
                 gd.gdn_step_rows_reference, 2),
    "ssd_step-128x256": (lambda: _ssd_operands(4, 128, 2, 256),
                         ssd.ssd_step_rows, ssd.ssd_step_rows_reference, 2),
    "ssd_step-64x128": (lambda: _ssd_operands(16, 64, 8, 128),
                        ssd.ssd_step_rows, ssd.ssd_step_rows_reference, 8),
}


def _grids(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [tuple(e.params["grid_mapping"].grid) for e in jaxpr.jaxpr.eqns
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_the_step_visits_its_live_rows_and_leaves_the_rest(monkeypatch,
                                                           kernel, case):
    """The WHOLE pool against the gather reference's, every row that takes
    no step (the null row, a dead row's own, the other layer) bit for bit
    as it was; a live row's output the reference's, a dead row's finite."""
    operands, step, reference, blocks = KERNELS[kernel]
    if step is gd.gdn_step_rows:
        monkeypatch.setattr(gd, "_STEP_BLOCK_BYTES", 2 * 16 * 128 * 4)
    live, fresh, rows = (np.asarray(v) for v in CASES[case])
    inputs, pool = operands()
    args = inputs + (pool, 1, jnp.asarray(rows), jnp.asarray(live),
                     jnp.asarray(fresh))
    assert _grids(lambda *a: step(*a, interpret=True), *args) == [
        (SLOTS, blocks)]
    o, new = step(*args, interpret=True)
    o_want, want = reference(*inputs, pool, 1,
                             jnp.asarray(np.where(live, rows, 0)),
                             jnp.asarray(live), jnp.asarray(fresh))
    np.testing.assert_allclose(new, want, atol=1e-5)
    np.testing.assert_allclose(o[live], o_want[live], atol=1e-5)
    assert bool(jnp.isfinite(o).all())
    assert not np.asarray(o)[~live].any()
    stepped = set(rows[live].tolist())
    assert 0 not in stepped and len(stepped) == live.sum()
    for row in range(SLOTS + 1):
        same = np.array_equal(np.asarray(new[1, row]),
                              np.asarray(pool[1, row]))
        assert same == (row not in stepped), row
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(pool[0]))


@pytest.mark.parametrize("case", list(CASES))
def test_the_order_is_the_live_rows_as_they_stand_and_their_count(case):
    live = np.asarray(CASES[case][0])
    order, count = gd.live_first(jnp.asarray(live))
    assert order.dtype == count.dtype == jnp.int32 and count.shape == (1,)
    n = int(count[0])
    assert n == live.sum()
    assert np.asarray(order)[:n].tolist() == np.flatnonzero(live).tolist()
    # Behind them, the last of them again: what a dead step names.
    assert set(np.asarray(order)[n:].tolist()) <= {
        int(np.flatnonzero(live)[-1]) if n else 0}


def test_the_order_takes_no_sort():
    """agents' `moe.route_sort_busy` sums every operation named `sort` in a
    trace: the order is a cumulative sum and one scatter."""
    hlo = jax.jit(gd.live_first).lower(jnp.zeros(64, bool)).as_text()
    assert "stablehlo.sort" not in hlo and "stablehlo.scatter" in hlo


@pytest.mark.parametrize("blocks", [1, 2, 8])
@pytest.mark.parametrize("case", list(CASES))
def test_a_grid_s_steps_name_a_block_a_live_row_and_none_for_a_dead_one(
        case, blocks):
    """`step_at` as a plain function over the whole grid: the (row, block)
    pair changes `count x blocks` times, the first step's naming counted
    (once where no row is live): each live row's blocks in turn, then the
    last one again to the grid's end. An unchanged block index is neither
    fetched nor written back by the pipeline."""
    live = np.asarray(CASES[case][0])
    order, count = gd.live_first(jnp.asarray(live))
    named = [tuple(int(v) for v in gd.step_at(i, j, order, count, blocks))
             for i in range(SLOTS) for j in range(blocks)]
    n = int(live.sum())
    changes = 1 + sum(a != b for a, b in zip(named, named[1:]))
    assert changes == max(n * blocks, 1)
    want = [(int(b), j) for b in np.flatnonzero(live) for j in range(blocks)]
    assert named[:n * blocks] == want
    assert set(named[n * blocks:]) <= {want[-1] if want else (0, blocks - 1)}
