"""The one list of a tick's tokens (`models/tick_tokens.py`, PR 62) against
a numpy enumeration of the valid (row, slot) pairs in row order: in both
ranks, at a bound reached exactly and one not reached, with no live row
and with a chunk that ends inside a tile; where the entries' K and V go;
and the three forms of the head's rows. No model is built and nothing is
compiled but the list."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models.tick_tokens import lm_head, tick_tokens
from tpu_engine.ops import latent_attention as la

WIDTH, BS = 16, 4
POS0 = np.array([0, 7, 30, 2, 11], np.int32)

# name -> (qlen, max_tokens): the bound is `tiles_bound`'s.
TICKS = {
    # 1 + 16 + 1 + 8 + 6 = 32 valid slots.
    "reached": (np.array([1, 16, 1, 8, 6], np.int32), 32),
    "not_reached": (np.array([1, 5, 0, 1, 0], np.int32), 24),
    "no_live_row": (np.zeros(5, np.int32), 24),
    "ends_mid_tile": (np.array([0, 13, 1, 0, 3], np.int32), 24),
}


def _pairs(qlen):
    return [(b, s) for b, n in enumerate(qlen) for s in range(n)]


def _listed(per_tile, qlen, max_tokens, n_tiles=None):
    return tick_tokens(jnp.asarray(POS0), jnp.asarray(qlen), WIDTH,
                       max_tokens, per_tile=per_tile, n_tiles=n_tiles)


@pytest.mark.parametrize("tick", sorted(TICKS))
@pytest.mark.parametrize("per_tile", [None, 1, 4, 8])
def test_the_list_is_the_valid_pairs_in_row_order(per_tile, tick):
    qlen, max_tokens = TICKS[tick]
    tt = _listed(per_tile, qlen, max_tokens)
    s = per_tile or 1
    assert tt.n == la.tiles_bound(len(qlen), WIDTH, s, max_tokens)
    shape = (tt.n,) if per_tile is None else (tt.n, s)
    assert tt.slot.shape == tt.valid.shape == tt.logical.shape == shape
    assert tt.row.shape == ((tt.n,) if per_tile is None else (tt.n, 1))
    row = np.broadcast_to(np.asarray(tt.row), shape)
    slot, valid = np.asarray(tt.slot), np.asarray(tt.valid)
    assert list(zip(row[valid], slot[valid])) == _pairs(qlen)
    np.testing.assert_array_equal(np.asarray(tt.logical), POS0[row] + slot)
    assert slot.max() < WIDTH
    # A row's run starts at `plan.start`; a tile holds one row's slots.
    tiles = -(-qlen // s)
    np.testing.assert_array_equal(np.asarray(tt.plan.start),
                                  np.cumsum(tiles) - tiles)
    live = int(tiles.sum())
    assert int(tt.plan.n_live[0]) == live <= tt.n
    # Past the live count the last live tile repeats, with nothing valid.
    rows = np.asarray(tt.plan.row)
    last = max(live - 1, 0)
    assert (rows[live:] == rows[last]).all()
    assert (np.asarray(tt.plan.tile)[live:]
            == np.asarray(tt.plan.tile)[last]).all()
    assert not valid.reshape(tt.n, -1)[live:].any()
    if tick == "reached" and s == 1:
        assert live == tt.n - len(qlen)       # the bound's tile a row
    if tick == "no_live_row":
        assert not valid.any() and (slot.reshape(tt.n, -1)[:, 0] == 0).all()


def test_a_caller_s_own_bound_is_the_list_s_length():
    """Rows of whole tiles (`models.sdar`) and one slot a tile
    (`models.transformer.pool_write_slots`) state a tighter length; the
    entries are the same."""
    qlen = np.array([4, 8, 0, 4, 0], np.int32)
    tt = _listed(4, qlen, 16, n_tiles=4)
    assert tt.n == 4 and bool(np.asarray(tt.valid).all())
    row = np.broadcast_to(np.asarray(tt.row), (4, 4))
    assert list(zip(row.ravel(), np.asarray(tt.slot).ravel())) == _pairs(qlen)
    tt = _listed(None, qlen, 16, n_tiles=16)
    assert list(zip(np.asarray(tt.row), np.asarray(tt.slot))) == _pairs(qlen)


@pytest.mark.parametrize("per_tile", [None, 4, 8])
def test_blocks_are_the_table_s_by_hand(per_tile):
    qlen, max_tokens = TICKS["ends_mid_tile"]
    tt = _listed(per_tile, qlen, max_tokens)
    # Row b's logical column c lies in block 100 * b + c // BS + 1.
    nb = 10
    table = (100 * np.arange(5)[:, None] + np.arange(nb)[None, :] + 1
             ).astype(np.int32)
    blk, off = (np.asarray(x) for x in tt.blocks(jnp.asarray(table), BS))
    assert blk.shape == off.shape == tt.slot.shape
    valid = np.asarray(tt.valid)
    row = np.broadcast_to(np.asarray(tt.row), valid.shape)
    for b, s, k, o in zip(row[valid], np.asarray(tt.slot)[valid],
                          blk[valid], off[valid]):
        col = POS0[b] + s
        assert (k, o) == (100 * b + col // BS + 1, col % BS)
    assert (blk[~valid] == 0).all()          # the null block
    # A column past the table's end is clipped onto its last (padding).
    short = jnp.asarray(table[:, :3])
    blk, off = (np.asarray(x) for x in tt.blocks(short, BS))
    assert blk.max() <= table[:, 2].max() and off.max() < BS


@pytest.mark.parametrize("per_tile", [None, 4])
def test_flat_names_a_row_s_tokens_in_the_list_read_as_tokens(per_tile):
    qlen, max_tokens = TICKS["ends_mid_tile"]
    tt = _listed(per_tile, qlen, max_tokens)
    base, row, slot = (np.asarray(x) for x in tt.flat())
    assert row.shape == slot.shape == (tt.n * (per_tile or 1),)
    for b, s in _pairs(qlen):
        assert (row[base[b] + s], slot[base[b] + s]) == (b, s)


def _marked(tt, d=3):
    """h whose entry of row b's slot s is b * 100 + s + 1 in every lane,
    and -1 where the list holds no token."""
    valid = np.asarray(tt.valid)
    row = np.broadcast_to(np.asarray(tt.row), valid.shape)
    mark = np.where(valid, row * 100 + np.asarray(tt.slot) + 1, -1)
    return jnp.asarray(np.repeat(mark[..., None], d, -1).astype(np.float32))


@pytest.mark.parametrize("per_tile", [None, 4])
@pytest.mark.parametrize("form", ["sampled", "run", "every"])
def test_head_rows_in_its_three_forms(form, per_tile):
    qlen, max_tokens = TICKS["ends_mid_tile"]
    tt = _listed(per_tile, qlen, max_tokens)
    h = _marked(tt)
    live = qlen > 0
    if form == "sampled":
        at = np.maximum(qlen - 1, 0)
        got = np.asarray(tt.head_rows(h, jnp.asarray(at)))
        assert got.shape == (5, 3)
        np.testing.assert_array_equal(
            got[live, 0], (np.arange(5) * 100 + at + 1)[live])
    elif form == "run":
        at = np.broadcast_to(np.arange(3)[None, :], (5, 3))
        got = np.asarray(tt.head_rows(h, jnp.asarray(at)))
        assert got.shape == (15, 3)          # a row's 3 slots side by side
        got = got.reshape(5, 3, 3)[..., 0]
        held = at < qlen[:, None]
        np.testing.assert_array_equal(
            got[held], (np.arange(5)[:, None] * 100 + at + 1)[held])
    else:
        got = np.asarray(tt.head_rows(h, None))
        assert got.shape == (5, WIDTH, 3)
        every = np.arange(WIDTH)[None, :]
        want = np.where(every < qlen[:, None],
                        np.arange(5)[:, None] * 100 + every + 1, 0)
        np.testing.assert_array_equal(got[..., 0], want)


@pytest.mark.parametrize("per_tile", [None, 8])
def test_embed_reads_the_list_s_tokens(per_tile):
    qlen, max_tokens = TICKS["not_reached"]
    tt = _listed(per_tile, qlen, max_tokens)
    tokens = (np.arange(5)[:, None] * WIDTH + np.arange(WIDTH)[None, :]
              ).astype(np.int32)
    table = jnp.arange(5 * WIDTH, dtype=jnp.float32)[:, None] * jnp.ones(2)
    h = np.asarray(tt.embed({"tok_embed": {"table": table}},
                            jnp.asarray(tokens), jnp.float32))
    assert h.shape == tt.slot.shape + (2,)
    valid = np.asarray(tt.valid)
    assert list(h[valid][:, 0]) == [tokens[b, s] for b, s in _pairs(qlen)]


def test_classes_are_the_ops_plan_at_the_list_s_bound():
    qlen, max_tokens = TICKS["ends_mid_tile"]
    tt = _listed(None, qlen, max_tokens)
    got = tt.classes(2)
    want = la.class_plan(jnp.asarray(qlen), WIDTH, 2, max_tokens)
    np.testing.assert_array_equal(np.asarray(got.short),
                                  np.asarray(want.short))
    np.testing.assert_array_equal(np.asarray(got.slot),
                                  np.asarray(want.slot))
    runs = tt.classes(2, run_slots=4)
    assert runs.run_slots == 4 and list(np.asarray(runs.runs)) == [0, 0, 1,
                                                                   0, 3]


def test_lm_head_is_the_final_norm_and_the_head_in_float32():
    h = jnp.asarray(np.random.RandomState(0).randn(3, 8), jnp.bfloat16)
    params = {"ln_f": {"scale": jnp.full((8,), 2.0)},
              "head": {"kernel": jnp.eye(8, 5), "bias": jnp.zeros((5,))}}
    got = lm_head(params, h, 1e-6, jnp.float32)
    x = np.asarray(h, np.float32)
    want = (2.0 * x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6))[:, :5]
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-2, atol=2e-2)
