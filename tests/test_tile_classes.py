"""The paged read's two classes of query tile (ops/latent_attention.py
`class_plan`, ops/paged_attention.py `ragged_read_by_class`): a row with one
new token is read a row a tile at width 1, every longer run in tall tiles of
its own. Over `pa.CLASS_CASES` at one and at four query heads a KV head: the
tiles cover every valid slot once and none twice, and short + tall reads
equal the gather reference on the whole batch, through the Pallas
interpreter and through the XLA reference."""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.ops import latent_attention as la
from tpu_engine.ops import paged_attention as pa

WIDTH = 256
CASES = sorted(pa.CLASS_CASES)
GROUPS = [1, 4]
# Five query heads a KV head (Falcon-H1: 20 over 4), the first group size
# coprime with the 128-row tile: a tall tile of 128 slots is 640 query rows,
# five tiles of the grid. Three of the cases, not the whole product.
ODD_GROUP = 5
ODD_GROUP_CASES = ["a-run-of-129-beside-short-rows",
                   "two-tall-runs-in-one-tick", "max-tokens-reached-exactly"]


def test_a_tall_tile_is_whole_tiles_of_the_kernel_s_query_rows():
    """One tile of 128 query rows at G = 1, three at G = 6 (64 slots), one
    at G = 4 (32 slots); never wider than the step; the plan's tile and the
    paged kernel's are the same 128 rows."""
    assert la._ROW_TILE == pa._ROW_TILE == 128
    assert [la.tall_slots(WIDTH, g) for g in (1, 4, 6, 9)] == [128, 32, 64,
                                                               128]
    assert la.tall_slots(16, 1) == 16 and la.tall_slots(1, 6) == 1
    assert la.tall_slots(WIDTH, ODD_GROUP) == 128
    assert pa._tile_geometry(ODD_GROUP, 4)[:2] == (5, 4)   # 4 x 5 rows packed
    for g in (1, 4, 5, 6):
        assert la.tall_slots(WIDTH, g) * g % pa._ROW_TILE == 0


@pytest.mark.parametrize("group", GROUPS)
@pytest.mark.parametrize("case", CASES)
def test_the_tiles_cover_every_valid_slot_once_and_none_twice(case, group):
    q_lens, _, max_tokens = pa.CLASS_CASES[case]
    qlen = np.asarray(q_lens, np.int32)
    classes = la.class_plan(jnp.asarray(qlen), WIDTH, group, max_tokens)
    height = la.tall_slots(WIDTH, group)
    assert classes.slot.shape == (la.tiles_bound(
        len(q_lens), WIDTH, height, max_tokens), height)
    seen = np.zeros((len(q_lens), WIDTH), np.int32)
    seen[np.asarray(classes.short), 0] += 1
    rows = np.broadcast_to(np.asarray(classes.tall.row)[:, None],
                           classes.slot.shape)
    valid = np.asarray(classes.valid)
    np.add.at(seen, (rows[valid], np.asarray(classes.slot)[valid]), 1)
    np.testing.assert_array_equal(
        seen, np.arange(WIDTH)[None, :] < qlen[:, None])
    # A short row is in no tall tile, and the host counts what the device
    # plans.
    assert not np.isin(rows[valid], np.flatnonzero(qlen == 1)).any()
    assert la.class_counts(qlen, WIDTH, group) == (
        int(np.asarray(classes.short).sum()), int(classes.tall.n_live[0]))
    assert int(valid.any(-1).sum()) == int(classes.tall.n_live[0])


@pytest.mark.parametrize("path", ["pallas-interpreter", "xla-reference"])
@pytest.mark.parametrize("group", GROUPS)
@pytest.mark.parametrize("case", CASES)
def test_short_and_tall_reads_equal_the_reference_on_the_whole_batch(
        case, group, path):
    if path == "pallas-interpreter":
        assert pa.class_parity_check(case, group, interpret=True) < 2e-5
        return
    q_lens, pos0, max_tokens = pa.CLASS_CASES[case]
    operands = pa.class_workload(
        q_lens, pos0, width=WIDTH, max_tokens=max_tokens,
        n_heads=2 * group, n_kv_heads=2, d_head=16, block_size=16,
        n_blocks=1 + len(q_lens) * 24, table_len=24, dtype=jnp.float32)
    out = pa.class_read(*operands, width=WIDTH, max_tokens=max_tokens,
                        attn_fn=pa.ragged_paged_attention_reference)
    assert pa.class_read_error(out, operands) < 2e-5


@pytest.mark.parametrize("case", ODD_GROUP_CASES)
def test_an_odd_group_s_tiles_cover_and_read_as_the_reference(case):
    """G = 5: the tall plan covers every slot of every longer run once in
    tiles of 128 slots, and short + tall reads through the Pallas
    interpreter equal the gather reference."""
    test_the_tiles_cover_every_valid_slot_once_and_none_twice(case, ODD_GROUP)
    assert pa.class_parity_check(case, ODD_GROUP, interpret=True) < 2e-5


def _calls(fn, *operands):
    """(q's shape of every attention call, every intermediate's shape)."""
    asked = []

    def attn_fn(q, *rest, **kw):
        asked.append(q.shape)
        return pa.ragged_paged_attention_reference(q, *rest, **kw)

    jaxpr = jax.make_jaxpr(functools.partial(fn, attn_fn=attn_fn))(*operands)
    return asked, [v.aval.shape for eqn in jaxpr.jaxpr.eqns
                   for v in eqn.outvars]


@pytest.mark.parametrize("group", GROUPS)
def test_a_chunk_tick_makes_two_calls_and_no_operand_of_rows_x_width(group):
    """Four rows in a step of 256 slots: a (4, 1, H, D) call and a
    (4 + ceil(max_tokens / height), height, H, D) call; nothing of
    (4, 256, ...) anywhere in the read."""
    q_lens, pos0, _ = pa.CLASS_CASES["two-tall-runs-in-one-tick"]
    operands = pa.class_workload(
        q_lens, pos0, width=WIDTH, max_tokens=260, n_heads=2 * group,
        n_kv_heads=2, d_head=16, block_size=16, n_blocks=97, table_len=24,
        dtype=jnp.float32)
    asked, shapes = _calls(functools.partial(pa.class_read, width=WIDTH,
                                             max_tokens=260), *operands)
    height = la.tall_slots(WIDTH, group)
    assert asked == [(4, 1, 2 * group, 16),
                     (4 + -(-260 // height), height, 2 * group, 16)]
    assert not [s for s in shapes if s[:2] == (4, WIDTH)]


def test_a_step_a_slot_wide_has_one_class_and_makes_one_call():
    operands = pa.class_workload(
        (1, 0, 1, 1), (37, 0, 301, 128), width=1, n_heads=8, n_kv_heads=2,
        d_head=16, block_size=16, n_blocks=97, table_len=24,
        dtype=jnp.float32)
    assert la.class_plan(operands[-1], 1, 4).tall is None
    asked, _ = _calls(functools.partial(pa.class_read, width=1), *operands)
    assert asked == [(4, 1, 8, 16)]
    out = pa.class_read(*operands, width=1, interpret=True)
    assert pa.class_read_error(out, operands) < 2e-5


def test_the_class_is_chosen_by_qlen_and_by_nothing_else():
    """No flag, environment variable or model's name: the same compiled
    plan sends a row to the short call in one tick and to the tall one in
    the next, as its q_len says."""
    plan = jax.jit(functools.partial(la.class_plan, width=WIDTH, group=1,
                                     max_tokens=272))
    for q_lens in ((1, 200, 1, 0), (200, 1, 0, 1), (1, 1, 1, 1),
                   (2, 2, 2, 2)):
        qlen = np.asarray(q_lens, np.int32)
        classes = plan(jnp.asarray(qlen))
        np.testing.assert_array_equal(np.asarray(classes.short), qlen == 1)
        live = np.asarray(classes.valid).any(-1)
        np.testing.assert_array_equal(
            np.unique(np.asarray(classes.tall.row)[live]),
            np.flatnonzero(qlen > 1))


@pytest.mark.parametrize("cap, group, q_lens, want", [
    (256, 1, (1, 1, 256, 0, 1), (3, 2)),
    (256, 1, (129, 1, 2, 128), (1, 2 + 1 + 1)),
    (256, 6, (1, 256, 65, 0), (1, 4 + 2)),
    (16, 1, (16, 7, 1, 1), (2, 2)),
    (256, 1, (0, 0, 0), (0, 0)),
])
def test_the_span_s_tile_counts_are_what_qlen_implies(cap, group, q_lens,
                                                      want):
    """`mixed_step`'s `attn_tiles_short` / `attn_tiles_tall`, as the
    scheduler computes them on the host from a tick's `qlen`: a tile a row
    with one new token, ceil(q_len / height) a longer run, the height the
    step's (`_chunk_cap` and the model's heads)."""
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    lane = types.SimpleNamespace(
        _chunk_cap=cap,
        cfg=types.SimpleNamespace(n_heads=2 * group, kv_heads=2))
    qlen = np.asarray(q_lens, np.int32)
    got = ContinuousGenerator._attn_tiles(lane, qlen)
    assert got == {"attn_tiles_short": want[0], "attn_tiles_tall": want[1]}
    height = la.tall_slots(cap, group)
    assert got["attn_tiles_short"] + sum(
        -(-q // height) for q in q_lens if q > 1) == sum(want)
    # Every new token is in a tile of one class or the other.
    assert (got["attn_tiles_short"] + got["attn_tiles_tall"] * height
            >= int(qlen.sum()))
