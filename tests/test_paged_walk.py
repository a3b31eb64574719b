"""The paged walk as one DMA pipeline over a call (ops/paged_attention.py
`_paged_kernel`): a tile's last fold starts the first group of the grid's
next step where that tile is live, and a group's copies stop at the tile's
horizon (and start at its lower bound).

Contracts under test, through the Pallas interpreters on the CPU:
- every case the pipeline can get wrong equals the gather reference;
- a row's output does not depend on its neighbours, bit for bit: alone,
  behind a dead row (a cold start) and behind a live one (a warm start, in
  either buffer);
- nothing but the blocks `walk_tiles` names is ever read: with every other
  block of the pool NaN, and VMEM scratch NaN until written, the output is
  finite and the reference's;
- `walk_counts` gives the hand-counted tiles and tokens of those cases.
"""

import functools

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from tpu_engine.ops import paged_attention as pa

# The cases PR 48 brought (the older ones run in test_mixed_step.py,
# test_paged_kv.py, test_kv_quant.py): name -> the call's width.
PIPELINE_CASES = {
    "neighbours-dead-and-live-width-1": 1,
    "live-last-row-width-1": 1,
    "rows-of-1-128-129-columns": 1,
    "horizon-on-a-block-edge-width-1": 1,
    "horizon-on-a-block-edge": 40,
    "tall-tile-dead-tile-live-row": 64,
}


@pytest.mark.parametrize("case", sorted(PIPELINE_CASES))
@pytest.mark.parametrize("kind", ["ragged", "quant_ragged"])
def test_the_pipeline_s_cases_equal_the_reference(kind, case):
    limit = 2e-4 if kind.startswith("quant") else 2e-5
    assert pa.walk_parity_check(kind, case, interpret=True) < limit


@pytest.mark.parametrize("case", sorted(
    c for c, width in PIPELINE_CASES.items() if width == 1))
@pytest.mark.parametrize("kind", ["ragged", "quant_ragged"])
def test_a_decode_only_tick_s_read_from_bfloat16_queries(kind, case):
    """The call every cell's decode ticks make: one slot wide (the heads
    packed into one score tile), the queries bfloat16 as a served lane's,
    the pool bfloat16 or int8 beside float32 scales."""
    assert pa.walk_parity_check(kind, case, interpret=True,
                                dtype=jnp.bfloat16) < 3e-2


def _workload(q_lens, pos0, table_len, window=None, group=4):
    return pa.parity_workload(
        "ragged", q_lens, n_heads=2 * group, n_kv_heads=2, d_head=16,
        block_size=16, n_blocks=1 + len(q_lens) * table_len,
        table_len=table_len, dtype=jnp.float32, pos0=pos0, window=window)


# The row read (the LAST of each workload) and what may stand before it:
# a neighbour of one group (the row's first group lands in the second
# buffer), of two (in the first again), of a longer walk.
@pytest.mark.parametrize("q_lens, pos0, window", [
    ((1, 1, 1, 1), (50, 200, 300, 201), None),      # width 1: 202 columns
    ((1, 1, 1, 1), (50, 200, 300, 15), None),       # one block
    ((40, 1, 40, 40), (3, 299, 300, 250), None),    # two tall tiles
    ((1, 1, 1, 1), (50, 200, 700, 650), 40),        # a window five groups in
], ids=["width-1", "width-1-one-block", "tall-tiles", "window"])
def test_a_row_s_output_does_not_depend_on_its_neighbours(q_lens, pos0,
                                                          window):
    (q, k, v, layer, tables, p0, qlen), _ = _workload(q_lens, pos0, 48,
                                                      window)
    me = len(q_lens) - 1

    def read(rows, live):
        idx = jnp.asarray(rows)
        out = pa.ragged_paged_attention(
            q[idx], k, v, layer, tables[idx], p0[idx],
            qlen[idx] * jnp.asarray(live, jnp.int32), window=window,
            interpret=True)
        return np.asarray(out[-1, :q_lens[me]])

    alone = read([me], [1])
    assert np.isfinite(alone).all()
    for neighbour in range(me):
        for live in (0, 1):
            np.testing.assert_array_equal(
                read([neighbour, me], [live, 1]), alone,
                err_msg=f"behind row {neighbour}, live={live}")
    np.testing.assert_array_equal(
        read(list(range(me + 1)), [1] * (me + 1)), alone)


NAN_CASES = {
    "walk/neighbours-dead-and-live-width-1": None,
    "walk/tall-tile-dead-tile-live-row": None,
    "walk/horizon-on-a-block-edge": None,
    "window/first-groups-differ-between-neighbours": 6,
    "window/chunk-across-the-edge": 6,
}


@pytest.mark.parametrize("name", sorted(NAN_CASES))
def test_no_block_past_a_tile_s_horizon_or_behind_its_window_is_read(name):
    """Every block `walk_tiles` does not name holds NaN (the null block
    too), and the TPU interpreter hands out VMEM scratch as NaN and runs a
    DMA when its semaphore is waited on: a fetch past the horizon, a fold
    of a buffer row no copy filled, or a wait that names another copy
    than was started would each show."""
    table, case = name.split("/")
    if table == "walk":
        (q_lens, pos0, table_len), window, group = (
            pa.WALK_CASES[case], None, 4)
    else:
        q_lens, pos0, window, table_len = pa.WINDOW_CASES[case]
        group = NAN_CASES[name]
    operands, qlen = _workload(q_lens, pos0, table_len, window, group)
    q, k, v, layer, tables, p0, _ = operands
    live, lo, hi = pa.walk_tiles(pos0, q_lens, width=max(q_lens),
                                 group=group, kv_heads=2, block_size=16,
                                 window=window)
    fetched = {int(np.asarray(tables)[b, j])
               for b, t in zip(*np.nonzero(live))
               for j in range(lo[b, t], hi[b, t])}
    assert 0 not in fetched
    assert pa.walk_counts(pos0, q_lens, width=max(q_lens), group=group,
                          kv_heads=2, block_size=16, window=window)[2] == \
        16 * sum(hi[b, t] - lo[b, t] for b, t in zip(*np.nonzero(live)))
    unread = np.ones(k.shape[1], bool)
    unread[sorted(fetched)] = False
    poisoned = [jnp.where(unread[None, :, None, None], jnp.nan, x)
                for x in (k, v)]
    out = pa.ragged_paged_attention(
        q, *poisoned, layer, tables, p0, qlen, window=window,
        interpret=pltpu.InterpretParams(uninitialized_memory="nan",
                                        dma_execution_mode="on_wait",
                                        detect_races=True))
    assert np.isfinite(np.asarray(out)).all()
    reference = pa.ragged_paged_attention_reference
    if window is not None:
        reference = functools.partial(reference, window=window)
    assert pa.reference_error(reference, out, operands, qlen) < 2e-5


# (live tiles, warm tiles, tokens fetched), counted by hand: a tile
# fetches ceil(horizon / 16) - lower // 16 blocks of 16 columns; at width
# 1 a row is a tile, at G = 4 a tile is 32 slots.
HAND_COUNTED = {
    # 38, 201, 131, 4 columns: 3 + 13 + 9 + 1 blocks; row 3 alone follows
    # a live row.
    "neighbours-dead-and-live-width-1": (4, 1, 416),
    "live-last-row-width-1": (3, 2, (1 + 19 + 9) * 16),
    "rows-of-1-128-129-columns": (4, 3, (1 + 8 + 9 + 1) * 16),
    "horizon-on-a-block-edge-width-1": (4, 3, (1 + 2 + 9 + 10) * 16),
    # Two rows of two tiles: horizons 40 and 48, 41 and 49.
    "horizon-on-a-block-edge": (4, 3, (3 + 3 + 3 + 4) * 16),
    # Row 0's one live tile (320 columns), row 1's two (72, 104), row 2's
    # one (501); the tiles behind a dead tile are cold.
    "tall-tile-dead-tile-live-row": (4, 2, (20 + 5 + 7 + 32) * 16),
    # An older case, wide: the chunk's two tiles see 272 and 304 columns,
    # the decode row 256.
    "chunk-tiles-straddle-a-group": (3, 2, (17 + 19 + 16) * 16),
}


@pytest.mark.parametrize("case", sorted(HAND_COUNTED))
def test_walk_counts_are_the_hand_counted_ones(case):
    q_lens, pos0, _ = pa.WALK_CASES[case]
    assert pa.walk_counts(pos0, q_lens, width=max(q_lens), group=4,
                          kv_heads=2, block_size=16) == HAND_COUNTED[case]


@pytest.mark.parametrize("pos0, want", [
    # window 40: columns [lower, pos0]: blocks 0-0, 0-2, 0-2, 41-43.
    ((5, 39, 40, 700), (4, 3, (1 + 3 + 3 + 3) * 16)),
    # five, zero, four and two groups in.
    ((700, 5, 650, 300), (4, 3, (3 + 1 + 3 + 3) * 16)),
])
def test_walk_counts_stop_at_a_window_s_lower_bound(pos0, want):
    assert pa.walk_counts(pos0, (1, 1, 1, 1), width=1, group=6, kv_heads=2,
                          block_size=16, window=40) == want


def test_a_decode_tick_of_batch_fetches_a_block_s_slack_and_no_more():
    """gpt2-large's batch cell: 32 rows of 48-304 columns at width 1. The
    parent fetched whole groups of 128 columns (1.36 x the context); a
    block is 16."""
    rng = np.random.default_rng(0)
    pos0 = rng.integers(47, 304, 32)
    live, warm, fetched = pa.walk_counts(
        pos0, np.ones(32, int), width=1, group=1, kv_heads=20, block_size=16)
    assert (live, warm) == (32, 31)
    ctx = int((pos0 + 1).sum())
    assert ctx <= fetched < 1.10 * ctx
    assert -(-(pos0 + 1) // 128).sum() * 128 > 1.25 * ctx


# -- the packed fold: two products a chunk of heads (PR 61) -------------------
#
# The packed shapes the benchmark's cells run, small: name -> (H_kv, G, D,
# q_lens, pos0, table_len, what else the call takes). A call one slot wide
# packs H_kv x G query rows into one score tile; `mask_block` 4 at G = 8 is
# reply's tile (4 slots x 8 heads = 32 rows a KV head, 4 heads packed);
# sixteen slots at G = 4 over 4 heads is two chunks of two heads. At D = 64
# ONE product takes a chunk's heads, at D = 128 a head (`pa._product_heads`).
DECODE_ROWS = ((1, 1, 0, 1), (37, 130, 9, 191), 13)
WINDOW_ROWS = ((1, 1, 1, 1), (5, 39, 40, 700), 48)
RUNS_OF_4 = ((4, 4, 0, 4, 4), (0, 36, 9, 124, 128), 16)
PACKED_SHAPES = {
    "5x1x64-an-odd-count-of-64-lane-heads": (5, 1, 64, *DECODE_ROWS, {}),
    "8x4x64": (8, 4, 64, *DECODE_ROWS, {}),
    "8x4x64-int8-pool": (8, 4, 64, *DECODE_ROWS, {"kind": "quant_ragged"}),
    "8x4x64-window": (8, 4, 64, *WINDOW_ROWS, {"window": 40}),
    "4x8x64-mask-block-4-at-rows-32": (4, 8, 64, *RUNS_OF_4,
                                       {"mask_block": 4}),
    "4x4x64-two-chunks-of-two-heads": (4, 4, 64, (16, 3, 0, 16),
                                       (0, 37, 9, 120), 13, {}),
    "4x5x128": (4, 5, 128, *DECODE_ROWS, {}),
    "8x6x128-window": (8, 6, 128, *WINDOW_ROWS, {"window": 40}),
    "6x1x128": (6, 1, 128, *DECODE_ROWS, {}),
    "2x16x128": (2, 16, 128, *DECODE_ROWS, {}),
    "2x16x128-int8-pool": (2, 16, 128, *DECODE_ROWS,
                           {"kind": "quant_ragged"}),
    "4x8x128-mask-block-4-at-rows-32": (4, 8, 128, *RUNS_OF_4,
                                        {"mask_block": 4}),
}


def _packed_workload(name):
    """(read path, q_lens, `parity_workload`'s shape, what else the call
    takes) of one of `PACKED_SHAPES`."""
    h_kv, group, d_head, q_lens, pos0, table_len, more = PACKED_SHAPES[name]
    more = dict(more)
    shape = dict(n_heads=h_kv * group, n_kv_heads=h_kv, d_head=d_head,
                 block_size=16, n_blocks=1 + len(q_lens) * table_len,
                 table_len=table_len, pos0=pos0)
    return more.pop("kind", "ragged"), q_lens, shape, more


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(PACKED_SHAPES))
def test_the_packed_fold_equals_the_reference_at_the_cells_packed_shapes(
        name, dtype):
    """One block-diagonal score product and one value product a chunk of
    packed heads (D = 64), or a product a head into one score tile (D =
    128), against the gather reference: the products that are added up are
    products with exact zeros, so float32 holds to rounding and bfloat16
    to the pool's own."""
    kind, q_lens, shape, more = _packed_workload(name)
    rows, pack, _ = pa._tile_geometry(
        max(q_lens) * shape["n_heads"] // shape["n_kv_heads"],
        shape["n_kv_heads"])
    assert pack > 1 and pack * rows <= 128
    limit = {"float32": 5e-5, "bfloat16": 3e-2}[dtype]
    assert pa._parity(kind, q_lens, dtype=jnp.dtype(dtype), interpret=True,
                      **shape, **more) < limit


def test_a_product_takes_the_chunk_s_heads_where_a_head_is_half_a_lane_tile():
    """`_product_heads` reads the call's shapes and nothing else: every
    packed head at D = 64 (and at the test models' narrower heads), one at
    a multiple of 128, where the chip showed nothing to gain."""
    assert [pa._product_heads(pack, d) for pack, d in
            ((20, 64), (8, 64), (2, 16), (5, 96), (1, 64))] == [20, 8, 2, 5, 1]
    assert [pa._product_heads(pack, d) for pack, d in
            ((30, 128), (8, 128), (4, 256), (1, 128))] == [1, 1, 1, 1]


@pytest.mark.parametrize("name", [
    "5x1x64-an-odd-count-of-64-lane-heads", "8x4x64", "8x4x64-window",
    "4x8x64-mask-block-4-at-rows-32", "4x4x64-two-chunks-of-two-heads",
    "4x5x128", "2x16x128"])
def test_a_packed_fold_holds_two_products_a_product_s_heads(name):
    """The `dot_general`s of the kernel's body (the fold is its only loop),
    nested jaxprs included. At D = 64 they are 2 x H_kv / pack, not the
    2 x H_kv of a product a head: a score product (M, pack x D) x (span,
    pack x D) and a value product (M, span) x (span, pack x D) a chunk. At
    D = 128 a head's product contracts a whole lane tile of an aligned
    slice already: 2 x H_kv products of (M, D) tiles, the parent's
    program."""
    import jax

    _, q_lens, shape, more = _packed_workload(name)
    operands, _ = pa.parity_workload("ragged", q_lens, dtype=jnp.bfloat16,
                                     window=more.get("window"), **shape)

    def eqns(jaxpr, primitive):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == primitive:
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub, primitive)

    (call,) = eqns(jax.make_jaxpr(functools.partial(
        pa.ragged_paged_attention, interpret=False, **more))(
            *operands).jaxpr, "pallas_call")
    found = [tuple(tuple(v.aval.shape) for v in eqn.invars)
             for eqn in eqns(call.params["jaxpr"], "dot_general")]
    h_kv, d_head = shape["n_kv_heads"], shape["d_head"]
    rows, pack, blocks = pa._tile_geometry(
        max(q_lens) * shape["n_heads"] // h_kv, h_kv)
    width = pack if d_head == 64 else 1
    m, lanes, span = pack * rows, width * d_head, blocks * 16
    assert sorted(found) == sorted(
        [((m, lanes), (span, lanes)), ((m, span), (span, lanes))]
        * (h_kv // width))
