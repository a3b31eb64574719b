"""Tier-1 compile gate for the TPU kernels, without a chip.

The Pallas interpreter (what every other kernel test runs) applies none
of the TPU lowering's block-shape rules and never runs Mosaic, so a
kernel can pass every parity test and still be refused by the compiler
at its first trace on the chip. The installed libtpu can describe a v5e
topology with no chip attached, and `jit(...).lower(shapes placed on a
topology device).compile()` runs the real Pallas TPU lowering and Mosaic
— so every `pallas_call` site is compiled here at the head geometry of
the registry's gpt2 and llama, plus one tensor-parallel paged tick and
the rules around start-up that only bite on the chip machine.

These tests FAIL when the topology cannot be built: a skip would be the
same silent pass the interpreter gives.
"""

import ast
import dataclasses
import functools
import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from tpu_engine.ops import kernel_check
from tpu_engine.ops.attention import KVCache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert len(topo.devices) == 4
    assert topo.devices[0].platform == "tpu"
    return topo.devices


@pytest.mark.parametrize("model", kernel_check.MODELS)
def test_every_kernel_site_compiles_for_v5e(v5e_devices, model):
    names = []
    for case in kernel_check.kernel_cases(model, interpret=False):
        kernel_check.compile_for_topology(case, v5e_devices[0])
        names.append(case.name.split("/", 1)[1])
    # The list itself is part of the gate: flash forward at every prompt
    # bucket, flash backward, and both paged read paths.
    assert [n for n in names if n.startswith("flash_fwd")] == [
        f"flash_fwd/S{s}" for s in kernel_check.FLASH_BUCKETS]
    assert {n.split("/")[0] for n in names} == {
        "flash_fwd", "flash_bwd", "ragged", "quant_ragged"}


def test_the_latent_read_compiles_for_v5e_at_both_widths(v5e_devices):
    names = []
    for model in kernel_check.LATENT_MODELS:
        for case in kernel_check.kernel_cases(model, interpret=False):
            kernel_check.compile_for_topology(case, v5e_devices[0])
            names.append(case.name)
    # 16 heads, and 32 in a model whose other layers are recurrent: both
    # forms of its channel-gated recurrence compile beside the read, the
    # step also at the live rows of reason's chunk ticks and with none.
    assert names == ["moonlight/latent/W1", "moonlight/latent/W256",
                     "kimi_linear/latent/W1", "kimi_linear/latent/W256",
                     "kimi_linear/kda_step/B128",
                     "kimi_linear/kda_step/B128/live69",
                     "kimi_linear/kda_step/B128/live0",
                     "kimi_linear/kda_chunk/T256"]


def test_the_state_space_recurrence_compiles_for_v5e_in_both_forms(
        v5e_devices):
    """`ssd_step` over 64 rows of a pool of 4.2 MB states (the 16 heads of
    a group a block: 2 MB in, 2 MB out, double-buffered) and `ssd_chunk`
    over a run of 256 tokens, at Falcon-H1's heads, groups and lanes."""
    names = []
    for case in kernel_check.kernel_cases("falcon_h1", interpret=False):
        kernel_check.compile_for_topology(case, v5e_devices[0])
        names.append(case.name)
    assert names == ["falcon_h1/ssd_step/B64", "falcon_h1/ssd_step/B64/live0",
                     "falcon_h1/ssd_chunk/T256"]
    for step in kernel_check.kernel_cases("falcon_h1"):
        if "ssd_step" in step.name:
            jaxpr = jax.make_jaxpr(step.kernel)(
                *jax.eval_shape(step.operands))
            assert _pallas_grids(jaxpr.jaxpr) == [(64, 2)]


def test_the_recurrence_compiles_for_v5e_at_128_heads_of_64_by_128(
        v5e_devices):
    """`ssd_step` and `ssd_chunk` cut the other way (Nemotron-H: 128 heads
    of (64, 128) in 8 groups): a block of the step is a group's 16 heads,
    512 KB, its dt x block (1, 1, 64, 16); the chunk kernel a head a grid
    step, 128 steps a row."""
    names = []
    for case in kernel_check.kernel_cases("nemotron_h", interpret=False):
        kernel_check.compile_for_topology(case, v5e_devices[0])
        names.append(case.name)
    assert names == ["nemotron_h/ssd_step/B64",
                     "nemotron_h/ssd_step/B64/live40",
                     "nemotron_h/ssd_step/B64/live0",
                     "nemotron_h/ssd_chunk/T256"]
    # The grid spans the lane's slots whatever is live: a dead row's steps
    # stay, empty (agents' mix: 40 of 64).
    for case in kernel_check.kernel_cases("nemotron_h"):
        grid = (64, 8) if "ssd_step" in case.name else (128,)
        jaxpr = jax.make_jaxpr(case.kernel)(*jax.eval_shape(case.operands))
        assert _pallas_grids(jaxpr.jaxpr) == [grid]


def test_the_recurrence_compiles_for_v5e_at_one_group(v5e_devices):
    """`ssd_step` and `ssd_chunk` at Granite-4.0-H's shape: the same 128
    heads of (64, 128) with B and C ONE group that every head reads. A
    block of the step is 64 heads (2 MB of state, its dt x block (1, 1, 64,
    64)), so a row is two grid steps that both name group 0; the chunk
    kernel a head a grid step, every head's B and C block the same one."""
    names = []
    for case in kernel_check.kernel_cases("granite_hybrid", interpret=False):
        kernel_check.compile_for_topology(case, v5e_devices[0])
        names.append(case.name)
    assert names == ["granite_hybrid/ssd_step/B64",
                     "granite_hybrid/ssd_step/B64/live63",
                     "granite_hybrid/ssd_step/B64/live0",
                     "granite_hybrid/ssd_chunk/T256"]
    for case in kernel_check.kernel_cases("granite_hybrid"):
        grid = (64, 2) if "ssd_step" in case.name else (128,)
        operands = jax.eval_shape(case.operands)
        assert operands[3].shape[1:] == (1, 128)         # B: one group
        jaxpr = jax.make_jaxpr(case.kernel)(*operands)
        assert _pallas_grids(jaxpr.jaxpr) == [grid]


def test_the_delta_rule_compiles_for_v5e_under_a_gate_a_head(v5e_devices):
    """`gdn_step` over digest's 16 slots (30 heads of 192 x 96, 15 a block:
    one dead row, 14 live, none) and `gdn_chunk` over a run of 256 tokens,
    at Olmo-Hybrid's heads and lanes."""
    names = []
    for case in kernel_check.kernel_cases("olmo_hybrid", interpret=False):
        kernel_check.compile_for_topology(case, v5e_devices[0])
        names.append(case.name)
    assert names == ["olmo_hybrid/gdn_step/B16",
                     "olmo_hybrid/gdn_step/B16/live14",
                     "olmo_hybrid/gdn_step/B16/live0",
                     "olmo_hybrid/gdn_chunk/T256"]
    for case in kernel_check.kernel_cases("olmo_hybrid"):
        grid = (16, 2) if "gdn_step" in case.name else (30,)
        jaxpr = jax.make_jaxpr(case.kernel)(*jax.eval_shape(case.operands))
        assert _pallas_grids(jaxpr.jaxpr) == [grid]


@pytest.mark.parametrize("name", list(kernel_check.GROUPED_SHAPES))
def test_the_grouped_product_states_its_tiles_for_v5e(v5e_devices, name):
    """`ops.moe.routed_experts` over the banks the cells hold, each at
    its decode-only and its chunk-tick list length
    (`kernel_check.GROUPED_SHAPES`), compiled for one v5e: both products
    are still XLA's grouped kernel (`ragged-dot-none` behind one
    `ragged-dot-metadata`), each carries the tiles `grouped_tiling` states
    for its shapes, the pair list is a whole number of row tiles, and
    nothing of a bank's size is copied or sliced on the way in."""
    from tpu_engine.ops import moe

    shape = kernel_check.GROUPED_SHAPES[name]
    (case,) = [c for c in kernel_check.grouped_cases() if c.name == name]
    hlo = kernel_check.compile_for_topology(case, v5e_devices[0]).as_text()
    lanes, hidden = shape["lanes"], shape["hidden"]
    pairs = shape["slots"] * shape["top_k"]
    tile = moe.row_tile(pairs, 2)
    pairs = -(-pairs // tile) * tile
    want = [(pairs, lanes, hidden * (1 + shape["gated"])),
            (pairs, hidden, lanes)]
    stated = {}
    for line in hlo.splitlines():
        found = re.match(r"\s*%ragged-dot-none[.\d]* = f32\[([\d,]+)\]"
                         r".* custom-call\(", line)
        if found:
            (tiles,) = re.findall(r'ragged_dot_tiling="([\d,]+)"', line)
            stated[tuple(map(int, found[1].split(",")))] = tuple(
                map(int, tiles.split(",")))
        else:
            assert "ragged_dot_tiling" not in line, line[:200]
    assert len(re.findall(r"%ragged-dot-metadata[.\d]* = ", hlo)) == 1
    assert stated == {(m, n): moe.grouped_tiling(m, k, n, 2)
                      for m, k, n in want}
    bank = jax.eval_shape(case.operands)[-1]
    sizes = {math.prod(x.shape) for x in jax.tree.leaves(bank)}
    assert not _moved(hlo, sizes)


def _moved(hlo, sizes):
    """The (op, dims) of every `copy`, `slice`, `dynamic-slice` and
    `dynamic-update-slice` of a compiled module whose result has one of
    `sizes` elements."""
    movers = re.compile(r"= \w+\[([\d,]+)\]\S* "
                        r"(copy|slice|dynamic-slice|dynamic-update-slice)\(")
    return [(op, dims) for dims, op in movers.findall(hlo)
            if math.prod(map(int, dims.split(","))) in sizes]


def _pallas_calls(jaxpr):
    """The parameters of every `pallas_call` in a jaxpr, nested calls
    included."""
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append(eqn.params)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            calls += _pallas_calls(sub)
    return calls


def _pallas_grids(jaxpr):
    """The grid of every `pallas_call` in a jaxpr, nested calls included."""
    return [tuple(call["grid_mapping"].grid) for call in _pallas_calls(jaxpr)]


@pytest.mark.parametrize("cell", sorted(kernel_check.CELL_SHAPES))
def test_paged_grid_is_the_query_tiles_not_the_table(v5e_devices, cell):
    """The ragged read at a benchmark cell's own shapes
    (`kernel_check.CELL_SHAPES`: docqa's 16 rows x 32 / 8 heads x 128
    with a 256-slot chunk under a 2048-column table; batch's 32 rows x
    20 x 64 at width 1 under 64 columns) compiles for a v5e, and its
    grid is (rows, query tiles a row) at that table and at one half as
    wide: no grid step for a table column (the parent's grid had the
    table's width as its third axis)."""
    shape = kernel_check.CELL_SHAPES[cell]
    rows = len(shape["rows"])
    width = max(q for q, _ in shape["rows"])
    geo = shape["geo"]
    tiles = -(-width * geo["n_heads"] // geo["n_kv_heads"] // 128)
    (case,) = [c for c in kernel_check.cell_cases(interpret=False)
               if c.name == cell]
    kernel_check.compile_for_topology(case, v5e_devices[0])
    full = jax.eval_shape(case.operands)
    assert full[4].shape == (rows, shape["table_len"])
    half = full[:4] + (jax.ShapeDtypeStruct(
        (rows, shape["table_len"] // 2), full[4].dtype),) + full[5:]
    for operands in (full, half):
        grids = _pallas_grids(jax.make_jaxpr(case.kernel)(*operands).jaxpr)
        assert grids == [(rows, tiles)], grids
    kernel_check.compile_for_topology(
        dataclasses.replace(case, operands=lambda: half), v5e_devices[0])


@pytest.mark.parametrize("cell", sorted(kernel_check.CELL_SHAPES))
def test_the_paged_walk_is_one_pipeline_over_a_sequential_grid(cell):
    """Since PR 48 a step starts the next step's first copies, so the
    grid's steps run in order (both axes "arbitrary"; a "parallel" axis
    could be split between cores or reordered) and what one step leaves
    the next is an SMEM scratch beside ONE DMA semaphore a tensor and
    buffer (a buffer's copies are waited for by their bytes). That Mosaic
    takes all of it at this shape is
    `test_paged_grid_is_the_query_tiles_not_the_table`."""
    (case,) = [c for c in kernel_check.cell_cases(interpret=False)
               if c.name == cell]
    (call,) = _pallas_calls(jax.make_jaxpr(case.kernel)(
        *jax.eval_shape(case.operands)).jaxpr)
    semantics = call["compiler_params"]["mosaic_tpu"].dimension_semantics
    assert tuple(str(s).lower().rsplit(".", 1)[-1] for s in semantics) == (
        "arbitrary", "arbitrary")
    scratch = [str(aval) for aval in call["grid_mapping"].scratch_avals]
    assert sum("smem" in s.lower() and "int32[2]" in s.replace(" ", "")
               for s in scratch) == 1, scratch
    assert sum("sem" in s.lower() and "[2,2]" in s.replace(" ", "")
               for s in scratch) == 1, scratch


def test_the_walk_s_own_cases_compile_for_v5e(v5e_devices):
    """`kernel_check.walk_cases`: what chip_smoke.py's kernel phase runs
    of the pipeline (dead steps between live ones, horizons on a block's
    edge, lower bounds groups apart), every one through Mosaic."""
    from tpu_engine.ops import paged_attention as pa

    names = []
    for case in kernel_check.walk_cases(interpret=False):
        kernel_check.compile_for_topology(case, v5e_devices[0])
        names.append(case.name)
    assert names == [f"walk/{n}" for n in pa.WALK_CASES] + [
        f"walk/window/{n}" for n in pa.WINDOW_CASES]


# The packed call of every cell that makes one, at the lane's own rows:
# name -> (q dtype, rows, slots a row, H, H_kv, D, the table's blocks, the
# pool's (layers, blocks), what else the call takes). The pool is bfloat16.
PACKED_CALLS = {
    # gpt2-large's q is float32 (its projections' accumulators).
    "gpt2-large.batch": ("float32", 32, 1, 20, 20, 64, 64, (36, 1537), {}),
    "lfm2-24b-a2b-9l.assist": ("bfloat16", 128, 1, 32, 8, 64, 320,
                               (2, 40961), {}),
    "olmo-hybrid-7b-12l.digest": ("bfloat16", 16, 1, 30, 30, 128, 1024,
                                  (3, 1537), {}),
    "ouro-2.6b.think": ("bfloat16", 8, 1, 16, 16, 128, 321, (192, 321), {}),
    "falcon-h1-34b-6l.converse": ("bfloat16", 64, 1, 20, 4, 128, 128,
                                  (6, 6145), {}),
    "laguna-s-2.1-5l.repo/full": ("bfloat16", 32, 1, 48, 8, 128, 1024,
                                  (2, 2049), {}),
    "laguna-s-2.1-5l.repo/window": ("bfloat16", 32, 1, 72, 8, 128, 1024,
                                    (3, 2049), {"window": 512}),
    "nemotron-3-super-120b-a12b-11l.agents": (
        "bfloat16", 64, 1, 32, 2, 128, 576, (2, 35841), {}),
    "sdar-30b-a3b-chat-7l.reply": ("bfloat16", 64, 4, 32, 4, 128, 128,
                                   (7, 8193), {"mask_block": 4}),
    "mistral-7b-v0.2-8l.docqa/W1": ("bfloat16", 16, 1, 32, 8, 128, 2048,
                                    (8, 2817), {}),
}


@pytest.mark.parametrize("cell", sorted(PACKED_CALLS))
def test_the_packed_fold_compiles_for_v5e_at_every_cell_s_shape(
        v5e_devices, cell):
    """Since PR 61 a packed tile's fold at D = 64 is two products a chunk of
    heads: (M, pack x D) x (span, pack x D) scores from the block-diagonal
    queries and (M, span) x (span, pack x D) values into an accumulator
    (H_kv / pack, M, pack x D) (the queries' rows are stored at a lane
    offset of 64); at D = 128 it stays a product a head into (H_kv, M, D)
    (`pa._product_heads`). Mosaic takes the call at every packed shape a
    cell makes (digest's accumulator is 460 KB), and the body holds two
    `dot_general`s a product."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.ops import paged_attention as pa

    dtype, rows, slots, h, h_kv, d, table, (layers, blocks), more = \
        PACKED_CALLS[cell]
    one = SingleDeviceSharding(v5e_devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dtype), sharding=one)

    pool = shape((layers, blocks, 16, h_kv * d), "bfloat16")
    operands = (shape((rows, slots, h, d), dtype), pool, pool, None, None,
                shape((1,), "int32"), shape((rows, table), "int32"),
                shape((rows,), "int32"), shape((rows,), "int32"))
    call = functools.partial(pa._paged_call, interpret=False, **more)
    jax.jit(call).lower(*operands).compile()
    tile, pack, _ = pa._tile_geometry(slots * h // h_kv, h_kv)
    assert pack > 1 and pack * tile <= 128
    width = pack if d == 64 else 1
    assert pa._product_heads(pack, d) == width
    (params,) = _pallas_calls(jax.make_jaxpr(call)(*operands).jaxpr)

    def products(jaxpr):
        return sum((eqn.primitive.name == "dot_general")
                   + sum(products(sub)
                         for sub in jax.core.jaxprs_in_params(eqn.params))
                   for eqn in jaxpr.eqns)

    assert products(params["jaxpr"]) == 2 * h_kv // width
    accumulator = str(params["grid_mapping"].scratch_avals[-1])
    assert f"float32[{h_kv // width},{pack * tile},{width * d}]" in \
        accumulator.replace(" ", ""), accumulator


@pytest.mark.parametrize("name, grids", [
    ("olmo-hybrid-7b-12l.digest/classes/W256", [(16, 1), (19, 1)]),
    ("laguna-s-2.1-5l.repo/full/classes/W256", [(8, 1), (13, 3)]),
    # G = 5: a decode row's 4 x 5 query rows are one packed tile, a tall
    # tile of 128 slots 640 query rows = five tiles of the grid; 16 rows and
    # ceil(272 / 128) more tall tiles.
    ("falcon-h1-34b-6l.converse/classes/W256", [(16, 1), (19, 5)]),
    # G = 16 over 2 KV heads: a decode row's 2 x 16 query rows are one
    # packed tile; a tall tile is 8 slots = 128 query rows = one tile of
    # the grid; 16 rows and ceil(320 / 8) more tall tiles.
    ("nemotron-3-super-120b-a12b-11l.agents/classes/W256",
     [(16, 1), (56, 1)]),
    # G = 4 over 8 KV heads of 64 lanes: a decode row's 8 x 4 query rows
    # are one packed tile, a tall tile is 32 slots = 128 query rows = one
    # tile of the grid; 16 rows and ceil(384 / 32) more tall tiles.
    ("lfm2-24b-a2b-9l.assist/classes/W256", [(16, 1), (28, 1)]),
    # G = 7 over 4 KV heads under a window of 4096: a decode row's 4 x 7
    # query rows are one packed tile, a tall tile of 128 slots 896 query
    # rows = seven tiles of the grid; 8 rows and ceil(288 / 128) more tall
    # tiles. Both calls carry the lower bound.
    ("smallthinker-21b-a3b-8l.history/window/classes/W256",
     [(8, 1), (11, 7)]),
])
def test_the_two_classes_of_tile_are_two_calls_with_grids_of_their_own(
        v5e_devices, name, grids):
    """A tick that carries a chunk, its rows read by the class of their
    runs (`kernel_check.CLASS_SHAPES`), compiles for a v5e as TWO paged
    calls: the short rows a row a tile at width 1 (the heads packed: one
    grid step a row), the tall tiles a row of their own call each (one
    tile of the grid at G = 1, three at G = 6: 64 slots x 6 heads), rows +
    ceil(max_tokens / height) of them; no operand of rows x 256 slots."""
    (case,) = [c for c in kernel_check.class_cases(interpret=False)
               if c.name == name]
    kernel_check.compile_for_topology(case, v5e_devices[0])
    jaxpr = jax.make_jaxpr(case.kernel)(*jax.eval_shape(case.operands))
    assert _pallas_grids(jaxpr.jaxpr) == grids
    rows = len(kernel_check.CLASS_SHAPES[name]["rows"])
    assert not [v.aval.shape for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars
                if v.aval.shape[:2] == (rows, kernel_check.CHUNK)]


def _mixed_tick(cfg, attn_fn, max_tokens=None):
    """The mixed step as the scheduler traces it, sampling left out:
    (params, caches, tables, tokens, pos0, qlen) -> (logits, caches).
    `max_tokens`: the lane's bound on a tick's tokens, its token budget
    plus a token a row (`_mixed_step_exe` states it since PR 52)."""
    from tpu_engine.models.transformer import transformer_step_rows_ragged

    def tick(params, caches, tables, tokens, pos0, qlen):
        return transformer_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            attn_fn=attn_fn, sample_slot=jnp.zeros_like(pos0),
            max_tokens=max_tokens)

    return tick


def _input_products(hlo):
    """[(name, result shape, opcode)] of the ENTRY computation's
    instructions whose `op_name` ends in `mixer/in/dot_general`: a
    recurrent layer's input projection and whatever XLA made of it, none
    of them a COPY (a name with `remat` in it): XLA computes a projection
    again for a consumer two parts away sooner than keep its result, where
    a slice of it is fused into that consumer (`models.falcon_h1.
    _ssm_inputs` writes the parts once, where the projection is split)."""
    entry = hlo[hlo.index("\nENTRY "):]
    under = re.findall(
        r"^\s*(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)\("
        r".*op_name=\"[^\"]*mixer/in/dot_general\"", entry, re.M)
    assert not [name for name, _, _ in under if "remat" in name], under
    return under


def _one_product_a_layer(hlo, lanes, layers):
    """The projection's products, fusions of result f32[rows, `lanes`],
    number the recurrent layers, and none is a copy."""
    products = [name for name, shape, op in _input_products(hlo)
                if op == "fusion"
                and re.fullmatch(rf"f32\[\d+,{lanes}\]", shape)]
    assert len(products) == layers, products


def _behind_a_step(tick, rows):
    """(`tick` as a lane's compiled step calls it since PR 40, the shapes
    of what it takes besides): a row's first token and its end come from
    the step before's own outputs, still on the device
    (`scheduler.take_from_prev`), and the row's end is an output again.
    (params, caches, tables, tokens, pos0, qlen, done, prev_nxt,
    prev_done, from_prev) -> (logits, caches, done)."""
    from tpu_engine.runtime.scheduler import take_from_prev

    def step(params, caches, tables, tokens, pos0, qlen, done, prev_nxt,
             prev_done, from_prev):
        tokens, done = take_from_prev(tokens, done, prev_nxt, prev_done,
                                      from_prev)
        logits, caches = tick(params, caches, tables, tokens, pos0, qlen)[:2]
        return logits, caches, done

    flag = jax.ShapeDtypeStruct(rows.shape, jnp.bool_,
                                sharding=rows.sharding)
    return step, (flag, rows, flag, flag)


def test_tp2_paged_tick_compiles_for_v5e(v5e_devices):
    """One --tp 2 mixed tick (transformer_step_rows_ragged over a
    head-sharded pool, params placed by the registry's TP rule) on two
    v5e devices. GSPMD refuses to partition a Mosaic kernel ("wrap the
    call in a shard_map"), so this compiles only because the read path
    runs per head shard (ops.paged_attention.shard_over_heads) — the
    wrapper the scheduler applies to every tp > 1 lane. The tick's
    per-row inputs arrive as the lane hands them: ONE control block
    (`scheduler.TickBlock`), replicated over the mesh and taken apart
    inside the program."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
        tp_shardings,
    )
    from tpu_engine.ops.paged_attention import (
        ragged_paged_attention,
        shard_over_heads,
    )
    from tpu_engine.parallel.mesh import tp_mesh
    from tpu_engine.runtime.scheduler import TickBlock

    _ensure_builtin_models_imported()
    spec = create_model("gpt2", n_layers=2)   # published widths, depth cut
    cfg = spec.config
    mesh = tp_mesh(2, v5e_devices)
    rows, nb, bs = 8, 513, 16
    # The bound a lane of 8 rows and a budget of 16 tokens states: the
    # pool write gathers the tick's 24 listed tokens out of 8 x 16 slots,
    # over K and V sharded by head.
    tick = _mixed_tick(cfg, shard_over_heads(
        functools.partial(ragged_paged_attention, interpret=False), mesh),
        max_tokens=16 + rows)

    param_shapes = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        param_shapes, tp_shardings(spec, param_shapes, mesh))
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, nb, bs, cfg.kv_heads * cfg.d_head), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, None, None, "model")))
    layout = TickBlock(16, [cfg.max_seq // bs])

    def step(params, caches, block):
        sent = layout.unpack(block)
        return tick(params, caches, sent["tables"][0], sent["tokens"],
                    sent["pos0"], sent["qlen"])

    block = jax.ShapeDtypeStruct((rows, layout.cols), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(step).lower(params, KVCache(pool, pool),
                                   block).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the tick copies no layer of the pool --------------------------------------

_POOL_MOVERS = re.compile(
    r"= \w+\[([\d,]+)\]\S* (copy|dynamic-slice|dynamic-update-slice)\(")


_CASTS = re.compile(r"= bf16\[([\d,]+)\]\S* convert\(")


def _cell_tick_shapes(v5e_devices, config, width):
    """(cfg, the master tree's shapes, `_mixed_tick`'s arguments) at a
    benchmark configuration's serving shapes (its rows, its pool), shapes
    only, placed on one v5e. The step is handed the tree the lane hands
    it (`spec.step_weights`)."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.runtime.kv_blocks import BlockPool

    with open(os.path.join(REPO, "benchmarks", "configs",
                           config + ".json")) as f:
        bench = json.load(f)
    serving = bench["serving"]
    assert width in (1, serving["gen_prefill_chunk"])
    _ensure_builtin_models_imported()
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg = spec.config
    rows, bs = serving["gen_max_batch_size"], serving["gen_kv_block_size"]
    on_chip = SingleDeviceSharding(v5e_devices[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)

    # The pool's shape is BlockPool's to state: ask it, at two blocks.
    block = BlockPool(cfg, 2, bs, jnp.bfloat16).caches.k
    pool = placed(jax.ShapeDtypeStruct(
        (block.shape[0], serving["gen_kv_blocks"]) + block.shape[2:],
        block.dtype))
    master = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    params = jax.tree.map(placed, jax.eval_shape(
        lambda p: spec.step_weights(p, jnp.bfloat16), master))

    def host(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    return cfg, master, (
        params, KVCache(pool, pool), host(rows, -(-cfg.max_seq // bs)),
        host(rows, width), host(rows), host(rows))


_CELL_TICKS = {}


def _compiled_cell_tick(v5e_devices, config, width):
    """(cfg, the master tree's shapes, the tick's arguments, the lane's
    bound on a tick's tokens, the step compiled for one v5e with the pool
    donated): `_cell_tick_shapes` under the bound the lane states (a
    budget of 256 plus a token a row), compiled once a (cell, width)."""
    from tpu_engine.ops.paged_attention import ragged_paged_attention

    if (config, width) not in _CELL_TICKS:
        cfg, master, args = _cell_tick_shapes(v5e_devices, config, width)
        bound = 256 + args[-1].shape[0]
        tick = _mixed_tick(
            cfg, functools.partial(ragged_paged_attention, interpret=False),
            max_tokens=bound)
        step, behind = _behind_a_step(tick, args[-1])
        _CELL_TICKS[config, width] = (
            cfg, master, args, bound,
            jax.jit(step, donate_argnums=(1,)).lower(
                *args, *behind).compile())
    return _CELL_TICKS[config, width]


@pytest.mark.parametrize("width", [1, 256])
@pytest.mark.parametrize("config", ["gpt2-large", "mistral-7b-v0.2-8l"])
def test_mixed_step_never_copies_the_pool(v5e_devices, config, width):
    """The mixed step at a benchmark configuration's serving shapes
    (its rows, its pool, shapes only), pool donated, compiled for one
    v5e: the optimized program holds no `copy`, `dynamic-slice` or
    `dynamic-update-slice` whose result is a layer of the pool or the
    whole pool — the pool is scattered into in place on the layer
    loop's carry and read by the kernel where it lies — and its
    temporaries stay below the pool's size. The parent of PR 26 held 17
    such copies and 7.18 GB of temporaries at gpt2-large's shapes. The
    step is handed the tree the lane hands it (`spec.step_weights`: the
    benchmark keeps float32 weights, the step reads their bfloat16 copy
    made once), so no kernel is cast inside it either: the parent of PR
    33 cast every stacked kernel every tick, for Mistral 3.5 GB of
    temporaries, more than the pool. Since PR 52 the lane states its
    bound on a tick's tokens (a budget of 256 plus a token a row) and a
    chunk tick's pool write takes that list: K and V are gathered at 288
    (272) listed tokens, whole rows of lanes, and the program holds no
    scatter over the pool flattened to (L x NB x bs, lanes), the form XLA
    gave the write of all rows x 256 slots."""
    cfg, master, args, bound, compiled = _compiled_cell_tick(
        v5e_devices, config, width)
    pool = args[1].k
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    lanes = f"{cfg.kv_heads * cfg.d_head}|{cfg.kv_heads},{cfg.d_head}"
    listed = re.search(rf"= bf16\[{bound},({lanes})\]\S* gather\(", hlo)
    assert (listed is not None) == (width > 1)
    assert f"[{math.prod(pool.shape[:3])},{pool.shape[3]}]" not in hlo
    whole = math.prod(pool.shape)
    moved = [(op, dims) for dims, op in _POOL_MOVERS.findall(hlo)
             if math.prod(map(int, dims.split(",")))
             in (whole, whole // cfg.n_layers)]
    assert not moved, moved
    kernels = {x.shape for x in jax.tree.leaves(master) if x.ndim >= 2}
    cast = [dims for dims in _CASTS.findall(hlo)
            if tuple(map(int, dims.split(","))) in kernels]
    assert not cast, cast
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * whole * pool.dtype.itemsize, temp


@pytest.mark.parametrize("config", ["gpt2-large", "mistral-7b-v0.2-8l"])
def test_chunk_step_feeds_forward_its_listed_tokens(v5e_devices, config):
    """Since PR 59 a chunk tick's feed-forward (gelu at gpt2-large's
    widths, SwiGLU at Mistral's) runs over the tick's token list, since
    PR 63 its output projection too: compiled for one v5e at the cell's
    serving shapes, the program's products with a `d_ff` side have 288
    (272) rows and none has rows x 256, `wo`'s product under `attn/out`
    has as many, and the gather of the residual's rows and their one
    scatter back are ops of the `attn/out` and `mlp` parts, so
    `step.attn_busy` and `step.ffn_busy` read them and
    `step.unscoped_busy` does not."""
    cfg, _, args, bound, compiled = _compiled_cell_tick(
        v5e_devices, config, 256)
    rows = args[-1].shape[0]
    hlo = compiled.as_text()
    shapes = {tuple(map(int, dims.split(",")))
              for dims in re.findall(r"= \w+\[([\d,]+)\]", hlo)}
    wide = {(rows, 256, cfg.d_ff), (rows * 256, cfg.d_ff)}
    # Mistral's 16 x 256 slots are as many as its d_model: a weight's shape.
    wide -= {(cfg.d_model, cfg.d_ff)}
    assert (bound, cfg.d_ff) in shapes and not wide & shapes
    out_products = set(re.findall(
        r"= \w+\[([\d,]+)\]\S* convolution\(.*"
        r'op_name="[^"]*/attn/out/dot_general"', hlo))
    assert out_products == {f"{bound},{cfg.d_model}"}, out_products
    paths = re.findall(
        rf"= \w+\[(?:{bound}|{rows * 256}),{cfg.d_model}\]\S* "
        r"(?:fusion|gather|scatter)\(.*"
        r'op_name="([^"]*/(?:gather|scatter))"', hlo)
    # Rows of d_model lanes are also what the embedding looks up and, at
    # gpt2-large's heads, what the pool write gathers of K and V.
    tails = {re.sub(r"^jit\(step\)/(while/body/closed_call/)?", "", p)
             for p in paths}
    assert {"attn/out/gather", "mlp/scatter"} <= tails <= {
        "attn/out/gather", "mlp/scatter", "attn/write/gather",
        "embed/gather"}, tails


_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition)=(%[\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")


def _computations(hlo):
    """{name: text} of an HLO module's computations, the entry's under
    "ENTRY"; and {name: names it calls}."""
    bodies, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?(%[\w.\-]+) \(.*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            bodies[name] = ""
        elif name is not None:
            bodies[name] += line + "\n"
    calls = {name: {c for one, many in _CALLED.findall(text)
                    for c in ([one] if one else many.split(", "))}
             for name, text in bodies.items()}
    return bodies, calls


def test_mixed_step_sorts_only_under_the_samplers_third_branch(v5e_devices):
    """The width-1 mixed step of gpt2-large WITH its sampling tail
    (`_sample` over the step's logits, `kept` = the live rows), compiled
    for one v5e: the optimized program holds ONE conditional of three
    branches and every `sort` lies under its third, so a tick in which no
    row filters runs none. The parent's step sorted 32 x 50257 logits in
    its entry computation every tick (2.2 ms of a 10.7 ms tick on the
    chip; at Moonlight's 163840, 7.2 ms)."""
    from tpu_engine.ops.paged_attention import ragged_paged_attention
    from tpu_engine.runtime.generator import _sample

    cfg, _master, args = _cell_tick_shapes(v5e_devices, "gpt2-large", 1)
    tick = _mixed_tick(
        cfg, functools.partial(ragged_paged_attention, interpret=False))
    rows = args[-1]

    def like(dtype):
        return jax.ShapeDtypeStruct(rows.shape, dtype,
                                    sharding=rows.sharding)

    def step(params, caches, tables, tokens, pos0, qlen, seeds, fold_pos,
             temps, topps, topks, minps, live):
        logits, caches = tick(params, caches, tables, tokens, pos0, qlen)
        return _sample(logits, seeds, fold_pos, temps, topps, topks, minps,
                       kept=live), caches

    hlo = jax.jit(step, donate_argnums=(1,)).lower(
        *args, rows, rows, like(jnp.float32), like(jnp.float32), rows,
        like(jnp.float32), like(jnp.bool_)).compile().as_text()
    bodies, calls = _computations(hlo)
    switches = re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}",
                          hlo)
    assert len(switches) == 1, switches
    branches = switches[0].split(", ")
    assert len(branches) == 3

    def under(name):
        seen, todo = set(), [name]
        while todo:
            one = todo.pop()
            if one not in seen:
                seen.add(one)
                todo += calls.get(one, ())
        return seen

    sorting = {name for name, text in bodies.items() if " sort(" in text}
    assert sorting and sorting <= under(branches[2]), sorting
    assert not sorting & (under(branches[0]) | under(branches[1]))
    assert "ENTRY" not in sorting


@pytest.mark.parametrize("width", [1, 256])
def test_latent_mixed_step_copies_neither_the_pool_nor_a_bank(v5e_devices,
                                                             width):
    """The Moonlight cell's mixed step at its serving shapes (shapes only),
    latent pool donated, compiled for one v5e: no `copy`, `slice`,
    `dynamic-slice` or `dynamic-update-slice` whose result is the pool, a
    layer of it, the stacked expert banks or one layer's bank (the grouped
    product takes the bank whole; a slice would be 1.2 GB a layer and
    tick), and temporaries of a few MB: the step runs over the tick's
    tokens (68 tiles of 8), not over 32 x 256 slots."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.moonlight import moonlight_step_rows_ragged
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.ops.latent_attention import latent_attention
    from tpu_engine.runtime.kv_blocks import BlockPool

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "moonlight-16b-a3b-7l.json")) as f:
        bench = json.load(f)
    serving = bench["serving"]
    assert width in (1, serving["gen_prefill_chunk"])
    _ensure_builtin_models_imported()
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg = spec.config
    rows, bs = serving["gen_max_batch_size"], serving["gen_kv_block_size"]
    on_chip = SingleDeviceSharding(v5e_devices[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)

    small = BlockPool(cfg, 2, bs, jnp.bfloat16).caches
    pool = KVCache(*(placed(jax.ShapeDtypeStruct(
        (x.shape[0], serving["gen_kv_blocks"]) + x.shape[2:], x.dtype))
        for x in small))
    params = jax.tree.map(placed,
                          jax.eval_shape(spec.init, jax.random.PRNGKey(0)))
    n_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert 8.5e9 < n_bytes < 8.6e9

    def tick(params, caches, tables, tokens, pos0, qlen):
        return moonlight_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            attn_fn=functools.partial(latent_attention, interpret=False),
            sample_slot=jnp.zeros_like(pos0),
            max_tokens=serving["gen_prefill_chunk"] + rows)

    def host(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    step, behind = _behind_a_step(tick, host(rows))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, host(rows, -(-cfg.max_seq // bs)), host(rows, width),
        host(rows), host(rows), *behind).compile()
    hlo = compiled.as_text()
    assert "mla_latent_read" in hlo and "ragged-dot" in hlo
    banks = jax.tree.leaves(params["moe"]["mlp"]["experts"])
    sizes = set()
    for x in list(pool) + banks:
        sizes |= {math.prod(x.shape), math.prod(x.shape[1:])}
    assert not _moved(hlo, sizes)
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6


@pytest.mark.parametrize("width", [1, 256])
def test_windowed_mixed_step_copies_no_pool_and_no_bank(v5e_devices, width):
    """The Laguna cell's mixed step at its serving shapes (shapes only),
    both pools donated, compiled for one v5e: the full layers' call and the
    window layers' (`swa_window_read`) and the grouped product are in it;
    no `copy`, `slice`, `dynamic-slice` or `dynamic-update-slice` whose
    result is a pool, a layer of one, or a layer's bank of held experts
    (1.6 GB); temporaries of tens of MB: the step runs over the tick's
    tokens (68 tiles of 8; since PR 45 the full layers' tall tiles beside
    them, 37 x 64 slots x 48 heads, 29 MB a copy: 85 MB in all, and
    `device.hbm_peak_gb` read 14.355 for 14.353 on the chip), and 11.15
    GB of weights with 2.6 GB of pools leave 2 GB of the chip."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.laguna import laguna_step_rows_ragged
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.ops.paged_attention import ragged_paged_attention

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna-s-2.1-5l.json")) as f:
        bench = json.load(f)
    serving = bench["serving"]
    assert width in (1, serving["gen_prefill_chunk"])
    _ensure_builtin_models_imported()
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg = spec.config
    rows, bs = serving["gen_max_batch_size"], serving["gen_kv_block_size"]
    on_chip = SingleDeviceSharding(v5e_devices[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)

    def pool(kind, blocks):
        one = placed(jax.ShapeDtypeStruct(
            (kind.n_layers, blocks, bs, kind.kv_lanes[0]), jnp.bfloat16))
        return KVCache(one, one)

    per_row = -(-(cfg.window + serving["gen_prefill_chunk"]) // bs) + 1
    pools = (pool(cfg.kv_block_kinds[0], serving["gen_kv_blocks"]),
             pool(cfg.kv_block_kinds[1], rows * per_row + 1))
    params = jax.tree.map(placed,
                          jax.eval_shape(spec.init, jax.random.PRNGKey(0)))

    def tick(params, caches, tables, tokens, pos0, qlen):
        return laguna_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            attn_fn=functools.partial(ragged_paged_attention,
                                      interpret=False),
            sample_slot=jnp.zeros_like(pos0),
            max_tokens=serving["gen_prefill_chunk"] + rows)

    def host(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    table = host(rows, -(-cfg.max_seq // bs))
    step, behind = _behind_a_step(tick, host(rows))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, (table, table), host(rows, width), host(rows),
        host(rows), *behind).compile()
    hlo = compiled.as_text()
    assert "swa_window_read" in hlo and "ragged-dot" in hlo
    assert "_paged_call" in hlo or "paged" in hlo
    banks = jax.tree.leaves(params["layers"][1]["mlp"]["experts"])
    sizes = {math.prod(x.shape) for x in banks}
    for x in pools:
        sizes |= {math.prod(x.k.shape), math.prod(x.k.shape[1:])}
    assert not _moved(hlo, sizes)
    assert compiled.memory_analysis().temp_size_in_bytes < 100e6


@pytest.mark.parametrize("width", [1, 256])
def test_early_route_mixed_step_copies_no_pool_and_no_bank(v5e_devices,
                                                           width):
    """The SmallThinker cell's mixed step at its serving shapes (shapes
    only), both pools donated, compiled for one v5e: at width 1 one packed
    call a layer (4 x 7 query rows a tile), at width 256 a second call a
    layer in tall tiles of 128 slots, the window layers' under
    `swa_window_read` (the lower bound handed to both classes), and the
    ReGLU banks' grouped product; no `copy`, `slice`, `dynamic-slice` or
    `dynamic-update-slice` whose result is a pool, a layer of one, or a
    layer's bank (377 MB and 189 MB); temporaries of tens of MB beside 7.9
    GB of weights and 3.5 GB of pools."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.ops.paged_attention import ragged_paged_attention

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "smallthinker-21b-a3b-8l.json")) as f:
        bench = json.load(f)
    serving = bench["serving"]
    assert width in (1, serving["gen_prefill_chunk"])
    _ensure_builtin_models_imported()
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg = spec.config
    rows, bs = serving["gen_max_batch_size"], serving["gen_kv_block_size"]
    on_chip = SingleDeviceSharding(v5e_devices[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)

    def pool(kind, blocks):
        one = placed(jax.ShapeDtypeStruct(
            (kind.n_layers, blocks, bs, kind.kv_lanes[0]), jnp.bfloat16))
        return KVCache(one, one)

    per_row = -(-(cfg.window + serving["gen_prefill_chunk"]) // bs) + 1
    pools = (pool(cfg.kv_block_kinds[0], serving["gen_kv_blocks"]),
             pool(cfg.kv_block_kinds[1], rows * per_row + 1))
    assert pools[1].k.shape == (6, 8737, 16, 512)
    params = jax.tree.map(placed,
                          jax.eval_shape(spec.init, jax.random.PRNGKey(0)))

    def tick(params, caches, tables, tokens, pos0, qlen):
        return spec.ragged_step(
            params, tokens, caches, tables, pos0, qlen, cfg,
            attn_fn=functools.partial(ragged_paged_attention,
                                      interpret=False),
            sample_slot=jnp.zeros_like(pos0),
            max_tokens=serving["gen_prefill_chunk"] + rows)

    def host(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    table = host(rows, -(-cfg.max_seq // bs))
    step, behind = _behind_a_step(tick, host(rows))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, (table, table), host(rows, width), host(rows),
        host(rows), *behind).compile()
    hlo = compiled.as_text()
    calls = (1 if width == 1 else 2) * cfg.n_layers
    assert len(re.findall(r"= \S+ custom-call\(.*tpu_custom_call", hlo)) \
        >= calls
    assert "swa_window_read" in hlo and "ragged-dot" in hlo
    banks = jax.tree.leaves(params["layers"][1]["mlp"]["experts"])
    sizes = {math.prod(x.shape) for x in banks}
    for x in pools:
        sizes |= {math.prod(x.k.shape), math.prod(x.k.shape[1:])}
    assert not _moved(hlo, sizes)
    assert compiled.memory_analysis().temp_size_in_bytes < 96e6


@pytest.mark.parametrize("width", [1, 256])
def test_hybrid_mixed_step_copies_neither_the_pool_nor_the_states(v5e_devices,
                                                                  width):
    """The Olmo-Hybrid cell's mixed step at its serving shapes (shapes
    only), the block pool and the state pool donated, compiled for one v5e:
    the full layers' paged call is in it (at 30 KV heads of 128 lanes its
    groups fit Mosaic's 16 MB: a 16-block group did not); no `copy`,
    `slice` or `dynamic-slice` whose result is a pool, a state array or a
    layer of one, and a `dynamic-update-slice` of that size only as a
    chunk row's write of its conv tail into its own state row (in place:
    the loop carries the array); both forms of the recurrence are Pallas
    calls that change the state pool where it lies (`gdn_step`,
    `gdn_chunk`); temporaries under 0.3 GB beside 13.4 GB of weights and
    pools."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.olmo_hybrid import olmo_hybrid_step_rows_ragged
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.ops.gated_delta import gdn_chunk_row, gdn_step_rows
    from tpu_engine.ops.paged_attention import ragged_paged_attention

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "olmo-hybrid-7b-12l.json")) as f:
        bench = json.load(f)
    serving = bench["serving"]
    assert width in (1, serving["gen_prefill_chunk"])
    _ensure_builtin_models_imported()
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg = spec.config
    rows, bs = serving["gen_max_batch_size"], serving["gen_kv_block_size"]
    on_chip = SingleDeviceSharding(v5e_devices[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)

    (kind,) = cfg.kv_block_kinds
    one = placed(jax.ShapeDtypeStruct(
        (kind.n_layers, serving["gen_kv_blocks"], bs, kind.kv_lanes[0]),
        jnp.bfloat16))
    pools = (KVCache(one, one),
             tuple(placed(jax.ShapeDtypeStruct(
                 (cfg.n_linear_layers, rows + 1) + shape, jnp.float32))
                 for shape in cfg.state_row_shapes))
    params = jax.tree.map(placed,
                          jax.eval_shape(spec.init, jax.random.PRNGKey(0)))

    def tick(params, caches, tables, tokens, pos0, qlen):
        return olmo_hybrid_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            attn_fn=functools.partial(ragged_paged_attention,
                                      interpret=False),
            step_fn=functools.partial(gdn_step_rows, interpret=False),
            chunk_fn=functools.partial(gdn_chunk_row, interpret=False),
            sample_slot=jnp.zeros_like(pos0),
            max_tokens=serving["gen_prefill_chunk"] + rows)

    def host(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    step, behind = _behind_a_step(tick, host(rows))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, (host(rows, -(-cfg.max_seq // bs)), host(rows)),
        host(rows, width), host(rows), host(rows), *behind).compile()
    hlo = compiled.as_text()
    assert "_paged_call" in hlo and "gdn_step" in hlo
    assert ("gdn_chunk" in hlo) == (width > 1)
    assert _input_products(hlo)                 # and none a copy
    sizes = set()
    for x in list(pools[0]) + list(pools[1]):
        sizes |= {math.prod(x.shape), math.prod(x.shape[1:])}
    movers = re.compile(r"= \w+\[([\d,]+)\]\S* "
                        r"(copy|slice|dynamic-slice|dynamic-update-slice)\(")
    moved = {op for dims, op in movers.findall(hlo)
             if math.prod(map(int, dims.split(","))) in sizes}
    assert moved <= ({"dynamic-update-slice"} if width > 1 else set()), moved
    analysis = compiled.memory_analysis()
    assert analysis.temp_size_in_bytes < 0.3e9
    assert analysis.alias_size_in_bytes > 6.8e9      # both pools in place


@pytest.mark.parametrize("width", [1, 256])
def test_latent_and_state_mixed_step_copies_no_pool_state_or_bank(v5e_devices,
                                                                  width):
    """The Kimi-Linear cell's mixed step at its serving shapes (shapes
    only: 128 rows, a latent pool of 90,113 blocks, a state pool of 129
    rows, 128 of 256 experts held), both pools donated, compiled for one
    v5e: the MLA layer's read is the latent kernel at 32 heads
    (`mla_latent_read`), both forms of the channel-gated recurrence are
    Pallas calls that change the state pool where it lies (`kda_step`,
    `kda_chunk`: at 128 key lanes a state is whole lane tiles) and the
    scalar gate's names are not in it; no `copy`, `slice` or
    `dynamic-slice` whose result is a pool, a state array, an expert bank
    or a layer of one, and a `dynamic-update-slice` of that size only as a
    chunk row's write of its conv tail into its own state row; the
    temporaries stay under 1 GB beside 11.6 GB of weights and pools."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.kimi_linear import kimi_linear_step_rows_ragged
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.ops.gated_delta import gdn_chunk_row, gdn_step_rows
    from tpu_engine.ops.latent_attention import latent_attention

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kimi-linear-48b-a3b-5l.json")) as f:
        bench = json.load(f)
    serving = bench["serving"]
    assert width in (1, serving["gen_prefill_chunk"])
    _ensure_builtin_models_imported()
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg = spec.config
    rows, bs = serving["gen_max_batch_size"], serving["gen_kv_block_size"]
    on_chip = SingleDeviceSharding(v5e_devices[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)

    (kind,) = cfg.kv_block_kinds
    assert (kind.n_layers, kind.kv_lanes) == (1, (128, 512))
    pools = (KVCache(*(placed(jax.ShapeDtypeStruct(
                 (kind.n_layers, serving["gen_kv_blocks"], bs, lanes),
                 jnp.bfloat16)) for lanes in kind.kv_lanes)),
             tuple(placed(jax.ShapeDtypeStruct(
                 (cfg.n_linear_layers, rows + 1) + shape, jnp.float32))
                 for shape in cfg.state_row_shapes))
    params = jax.tree.map(placed,
                          jax.eval_shape(spec.init, jax.random.PRNGKey(0)))

    def tick(params, caches, tables, tokens, pos0, qlen):
        return kimi_linear_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            attn_fn=functools.partial(latent_attention, interpret=False),
            step_fn=functools.partial(gdn_step_rows, interpret=False),
            chunk_fn=functools.partial(gdn_chunk_row, interpret=False),
            sample_slot=jnp.zeros_like(pos0), held=spec.held,
            max_tokens=serving["gen_prefill_chunk"] + rows)

    def host(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    step, behind = _behind_a_step(tick, host(rows))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, (host(rows, -(-cfg.max_seq // bs)), host(rows)),
        host(rows, width), host(rows), host(rows), *behind).compile()
    hlo = compiled.as_text()
    assert "mla_latent_read" in hlo and "kda_step" in hlo
    assert ("kda_chunk" in hlo) == (width > 1)
    assert '"gdn_step"' not in hlo and '"gdn_chunk"' not in hlo
    assert _input_products(hlo)                 # and none a copy
    # A bank whole; a pool or a state array whole or a layer of it (ONE
    # expert's 2304 x 2048 is as many numbers as 128 rows' conv tails).
    banks = [bp["mlp"]["experts"] for bp in params["layers"][1:]]
    sizes = {math.prod(x.shape) for x in jax.tree.leaves(banks)}
    for x in list(pools[0]) + list(pools[1]):
        sizes |= {math.prod(x.shape), math.prod(x.shape[1:])}
    movers = re.compile(r"= \w+\[([\d,]+)\]\S* "
                        r"(copy|slice|dynamic-slice|dynamic-update-slice)\(")
    moved = {op for dims, op in movers.findall(hlo)
             if math.prod(map(int, dims.split(","))) in sizes}
    assert moved <= ({"dynamic-update-slice"} if width > 1 else set()), moved
    analysis = compiled.memory_analysis()
    assert analysis.temp_size_in_bytes < 1.0e9
    assert analysis.alias_size_in_bytes > 2.9e9      # both pools in place


@pytest.mark.parametrize("width", [1, 256])
def test_two_mixers_a_layer_mixed_step_copies_neither_pool(v5e_devices, width):
    """The Falcon-H1 cell's mixed step at its serving shapes (shapes only:
    64 rows, six layers that each read a K/V chain at G = 5 AND step or
    chunk a 4.2 MB state, the 261,120-row head), both pools donated,
    compiled for one v5e: the paged calls at five query heads a KV head and
    both forms of the Mamba-2 recurrence are Pallas calls in it (`ssd_step`
    with the 16 heads of a group a block, `ssd_chunk`); no `copy`, `slice`
    or `dynamic-slice` whose result is a pool, a state array or a layer of
    one, a `dynamic-update-slice` of that size only as a chunk row's write
    of its conv tail into its own state row; both pools in place."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.falcon_h1 import falcon_h1_step_rows_ragged
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.ops.paged_attention import ragged_paged_attention
    from tpu_engine.ops.ssd import ssd_chunk_row, ssd_step_rows

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "falcon-h1-34b-6l.json")) as f:
        bench = json.load(f)
    serving = bench["serving"]
    assert width in (1, serving["gen_prefill_chunk"])
    _ensure_builtin_models_imported()
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg = spec.config
    assert cfg.n_heads // cfg.kv_heads == 5
    rows, bs = serving["gen_max_batch_size"], serving["gen_kv_block_size"]
    on_chip = SingleDeviceSharding(v5e_devices[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)

    (kind,) = cfg.kv_block_kinds
    one = placed(jax.ShapeDtypeStruct(
        (kind.n_layers, serving["gen_kv_blocks"], bs, kind.kv_lanes[0]),
        jnp.bfloat16))
    pools = (KVCache(one, one),
             tuple(placed(jax.ShapeDtypeStruct(
                 (cfg.n_linear_layers, rows + 1) + shape, jnp.float32))
                 for shape in cfg.state_row_shapes))
    params = jax.tree.map(placed,
                          jax.eval_shape(spec.init, jax.random.PRNGKey(0)))

    def tick(params, caches, tables, tokens, pos0, qlen):
        return falcon_h1_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            attn_fn=functools.partial(ragged_paged_attention,
                                      interpret=False),
            step_fn=functools.partial(ssd_step_rows, interpret=False),
            chunk_fn=functools.partial(ssd_chunk_row, interpret=False),
            sample_slot=jnp.zeros_like(pos0),
            max_tokens=serving["gen_prefill_chunk"] + rows)

    def host(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    step, behind = _behind_a_step(tick, host(rows))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, (host(rows, -(-cfg.max_seq // bs)), host(rows)),
        host(rows, width), host(rows), host(rows), *behind).compile()
    hlo = compiled.as_text()
    assert "_paged_call" in hlo and "ssd_step" in hlo
    assert ("ssd_chunk" in hlo) == (width > 1)
    w_in = params["layers"][0]["ssm"]["w_in"]["kernel"]
    assert w_in.shape[1] == 9248
    _one_product_a_layer(hlo, w_in.shape[1], cfg.n_linear_layers)
    sizes = set()
    for x in list(pools[0]) + list(pools[1]):
        sizes |= {math.prod(x.shape), math.prod(x.shape[1:])}
    movers = re.compile(r"= \w+\[([\d,]+)\]\S* "
                        r"(copy|slice|dynamic-slice|dynamic-update-slice)\(")
    moved = {op for dims, op in movers.findall(hlo)
             if math.prod(map(int, dims.split(","))) in sizes}
    assert moved <= ({"dynamic-update-slice"} if width > 1 else set()), moved
    analysis = compiled.memory_analysis()
    print("falcon_h1 step width", width, "temp bytes",
          analysis.temp_size_in_bytes, "alias", analysis.alias_size_in_bytes)
    assert analysis.temp_size_in_bytes < 0.6e9
    assert analysis.alias_size_in_bytes > 2.8e9      # both pools in place


@pytest.mark.parametrize("width", [1, 256])
def test_one_mixer_a_layer_mixed_step_copies_no_pool_state_or_bank(
        v5e_devices, width):
    """The Nemotron-H cell's mixed step at its serving shapes (shapes only:
    64 rows, eleven layers of three shapes: five step or chunk a 4.2 MB
    state of 128 heads of (64, 128), one reads a K/V chain at G = 16, five
    route 22 of 512 experts over a bank of 128 two-matrix experts in a
    1024-lane latent), both pools donated, compiled for one v5e: the paged
    calls and both forms of the recurrence are Pallas calls in it; no
    `copy`, `slice` or `dynamic-slice` whose result is a pool, a state
    array, an expert bank or a layer of one, a `dynamic-update-slice` of
    that size only as a chunk row's write of its conv tail into its own
    state row; both pools in place."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.nemotron_h import nemotron_h_step_rows_ragged
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.ops.paged_attention import ragged_paged_attention
    from tpu_engine.ops.ssd import ssd_chunk_row, ssd_step_rows

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron-3-super-120b-a12b-11l.json")) as f:
        bench = json.load(f)
    serving = bench["serving"]
    assert width in (1, serving["gen_prefill_chunk"])
    _ensure_builtin_models_imported()
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg = spec.config
    assert cfg.n_heads // cfg.kv_heads == 16
    assert (cfg.n_linear_layers, cfg.n_moe_layers, cfg.n_full_layers) == (
        5, 5, 1)
    rows, bs = serving["gen_max_batch_size"], serving["gen_kv_block_size"]
    on_chip = SingleDeviceSharding(v5e_devices[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)

    (kind,) = cfg.kv_block_kinds
    one = placed(jax.ShapeDtypeStruct(
        (kind.n_layers, serving["gen_kv_blocks"], bs, kind.kv_lanes[0]),
        jnp.bfloat16))
    pools = (KVCache(one, one),
             tuple(placed(jax.ShapeDtypeStruct(
                 (cfg.n_linear_layers, rows + 1) + shape, jnp.float32))
                 for shape in cfg.state_row_shapes))
    params = jax.tree.map(placed,
                          jax.eval_shape(spec.init, jax.random.PRNGKey(0)))

    def tick(params, caches, tables, tokens, pos0, qlen):
        return nemotron_h_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            attn_fn=functools.partial(ragged_paged_attention,
                                      interpret=False),
            step_fn=functools.partial(ssd_step_rows, interpret=False),
            chunk_fn=functools.partial(ssd_chunk_row, interpret=False),
            sample_slot=jnp.zeros_like(pos0), held=spec.held,
            max_tokens=serving["gen_prefill_chunk"] + rows)

    def host(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    step, behind = _behind_a_step(tick, host(rows))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, (host(rows, -(-cfg.max_seq // bs)), host(rows)),
        host(rows, width), host(rows), host(rows), *behind).compile()
    hlo = compiled.as_text()
    assert "_paged_call" in hlo and "ssd_step" in hlo
    assert ("ssd_chunk" in hlo) == (width > 1)
    assert _input_products(hlo)                 # and none a copy
    banks = [bp["mlp"]["experts"] for bp in params["layers"] if "mlp" in bp]
    assert len(banks) == 5
    sizes = {math.prod(x.shape) for x in jax.tree.leaves(banks)}
    for x in list(pools[0]) + list(pools[1]):
        sizes |= {math.prod(x.shape), math.prod(x.shape[1:])}
    movers = re.compile(r"= \w+\[([\d,]+)\]\S* "
                        r"(copy|slice|dynamic-slice|dynamic-update-slice)\(")
    moved = {op for dims, op in movers.findall(hlo)
             if math.prod(map(int, dims.split(","))) in sizes}
    assert moved <= ({"dynamic-update-slice"} if width > 1 else set()), moved
    analysis = compiled.memory_analysis()
    print("nemotron_h step width", width, "temp bytes",
          analysis.temp_size_in_bytes, "alias", analysis.alias_size_in_bytes)
    assert analysis.temp_size_in_bytes < 1.0e9
    assert analysis.alias_size_in_bytes > 1.9e9      # both pools in place


@pytest.mark.parametrize("width", [1, 256])
def test_conv_operator_mixed_step_copies_no_pool_tail_or_bank(v5e_devices,
                                                              width):
    """The LFM2 cell's mixed step at its serving shapes (shapes only: 128
    rows, nine layers: seven gated convs of three taps whose tails are the
    state pool's ONE array, two read a K/V chain at G = 4 over 8 KV heads of
    64 lanes, eight route 4 of 64 experts over banks held whole), both pools
    donated, compiled for one v5e: the paged calls are Pallas calls in it
    and NOTHING of the conv operator is (no kernel, no loop a chunk row: the
    only loops left are the grouped product's own); no `copy`, `slice`,
    `dynamic-slice` or `dynamic-update-slice` whose result is a pool, the
    tails' array, an expert bank or a layer of one; both pools in place."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.lfm2 import lfm2_step_rows_ragged
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.ops.paged_attention import ragged_paged_attention

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "lfm2-24b-a2b-9l.json")) as f:
        bench = json.load(f)
    serving = bench["serving"]
    assert width in (1, serving["gen_prefill_chunk"])
    _ensure_builtin_models_imported()
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg = spec.config
    assert (cfg.n_heads // cfg.kv_heads, cfg.d_head) == (4, 64)
    assert (cfg.n_linear_layers, cfg.n_moe_layers, cfg.n_full_layers) == (
        7, 8, 2)
    assert cfg.state_row_shapes == ((2, 2048),)
    rows, bs = serving["gen_max_batch_size"], serving["gen_kv_block_size"]
    on_chip = SingleDeviceSharding(v5e_devices[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)

    (kind,) = cfg.kv_block_kinds
    one = placed(jax.ShapeDtypeStruct(
        (kind.n_layers, serving["gen_kv_blocks"], bs, kind.kv_lanes[0]),
        jnp.bfloat16))
    pools = (KVCache(one, one),
             tuple(placed(jax.ShapeDtypeStruct(
                 (cfg.n_linear_layers, rows + 1) + shape, jnp.float32))
                 for shape in cfg.state_row_shapes))
    params = jax.tree.map(placed,
                          jax.eval_shape(spec.init, jax.random.PRNGKey(0)))

    def tick(params, caches, tables, tokens, pos0, qlen):
        return lfm2_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            attn_fn=functools.partial(ragged_paged_attention,
                                      interpret=False),
            sample_slot=jnp.zeros_like(pos0), held=spec.held,
            max_tokens=serving["gen_prefill_chunk"] + rows)

    def host(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    step, behind = _behind_a_step(tick, host(rows))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, (host(rows, -(-cfg.max_seq // bs)), host(rows)),
        host(rows, width), host(rows), host(rows), *behind).compile()
    hlo = compiled.as_text()
    assert "_paged_call" in hlo
    assert _input_products(hlo)                 # and none a copy
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", hlo)) == 16
    banks = [bp["mlp"]["experts"] for bp in params["layers"]
             if "experts" in bp["mlp"]]
    assert len(banks) == 8
    sizes = {math.prod(x.shape) for x in jax.tree.leaves(banks)}
    for x in list(pools[0]) + list(pools[1]):
        sizes |= {math.prod(x.shape), math.prod(x.shape[1:])}
    movers = re.compile(r"= \w+\[([\d,]+)\]\S* "
                        r"(copy|slice|dynamic-slice|dynamic-update-slice)\(")
    moved = {op for dims, op in movers.findall(hlo)
             if math.prod(map(int, dims.split(","))) in sizes}
    assert not moved, moved
    analysis = compiled.memory_analysis()
    print("lfm2 step width", width, "temp bytes",
          analysis.temp_size_in_bytes, "alias", analysis.alias_size_in_bytes)
    assert analysis.temp_size_in_bytes < 1.0e9
    assert analysis.alias_size_in_bytes > 2.6e9      # both pools in place


@pytest.mark.parametrize("width", [1, 256])
def test_mixer_and_experts_a_layer_mixed_step_copies_no_pool_state_or_bank(
        v5e_devices, width):
    """The Granite-4.0-H cell's mixed step at its serving shapes (shapes
    only: 64 rows, ten layers of two shapes: nine step or chunk a 4.2 MB
    state of 128 heads of (64, 128) at ONE group, one reads a K/V chain at
    G = 4 over 8 KV heads of 128 lanes, and EVERY one then routes 10 of 72
    experts over a bank of 36 held SwiGLU experts of 768 lanes beside a
    shared one), both pools donated, compiled for one v5e: the paged calls
    and both forms of the recurrence are Pallas calls in it, twenty grouped
    products; no `copy`, `slice` or `dynamic-slice` whose result is a pool,
    a state array, an expert bank or a layer of one, a
    `dynamic-update-slice` of that size only as a chunk row's write of its
    conv tail into its own state row; both pools in place."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.granite_hybrid import (
        granite_hybrid_step_rows_ragged,
    )
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.ops.paged_attention import ragged_paged_attention
    from tpu_engine.ops.ssd import ssd_chunk_row, ssd_step_rows

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "granite-4.0-h-small-10l.json")) as f:
        bench = json.load(f)
    serving = bench["serving"]
    assert width in (1, serving["gen_prefill_chunk"])
    _ensure_builtin_models_imported()
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg = spec.config
    assert (cfg.n_heads // cfg.kv_heads, cfg.d_head) == (4, 128)
    assert (cfg.n_linear_layers, cfg.n_moe_layers, cfg.n_full_layers) == (
        9, 10, 1)
    assert cfg.state_row_shapes == ((128, 64, 128), (8, 3168))
    rows, bs = serving["gen_max_batch_size"], serving["gen_kv_block_size"]
    on_chip = SingleDeviceSharding(v5e_devices[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)

    (kind,) = cfg.kv_block_kinds
    one = placed(jax.ShapeDtypeStruct(
        (kind.n_layers, serving["gen_kv_blocks"], bs, kind.kv_lanes[0]),
        jnp.bfloat16))
    pools = (KVCache(one, one),
             tuple(placed(jax.ShapeDtypeStruct(
                 (cfg.n_linear_layers, rows + 1) + shape, jnp.float32))
                 for shape in cfg.state_row_shapes))
    params = jax.tree.map(placed,
                          jax.eval_shape(spec.init, jax.random.PRNGKey(0)))

    def tick(params, caches, tables, tokens, pos0, qlen):
        return granite_hybrid_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            attn_fn=functools.partial(ragged_paged_attention,
                                      interpret=False),
            step_fn=functools.partial(ssd_step_rows, interpret=False),
            chunk_fn=functools.partial(ssd_chunk_row, interpret=False),
            sample_slot=jnp.zeros_like(pos0), held=spec.held,
            max_tokens=serving["gen_prefill_chunk"] + rows)

    def host(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    step, behind = _behind_a_step(tick, host(rows))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, (host(rows, -(-cfg.max_seq // bs)), host(rows)),
        host(rows, width), host(rows), host(rows), *behind).compile()
    hlo = compiled.as_text()
    assert "_paged_call" in hlo and "ssd_step" in hlo
    assert ("ssd_chunk" in hlo) == (width > 1)
    w_in = params["layers"][0]["ssm"]["w_in"]["kernel"]
    assert w_in.shape[1] == 16768
    _one_product_a_layer(hlo, w_in.shape[1], cfg.n_linear_layers)
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", hlo)) == 20
    banks = [bp["mlp"]["experts"] for bp in params["layers"]]
    assert len(banks) == 10
    sizes = {math.prod(x.shape) for x in jax.tree.leaves(banks)}
    for x in list(pools[0]) + list(pools[1]):
        sizes |= {math.prod(x.shape), math.prod(x.shape[1:])}
    movers = re.compile(r"= \w+\[([\d,]+)\]\S* "
                        r"(copy|slice|dynamic-slice|dynamic-update-slice)\(")
    moved = {op for dims, op in movers.findall(hlo)
             if math.prod(map(int, dims.split(","))) in sizes}
    assert moved <= ({"dynamic-update-slice"} if width > 1 else set()), moved
    analysis = compiled.memory_analysis()
    print("granite_hybrid step width", width, "temp bytes",
          analysis.temp_size_in_bytes, "alias", analysis.alias_size_in_bytes)
    assert analysis.temp_size_in_bytes < 1.0e9
    assert analysis.alias_size_in_bytes > 3.8e9      # both pools in place


@pytest.mark.parametrize("name,q_lens,width,grid", [
    # A tick of runs alone: 64 rows x 4 slots x 8 heads = 32 query rows a
    # KV head, the 4 heads packed into one 128-row score tile a row.
    ("runs", (4,) * 64, 4, (64, 1)),
    # A tall tile of a prompt chunk: 16 slots x 8 heads = one 128-row tile.
    ("tall", (16,) * 32, 16, (32, 1)),
])
def test_the_block_mask_read_compiles_for_v5e_in_both_classes(
        v5e_devices, name, q_lens, width, grid):
    """The paged kernel under the block-causal mask (`mask_block` 4) at the
    SDAR cell's shapes (G = 8: 32 query heads over 4 KV heads of 128 lanes,
    blocks of 16, a table of 256 entries) compiles for a v5e in both
    classes of tile, under its own name, with the query tiles its grid."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.ops import paged_attention as pa

    del name
    on_chip = SingleDeviceSharding(v5e_devices[0])
    shapes = jax.eval_shape(lambda: pa.parity_workload(
        "ragged", q_lens, n_heads=32, n_kv_heads=4, d_head=128,
        block_size=16, n_blocks=9217, table_len=256,
        dtype=jnp.bfloat16)[0])
    assert shapes[0].shape == (len(q_lens), width, 32, 128)
    placed = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)
              for x in shapes]

    def read(*operands):
        return pa.ragged_paged_attention(*operands, mask_block=4,
                                         interpret=False)

    hlo = jax.jit(read).lower(*placed).compile().as_text()
    assert "block_mask_read" in hlo
    assert _pallas_grids(jax.make_jaxpr(read)(*shapes).jaxpr) == [grid]


@pytest.mark.parametrize("width", [4, 256])
def test_block_decode_mixed_step_copies_no_pool_and_no_bank(v5e_devices,
                                                           width):
    """The SDAR cell's mixed step at its serving shapes (shapes only: 64
    rows, seven layers of GQA 32 / 4 under the block mask and 128 whole
    experts top 8 of (2048, 768); a run of 4 tokens a generating row, a
    chunk of 256), with the L-position head, its confidences and the
    reveal, the pool donated, compiled for one v5e: the block-mask reads
    are Pallas calls in it (one call a layer without a chunk, two with);
    no `copy`, `slice` or `dynamic-slice` whose result is the pool, an
    expert bank or a layer of one; the pool in place; the grouped products
    carry the tiles `ops.moe.grouped_tiling` states."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.models.sdar import sdar_step_rows_ragged
    from tpu_engine.ops.paged_attention import ragged_paged_attention
    from tpu_engine.runtime.generator import reveal_block, sample_block
    from tpu_engine.runtime.scheduler import take_block_from_prev

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sdar-30b-a3b-chat-7l.json")) as f:
        bench = json.load(f)
    serving = bench["serving"]
    _ensure_builtin_models_imported()
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg, block = spec.config, spec.block_decode
    run = block.block_length
    assert width in (run, serving["gen_prefill_chunk"])
    assert cfg.n_heads // cfg.kv_heads == 8 and cfg.n_moe_layers == 7
    rows, bs = serving["gen_max_batch_size"], serving["gen_kv_block_size"]
    bound = run + max(serving["gen_mixed_token_budget"], rows * run)
    on_chip = SingleDeviceSharding(v5e_devices[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)

    one = placed(jax.ShapeDtypeStruct(
        (cfg.n_layers, serving["gen_kv_blocks"], bs, cfg.kv_lanes[0]),
        jnp.bfloat16))
    params = jax.tree.map(placed,
                          jax.eval_shape(spec.init, jax.random.PRNGKey(0)))

    def host(*shape, dtype=jnp.int32):
        return placed(jax.ShapeDtypeStruct(shape, dtype))

    def step(params, caches, tables, tokens, pos0, qlen, done, live, count,
             seeds, temps, prev_blk, prev_done, from_prev):
        tokens, blk, done = take_block_from_prev(
            tokens, done, prev_blk, prev_done, from_prev, run, block.mask_id)
        logits, caches, moe_rows = sdar_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            attn_fn=functools.partial(ragged_paged_attention,
                                      interpret=False),
            sample_slot=jnp.broadcast_to(jnp.arange(run)[None], blk.shape),
            max_tokens=bound)
        x0, conf = sample_block(logits, seeds, pos0, temps, temps,
                                seeds, temps, live)
        return (caches, reveal_block(blk, x0, conf, count, block.reveal,
                                     block.threshold), done, moe_rows)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, KVCache(one, one), host(rows, -(-cfg.max_seq // bs)),
        host(rows, width), host(rows), host(rows),
        host(rows, dtype=jnp.bool_), host(rows, dtype=jnp.bool_), host(rows),
        host(rows), host(rows, dtype=jnp.float32), host(rows, run),
        host(rows, dtype=jnp.bool_), host(rows, dtype=jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert "block_mask_read" in hlo and "ragged_dot_tiling" in hlo
    banks = [bp["mlp"]["experts"] for bp in params["layers"]]
    sizes = {math.prod(x.shape) for x in jax.tree.leaves(banks)}
    sizes |= {math.prod(one.shape), math.prod(one.shape[1:])}
    movers = re.compile(r"= \w+\[([\d,]+)\]\S* "
                        r"(copy|slice|dynamic-slice|dynamic-update-slice)\(")
    moved = {op for dims, op in movers.findall(hlo)
             if math.prod(map(int, dims.split(","))) in sizes}
    assert not moved, moved
    analysis = compiled.memory_analysis()
    print("sdar step width", width, "temp bytes",
          analysis.temp_size_in_bytes, "alias", analysis.alias_size_in_bytes)
    assert analysis.temp_size_in_bytes < 1.0e9
    assert analysis.alias_size_in_bytes > 2.0e9      # the pool in place


@pytest.mark.parametrize("name,q_lens,width,grid", [
    # A decode tick: 8 rows, one query row a head, the 16 heads packed.
    ("short", (1,) * 8, 1, (8, 1)),
    # A tall tile of a prompt chunk: 128 slots x 1 head = one 128-row tile.
    ("tall", (128,) * 10, 128, (10, 1)),
])
def test_the_looped_read_and_write_compile_for_v5e_under_a_traced_plane(
        v5e_devices, name, q_lens, width, grid):
    """The pool write and the paged read at the Ouro cell's geometry (G = 1,
    16 KV heads of 128 lanes, blocks of 16, a table of 40 entries) over its
    pool of 192 planes x 321 blocks (2.02 G elements a tensor, under 2^31)
    with the plane index an OPERAND, as the scanned layer body hands it:
    both classes of tile compile for a v5e with the query tiles their grid,
    and the write updates the pool where it lies."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.transformer import _write_pool
    from tpu_engine.ops import paged_attention as pa

    del name
    on_chip = SingleDeviceSharding(v5e_devices[0])
    shapes = jax.eval_shape(lambda: pa.parity_workload(
        "ragged", q_lens, n_heads=16, n_kv_heads=16, d_head=128,
        block_size=16, n_blocks=321, table_len=40, dtype=jnp.bfloat16)[0])
    q, k_pool, v_pool, _, tables, pos0, qlen = shapes
    assert q.shape == (len(q_lens), width, 16, 128)
    planes = jax.ShapeDtypeStruct((192,) + k_pool.shape[1:], k_pool.dtype)
    assert math.prod(planes.shape) == 192 * 321 * 16 * 2048 < 2 ** 31
    plane = jax.ShapeDtypeStruct((), jnp.int32)
    new = jax.ShapeDtypeStruct((len(q_lens) * width, 16, 128), jnp.bfloat16)
    at = jax.ShapeDtypeStruct((len(q_lens) * width,), jnp.int32)

    def write_then_read(q, k_pool, v_pool, plane, tables, pos0, qlen, k, v,
                        blk, off):
        pool = _write_pool((k_pool, v_pool), plane, blk, off, k, v)
        return pa.ragged_paged_attention(q, *pool, plane, tables, pos0, qlen,
                                         interpret=False), pool

    operands = (q, planes, planes, plane, tables, pos0, qlen, new, new, at,
                at)
    compiled = jax.jit(write_then_read, donate_argnums=(1, 2)).lower(*(
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)
        for x in operands)).compile()
    assert "_paged_call" in compiled.as_text()
    assert not _moved(compiled.as_text(), {math.prod(planes.shape),
                                           math.prod(planes.shape[1:])})
    assert compiled.memory_analysis().alias_size_in_bytes > 8.0e9
    assert _pallas_grids(jax.make_jaxpr(write_then_read)(
        *operands).jaxpr) == [grid]


@pytest.mark.parametrize("width", [1, 256])
def test_looped_mixed_step_holds_one_layer_body_and_copies_no_pool(
        v5e_devices, width):
    """The Ouro cell's mixed step at its serving shapes (shapes only), the
    pool of 192 planes donated, compiled for one v5e: 48 layers x 4 passes
    are ONE layer body under two loops (a paged call a class of tile, seven
    products and the head: a Python loop would hold 192 of each); no
    `copy`, `slice`, `dynamic-slice` or `dynamic-update-slice` whose result
    is the pool or a plane of it; temporaries under 0.1 GB beside 13.4 GB
    of weights and pool."""
    from jax.sharding import SingleDeviceSharding

    from tpu_engine.models.ouro import ouro_step_rows_ragged
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.ops.paged_attention import ragged_paged_attention

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "ouro-2.6b.json")) as f:
        bench = json.load(f)
    serving = bench["serving"]
    assert width in (1, serving["gen_prefill_chunk"])
    _ensure_builtin_models_imported()
    spec = create_model(bench["factory"], **bench["kwargs"])
    cfg = spec.config
    rows, bs = serving["gen_max_batch_size"], serving["gen_kv_block_size"]
    on_chip = SingleDeviceSharding(v5e_devices[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip)

    (kind,) = cfg.kv_block_kinds
    one = placed(jax.ShapeDtypeStruct(
        (kind.n_layers, serving["gen_kv_blocks"], bs, kind.kv_lanes[0]),
        jnp.bfloat16))
    assert one.shape == (192, 321, 16, 2048)
    pool = KVCache(one, one)
    params = jax.tree.map(placed,
                          jax.eval_shape(spec.init, jax.random.PRNGKey(0)))

    def tick(params, caches, tables, tokens, pos0, qlen):
        return ouro_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            attn_fn=functools.partial(ragged_paged_attention,
                                      interpret=False),
            sample_slot=jnp.zeros_like(pos0),
            max_tokens=serving["gen_prefill_chunk"] + rows)

    def host(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    step, behind = _behind_a_step(tick, host(rows))
    lowered = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, host(rows, -(-cfg.max_seq // bs)), host(rows, width),
        host(rows), host(rows), *behind)
    text = lowered.as_text()
    assert text.count("stablehlo.dot_general") == 8
    assert text.count("tpu_custom_call") == (1 if width == 1 else 2)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert "_paged_call" in hlo
    assert not _moved(hlo, {math.prod(one.shape),
                            math.prod(one.shape[1:])})
    analysis = compiled.memory_analysis()
    assert analysis.temp_size_in_bytes < 0.1e9
    assert analysis.alias_size_in_bytes > 8.0e9      # the pool in place


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, no code names a cache
    directory (JAX reads the variable itself); unset, the directory is
    <checkout>/.jax_cache — a fixed path, never ~, a temp name, a pid or
    a time — exported so children take the first branch."""
    from tpu_engine.utils import checkpoint

    updates = []
    monkeypatch.setattr(checkpoint.jax.config, "update",
                        lambda name, value: updates.append((name, value)))

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert checkpoint.enable_compilation_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in dict(updates)

    updates.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    expected = os.path.join(REPO, ".jax_cache")
    assert checkpoint.enable_compilation_cache() == expected
    assert dict(updates)["jax_compilation_cache_dir"] == expected
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == expected


def test_chip_smoke_parent_is_stdlib_only():
    """One process per chip: chip_smoke.py's parent must never import jax
    (or anything under tpu_engine, which does) — a parent that touched
    JAX would hold the chip its serving children need."""
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, "relative import in chip_smoke.py"
            imported.add(node.module.split(".")[0])
        elif isinstance(node, ast.Call):
            # No side door: __import__ / importlib.import_module.
            fn = node.func
            name = getattr(fn, "id", None) or getattr(fn, "attr", None)
            assert name not in ("__import__", "import_module"), name
    assert imported, "chip_smoke.py imports nothing?"
    assert imported <= sys.stdlib_module_names, sorted(
        imported - sys.stdlib_module_names)


def test_serve_refuses_to_start_without_a_tpu(monkeypatch):
    """The entry point decides the platform once: without
    TPU_ENGINE_PLATFORM=cpu, a backend that is not a TPU (JAX's own
    silent drop to the CPU) is a failed start that names the missing
    TPU — never a serving process."""
    from tpu_engine.serving import cli

    monkeypatch.delenv("TPU_ENGINE_PLATFORM", raising=False)
    with pytest.raises(SystemExit) as exc:
        cli.main(["serve", "--model", "mlp", "--lanes", "1"])
    assert "TPU" in str(exc.value) and "TPU_ENGINE_PLATFORM=cpu" in str(
        exc.value)


def test_failed_warmup_is_a_failed_start(monkeypatch):
    """A warm-up that raises (on the chip: a kernel Mosaic rejects at the
    first tick's trace) must fail `serve`, not print "skipped" and come
    up ready with a generation lane that can never answer."""
    from tpu_engine.ops import paged_attention
    from tpu_engine.serving.app import serve_combined
    from tpu_engine.utils.config import WorkerConfig

    def rejected(*_args, **_kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel (forced)")

    monkeypatch.setattr(paged_attention, "default_ragged_attention",
                        lambda: rejected)
    cfg = WorkerConfig(gen_kv_block_size=16,
                       gen_prefill_chunk=16, batch_buckets=(1,),
                       max_batch_size=1)
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        serve_combined(model="gpt2-small-test", lanes=1, port=0,
                       worker_config=cfg, warmup=True, native_front=False)
