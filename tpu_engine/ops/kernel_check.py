"""Every Pallas kernel site at the geometry of the models the registry serves.

One list of cases (`kernel_cases`) covers the flash kernel (forward at
every prompt bucket, backward) and the paged read paths (ragged at
q_len 1 and at the prefill-chunk width, and the int8 variant of each) at the
head geometry of a registered model; for a latent-attention model
(`LATENT_MODELS`) it is the latent read at both widths instead, and for
one whose rows also own a recurrent state (`RECURRENT_MODELS`) both forms
of its recurrence (`gdn_step`, `gdn_chunk`; `kda_step`, `kda_chunk`;
`ssd_step`, `ssd_chunk`), against the scan, the step also at the share of
live rows its cell's ticks hold and with none (`STEP_LIVE`);
`grouped_cases` the routed experts' grouped product over the banks
the benchmark's cells hold (`GROUPED_SHAPES`).
`cell_cases` adds the ragged read at the shapes the benchmark's cells
serve it at (`CELL_SHAPES`), `class_cases` the two calls a tick that
carries a chunk makes of its rows, by the class of their runs
(`CLASS_SHAPES`), `walk_cases` the small workloads the paged walk's
pipeline can get wrong. Two consumers:

- `python -m tpu_engine.ops.kernel_check` — chip_smoke.py's kernel
  phase: on the attached TPU, compile every case with `interpret=False`
  and compare it with its XLA reference (`dot_product_attention`, the
  `*_reference` gathers) run on f32 copies of the same operands at the
  highest matmul precision. A miss, a compile error or a non-TPU backend
  exits non-zero. With names after it, `... kernel_check smallthinker
  several-groups`, only the cases whose name holds one of them run: a PR
  checks the cases it adds in minutes, where the whole list no longer
  fits a cold chip call (ROADMAP A8).
- tests/test_kernels_tpu_compile.py — AOT-compiles the same cases for a
  v5e topology from this sandbox, no chip attached
  (`compile_for_topology`). That is also the recipe for kernel work:
  Mosaic's errors can be iterated on without a chip call.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import json
import sys
import time
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_engine.ops import latent_attention as la
from tpu_engine.ops import paged_attention as pa
from tpu_engine.ops.attention import dot_product_attention
from tpu_engine.ops.flash import flash_attention

# The models whose geometry the checks run at: gpt2 is the full-MHA
# serving model chip_smoke.py launches; llama is the grouped case
# (32 query heads over 4 KV heads).
MODELS = ("gpt2", "llama")
# A latent pool: one kernel, the absorbed read (16 heads, and 32 in a model
# that rotates nothing and whose other layers are recurrent).
LATENT_MODELS = ("moonlight", "kimi_linear")
# A state row beside the blocks: the step over a lane's rows and the
# chunked form over a row's run, where the states lie. kimi_linear: the
# delta rule with a gate a key channel's; falcon_h1: Mamba-2 (its paged read
# at five query heads a KV head is a case of `CLASS_SHAPES`); nemotron_h:
# Mamba-2 cut the other way, 128 heads of (64, 128) in 8 groups (its read at
# sixteen query heads a KV head a case of `CLASS_SHAPES`, its experts'
# two-matrix grouped product `grouped_cases`); granite_hybrid: the same 128
# heads of (64, 128) with B and C ONE group that every head reads (a block of
# the step is 64 heads, 2 MB, and both blocks of a row read group 0).
RECURRENT_MODELS = ("kimi_linear", "falcon_h1", "nemotron_h", "olmo_hybrid",
                    "granite_hybrid")
# The step over a lane's slots at the live rows a tick of the model's cell
# holds beside the first case's (one dead row in 40 or 50: converse's mix):
# reason's chunk ticks, agents' every tick, digest's, sessions'. A dead row's
# blocks are never named (`ops.gated_delta.step_at`); with no live row the
# pool comes back as it was, the null row too.
STEP_LIVE = {"kimi_linear": (69, 0), "falcon_h1": (0,), "nemotron_h": (40, 0),
             "olmo_hybrid": (14, 0), "granite_hybrid": (63, 0)}
# Max |kernel - scan| accepted for the recurrence: float32 throughout, the
# MXU's float32 passes.
F32_TOLERANCE = 1e-3
# And for `ssd_chunk`, whose outputs here reach |y| = 40-50 (C is drawn at
# unit spread over 128 or 256 state lanes): at 128 heads of (64, 128) the
# chip read 0.74e-3 to 1.08e-3 over eight draws of these operands, at 8
# groups and at 1 alike, 2-4e-5 of the value where it lies (PERF.md section
# 6, PR 64: the accepted case's 9.6e-4 was one draw under 1e-3).
SSD_CHUNK_TOLERANCE = 2e-3
# Serving shapes: the smoke's launch (--kv-block-size 16, 8 decode slots,
# max_seq 1024 -> 64-block tables over the auto-sized 513-block pool,
# --gen-prefill-chunk 256) and the scheduler's prompt buckets.
BLOCK_SIZE = 16
ROWS = 8
TABLE_LEN = 64
N_BLOCKS = ROWS * TABLE_LEN + 1
CHUNK = 256
ROWS_RECURRENT = 128     # a lane whose rows' states are small: its slots
ROWS_SSD = 64            # and one whose rows' states are 4.2 MB a layer
ROWS_GDN = 16            # and one whose rows read contexts of 64 k tokens
FLASH_BUCKETS = (16, 32, 64, 128, 256, 512, 1024)
FLASH_BACKWARD_SEQ = 256
# Max |kernel - reference| accepted for bf16 operands (and for the int8
# pools, whose queries are bf16): the kernels keep f32 accumulators but
# round P to the MXU dtype before the PV matmul and the output to bf16 —
# 2^-8 relative on values of order 1.
BF16_TOLERANCE = 2e-2


# The ragged read as the benchmark's cells call it (benchmarks/configs/
# gpt2-large.json, mistral-7b-v0.2-8l.json; stated here, the package does
# not read the benchmark): heads, rows = slots, the table's width
# (max_seq / 16), the pool's blocks, and one tick's rows as (q_len, pos0).
# batch: 32 decode rows of 40-320 columns. docqa: a 256-token chunk whose
# context ends on a group boundary beside four decode rows of 0.6-1.6 k
# columns and eleven free slots, under a table of 2048 columns.
CELL_SHAPES = {
    "gpt2-large.batch/ragged/W1": dict(
        geo=dict(n_heads=20, n_kv_heads=20, d_head=64), table_len=64,
        n_blocks=1537, rows=tuple((1, 39 + 9 * r) for r in range(32))),
    "mistral-7b-v0.2-8l.docqa/ragged/W256": dict(
        geo=dict(n_heads=32, n_kv_heads=8, d_head=128), table_len=2048,
        n_blocks=2817,
        rows=((256, 1024), (1, 600), (1, 1023), (1, 1290), (1, 1567))
        + ((0, 0),) * 11),
    # repo (benchmarks/configs/laguna-s-2.1-5l.json): G = 6 on full layers
    # and G = 9 on window layers (`window` 512: the walk's lower bound),
    # under a table of 16384 columns. Width 1: 32 decode rows of 0.3-8 k
    # columns, heads packed. Width 8: a TILE of the step's token list is a
    # row of the call; eight tiles of one chunk beside decode rows' tiles
    # and dead ones.
    "laguna-s-2.1-5l.repo/full/W1": dict(
        geo=dict(n_heads=48, n_kv_heads=8, d_head=128), table_len=1024,
        n_blocks=2049, rows=tuple((1, 300 + 250 * r) for r in range(32))),
    "laguna-s-2.1-5l.repo/window/W1": dict(
        geo=dict(n_heads=72, n_kv_heads=8, d_head=128), table_len=1024,
        n_blocks=2049, window=512,
        rows=tuple((1, 300 + 250 * r) for r in range(32))),
    "laguna-s-2.1-5l.repo/full/W8": dict(
        geo=dict(n_heads=48, n_kv_heads=8, d_head=128), table_len=1024,
        n_blocks=2049,
        rows=tuple((8, 4096 + 8 * t) for t in range(8))
        + ((1, 700), (1, 5000), (5, 0), (0, 0))),
    "laguna-s-2.1-5l.repo/window/W8": dict(
        geo=dict(n_heads=72, n_kv_heads=8, d_head=128), table_len=1024,
        n_blocks=2049, window=512,
        rows=tuple((8, 4096 + 8 * t) for t in range(8))
        + ((1, 700), (1, 5000), (5, 0), (0, 0))),
    # history (benchmarks/configs/smallthinker-21b-a3b-8l.json): G = 7 over
    # 4 KV heads, a window of 4096 (the walk's lower bound up to nine
    # thousand columns in), under a table of 16384 columns. Width 1: 32
    # decode rows of 2-13 k columns, the 4 x 7 query rows packed; a row in
    # eight is short of the window. (The tall tile: `CLASS_SHAPES`.)
    "smallthinker-21b-a3b-8l.history/window/W1": dict(
        geo=dict(n_heads=28, n_kv_heads=4, d_head=128), table_len=1024,
        n_blocks=8737, window=4096,
        rows=tuple((1, 2000 + 355 * r) for r in range(32))),
    # digest (benchmarks/configs/olmo-hybrid-7b-12l.json): the full layers
    # of a model whose other layers are recurrent, G = 1 at head size 128,
    # 30 KV heads (3840 lanes a token: the walk's groups are 8 blocks).
    # Width 1: 16 decode rows of 0.6-8.4 k columns, the 30 heads packed.
    # Width 256: one row's chunk, two tiles, beside decode rows and a dead
    # one: a ROW of the step is a row of the call (contexts to 2.3 k: the
    # check's gather reference holds every row's scores at once).
    "olmo-hybrid-7b-12l.digest/W1": dict(
        geo=dict(n_heads=30, n_kv_heads=30, d_head=128), table_len=1024,
        n_blocks=1537, rows=tuple((1, 600 + 520 * r) for r in range(16))),
    "olmo-hybrid-7b-12l.digest/W256": dict(
        geo=dict(n_heads=30, n_kv_heads=30, d_head=128), table_len=1024,
        n_blocks=1537,
        rows=((256, 2048), (1, 700), (1, 2300), (1, 1500), (0, 0))
        + tuple((1, 300 + 150 * r) for r in range(11))),
}


# The two calls of a tick that carries a chunk, by the class of a row's run
# (`ops.latent_attention.class_plan`, `pa.ragged_read_by_class`), at the
# two geometries that make them: rows = slots, `max_tokens` the lane's
# (chunk + slots), a decode row of 301 columns beside a 242-slot chunk at
# column 3000 (two tall tiles of 128 slots at G = 1, four of 64 at G = 6),
# decode rows to 2.3 k columns and a free slot. The short call is the
# cell's width-1 program; the tall call has a TILE a row.
CLASS_SHAPES = {
    "olmo-hybrid-7b-12l.digest/classes/W256": dict(
        geo=dict(n_heads=30, n_kv_heads=30, d_head=128), table_len=1024,
        n_blocks=1537, max_tokens=272,
        rows=((1, 300), (242, 3000), (1, 700), (1, 2300), (0, 0))
        + tuple((1, 300 + 150 * r) for r in range(11))),
    "laguna-s-2.1-5l.repo/full/classes/W256": dict(
        geo=dict(n_heads=48, n_kv_heads=8, d_head=128), table_len=1024,
        n_blocks=2049, max_tokens=264,
        rows=((1, 300), (242, 3000), (1, 700), (1, 2300), (0, 0))
        + tuple((1, 300 + 150 * r) for r in range(3))),
    # converse (benchmarks/configs/falcon-h1-34b-6l.json): G = 5, the first
    # group size coprime with the 128-row tile: a decode row packs 4 KV
    # heads x 5 query rows, a tall tile of 128 slots is five grid tiles.
    # Under the cell's table of 2048 columns and its pool: a 242-slot chunk
    # at column 700 beside decode rows of 0.3-1.5 k columns and a free slot
    # (16 of the lane's 64 rows: the check's gather reference holds every
    # row's scores at once, and the geometry is a row's, not the batch's).
    "falcon-h1-34b-6l.converse/classes/W256": dict(
        geo=dict(n_heads=20, n_kv_heads=4, d_head=128), table_len=128,
        n_blocks=6145, max_tokens=272,
        rows=((1, 300), (242, 700), (1, 1500), (0, 0))
        + tuple((1, 32 + 120 * r) for r in range(12))),
    # agents (benchmarks/configs/nemotron-3-super-120b-a12b-11l.json): G =
    # 16 over 2 KV heads, the largest group: a decode row packs 2 x 16 query
    # rows in one score tile, a tall tile is 8 slots = one grid tile of 128
    # query rows, so the 242-slot chunk is 31 tiles that each walk the row's
    # 3 k columns. Under the cell's table of 9,216 columns and its pool (16
    # of the lane's 64 rows, contexts to 3.2 k: the check's gather reference
    # holds every row's scores at once).
    "nemotron-3-super-120b-a12b-11l.agents/classes/W256": dict(
        geo=dict(n_heads=32, n_kv_heads=2, d_head=128), table_len=576,
        n_blocks=35841, max_tokens=320,
        rows=((1, 300), (242, 3000), (1, 2900), (0, 0))
        + tuple((1, 130 + 250 * r) for r in range(12))),
    # assist (benchmarks/configs/lfm2-24b-a2b-9l.json): G = 4 over 8 KV
    # heads of 64 lanes, the narrowest head a grouped cell reads (512 lanes
    # a token): a decode row packs 8 x 4 query rows in one score tile, a
    # tall tile is 32 slots = one grid tile of 128 query rows, so the
    # 242-slot chunk is 8 tiles that each walk the row's 3 k columns. Under the
    # cell's table of 5,120 columns and its pool (16 of the lane's 128
    # rows, contexts to 3.2 k: the check's gather reference holds every
    # row's scores at once).
    "lfm2-24b-a2b-9l.assist/classes/W256": dict(
        geo=dict(n_heads=32, n_kv_heads=8, d_head=64), table_len=320,
        n_blocks=40961, max_tokens=384,
        rows=((1, 300), (242, 3000), (1, 2900), (0, 0))
        + tuple((1, 130 + 250 * r) for r in range(12))),
    # history (benchmarks/configs/smallthinker-21b-a3b-8l.json): the WINDOW
    # layers' two calls at G = 7 over 4 KV heads, `window` 4096 handed to
    # both: a decode row's 4 x 7 query rows are one packed tile, a tall tile
    # of 128 slots 896 query rows = seven tiles of the grid whose edges a
    # slot's heads straddle. A 242-slot chunk at column 5000 (its lower
    # bound 900 columns in, the table behind it null) beside decode rows
    # past the window, short of it and a free slot (8 of the lane's 32
    # rows, contexts to 5.3 k: the check's gather reference holds every
    # row's scores at once).
    "smallthinker-21b-a3b-8l.history/window/classes/W256": dict(
        geo=dict(n_heads=28, n_kv_heads=4, d_head=128), table_len=1024,
        n_blocks=8737, max_tokens=288, window=4096,
        rows=((1, 300), (242, 5000), (1, 4095), (0, 0), (1, 5200),
              (1, 4096), (1, 2900), (1, 4500))),
}

# The grouped product of the served expert layers (`ops.moe.routed_experts`)
# at the banks the benchmark's cells hold, each at its lane's two list
# lengths: a decode-only tick (the slots alone) and a tick that carries a
# chunk of 256 prompt tokens. `slots` x `top_k` pairs, of which the share
# routed to the `held` experts form rows. `gated`: a SwiGLU bank {"gate_up"
# (G, lanes, 2 hidden), "down"}; else two matrices {"up", "down"} with
# relu^2 between them (the agents cell's, in a 1024-lane latent). `gate`:
# a gated bank's activation where it is not SiLU ("relu": a ReGLU).
GROUPED_SHAPES = {
    "moonlight/grouped/2048x1408/decode": dict(
        slots=32, valid=32, top_k=6, n_experts=64, held=(0, 64),
        lanes=2048, hidden=1408, gated=True),
    "moonlight/grouped/2048x1408/chunk": dict(
        slots=288, valid=280, top_k=6, n_experts=64, held=(0, 64),
        lanes=2048, hidden=1408, gated=True),
    "laguna/grouped/3072x1024/decode": dict(
        slots=32, valid=32, top_k=10, n_experts=256, held=(0, 128),
        lanes=3072, hidden=1024, gated=True),
    "laguna/grouped/3072x1024/chunk": dict(
        slots=288, valid=280, top_k=10, n_experts=256, held=(0, 128),
        lanes=3072, hidden=1024, gated=True),
    "kimi_linear/grouped/2304x1024/decode": dict(
        slots=128, valid=128, top_k=8, n_experts=256, held=(0, 128),
        lanes=2304, hidden=1024, gated=True),
    "kimi_linear/grouped/2304x1024/chunk": dict(
        slots=384, valid=370, top_k=8, n_experts=256, held=(0, 128),
        lanes=2304, hidden=1024, gated=True),
    "nemotron_h/grouped/latent1024x2688/decode": dict(
        slots=64, valid=64, top_k=22, n_experts=512, held=(0, 128),
        lanes=1024, hidden=2688, gated=False),
    "nemotron_h/grouped/latent1024x2688/chunk": dict(
        slots=320, valid=300, top_k=22, n_experts=512, held=(0, 128),
        lanes=1024, hidden=2688, gated=False),
    # assist: every one of 64 experts held, 8 rows an expert a decode tick.
    "lfm2/grouped/2048x1536/decode": dict(
        slots=128, valid=128, top_k=4, n_experts=64, held=(0, 64),
        lanes=2048, hidden=1536, gated=True),
    "lfm2/grouped/2048x1536/chunk": dict(
        slots=384, valid=370, top_k=4, n_experts=64, held=(0, 64),
        lanes=2048, hidden=1536, gated=True),
    # sessions: 36 of 72 experts held, the shortest contraction a bank has
    # (K = 768 in the second product), ~9 rows an expert a decode tick.
    "granite_hybrid/grouped/4096x768/decode": dict(
        slots=64, valid=64, top_k=10, n_experts=72, held=(0, 36),
        lanes=4096, hidden=768, gated=True),
    "granite_hybrid/grouped/4096x768/chunk": dict(
        slots=320, valid=300, top_k=10, n_experts=72, held=(0, 36),
        lanes=4096, hidden=768, gated=True),
    # history: every one of 64 ReGLU experts held, banks of 2560 x 1536
    # and 768 x 2560; 3 rows an expert a decode tick, ~26 in a chunk tick.
    "smallthinker/grouped/2560x768/decode": dict(
        slots=32, valid=32, top_k=6, n_experts=64, held=(0, 64),
        lanes=2560, hidden=768, gated=True, gate="relu"),
    "smallthinker/grouped/2560x768/chunk": dict(
        slots=288, valid=280, top_k=6, n_experts=64, held=(0, 64),
        lanes=2560, hidden=768, gated=True, gate="relu"),
}


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """`kernel(*operands())` is the Pallas side (jit-able); `operands` is
    a traceable thunk, so its shapes come from `jax.eval_shape` without
    generating a value; `check(out, operands)` returns max |kernel -
    reference|, or is None for a compile-only case (the flash backward:
    its gradients are pinned to the XLA path in interpret mode by
    tests/test_flash_backward.py)."""
    name: str
    kernel: Callable
    operands: Callable[[], Tuple]
    check: Optional[Callable]


def _config(model: str):
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )

    _ensure_builtin_models_imported()
    return create_model(model).config


def _geometry(model: str) -> dict:
    cfg = _config(model)
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "d_head": cfg.d_head}


def _flash_cases(model: str, geo: dict, interpret: bool):
    h, d = geo["n_heads"], geo["d_head"]
    kernel = functools.partial(flash_attention, causal=True,
                               interpret=interpret)

    def qkv(s):
        # Heads are already expanded to n_heads when flash runs
        # (models.transformer repeat_kv), so k/v carry H, not H_kv.
        return tuple(jax.random.normal(key, (1, s, h, d), jnp.bfloat16)
                     for key in jax.random.split(jax.random.PRNGKey(s), 3))

    def check(out, operands):
        with jax.default_matmul_precision("highest"):
            ref = dot_product_attention(
                *(x.astype(jnp.float32) for x in operands), causal=True)
        return float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))

    for s in FLASH_BUCKETS:
        yield KernelCase(f"{model}/flash_fwd/S{s}", kernel,
                         functools.partial(qkv, s), check)

    def loss(q, k, v):
        return kernel(q, k, v).astype(jnp.float32).sum()

    yield KernelCase(f"{model}/flash_bwd/S{FLASH_BACKWARD_SEQ}",
                     jax.grad(loss, argnums=(0, 1, 2)),
                     functools.partial(qkv, FLASH_BACKWARD_SEQ), None)


def _paged_cases(model: str, geo: dict, interpret: bool):
    # One decode-shaped batch and one mixed batch: decode rows beside
    # full and partial prefill chunks, one a block past a boundary.
    mixed = (1, CHUNK, 130, BLOCK_SIZE + 1, 1, 1, 77, CHUNK)
    for kind, label, q_lens in (
            ("ragged", "ragged/W1", (1,) * ROWS),
            ("ragged", f"ragged/W{CHUNK}", mixed),
            ("quant_ragged", "quant_ragged/W1", (1,) * ROWS),
            ("quant_ragged", f"quant_ragged/W{CHUNK}", mixed)):
        kernel_fn, reference_fn = pa.READ_PATHS[kind]
        workload = functools.partial(
            pa.parity_workload, kind, q_lens, block_size=BLOCK_SIZE,
            n_blocks=N_BLOCKS, table_len=TABLE_LEN, dtype=jnp.bfloat16,
            **geo)
        qlen = jnp.asarray(q_lens, jnp.int32)

        def check(out, operands, reference_fn=reference_fn, qlen=qlen):
            return pa.reference_error(reference_fn, out, operands, qlen)

        yield KernelCase(f"{model}/{label}",
                         functools.partial(kernel_fn, interpret=interpret),
                         lambda workload=workload: workload()[0], check)


def _latent_cases(model: str, interpret: bool):
    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )

    _ensure_builtin_models_imported()
    cfg = create_model(model).config
    kernel = functools.partial(la.latent_attention, scale=cfg.attn_scale,
                               interpret=interpret)
    mixed = (1, CHUNK, 130, BLOCK_SIZE + 1, 1, 1, 77, CHUNK)
    for label, q_lens in (("latent/W1", (1,) * ROWS),
                          (f"latent/W{CHUNK}", mixed)):
        workload = functools.partial(
            la.parity_workload, q_lens, n_heads=cfg.n_heads,
            latent=cfg.kv_lora_rank, rope=cfg.qk_rope,
            block_size=BLOCK_SIZE, n_blocks=N_BLOCKS, table_len=TABLE_LEN,
            dtype=jnp.bfloat16)

        def check(out, operands):
            return la.reference_error(out, operands, cfg.attn_scale)

        yield KernelCase(f"{model}/{label}", kernel,
                         workload, check)


def _live_rows(slots: int, live):
    """A step's rows over a pool of `slots` + 1: `live` None is one dead
    row in 40 (in 50 over 128 slots), else that many live rows spread over
    the slots; row 3 from a zero state. (rows, live, fresh)."""
    at = jnp.arange(slots)
    if live is None:
        live = at % (50 if slots > 64 else 40) != 7
    else:
        live = (at * live) // slots != ((at + 1) * live) // slots
    return jnp.where(live, at + 1, 0), live, at == 3


def _step_error(out, want, operands) -> float:
    """Max |kernel - reference| over the live rows' outputs and the whole
    pool; NaN where a row that took no step (the null row, a dead row's,
    the other layer's) is not bit for bit as it was."""
    (o, pool), (o_want, pool_want) = out, want
    old, layer, rows, live = operands[5:9]
    stepped = jnp.zeros(old.shape[1], bool).at[rows].set(live)
    same = (pool == old).all(axis=(2, 3, 4))
    kept = same.at[layer].set(same[layer] | stepped).all()
    err = jnp.maximum(
        jnp.abs(jnp.where(live[:, None, None], o - o_want, 0.0)).max(),
        jnp.abs(pool - pool_want).max())
    return float(jnp.where(kept, err, jnp.nan))


def _step_names(model: str, kernel: str, slots: int):
    return [(f"{model}/{kernel}_step/B{slots}"
             + ("" if live is None else f"/live{live}"), live)
            for live in (None,) + STEP_LIVE[model]]


def _recurrence_cases(model: str, interpret: bool):
    """The delta rule's step over a lane's slots of a pool of slots + 1 rows
    (`_live_rows`: 128 of kimi_linear's, 16 of olmo_hybrid's) and its chunked
    form over a run of 256 tokens, at `model`'s heads and lanes and under its
    gate (a key channel's, `kda_*`, or a head's, `gdn_*`), the decays over
    the draw's range by head, channel and token; each against `gdn_scan`
    from the same states."""
    from tpu_engine.ops import gated_delta as gd

    cfg = _config(model)
    h, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    kernel = cfg.recurrence
    slots = ROWS_RECURRENT if kernel == "kda" else ROWS_GDN

    def operands(t, rows):
        ks = jax.random.split(jax.random.PRNGKey(t), 6)

        def unit(x):
            return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

        q = unit(jax.random.normal(ks[0], (t, h, dk))) / dk ** 0.5
        k = unit(jax.random.normal(ks[1], (t, h, dk)))
        v = jax.random.normal(ks[2], (t, h, dv))
        g = jnp.log(jax.random.uniform(
            ks[3], (t, h, dk) if kernel == "kda" else (t, h), minval=0.55,
            maxval=1.0))
        beta = jax.random.uniform(ks[4], (t, h))
        pool = jax.random.normal(ks[5], (2, rows, h, dv, dk))
        return q, k, v, g, beta, pool

    def step_operands(live):
        return (operands(slots, slots + 1) + (jnp.int32(1),)
                + _live_rows(slots, live))

    def step_check(out, operands):
        return _step_error(out, gd.gdn_step_rows_reference(*operands),
                           operands)

    def chunk_operands():
        return operands(CHUNK, 3) + (jnp.int32(1), jnp.int32(2),
                                     jnp.bool_(False))

    def chunk_check(out, operands):
        (o, pool), (q, k, v, g, beta, old, layer, row, _) = out, operands
        with jax.default_matmul_precision("highest"):
            o_want, last = gd.gdn_scan(q, k, v, g, beta, old[layer, row])
        return float(jnp.maximum(
            jnp.abs(o - o_want).max(),
            jnp.abs(pool - old.at[layer, row].set(last)).max()))

    for name, live in _step_names(model, kernel, slots):
        yield KernelCase(name,
                         functools.partial(gd.gdn_step_rows,
                                           interpret=interpret),
                         functools.partial(step_operands, live), step_check)
    yield KernelCase(f"{model}/{kernel}_chunk/T{CHUNK}",
                     functools.partial(gd.gdn_chunk_row, interpret=interpret),
                     chunk_operands, chunk_check)


def _ssd_cases(model: str, interpret: bool):
    """`ssd_step` over 64 rows of a 65-row pool (`_live_rows`: two of them
    on the null row, then `STEP_LIVE`'s mixes; one from a zero state) and
    `ssd_chunk` over a run of 256 tokens, at
    `model`'s heads, groups and lanes, the decays over the draw's range by
    head and token; each against `ssd_recurrent` from the same states."""
    from tpu_engine.ops import ssd

    cfg = _config(model)
    h, p, g, n = cfg.lin_heads, cfg.ssm_head_dim, cfg.n_groups, cfg.d_state

    def operands(t, rows):
        ks = jax.random.split(jax.random.PRNGKey(t), 6)
        x = jax.random.normal(ks[0], (t, h, p))
        dt = jax.random.uniform(ks[1], (t, h), minval=0.1, maxval=2.5)
        a = -jax.random.uniform(ks[2], (h,), minval=0.02, maxval=0.25)
        b = jax.random.normal(ks[3], (t, g, n)) / n ** 0.5
        c = jax.random.normal(ks[4], (t, g, n))
        return x, dt, a, b, c, jax.random.normal(ks[5], (2, rows, h, p, n))

    def step_operands(live):
        return (operands(ROWS_SSD, ROWS_SSD + 1) + (jnp.int32(1),)
                + _live_rows(ROWS_SSD, live))

    def step_check(out, operands):
        return _step_error(out, ssd.ssd_step_rows_reference(*operands),
                           operands)

    def chunk_operands():
        return operands(CHUNK, 3) + (jnp.int32(1), jnp.int32(2),
                                     jnp.bool_(False))

    def chunk_check(out, operands):
        (y, pool), (x, dt, a, b, c, old, layer, row, _) = out, operands
        y_want, last = ssd.ssd_recurrent(
            x[None], dt[None], a, b[None], c[None],
            initial_state=old[layer, row][None])
        return float(jnp.maximum(
            jnp.abs(y - y_want[0]).max(),
            jnp.abs(pool - old.at[layer, row].set(last[0])).max()))

    for name, live in _step_names(model, "ssd", ROWS_SSD):
        yield KernelCase(name,
                         functools.partial(ssd.ssd_step_rows,
                                           interpret=interpret),
                         functools.partial(step_operands, live), step_check)
    yield KernelCase(f"{model}/ssd_chunk/T{CHUNK}",
                     functools.partial(ssd.ssd_chunk_row,
                                       interpret=interpret),
                     chunk_operands, chunk_check)


def grouped_cases():
    """The served expert layer's grouped product at every entry of
    `GROUPED_SHAPES`, against every held expert applied to every token under
    the router's mask in float32. No `interpret`: the product is XLA's own
    kernel (`jax.lax.ragged_dot`) under the tiles `ops.moe.grouped_tiling`
    states."""
    from tpu_engine.ops import moe

    for name, shape in GROUPED_SHAPES.items():
        n, k, e = shape["slots"], shape["top_k"], shape["n_experts"]
        held, lanes, hidden = shape["held"], shape["lanes"], shape["hidden"]
        gated = shape["gated"]
        first = "gate_up" if gated else "up"
        act = (jax.nn.relu if shape.get("gate") == "relu"
               else jax.nn.silu if gated else moe.relu2)

        def operands(n=n, k=k, e=e, held=held, lanes=lanes, hidden=hidden,
                     live=shape["valid"], gated=gated, first=first):
            ks = jax.random.split(jax.random.PRNGKey(n), 4)
            x = jax.random.normal(ks[0], (n, lanes), jnp.bfloat16)
            scores = jax.random.uniform(ks[1], (n, e))
            chosen, experts = jax.lax.top_k(scores, k)
            weights = chosen / chosen.sum(-1, keepdims=True) * 5.0
            bank = {first: jax.random.normal(
                        ks[2], (held[1], lanes, hidden * (1 + gated)),
                        jnp.bfloat16) * jnp.bfloat16(lanes ** -0.5),
                    "down": jax.random.normal(
                        ks[3], (held[1], hidden, lanes), jnp.bfloat16)
                    * jnp.bfloat16((1.5 * hidden) ** -0.5)}
            return (x, jnp.arange(n) < live, experts.astype(jnp.int32),
                    weights, bank)

        kernel = functools.partial(
            moe.routed_experts, first_group=-held[0], n_experts=e, held=held,
            max_tokens=n, activation=act)

        def check(out, operands, e=e, held=held, gated=gated, first=first,
                  act=act):
            (y, rows), (x, valid, experts, weights, bank) = out, operands
            gates = jnp.zeros((x.shape[0], e)).at[
                jnp.arange(x.shape[0])[:, None], experts].set(weights)
            gates = jnp.where(valid[:, None], gates, 0.0)
            x = x.astype(jnp.float32)

            def one(want, i):
                up, down = (jax.lax.dynamic_index_in_dim(
                    bank[m], i, keepdims=False).astype(jnp.float32)
                    for m in (first, "down"))
                mine = jax.lax.dynamic_index_in_dim(gates, held[0] + i, 1)
                hid = x @ up
                if gated:
                    gate, lin = jnp.split(hid, 2, axis=-1)
                    hid = act(gate) * lin
                else:
                    hid = act(hid)
                return want + mine * (hid @ down), None

            with jax.default_matmul_precision("highest"):
                want, _ = jax.lax.scan(one, jnp.zeros_like(x),
                                       jnp.arange(held[1]))
            taken = (gates > 0).sum(0)[held[0]:held[0] + held[1]]
            if not bool((rows[held[0]:held[0] + held[1]] == taken).all()):
                return float("nan")
            return float(jnp.abs(y - want).max())

        yield KernelCase(name, kernel, operands, check)


def cell_cases(interpret: bool = False):
    """The ragged read at every entry of `CELL_SHAPES`."""
    for name, shape in CELL_SHAPES.items():
        # A window layer's call: the same read with a lower bound.
        kernel_fn, reference_fn = (
            functools.partial(fn, window=shape["window"])
            if "window" in shape else fn for fn in pa.READ_PATHS["ragged"])
        q_lens, pos0 = zip(*shape["rows"])
        workload = functools.partial(
            pa.parity_workload, "ragged", q_lens, block_size=BLOCK_SIZE,
            n_blocks=shape["n_blocks"], table_len=shape["table_len"],
            dtype=jnp.bfloat16, pos0=pos0, window=shape.get("window"),
            **shape["geo"])
        # The gather reference over the columns a row reaches, not the
        # table's width (2048 columns of 16 rows x 32 heads x 256 slots
        # are 17 GB of scores).
        reach = -(-max(q + p for q, p in shape["rows"]) // BLOCK_SIZE)

        def check(out, operands, reach=reach, reference_fn=reference_fn,
                  qlen=jnp.asarray(q_lens, jnp.int32)):
            near = operands[:4] + (operands[4][:, :reach],) + operands[5:]
            return pa.reference_error(reference_fn, out, near, qlen)

        yield KernelCase(name,
                         functools.partial(kernel_fn, interpret=interpret),
                         lambda workload=workload: workload()[0], check)


def class_cases(interpret: bool = False):
    """The short and the tall call over one token list, at every entry of
    `CLASS_SHAPES`, against the gather reference on the whole batch."""
    for name, shape in CLASS_SHAPES.items():
        q_lens, pos0 = zip(*shape["rows"])
        workload = functools.partial(
            pa.class_workload, q_lens, pos0, width=CHUNK,
            max_tokens=shape["max_tokens"], block_size=BLOCK_SIZE,
            n_blocks=shape["n_blocks"], table_len=shape["table_len"],
            dtype=jnp.bfloat16, **shape["geo"])
        reach = -(-max(q + p for q, p in shape["rows"]) // BLOCK_SIZE)
        # A window layer's calls: the same reads with a lower bound.
        window = shape.get("window")
        if window is not None:
            workload = functools.partial(workload, window=window)

        def check(out, operands, reach=reach, window=window):
            return pa.class_read_error(
                out, operands[:4] + (operands[4][:, :reach],) + operands[5:],
                window=window)

        yield KernelCase(
            name, functools.partial(pa.class_read, width=CHUNK,
                                    max_tokens=shape["max_tokens"],
                                    interpret=interpret, window=window),
            workload, check)


# The cases the paged walk can get wrong (`pa.WALK_CASES`, `pa.WINDOW_CASES`:
# dead steps between live ones, a horizon on a block's edge, walks that
# start groups apart side by side), compiled: 2 KV heads of 64 lanes, a
# block the 128 lanes Mosaic's DMA needs, at the tables' own group sizes
# (four query heads a KV head, six under a window).
WALK_GEOMETRY = dict(n_kv_heads=2, d_head=64)


def walk_cases(interpret: bool = False):
    """The ragged read over every entry of `pa.WALK_CASES` and of
    `pa.WINDOW_CASES`: the pipeline over a call's steps as the chip runs
    it, where the interpreters on the CPU only model its DMAs."""
    cases = [(f"walk/{name}", q_lens, pos0, None, table_len, 4)
             for name, (q_lens, pos0, table_len) in pa.WALK_CASES.items()]
    cases += [(f"walk/window/{name}", q_lens, pos0, window, table_len, 6)
              for name, (q_lens, pos0, window, table_len)
              in pa.WINDOW_CASES.items()]
    for name, q_lens, pos0, window, table_len, group in cases:
        kernel_fn, reference_fn = (
            functools.partial(fn, window=window)
            for fn in pa.READ_PATHS["ragged"])
        workload = functools.partial(
            pa.parity_workload, "ragged", q_lens, block_size=BLOCK_SIZE,
            n_blocks=1 + len(q_lens) * table_len, table_len=table_len,
            dtype=jnp.bfloat16, pos0=pos0, window=window,
            n_heads=group * WALK_GEOMETRY["n_kv_heads"], **WALK_GEOMETRY)

        # Nineteen small shapes: the workload and the gather reference one
        # compiled program each, not an XLA compile an eager operation.
        gap = jax.jit(functools.partial(pa.reference_gap, reference_fn))

        def check(out, operands, gap=gap,
                  qlen=jnp.asarray(q_lens, jnp.int32)):
            return float(gap(out, operands, qlen))

        yield KernelCase(name,
                         functools.partial(kernel_fn, interpret=interpret),
                         jax.jit(lambda workload=workload: workload()[0]),
                         check)


def block_mask_cases(interpret: bool = False):
    """The ragged read under the block-causal mask (`mask_block`) over every
    entry of `pa.BLOCK_MASK_CASES`, compiled: eight query heads a KV head
    over `WALK_GEOMETRY`'s 2 KV heads of 64 lanes, runs of 4 (the heads
    packed) and tall tiles of 16 slots."""
    for name, (q_lens, pos0, table_len) in pa.BLOCK_MASK_CASES.items():
        block = 1 if name.startswith("blocks-of-one") else 4
        kernel_fn, reference_fn = (
            functools.partial(fn, mask_block=block)
            for fn in pa.READ_PATHS["ragged"])
        workload = functools.partial(
            pa.parity_workload, "ragged", q_lens, block_size=BLOCK_SIZE,
            n_blocks=1 + len(q_lens) * table_len, table_len=table_len,
            dtype=jnp.bfloat16, pos0=pos0,
            n_heads=8 * WALK_GEOMETRY["n_kv_heads"], **WALK_GEOMETRY)
        gap = jax.jit(functools.partial(pa.reference_gap, reference_fn))

        def check(out, operands, gap=gap,
                  qlen=jnp.asarray(q_lens, jnp.int32)):
            return float(gap(out, operands, qlen))

        yield KernelCase(f"walk/block-mask/{name}",
                         functools.partial(kernel_fn, interpret=interpret),
                         jax.jit(lambda workload=workload: workload()[0]),
                         check)


def kernel_cases(model: str, interpret: bool = False):
    """Every Pallas kernel site at `model`'s registry geometry."""
    if model in LATENT_MODELS + RECURRENT_MODELS:
        if model in LATENT_MODELS:
            yield from _latent_cases(model, interpret)
        if model in RECURRENT_MODELS:
            # By the kernels' names in a trace (the model's `recurrence`).
            cases = {"gdn": _recurrence_cases, "kda": _recurrence_cases,
                     "ssd": _ssd_cases}
            yield from cases[_config(model).recurrence](model, interpret)
        return
    geo = _geometry(model)
    yield from _flash_cases(model, geo, interpret)
    yield from _paged_cases(model, geo, interpret)


def compile_for_topology(case: KernelCase, device):
    """AOT-compile `case` for `device` of a
    `jax.experimental.topologies.get_topology_desc(platform="tpu", ...)`
    description: lowers through the Pallas TPU rules and runs Mosaic
    with no chip attached. Raises what the compiler raises; returns the
    compiled program."""
    from jax.sharding import SingleDeviceSharding

    sharding = SingleDeviceSharding(device)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(case.operands))
    return jax.jit(case.kernel).lower(*shapes).compile()


def main(only=()) -> int:
    from tpu_engine.utils.checkpoint import enable_compilation_cache

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"kernel_check: JAX backend is {backend!r}, not a TPU — the "
              f"compiled kernels cannot be checked here", file=sys.stderr)
        return 1
    enable_compilation_cache()
    worst, failed = 0.0, []
    for case in itertools.chain(
            *(kernel_cases(model) for model in dict.fromkeys(
                MODELS + LATENT_MODELS + RECURRENT_MODELS)),
            cell_cases(), class_cases(), walk_cases(), block_mask_cases(),
            grouped_cases()):
        if only and not any(part in case.name for part in only):
            continue
        t0 = time.monotonic()
        operands = case.operands()
        if case.check is None:
            jax.jit(case.kernel).lower(*operands).compile()
            err = None
        else:
            err = case.check(jax.block_until_ready(
                jax.jit(case.kernel)(*operands)), operands)
            worst = max(worst, err)
            if "/ssd_chunk/" in case.name:
                limit = SSD_CHUNK_TOLERANCE
            elif any(f"/{kernel}_" in case.name
                     for kernel in ("gdn", "kda", "ssd")):
                limit = F32_TOLERANCE
            else:
                limit = BF16_TOLERANCE
            if not err <= limit:            # NaN fails too
                failed.append(case.name)
        print(json.dumps({"kernel": case.name, "interpret": False,
                          "max_abs_err": err,
                          "seconds": round(time.monotonic() - t0, 2)}),
              flush=True)
    print(json.dumps({"kernel_check": "failed" if failed else "ok",
                      "tolerance": BF16_TOLERANCE, "worst": worst,
                      "failed": failed}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(tuple(sys.argv[1:])))
