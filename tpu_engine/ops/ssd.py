"""State Space Duality (SSD) primitives — the Mamba-2-style selective
state-space scan in its two dual forms.

The Compiler-First State Space Duality paper (PAPERS.md) is the source:
a selective SSM layer admits ONE mathematical recurrence

    s_t = exp(dt_t * A) * s_{t-1} + dt_t * x_t ⊗ B_t        (state update)
    y_t = C_t · s_t                                          (readout)

with two dual computational forms:

- **O(1) recurrence** (`ssd_step` / `ssd_recurrent`): one step per token,
  a fixed-size state ``(heads, head_dim, d_state)`` per row. This is the
  DECODE form — autoregressive serving costs constant state per stream
  no matter how long it runs (the "portable O(1) autoregressive caching"
  the paper names), and it is partition-invariant: processing a sequence
  in windows of any size through repeated steps produces bit-identical
  states, which is what makes the serving scheduler's budgeted prefill
  chunks and crash-replay resumes
  byte-identical (runtime.scheduler, DESIGN.md "Recurrent state
  serving").
- **Chunked matmul form** (`ssd_chunked`): the sequence splits into
  chunks; within a chunk the scan becomes an attention-like masked
  matmul (decay-weighted score matrix @ inputs) and only one recurrence
  per CHUNK carries state across — MXU-shaped work instead of T
  sequential steps. This is the PREFILL throughput form. Floating-point
  association differs from the recurrence (low-bit diffs), so the
  serving path keeps the recurrence form for byte-identity and this
  form is the on-chip prefill fast path staged behind
  `ssd_parity_check` (diagnostics.py --ssd-parity), the same
  correctness-anchor-first pattern as ops.paged_attention.

Conventions (Mamba-2 defaults): ``A`` is one negative scalar per head;
``dt`` is a per-head per-step rate; ``B``/``C`` belong to a GROUP of heads,
head i reading group ``i // (h / g)``, and come with a group axis,
(..., g, n), or without one where every head shares them (one group: the
slab family of models.ssd). Shapes:
  x (b, t, h, p) · dt (b, t, h) · A (h,) · B, C (b, t, [g,] n)
  → y (b, t, h, p), final state (b, h, p, n).

**The served forms** (models.falcon_h1: a row's state lies in a POOL
(layers, rows, h, p, n) float32, `runtime.kv_blocks.StateRowPool`, and is
changed where it lies, under `ops.gated_delta`'s contract: the layer and
the row's pool row reach a Pallas kernel's index maps through SMEM, the
state block is aliased to the output, and neither the layer nor a gather
of the rows ever materializes):

- `ssd_step_rows`: one token a row, every row of a tick at once, the
  kernel `ssd_step` in a trace. The grid is (rows, blocks of a group's
  heads), the rows that step first and no byte moved for the others
  (`ops.gated_delta.step_at`); a head's update is multiplies and lane sums
  on its (p, n) tile: the decay and the group's B and C come as rows, dt x
  as a column, y leaves as a column. 2 x 4 p n bytes moved for 4 p n
  operations a head: the memory bounds it by construction.
- `ssd_chunk_row`: a row's run of T tokens (a multiple of `SUB_CHUNK`, 64:
  the length this file's chunked kernel works in, whatever
  `mamba_chunk_size` a model states: the result does not depend on it)
  from the state its last run left, the kernel `ssd_chunk`, a head a grid
  step: a sub-chunk is three matrix products, (L * C B^T) (dt x), the
  entering state read through C and the state's update through B, the
  state carried in VMEM. With G_i = sum_{j<=i} dt_j A, every decay is
  exp of a DIFFERENCE of G's that is at most 0 (L_ij = exp(G_i - G_j),
  exp(G_i), exp(G_C - G_i), exp(G_C)): no quotient by a product of decays
  is taken, so a decay of exp(-30) a token underflows to 0 and nothing
  overflows.

Off a TPU (and as the kernels' references) they are `ssd_step` over a
gather of the rows and `ssd_chunked` on the row's state, written back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_engine.ops.gated_delta import (
    PRECISION,
    SUB_CHUNK,
    _copy_null_block,
    _dot,
    _in_order,
    step_at,
)

# State a grid step of the step kernel holds (in and out, double-buffered:
# four of these in VMEM): the 16 heads of one of Falcon-H1's two groups.
_STEP_BLOCK_BYTES = 1 << 21


def _per_head(v, heads: int):
    """B or C, (..., g, n), as each of `heads` heads reads it: (..., h, n)."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def ssd_step(state, x, dt, A, B, C):
    """One recurrence step for a batch of rows — the O(1) decode form.

    state (b, h, p, n) · x (b, h, p) · dt (b, h) · A (h,) · B, C (b, n)
    or (b, g, n) → (y (b, h, p), new_state). The caller owns masking (a
    row that must not advance keeps its old state) and the D·x skip term.
    With a group axis the readout is multiplies and a lane sum (exact
    float32 on a TPU too, where a default-precision product is not)."""
    dA = jnp.exp(dt * A)                                   # (b, h) decay
    if B.ndim == 2:
        dBx = (dt[..., None] * x)[..., None] * B[:, None, None, :]
        new_state = state * dA[..., None, None] + dBx      # (b, h, p, n)
        return jnp.einsum("bhpn,bn->bhp", new_state, C), new_state
    h = x.shape[1]
    dBx = (dt[..., None] * x)[..., None] * _per_head(B, h)[:, :, None, :]
    new_state = state * dA[..., None, None] + dBx
    y = (new_state * _per_head(C, h)[:, :, None, :]).sum(-1)
    return y, new_state


def ssd_recurrent(x, dt, A, B, C, initial_state=None):
    """Sequential reference: scan `ssd_step` over t. This IS the serving
    decode computation unrolled — the parity anchor `ssd_chunked` must
    match."""
    b, t, h, p = x.shape
    n = B.shape[-1]
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), x.dtype)

    def body(state, inp):
        x_t, dt_t, B_t, C_t = inp
        y_t, state = ssd_step(state, x_t, dt_t, A, B_t, C_t)
        return state, y_t

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(B, 1, 0), jnp.moveaxis(C, 1, 0))
    final, ys = jax.lax.scan(body, initial_state, xs)
    return jnp.moveaxis(ys, 0, 1), final


def _segsum(a):
    """Lower-triangular pairwise decay sums: out[..., i, j] =
    sum_{j < m <= i} a[..., m] for i >= j, -inf above the diagonal
    (exp → 0, so masked positions contribute nothing)."""
    T = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    s = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, s, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int = 16, initial_state=None):
    """Chunked matmul form — the prefill-throughput dual of
    `ssd_recurrent`. Sequences whose length is not a chunk multiple are
    zero-padded (dt 0 = identity step: exp(0·A) = 1, no input injected),
    so any T works. B, C: (b, t, n) or (b, t, g, n). Returns (y (b, t, h,
    p), final state (b, h, p, n)); equal to the recurrence up to float
    association (`ssd_parity_check`). The products take `PRECISION`: a
    state error is carried for the rest of the row."""
    b, t, h, p = x.shape
    if B.ndim == 3:
        B, C = B[:, :, None, :], C[:, :, None, :]
    g, n = B.shape[-2:]
    c = max(1, int(chunk))
    pad = (-t) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    T = t + pad
    k = T // c
    mm = functools.partial(jnp.einsum, precision=PRECISION)
    xd = x * dt[..., None]                                  # dt-weighted input
    a = dt * A[None, None, :]                               # (b, T, h) log decay
    # Heads as (group, head of the group): e.
    xd_c = xd.reshape(b, k, c, g, h // g, p)
    a_c = jnp.moveaxis(a.reshape(b, k, c, g, h // g), 2, -1)  # (b, k, g, e, c)
    B_c = B.reshape(b, k, c, g, n)
    C_c = C.reshape(b, k, c, g, n)

    # Intra-chunk: attention-like masked matmul. L[i, j] carries the
    # decay from step j's injection to step i's readout.
    L = jnp.exp(_segsum(a_c))                               # (b, k, g, e, c, c)
    scores = mm("bkign,bkjgn->bkgij", C_c, B_c)             # a group's, once
    y_diag = mm("bkgeij,bkgij,bkjgep->bkigep", L, scores, xd_c)

    # Each chunk's contribution to the state at its own end.
    a_cum = jnp.cumsum(a_c, axis=-1)                        # (b, k, g, e, c)
    decay_to_end = jnp.exp(a_cum[..., -1:] - a_cum)
    chunk_states = mm("bkjgn,bkgej,bkjgep->bkgepn", B_c, decay_to_end, xd_c)

    # One recurrence per chunk carries state across chunk boundaries.
    chunk_decay = jnp.exp(a_cum[..., -1])                   # (b, k, g, e)
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), x.dtype)

    def body(carry, inp):
        contrib, decay = inp                        # (b,g,e,p,n), (b,g,e)
        new = carry * decay[..., None, None] + contrib
        return new, carry                                   # emit ENTERING state

    final, entering = jax.lax.scan(
        body, initial_state.reshape(b, g, h // g, p, n),
        (jnp.moveaxis(chunk_states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                 # (b, k, g, e, p, n)

    # Off-diagonal: the entering state decayed THROUGH each step i
    # (inclusive — the state update runs before the readout).
    state_decay = jnp.exp(a_cum)                            # (b, k, g, e, c)
    y_off = mm("bkign,bkgepn,bkgei->bkigep", C_c, entering, state_decay)

    y = (y_diag + y_off).reshape(b, T, h, p)[:, :t]
    return y, final.reshape(b, h, p, n)


# -- the served forms: a row's state where it lies in a pool -------------------

def ssd_step_rows_reference(x, dt, A, B, C, pool, layer, rows, live, fresh):
    """`ssd_step_rows` by a gather of the rows' states and a scatter back."""
    old = pool[layer, rows]
    y, new = ssd_step(jnp.where(fresh[:, None, None, None], 0.0, old),
                      x, dt, A, B, C)
    return y, pool.at[layer, rows].set(
        jnp.where(live[:, None, None, None], new, old))


def _step_kernel(rows_ref, layer_ref, order_ref, count_ref, a_ref, dtx_ref,
                 bc_ref, s_ref, s_out, y_ref, *, heads):
    del rows_ref, layer_ref, order_ref           # the index maps read them

    @pl.when(pl.program_id(0) < count_ref[0])
    def _():
        b_row, c_row = bc_ref[0, 0, 0:1, :], bc_ref[0, 0, 1:2, :]
        for i in range(heads):
            s = (s_ref[0, 0, i] * a_ref[0, 0, i:i + 1, :]         # (p, n)
                 + dtx_ref[0, 0, :, i:i + 1] * b_row)
            s_out[0, 0, i] = s
            y_ref[0, 0, :, i:i + 1] = jnp.sum(s * c_row, axis=1,
                                              keepdims=True)

    _copy_null_block(count_ref, s_ref, s_out)


def _step_call(x, dt, A, B, C, pool, layer, rows, live, fresh, *,
               interpret: bool):
    b, h, p = x.shape
    g, n = B.shape[1:]
    lanes = -(-n // 128) * 128
    heads = max(e for e in range(1, h // g + 1) if (h // g) % e == 0
                and (e == 1 or e * p * lanes * 4 <= _STEP_BLOCK_BYTES))
    blocks = h // heads                          # a block lies in ONE group
    rows, order, count = _in_order(live, rows)
    # The decay a head as a row of the state's lanes, 0 where the row
    # starts from nothing; dt x as columns, a block's heads on the lanes.
    a = jnp.where(fresh[:, None], 0.0, jnp.exp(dt * A))
    a = jnp.broadcast_to(a[..., None], (b, h, n)).reshape(b, blocks, heads, n)
    dtx = (dt[..., None] * x).reshape(b, blocks, heads, p)
    dtx = dtx.transpose(0, 1, 3, 2)
    bc = jnp.stack([B, C], axis=2)                          # (b, g, 2, n)

    def state(i, j, rows, layer, order, count):
        return (layer[0], rows[i], step_at(i, j, order, count, blocks)[1],
                0, 0)

    def block(i, j, rows, layer, order, count):
        return step_at(i, j, order, count, blocks) + (0, 0)

    def group(i, j, rows, layer, order, count):
        b, j = step_at(i, j, order, count, blocks)
        return (b, j * heads * g // h, 0, 0)

    pool, y = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,               # rows, layer, order, count
            grid=(b, blocks),
            in_specs=[pl.BlockSpec((1, 1, heads, n), block),
                      pl.BlockSpec((1, 1, p, heads), block),
                      pl.BlockSpec((1, 1, 2, n), group),
                      pl.BlockSpec((1, 1, heads, p, n), state)],
            out_specs=[pl.BlockSpec((1, 1, heads, p, n), state),
                       pl.BlockSpec((1, 1, p, heads), block)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, blocks, p, heads), jnp.float32)],
        input_output_aliases={7: 0},             # the pool, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="ssd_step",
    )(rows, jnp.asarray(layer, jnp.int32).reshape(1), order, count,
      a, dtx, bc, pool)
    # A dead row's column was never written.
    y = y.transpose(0, 1, 3, 2).reshape(b, h, p)
    return jnp.where(live[:, None, None], y, 0.0), pool


def ssd_step_rows(x, dt, A, B, C, pool, layer, rows, live, fresh, *,
                  interpret=None):
    """One token a row, the states changed where they lie. x: (B, h, p);
    dt: (B, h); A: (h,); B, C: (B, g, n); pool: (L, R, h, p, n) float32,
    donated; layer: the pool's layer; rows: (B,) each row's pool row;
    live: (B,) rows that take the step (the others' states are left as
    they are, their blocks never visited; their outputs mean nothing, 0
    from the kernel); fresh: (B,) rows whose state is zero before the
    step. Returns (y (B, h, p) without the D x skip, pool). With no live
    row the call is still made: the one block its grid names, of the null
    row, is copied onto itself (`ops.gated_delta.step_at`).
    `interpret=None` picks the kernel `ssd_step` on a TPU and the gather
    elsewhere; True runs the kernel in the Pallas interpreter."""
    if interpret is None and jax.default_backend() != "tpu":
        return ssd_step_rows_reference(x, dt, A, B, C, pool, layer, rows,
                                       live, fresh)
    return _step_call(x, dt, A, B, C, pool, layer, rows, live, fresh,
                      interpret=bool(interpret))


def ssd_chunk_row_reference(x, dt, A, B, C, pool, layer, row, fresh):
    """`ssd_chunk_row` by `ssd_chunked` on the row's state, written back."""
    y, last = ssd_chunked(
        x[None], dt[None], A, B[None], C[None], chunk=SUB_CHUNK,
        initial_state=jnp.where(fresh, 0.0, pool[layer, row])[None])
    return y[0], pool.at[layer, row].set(last[0])


def _chunk_terms(x, dt, A, B, C):
    """What a run's sub-chunks need before the state is touched, n
    sub-chunks of c = `SUB_CHUNK` tokens: L * C B^T (h, n, c, c), lower
    triangular; dt x (h, n, c, p); the decays exp(G) and exp(G_C - G) side
    by side (h, n, c, 2); exp(G_C) over the state's lanes (h, n, 1, N); C
    and B a group (g, n, c, N). Every exponent is a difference that is at
    most 0 (module docstring)."""
    t, h, p = x.shape
    g, lanes = B.shape[1:]
    c = SUB_CHUNK
    if t % c:
        raise ValueError(f"a run of {t} tokens is no multiple of {c}")
    n = t // c

    def first(y):
        """(T, H or g, ...) -> (H or g, n, c, ...)."""
        y = jnp.moveaxis(y.astype(jnp.float32), 1, 0)
        return y.reshape(y.shape[0], n, c, *y.shape[2:])

    cum = jnp.cumsum(first(dt * A), axis=-1)                # (h, n, c)
    at = jnp.arange(c)
    decay = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    b_g, c_g = first(B), first(C)
    scores = jnp.einsum("gnik,gnjk->gnij", c_g, b_g, precision=PRECISION)
    return (decay * jnp.repeat(scores, h // g, axis=0),
            first(dt[..., None] * x),
            jnp.stack([jnp.exp(cum), jnp.exp(cum[..., -1:] - cum)], axis=-1),
            jnp.broadcast_to(jnp.exp(cum[..., -1])[..., None, None],
                             (h, n, 1, lanes)),
            c_g, b_g)


def _chunk_kernel(row_ref, layer_ref, fresh_ref, lcb_ref, u_ref, dec_ref,
                  last_ref, c_ref, b_ref, s_ref, s_out, y_ref, *, n):
    """A head of a row's run: its sub-chunks in turn, the state in VMEM."""
    del row_ref, layer_ref                       # the index maps read them
    s = s_ref[0, 0, 0] * (fresh_ref[0] == 0).astype(jnp.float32)  # (p, N)
    for i in range(n):
        u = u_ref[0, i]                                           # (c, p)
        y_ref[0, i] = (dec_ref[0, i, :, 0:1]
                       * _dot(c_ref[0, i], s, ((1,), (1,)))
                       + _dot(lcb_ref[0, i], u, ((1,), (0,))))
        s = s * last_ref[0, i] + _dot(u * dec_ref[0, i, :, 1:2],
                                      b_ref[0, i], ((0,), (0,)))
    s_out[0, 0, 0] = s


def _chunk_call(x, dt, A, B, C, pool, layer, row, fresh, *, interpret: bool):
    t, h, p = x.shape
    g, lanes = B.shape[1:]
    n, c = t // SUB_CHUNK, SUB_CHUNK

    def head(j, *_):
        return (j, 0, 0, 0)

    def group(j, *_):
        return (j * g // h, 0, 0, 0)

    def state(j, row, layer, *_):
        return (layer[0], row[0], j, 0, 0)

    def sub_chunks(*shape, at=head):
        return pl.BlockSpec((1, n) + shape, at)

    pool, y = pl.pallas_call(
        functools.partial(_chunk_kernel, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,               # row, layer, fresh
            grid=(h,),
            in_specs=[sub_chunks(c, c), sub_chunks(c, p), sub_chunks(c, 2),
                      sub_chunks(1, lanes), sub_chunks(c, lanes, at=group),
                      sub_chunks(c, lanes, at=group),
                      pl.BlockSpec((1, 1, 1, p, lanes), state)],
            out_specs=[pl.BlockSpec((1, 1, 1, p, lanes), state),
                       sub_chunks(c, p)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((h, n, c, p), jnp.float32)],
        input_output_aliases={9: 0},             # the pool, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="ssd_chunk",
    )(*(jnp.asarray(v, jnp.int32).reshape(1) for v in (row, layer, fresh)),
      *_chunk_terms(x, dt, A, B, C), pool)
    return jnp.moveaxis(y.reshape(h, t, p), 0, 1), pool


def ssd_chunk_row(x, dt, A, B, C, pool, layer, row, fresh, *,
                  interpret=None):
    """A row's run of T tokens (a multiple of `SUB_CHUNK`) from the state
    at `pool[layer, row]`, which is changed where it lies (`fresh`: it
    counts as zero). x: (T, h, p); dt: (T, h), 0 on a token past the run's
    end (nothing decays, nothing is written); A: (h,); B, C: (T, g, n);
    pool: (L, R, h, p, n) float32, donated. Returns (y (T, h, p) without
    the D x skip, pool). On a TPU the pass over the sub-chunks is the
    Pallas kernel `ssd_chunk`, a head a grid step (module docstring); what
    the sub-chunks need before the state is touched (`_chunk_terms`) is
    batched XLA. `interpret=None` picks the kernel on a TPU and
    `ssd_chunked` elsewhere; True runs the kernel in the Pallas
    interpreter."""
    if interpret is None and jax.default_backend() != "tpu":
        return ssd_chunk_row_reference(x, dt, A, B, C, pool, layer, row,
                                       fresh)
    return _chunk_call(x, dt, A, B, C, pool, layer, row, fresh,
                       interpret=bool(interpret))


def ssd_parity_check(batch: int = 2, seq: int = 37, heads: int = 3,
                     head_dim: int = 8, d_state: int = 5, chunk: int = 8,
                     seed: int = 0, tol: float = 1e-4) -> dict:
    """Duality proof: the chunked matmul form and the O(1) recurrence
    produce the same outputs and final state (max|Δ| bounded — float
    association is the only difference). Deliberately uses a seq length
    that is NOT a chunk multiple so the padding path is covered.
    `diagnostics.py --ssd-parity` runs this; tests pin the bound."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((batch, seq, heads, head_dim)),
                    jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.4, (batch, seq, heads)),
                     jnp.float32)
    A = -jnp.exp(jnp.asarray(rng.uniform(-1.0, 1.0, (heads,)), jnp.float32))
    B = jnp.asarray(rng.standard_normal((batch, seq, d_state)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((batch, seq, d_state)), jnp.float32)
    y_rec, s_rec = ssd_recurrent(x, dt, A, B, C)
    y_chk, s_chk = ssd_chunked(x, dt, A, B, C, chunk=chunk)
    dy = float(jnp.max(jnp.abs(y_rec - y_chk)))
    ds = float(jnp.max(jnp.abs(s_rec - s_chk)))
    return {"max_abs_diff_y": dy, "max_abs_diff_state": ds,
            "tol": float(tol), "chunk": int(chunk), "seq": int(seq),
            "ok": bool(dy < tol and ds < tol)}
