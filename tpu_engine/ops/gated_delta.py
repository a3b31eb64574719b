"""The gated delta rule: a linear-attention layer whose fixed-size state is
decayed, corrected by a rank-one term and written to, a token at a time.

A head keeps S (d_v x d_k, float32). Token t brings a key k_t and a query
q_t (d_k lanes), a value v_t (d_v lanes), a decay a_t = exp(g_t) in (0, 1]
and a writing strength b_t (in (0, 2) where negative eigenvalues are
allowed):

    S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t

Two forms of the one recurrence:

- `gdn_step`: one token a row, every row of a batch at once. The DECODE
  form: the state is read, changed and written once.
- `gdn_chunk`: a row's run of tokens in sub-chunks of `SUB_CHUNK`. Inside a
  sub-chunk the rank-one corrections couple the tokens through a unit
  lower-triangular system (the WY form of the Gated DeltaNet paper): with
  G_i = sum_{j<=i} g_j, A_ij = b_i exp(G_i - G_j) k_i.k_j for j < i and
  T = (I + A)^-1,

      U = T (b v) - T (b exp(G) k) S_0^T         the values really written
      O = exp(G) q S_0^T + (q k^T * D) U         D_ij = exp(G_i - G_j), j <= i
      S_C = exp(G_C) S_0 + U^T (exp(G_C - G) k)

  so a sub-chunk is a handful of matrix products and the state is carried
  once a sub-chunk. The PREFILL form; it starts from a GIVEN state and
  hands the last one back. A token with b = 0 and g = 0 changes nothing:
  that is how a run shorter than its padded length is masked.
  `gdn_chunk_row` is the form as the served path takes it, on a state that
  lies in a pool (below).

`gdn_scan` is the recurrence as written, a `lax.scan` over tokens: what the
two forms are tested against.

`gdn_step_rows` is the step as the served path takes it: the rows' states
lie in a POOL (layers, rows, H, d_v, d_k) and are changed where they lie.
On a TPU it is a Pallas kernel named `gdn_step` in a trace: the grid is
(rows, blocks of heads), a step's block of the pool is chosen by the layer
and the row's pool row in SMEM (neither the layer nor the gather ever
materializes) and aliased to the output, so a state is read once and
written once. The grid takes the rows that step FIRST (`live_first`) and a
grid step past the last of them names the blocks the step before it named
(`step_at`): a row that takes no step (a free slot, a prompt that waits,
the tick's chunk row) moves no byte. A head's update is multiplies and
lane sums on a (d_v, d_k) tile: k, q and the decay come as rows, b v as a
column (the heads of a block on the lanes, so a column is a lane slice), o
leaves as a column.
Elsewhere (and as the kernel's reference) it is `gdn_step` over a gather
of the rows and a scatter back. `gdn_chunk_row` is a row's run of tokens
from its state in the same pool: the kernel `gdn_chunk`, a head a grid
step, solves the triangular systems of ALL the run's sub-chunks first
(they wait for no state) and then carries the state through the
sub-chunks' products in VMEM. The solve is BLOCKED, in blocks of
`KDA_BLOCK` = 16 rows. Only the 16 x 16 diagonal blocks L_b are solved by
forward substitution, every diagonal block of the run at once (16 blocks
for 256 tokens, a block a sublane row: 16 dependent rows a head where a
row-by-row substitution over the whole right-hand side takes 64 a
sub-chunk, one sub-chunk after another). A block's right-hand side is its
own rows of A left of the diagonal block beside the identity, 64 lanes,
so what comes out is D_b = (I + L_b)^-1 and N = D A_below at once; a row
is its right-hand side less L_b[r, j] times row j, a multiply and a
subtract and no reduction (`_solve_diagonal_blocks`). What couples the
blocks goes to the MXU: I + A = (I + A_diagonal)(I + N) and N is strictly
block-lower over four blocks, N^4 = 0, so

    (I + A)^-1 = (I + N^2)(I - N) D

with nothing left out: two small products a sub-chunk, and U_v, W are one
product each with that. The diagonal blocks are NOT inverted by a series
(I - L + L^2 - ... or its doubling form): with b up to 2 and unit keys
|L_ij| reaches 2, the sixteen powers grow to 2^15 before they cancel in
float32; substitution never forms them. The powers of N are the products
the two-level merge (T_21 = -T_22 A_21 T_11 at 16 -> 32 -> 64) would sum.

**A gate a key CHANNEL** (Kimi Delta Attention, arXiv:2510.26692). Every
form takes `g` a head, (..., H), or a key channel, (..., H, d_k): the decay
is then Diag(a_t) on the state's d_k lanes,

    S_t = S_{t-1} Diag(a_t) + b_t (v_t - S_{t-1} Diag(a_t) k_t) k_t^T

and the two coincide where a channel gate is the same in every channel.
The step is the same multiplies with the decay row no longer constant. In
the chunked form the decays no longer factor out of k_i.k_j: with
Gamma_i = exp(G_i) a vector, A_ij = b_i sum_c k_ic k_jc exp(G_ic - G_jc).
The textbook split k_i Gamma_i . k_j / Gamma_j overflows (a channel whose
gate is -5 a token underflows Gamma within 18 tokens), so
`_kda_chunk_terms` takes every difference BEFORE the exponential: inside a
diagonal block of `KDA_BLOCK` tokens as it stands, and between a block and
the earlier ones of its sub-chunk through the block's start, whose two
factors exp(G_i - G_start) and exp(G_start - G_j) are both at most 1. What
meets the state (exp(G) q, b exp(G) k, exp(G_C - G) k, exp(G_C)) never
needed a quotient. The pass over the sub-chunks is then the scalar gate's,
with exp(G_C) a lane vector: the same kernel bodies, named `kda_step` and
`kda_chunk` in a trace where the gate is a channel's.

Everything here is float32; the products take `PRECISION` (the MXU's
float32 passes), since a state error is carried for the rest of the row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.scipy.linalg import solve_triangular

SUB_CHUNK = 64
# A diagonal block inside a sub-chunk: a channel gate's, and the chunk
# kernel's solve (module docstring), which counts on four to a sub-chunk.
KDA_BLOCK = 16
assert SUB_CHUNK == 4 * KDA_BLOCK
PRECISION = jax.lax.Precision.HIGHEST
# State a grid step of the step kernel holds (in and out, double-buffered:
# four of these in VMEM): 15 of Olmo-Hybrid-7B's 30 heads, 1.47 MB.
_STEP_BLOCK_BYTES = 3 << 19


def _over_state(g, k):
    """A gate as it meets a state's (..., d_v, d_k): a head's number, or
    (where `g` has `k`'s rank) a key channel's vector on the lanes."""
    return g[..., None, :] if g.ndim == k.ndim else g[..., None, None]


def gdn_scan(q, k, v, g, beta, state):
    """The recurrence, token by token. q, k: (T, H, d_k); v: (T, H, d_v);
    g: (T, H) or (T, H, d_k); beta: (T, H); state: (H, d_v, d_k). Returns
    (o (T, H, d_v), state)."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(_over_state(g_t, k_t))
        u = b_t[:, None] * (v_t - (s * k_t[:, None, :]).sum(-1))
        s = s + u[:, :, None] * k_t[:, None, :]
        return s, (s * q_t[:, None, :]).sum(-1)

    state, o = jax.lax.scan(step, state.astype(jnp.float32),
                            tuple(x.astype(jnp.float32)
                                  for x in (q, k, v, g, beta)))
    return o, state


def gdn_step(q, k, v, g, beta, state):
    """One token a row. q, k: (B, H, d_k); v: (B, H, d_v); g: (B, H) or
    (B, H, d_k); beta: (B, H); state: (B, H, d_v, d_k) float32. Returns
    (o (B, H, d_v), state). Plain multiplies and sums over the state's
    lanes: one pass over the state for S k, one for the update and S q."""
    s = state * jnp.exp(_over_state(g, k))
    u = beta[..., None] * (v - (s * k[..., None, :]).sum(-1))
    s = s + u[..., None] * k[..., None, :]
    return (s * q[..., None, :]).sum(-1), s


def gdn_step_rows_reference(q, k, v, g, beta, pool, layer, rows, live, fresh):
    """`gdn_step_rows` by a gather of the rows' states and a scatter back."""
    old = pool[layer, rows]
    o, new = gdn_step(q, k, v, g, beta,
                      jnp.where(fresh[:, None, None, None], 0.0, old))
    return o, pool.at[layer, rows].set(
        jnp.where(live[:, None, None, None], new, old))


def live_first(live):
    """The order a step call's grid takes a tick's rows in: (order (B,)
    int32, the rows with `live` set first and among themselves as they
    stand, the places behind them filled with the last of them (row 0 where
    none is live); count (1,) int32, how many are live). A cumulative sum
    and one scatter, and nothing of `live`'s layer: XLA shares it between
    the layers of a step."""
    b = live.shape[0]
    at = jnp.cumsum(live.astype(jnp.int32))
    place = jnp.arange(b, dtype=jnp.int32)
    order = jnp.zeros(b, jnp.int32).at[jnp.where(live, at - 1, b)].set(
        place, mode="drop")
    count = at[-1:]
    return jnp.where(place < count, order,
                     order[jnp.maximum(count - 1, 0)]), count


def step_at(i, j, order, count, blocks: int):
    """The (row of the batch, block of heads) that step (i, j) of a step
    call's grid names, `order` and `count` from `live_first`. The first
    `count` rows of the grid are the live rows, a block a step. A step
    past them names what the last live step named, so the pipeline fetches
    nothing for it and writes nothing back: the last live block leaves
    VMEM once, when the grid ends. With no live row every step names the
    last block of row 0 of the batch, which the wrappers point at the
    pool's null row."""
    return order[i], jnp.where(i < count[0], j, blocks - 1)


def _copy_null_block(count_ref, s_ref, s_out):
    """With no live row the grid's one block, one of the null row's, goes
    back as it came: the first step copies it, no other touches it."""
    @pl.when((count_ref[0] == 0) & (pl.program_id(0) == 0)
             & (pl.program_id(1) == 0))
    def _():
        s_out[...] = s_ref[...]


def _step_kernel(rows_ref, layer_ref, fresh_ref, order_ref, count_ref,
                 vec_ref, bv_ref, s_ref, s_out, o_ref, *, heads):
    del rows_ref, layer_ref, order_ref           # the index maps read them
    b = pl.program_id(0)

    @pl.when(b < count_ref[0])
    def _():
        keep = (fresh_ref[b] == 0).astype(jnp.float32)
        for i in range(heads):
            s = s_ref[0, 0, i] * keep                        # (d_v, d_k)
            k, q, a, kb = (vec_ref[0, 0, n, i:i + 1, :] for n in range(4))
            u = bv_ref[0, 0, :, i:i + 1] - jnp.sum(s * kb, axis=1,
                                                   keepdims=True)
            s = s * a + u * k
            s_out[0, 0, i] = s
            o_ref[0, 0, :, i:i + 1] = jnp.sum(s * q, axis=1, keepdims=True)

    _copy_null_block(count_ref, s_ref, s_out)


def _in_order(live, rows, *per_row):
    """What a step call's scalar core reads: each row's pool row (the null
    row for a row that takes no step) and `per_row`, by the grid's place
    and not the batch's; then `live_first`'s order and count."""
    order, count = live_first(live)
    return tuple(v.astype(jnp.int32)[order]
                 for v in (jnp.where(live, rows, 0),) + per_row) + (
        order, count)


def _step_call(q, k, v, g, beta, pool, layer, rows, live, fresh, *,
               interpret: bool):
    b, h, dk = k.shape
    dv = v.shape[-1]
    lanes = -(-dk // 128) * 128
    heads = max(p for p in range(1, h + 1) if h % p == 0
                and (p == 1 or p * dv * lanes * 4 <= _STEP_BLOCK_BYTES))
    blocks = h // heads
    rows, fresh, order, count = _in_order(live, rows, fresh)
    a = jnp.exp(g)
    channel = g.ndim == k.ndim
    # Rows of d_k lanes a head: k, q, the decay, and b a k (S k is wanted
    # as b a S k); the heads of a block side by side.
    if channel:
        vec = jnp.stack([k, q, a, beta[..., None] * a * k], axis=1)
    else:
        vec = jnp.stack([k, q, jnp.broadcast_to(a[..., None], k.shape),
                         (beta * a)[..., None] * k], axis=1)
    vec = vec.reshape(b, 4, blocks, heads, dk).transpose(0, 2, 1, 3, 4)
    # b v as columns: a block's heads on the lanes.
    bv = (beta[..., None] * v).reshape(b, blocks, heads, dv)
    bv = bv.transpose(0, 1, 3, 2)

    def state(i, j, rows, layer, fresh, order, count):
        return (layer[0], rows[i], step_at(i, j, order, count, blocks)[1],
                0, 0)

    def column(i, j, rows, layer, fresh, order, count):
        return step_at(i, j, order, count, blocks) + (0, 0)

    def rows_of_lanes(i, j, *prefetch):
        return column(i, j, *prefetch) + (0,)

    pool, o = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,      # rows, layer, fresh, order, count
            grid=(b, blocks),
            in_specs=[
                pl.BlockSpec((1, 1, 4, heads, dk), rows_of_lanes),
                pl.BlockSpec((1, 1, dv, heads), column),
                pl.BlockSpec((1, 1, heads, dv, dk), state)],
            out_specs=[pl.BlockSpec((1, 1, heads, dv, dk), state),
                       pl.BlockSpec((1, 1, dv, heads), column)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, blocks, dv, heads),
                                        jnp.float32)],
        input_output_aliases={7: 0},          # the pool, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="kda_step" if channel else "gdn_step",
    )(rows, jnp.asarray(layer, jnp.int32).reshape(1), fresh, order, count,
      vec, bv, pool)
    # A dead row's column was never written.
    o = o.transpose(0, 1, 3, 2).reshape(b, h, dv)
    return jnp.where(live[:, None, None], o, 0.0), pool


def gdn_step_rows(q, k, v, g, beta, pool, layer, rows, live, fresh, *,
                  interpret=None):
    """One token a row, the states changed where they lie. q, k: (B, H,
    d_k); v: (B, H, d_v); g: (B, H) or (B, H, d_k); beta: (B, H); pool: (L, R, H, d_v, d_k)
    float32, donated; layer: the pool's layer; rows: (B,) each row's pool
    row; live: (B,) rows that take the step (the others' states are left
    as they are, their blocks never visited; their outputs mean nothing,
    0 from the kernel); fresh: (B,) rows whose state is zero before the
    step. Returns (o (B, H, d_v), pool). The kernel is named `kda_step` in
    a trace where the gate is a channel's. With no live row the call is
    still made: the one block its grid names, of the null row, is copied
    onto itself (`step_at`).
    `interpret=None` picks the kernel on a TPU and the gather elsewhere;
    True runs the kernel in the Pallas interpreter."""
    if interpret is None and jax.default_backend() != "tpu":
        return gdn_step_rows_reference(q, k, v, g, beta, pool, layer, rows,
                                       live, fresh)
    return _step_call(q, k, v, g, beta, pool, layer, rows, live, fresh,
                      interpret=bool(interpret))


def _heads_first(x, n):
    """(T, H, ...) -> (H, n, C, ...)."""
    x = jnp.moveaxis(x, 1, 0)
    return x.reshape(x.shape[0], n, SUB_CHUNK, *x.shape[2:])


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=PRECISION)


def _chunk_terms(q, k, v, g, beta):
    """What a run's sub-chunks need before the state is touched, each
    (H, n, C, ...), n sub-chunks of C = `SUB_CHUNK` tokens: A (strictly
    lower), the solve's two right-hand sides b v and b exp(G) k, q k^T * D,
    exp(G) q (reads S_0), exp(G_C - G) k (feeds S_C), and exp(G_C)
    (H, n)."""
    t = g.shape[0]
    if t % SUB_CHUNK:
        raise ValueError(f"a run of {t} tokens is no multiple of "
                         f"{SUB_CHUNK}")
    q, k, v, g, beta = (_heads_first(x.astype(jnp.float32), t // SUB_CHUNK)
                        for x in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=-1)                              # (H, n, C)
    at = jnp.arange(SUB_CHUNK)
    # exp(G_i - G_j) where j <= i, 0 above the diagonal: masked before
    # the exponential, whose argument is positive there.
    decay = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    a = jnp.where(at[:, None] > at[None, :],
                  beta[..., None] * decay * _mm("hnik,hnjk->hnij", k, k),
                  0.0)
    return (a, beta[..., None] * v, (beta * jnp.exp(cum))[..., None] * k,
            _mm("hnik,hnjk->hnij", q, k) * decay,
            q * jnp.exp(cum)[..., None],
            k * jnp.exp(cum[..., -1:] - cum)[..., None],
            jnp.exp(cum[..., -1]))


def _kda_chunk_terms(q, k, v, g, beta):
    """`_chunk_terms` for a gate a key channel, g (T, H, d_k); exp(G_C)
    comes as (H, n, d_k). No quotient by exp(G) is taken (module
    docstring): a diagonal block of `KDA_BLOCK` tokens sums k_ic k_jc
    exp(G_ic - G_jc) over the channels as it stands; block I reaches an
    earlier token j of its sub-chunk through its own start,
    exp(G_i - G_start(I)) exp(G_start(I) - G_j), a matrix product."""
    t, h = g.shape[:2]
    if t % SUB_CHUNK:
        raise ValueError(f"a run of {t} tokens is no multiple of "
                         f"{SUB_CHUNK}")
    n, c, m = t // SUB_CHUNK, SUB_CHUNK, SUB_CHUNK // KDA_BLOCK
    q, k, v, g, beta = (_heads_first(x.astype(jnp.float32), n)
                        for x in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=2)                               # (H, n, C, d_k)

    def blocks(x):
        return x.reshape(h, n, m, KDA_BLOCK, -1)

    q_b, k_b, cum_b = blocks(q), blocks(k), blocks(cum)
    at = jnp.arange(KDA_BLOCK)
    decay = jnp.exp(jnp.where(
        (at[:, None] >= at[None, :])[:, :, None],
        cum_b[..., :, None, :] - cum_b[..., None, :, :], -jnp.inf))

    def on_diagonal(x_b):
        """(H, n, m, B, B) -> (H, n, C, C), the blocks down the diagonal."""
        inside = (x_b[..., :, None, :] * k_b[..., None, :, :] * decay).sum(-1)
        return (inside[..., :, :, None, :]
                * jnp.eye(m)[:, None, :, None]).reshape(h, n, c, c)

    # G at each block's start (the last token of the block before it) and
    # the two factors through it; a token at or past the start gets 0.
    start = jnp.concatenate([jnp.zeros_like(cum_b[:, :, :1, -1]),
                             cum_b[:, :, :-1, -1]], axis=2)   # (H, n, m, d_k)
    down = jnp.exp(cum_b - start[..., None, :])
    before = (jnp.arange(c)[None, :]
              < (jnp.arange(m) * KDA_BLOCK)[:, None])[:, :, None]
    up = k[:, :, None] * jnp.exp(jnp.where(
        before, start[..., None, :] - cum[:, :, None], -jnp.inf))

    def below_diagonal(x_b):
        return _mm("hnmik,hnmjk->hnmij", x_b * down, up).reshape(h, n, c, c)

    rows = jnp.arange(c)
    a = jnp.where(rows[:, None] > rows[None, :],
                  beta[..., None] * (on_diagonal(k_b) + below_diagonal(k_b)),
                  0.0)
    return (a, beta[..., None] * v, beta[..., None] * jnp.exp(cum) * k,
            on_diagonal(q_b) + below_diagonal(q_b),
            q * jnp.exp(cum), k * jnp.exp(cum[:, :, -1:] - cum),
            jnp.exp(cum[:, :, -1]))


def _terms(q, k, v, g, beta):
    """A run's chunk terms by the gate's rank; exp(G_C) as it scales a
    state's lanes, (H, n, 1 or d_k)."""
    if g.ndim == k.ndim:
        return _kda_chunk_terms(q, k, v, g, beta)
    terms = _chunk_terms(q, k, v, g, beta)
    return terms[:-1] + (terms[-1][..., None],)


def gdn_chunk(q, k, v, g, beta, state):
    """A row's run of T tokens, T a multiple of `SUB_CHUNK`, from `state`.
    Shapes as `gdn_scan`. Returns (o (T, H, d_v), state (H, d_v, d_k))."""
    t, h = g.shape[:2]
    a, bv, kb, qk, q_in, k_out, last = _terms(q, k, v, g, beta)
    solved = solve_triangular(a + jnp.eye(SUB_CHUNK),
                              jnp.concatenate([bv, kb], axis=-1),
                              lower=True, unit_diagonal=True)
    u_v, w = solved[..., :bv.shape[-1]], solved[..., bv.shape[-1]:]

    def sub_chunk(s, x):
        u_v, w, qk, q_in, k_out, last = x
        u = u_v - _mm("hck,hvk->hcv", w, s)
        o = _mm("hck,hvk->hcv", q_in, s) + _mm("hij,hjv->hiv", qk, u)
        s = s * last[:, None, :] + _mm("hcv,hck->hvk", u, k_out)
        return s, o

    state, o = jax.lax.scan(
        sub_chunk, state.astype(jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0)
              for x in (u_v, w, qk, q_in, k_out, last)))
    # (n, H, C, d_v) -> (T, H, d_v)
    return jnp.moveaxis(o, 1, 2).reshape(t, h, -1), state


def gdn_chunk_row_reference(q, k, v, g, beta, pool, layer, row, fresh):
    """`gdn_chunk_row` by `gdn_chunk` on the row's state, written back."""
    o, last = gdn_chunk(q, k, v, g, beta,
                        jnp.where(fresh, 0.0, pool[layer, row]))
    return o, pool.at[layer, row].set(last)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=PRECISION,
                               preferred_element_type=jnp.float32)


def _solve_diagonal_blocks(a_ref, nd_ref):
    """Forward substitution inside the diagonal blocks of a head's run, all
    nb = T / `KDA_BLOCK` of them at once, a block a SUBLANE row. Block b
    (block row p of its sub-chunk) solves (I + L_b) X = [A_below | I | 0],
    its own `KDA_BLOCK` rows of A left of the diagonal block, the identity
    on it, zeros right of it (the sub-chunk's 64 lanes): X = [N | D_b | 0],
    D_b = (I + L_b)^-1 and N = D A_below, into the block's rows of
    `nd_ref` (T, C). Row r of every block is its right-hand side less
    L_b[r, j] times row j for j < r: a multiply and a subtract of two vregs
    each, L_b[r, j] spread over the lanes, and no reduction at all: 16
    dependent rows a head. L_b's row r lies in A's row at lane 16 p on:
    four lane slices, a block taking its block row's. (A roll whose shift
    grows by 16 a sublane row would do it in one: the v5e takes no such
    stride, and the interpreter does.)"""
    nb = a_ref.shape[1] // KDA_BLOCK
    shape = (nb, SUB_CHUNK)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    first = KDA_BLOCK * (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                         % (SUB_CHUNK // KDA_BLOCK))
    block_row = jax.lax.broadcasted_iota(
        jnp.int32, (nb, KDA_BLOCK), 0) % (SUB_CHUNK // KDA_BLOCK)
    rows = []
    for r in range(KDA_BLOCK):
        at = pl.ds(r, nb, stride=KDA_BLOCK)      # row r of every block
        a_r = a_ref[0, at, :]
        x = jnp.where(lane < first, a_r,
                      (lane == first + r).astype(jnp.float32))
        if r:
            l_r = a_r[:, :KDA_BLOCK]
            for p in range(1, SUB_CHUNK // KDA_BLOCK):
                l_r = jnp.where(block_row == p,
                                a_r[:, p * KDA_BLOCK:(p + 1) * KDA_BLOCK],
                                l_r)
        for j in range(r):
            x = x - l_r[:, j:j + 1] * rows[j]
        rows.append(x)
        nd_ref[at, :] = x


def _chunk_kernel(row_ref, layer_ref, fresh_ref, a_ref, bv_ref,
                  kb_ref, qk_ref, qin_ref, kout_ref, last_ref, s_ref, s_out,
                  o_ref, u_v, w, nd, *, n):
    """A head of a row's run. The triangular systems of all its sub-chunks
    first, in blocks of `KDA_BLOCK` (module docstring), then the pass that
    carries the state."""
    del row_ref, layer_ref                       # the index maps read them
    c = SUB_CHUNK
    _solve_diagonal_blocks(a_ref, nd)
    block = tuple(jax.lax.broadcasted_iota(jnp.int32, (c, c), axis)
                  // KDA_BLOCK for axis in (0, 1))
    for i in range(n):
        at = slice(i * c, (i + 1) * c)
        # I + A = (I + A_diagonal)(I + N): (I + A)^-1 = (I + N)^-1 D, and
        # N is strictly block-lower over four blocks, N^4 = 0, so
        # (I + N)^-1 = (I + N^2)(I - N) with nothing left out.
        solved = nd[at, :]                       # N, and D on its blocks
        d = jnp.where(block[0] == block[1], solved, 0.0)
        nil = jnp.where(block[0] > block[1], solved, 0.0)
        squared = _dot(nil, jnp.concatenate([nil, d], axis=1),
                       ((1,), (0,)))             # N^2 | N D
        t = d - squared[:, c:]                   # (I - N) D
        t = t + _dot(squared[:, :c], t, ((1,), (0,)))   # (I + A)^-1
        u_v[at, :] = _dot(t, bv_ref[0, i], ((1,), (0,)))
        w[at, :] = _dot(t, kb_ref[0, i], ((1,), (0,)))
    s = s_ref[0, 0, 0] * (fresh_ref[0] == 0).astype(jnp.float32)
    for i in range(n):
        at = slice(i * c, (i + 1) * c)
        u = u_v[at, :] - _dot(w[at, :], s, ((1,), (1,)))         # (C, d_v)
        o_ref[0, i] = (_dot(qin_ref[0, i], s, ((1,), (1,)))
                       + _dot(qk_ref[0, i], u, ((1,), (0,))))
        s = s * last_ref[0, i] + _dot(u, kout_ref[0, i], ((0,), (0,)))
    s_out[0, 0, 0] = s


def _chunk_call(q, k, v, g, beta, pool, layer, row, fresh, *,
                interpret: bool):
    t, h, dk = k.shape
    dv = v.shape[-1]
    n = t // SUB_CHUNK
    a, bv, kb, qk, q_in, k_out, last = _terms(q, k, v, g, beta)
    last = jnp.broadcast_to(last[:, :, None, :], (h, n, 1, dk))

    def head(j, *_):
        return (j, 0, 0, 0)

    def state(j, row, layer, *_):
        return (layer[0], row[0], j, 0, 0)

    def sub_chunks(*shape):
        return pl.BlockSpec((1, n) + shape, head)

    c = SUB_CHUNK
    pool, o = pl.pallas_call(
        functools.partial(_chunk_kernel, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                # row, layer, fresh
            grid=(h,),
            in_specs=[pl.BlockSpec((1, t, c), lambda j, *_: (j, 0, 0)),
                      sub_chunks(c, dv), sub_chunks(c, dk),
                      sub_chunks(c, c), sub_chunks(c, dk),
                      sub_chunks(c, dk), sub_chunks(1, dk),
                      pl.BlockSpec((1, 1, 1, dv, dk), state)],
            out_specs=[pl.BlockSpec((1, 1, 1, dv, dk), state),
                       sub_chunks(c, dv)],
            scratch_shapes=[pltpu.VMEM((t, dv), jnp.float32),
                            pltpu.VMEM((t, dk), jnp.float32),
                            pltpu.VMEM((t, c), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((h, n, c, dv), jnp.float32)],
        input_output_aliases={10: 0},             # the pool, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="kda_chunk" if g.ndim == k.ndim else "gdn_chunk",
    )(*(jnp.asarray(x, jnp.int32).reshape(1) for x in (row, layer, fresh)),
      a.reshape(h, t, c), bv, kb, qk, q_in, k_out, last, pool)
    return jnp.moveaxis(o.reshape(h, t, dv), 0, 1), pool


def gdn_chunk_row(q, k, v, g, beta, pool, layer, row, fresh, *,
                  interpret=None):
    """A row's run of T tokens (a multiple of `SUB_CHUNK`; shapes as
    `gdn_scan`) from the state at `pool[layer, row]`, which is changed
    where it lies (`fresh`: it counts as zero). pool: (L, R, H, d_v, d_k)
    float32, donated. Returns (o (T, H, d_v), pool). On a TPU the
    triangular solves (blocked: module docstring) and the pass over the
    sub-chunks are a Pallas kernel named `gdn_chunk` in a trace, a head a
    grid step, its state block chosen by the layer and the row in SMEM and
    aliased to the output; what the sub-chunks need before the state is
    touched (`_chunk_terms`) is batched XLA. Where the gate is a channel's
    the kernel is named `kda_chunk`. `interpret=None` picks the kernel on
    a TPU and `gdn_chunk` elsewhere; True runs the kernel in the Pallas
    interpreter."""
    if interpret is None and jax.default_backend() != "tpu":
        return gdn_chunk_row_reference(q, k, v, g, beta, pool, layer, row,
                                       fresh)
    return _chunk_call(q, k, v, g, beta, pool, layer, row, fresh,
                       interpret=bool(interpret))
