"""Mixture-of-Experts with expert parallelism over a mesh axis.

The reference has no expert parallelism (SURVEY.md §2 checklist: EP ❌);
the TPU-native framework carries it as a first-class strategy so MoE
transformer variants serve and train across chips.

TPU-first design (Mesh-TensorFlow/GShard style, static shapes throughout):

- **Router**: per-token softmax over E experts, top-k gating with
  renormalized weights.
- **Dispatch/combine as einsums**: tokens route via a dense one-hot
  dispatch tensor (B·T, E, C) built with capacity-slot assignment
  (cumsum over the token order per expert, overflow DROPPED — the
  capacity-factor contract of training). No gather/scatter, no dynamic
  shapes: everything lowers to MXU matmuls XLA can shard. A dropped
  token's output depends on which other tokens shared its batch, so this
  path is for the models that name it (`TransformerConfig.n_experts`),
  never for a served model whose rows must not see their tick-mates.
- **Expert parallelism**: expert FFN params are stacked on a leading E
  axis and sharded `P("expert")`; under jit the dispatch einsum's expert
  dim shards the same way, so XLA inserts the all-to-all over ICI —
  exactly the pjit recipe (no hand-written collectives needed).

`moe_apply` is exact w.r.t. its single-device evaluation: sharding the
expert axis changes placement, not math (tests assert equality).

**The served path drops nothing** (`routed_experts`, models.moonlight):
sigmoid scores with a selection bias, top-k weights normalised and
scaled (`sigmoid_topk_route`), or a soft-max over every expert taken
before the choice (`softmax_topk_route`, models.sdar), the tick's VALID (token, expert) pairs
sorted by expert (a stable sort, so a token's own pairs keep their
order whatever its tick-mates), one grouped matrix product over the
experts held (`jax.lax.ragged_dot`: on a TPU XLA lowers it to a Mosaic
grouped matmul whose steps are, for each output tile, the (row tile,
expert) pairs that MEET, each over its contraction tiles: a visit
streams the expert's tk x tn weight pieces against one tile of rows, so
an expert whose rows straddle two row tiles is streamed twice, and rows
past the last expert are never visited), SwiGLU experts or ungated ones
of two matrices (the bank's keys say which), weighted scatter-add back.
The tiles are stated HERE from the operands' shapes (`grouped_tiling`,
the op's `ragged_dot_tiling` attribute) in place of XLA's pick, the
largest power of two up to 512 that divides each dimension: that pick
streams a bank of 2816, 1408, 2304 or 2688 lanes in pieces of 128-256 KB
and multiplies a 512-row tile for the few rows an expert took. Padding
slots form no pair, reach no expert and count in no load. The bank is
handed over WHOLE, all layers' experts on one leading axis, with the
layer's offset into it: a slice of a layer's bank would be copied for
the kernel's operand, 1.2 GB a layer and tick at Moonlight's widths.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from tpu_engine.ops import nn
from tpu_engine.utils.tracing import step_part


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25

    def capacity(self, n_tokens: int) -> int:
        # Static per-expert slot count for a given token count.
        c = int(self.capacity_factor * self.top_k * n_tokens / self.n_experts)
        return max(1, min(c, n_tokens))


def moe_init(key, cfg: MoEConfig):
    kg, kf, kp = jax.random.split(key, 3)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    scale_in = 1.0 / jnp.sqrt(d)
    scale_out = 1.0 / jnp.sqrt(f)
    return {
        "gate": {"kernel": jax.random.normal(kg, (d, e)) * scale_in},
        # Stacked expert FFNs: leading E axis is the expert-parallel shard dim.
        "wi": jax.random.normal(kf, (e, d, f)) * scale_in,
        "wo": jax.random.normal(kp, (e, f, d)) * scale_out,
    }


def _dispatch_tensors(logits, cfg: MoEConfig, n_tokens: int):
    """Build (dispatch, combine) tensors (N, E, C) from router logits (N, E).

    Top-k per token; each chosen (token, expert) pair takes the expert's
    next capacity slot in token order; pairs past capacity are dropped
    (their combine weight is zero) — the standard static-shape contract.
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (N, E)
    cap = cfg.capacity(n_tokens)

    gates = jnp.zeros_like(probs)
    masks = []
    p = probs
    for _ in range(cfg.top_k):
        idx = jnp.argmax(p, axis=-1)
        onehot = jax.nn.one_hot(idx, cfg.n_experts, dtype=probs.dtype)
        masks.append(onehot)
        gates = gates + probs * onehot
        p = p * (1.0 - onehot)
    # Renormalize the kept gates per token.
    denom = jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    gates = gates / denom

    # Capacity slots: for the r-th choice mask, slot = (# earlier tokens
    # choosing this expert across all ranks up to r) — exclusive cumsum.
    dispatch = jnp.zeros((logits.shape[0], cfg.n_experts, cap), jnp.float32)
    combine = jnp.zeros_like(dispatch)
    prior = jnp.zeros((cfg.n_experts,), jnp.float32)
    for onehot in masks:
        pos = jnp.cumsum(onehot, axis=0) - onehot + prior[None, :]  # (N, E)
        prior = prior + jnp.sum(onehot, axis=0)
        in_cap = (pos < cap).astype(jnp.float32) * onehot
        slot = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
        sel = in_cap[..., None] * slot  # (N, E, C)
        dispatch = dispatch + sel
        combine = combine + sel * gates[..., None]
    return dispatch, combine


def moe_apply(params, x, cfg: MoEConfig, dtype=jnp.bfloat16):
    """x: (B, T, d_model) → (B, T, d_model). Dense-dispatch MoE FFN."""
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)
    # Pass the gate dict through (plus a zero bias) so both the plain
    # {"kernel"} and the ops.quant {"kernel_q","kernel_scale"} forms work.
    gate = dict(params["gate"])
    gate.setdefault("bias", jnp.zeros((cfg.n_experts,)))
    logits = nn.dense(gate, xf, dtype=dtype)
    dispatch, combine = _dispatch_tensors(logits, cfg, n)

    xc = xf.astype(dtype)
    # Dispatch: (N, D) x (N, E, C) -> (E, C, D); expert dim shards over
    # the `expert` mesh axis -> XLA all-to-alls tokens to their experts.
    expert_in = jnp.einsum("nd,nec->ecd", xc, dispatch.astype(dtype))
    # Expert stacks may be ops.quant int8 ({wi_q, wi_scale}): the
    # per-(expert, out-channel) scale applies to the einsum OUTPUT —
    # exact, with weights streaming from HBM at 1 byte each. The router
    # gate above deliberately stays full precision (top-k is
    # discontinuous; see ops/quant.quantize_params).
    if "wi_q" in params:
        h = jnp.einsum("ecd,edf->ecf", expert_in,
                       params["wi_q"].astype(dtype))
        h = h * params["wi_scale"][:, None, :]
    else:
        h = jnp.einsum("ecd,edf->ecf", expert_in, params["wi"].astype(dtype))
    h = jax.nn.gelu(h)
    if "wo_q" in params:
        expert_out = jnp.einsum("ecf,efd->ecd", h.astype(dtype),
                                params["wo_q"].astype(dtype))
        expert_out = expert_out * params["wo_scale"][:, None, :]
    else:
        expert_out = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(dtype))
    # Combine: weighted return of expert outputs to token positions.
    out = jnp.einsum("ecd,nec->nd", expert_out,
                     combine.astype(dtype))
    return out.reshape(b, t, d).astype(x.dtype)


def shard_moe_params(params, mesh, axis: str = "expert"):
    """NamedShardings: expert-stacked tensors shard their leading E dim."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def spec(path_leaf):
        path, leaf = path_leaf
        name = "/".join(str(p) for p in path)
        if "wi" in name or "wo" in name:
            return NamedSharding(mesh, P(axis, None, None))
        return NamedSharding(mesh, P())

    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    shardings = [spec(pl) for pl in flat]
    return jax.tree_util.tree_unflatten(tree, shardings)


# -- the served path: no drop --------------------------------------------------

def sigmoid_topk_route(x, router, top_k: int, scale: float):
    """DeepSeek-V3 routing without groups. x: (N, d); router: {"kernel"
    (d, E), "bias" (E,)} float32, the bias being the selection bias
    (`e_score_correction_bias`). Scores are sigmoids in float32; the top
    `top_k` of score + bias are CHOSEN, the weights are the chosen
    scores themselves (not biased), normalised to sum one and times
    `scale`. Returns (experts (N, k) int32, weights (N, k) float32)."""
    with step_part("moe/route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router["kernel"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, experts = jax.lax.top_k(scores + router["bias"], top_k)
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * scale
        return experts.astype(jnp.int32), weights


def softmax_topk_route(x, router, top_k: int):
    """Qwen3-MoE routing. x: (N, d); router: {"kernel" (d, E)} float32.
    The probabilities are a soft-max over ALL E experts in float32, taken
    BEFORE the choice; the top `top_k` are chosen and their probabilities
    normalised to sum one (`norm_topk_prob`). No selection bias, no
    scaling factor. Returns (experts (N, k) int32, weights (N, k)
    float32)."""
    with step_part("moe/route"):
        probs = jax.nn.softmax(jnp.dot(
            x.astype(jnp.float32), router["kernel"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        chosen, experts = jax.lax.top_k(probs, top_k)
        weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), weights


def relu2(x):
    """relu(x)^2: the nonlinearity of an ungated two-matrix expert."""
    return jnp.square(jax.nn.relu(x))


# -- the grouped product's tiles ------------------------------------------------

_LANES = 128
# Rows a tile. Measured on a v5e at the cells' shapes (PERF.md section 6,
# PR 51): 64, 128 and 192 rows give the same call within 2 %, 256 is 3-8 %
# slower (a visit's product, 2 * tm * tk * tn FLOPs at 197 TF/s, passes its
# weight piece's DMA at 819 GB/s from ~240 rows), and 128 leaves the VMEM for
# the whole contraction of every bank served today.
_ROW_TILE = 128
# A weight piece (tk x tn) of at least this much keeps a step's fixed cost
# small beside its DMA.
_PIECE_BYTES = 2 ** 20
# What the Mosaic grouped matmul may hold in its scoped VMEM (16 MiB on a
# v5e): compiled for a v5e with no chip attached, blocks counted as
# `_block_bytes` counts them pass up to 14.8 MiB and fail from 15.2.
_VMEM_BLOCK_BYTES = 14 * 2 ** 20

# "<m>x<K>x<N>" -> "tm,tk,tn" ("xla": none stated): the tiling each grouped
# product was traced with, once a shape (`traced_tilings`).
_TRACED = {}


def row_tile(m: int, itemsize: int) -> int:
    """The row tile of a pair list of m rows: `_ROW_TILE`, or the whole of
    a shorter list rounded up to the dtype's sublane packing."""
    packing = 32 // itemsize
    return min(_ROW_TILE, -(-m // packing) * packing)


def _block_bytes(tm: int, tk: int, tn: int, k: int, itemsize: int) -> int:
    """The kernel's VMEM blocks: weights, x and the float32 output double
    buffered, and the accumulator a split contraction adds."""
    return (2 * tk * tn * itemsize + 2 * tm * tk * itemsize
            + 2 * tm * tn * 4 + (tm * tn * 4 if tk < k else 0))


def _lane_divisors(d: int):
    """The multiples of 128 that divide d, widest first."""
    return [t for t in range(d, 0, -_LANES) if d % t == 0]


def grouped_tiling(m: int, k: int, n: int, itemsize: int):
    """The (row, contraction, output) tiles of a grouped product x (m, k) @
    bank (G, k, n) -> float32, from the shapes alone, or None where none is
    legal (XLA then picks, from the dimensions' power-of-two divisors). tk
    and tn are multiples of 128 that divide k and n; tm divides m
    (`row_tile`: the caller rounds its list up to it). Of the tiles whose
    blocks fit (`_VMEM_BLOCK_BYTES`): a weight piece of `_PIECE_BYTES` or
    more, then the whole contraction (no accumulator pass, x fetched once a
    row tile), then the larger piece."""
    tm = row_tile(m, itemsize)
    if m % tm or k % _LANES or n % _LANES:
        return None
    fits = [(tk, tn) for tk in _lane_divisors(k) for tn in _lane_divisors(n)
            if _block_bytes(tm, tk, tn, k, itemsize) <= _VMEM_BLOCK_BYTES]
    tk, tn = max(fits, key=lambda t: (          # 128 x 128 always fits
        t[0] * t[1] * itemsize >= _PIECE_BYTES, t[0] == k, t[0] * t[1]))
    return tm, tk, tn


def traced_tilings() -> dict:
    """{"<m>x<K>x<N>": "tm,tk,tn" or "xla"} of every grouped product this
    process has traced."""
    return dict(_TRACED)


def grouped_dot(x, bank, sizes):
    """`jax.lax.ragged_dot` (float32 out) with the tiling `grouped_tiling`
    states for its shapes as the op's `ragged_dot_tiling` attribute, which
    XLA's TPU lowering follows; no attribute where it states none."""
    (m, k), n = x.shape, bank.shape[-1]
    tiling = grouped_tiling(m, k, n, x.dtype.itemsize)
    stated = "xla" if tiling is None else ",".join(map(str, tiling))
    _TRACED[f"{m}x{k}x{n}"] = stated
    with (contextlib.nullcontext() if tiling is None
          else set_xla_metadata(ragged_dot_tiling=stated)):
        return jax.lax.ragged_dot(x, bank, sizes,
                                  preferred_element_type=jnp.float32)


def routed_experts(x, valid, experts, weights, bank, *, first_group,
                   n_experts: int, held=None, max_tokens=None,
                   dtype=jnp.bfloat16, activation=None):
    """Apply each valid token's chosen experts and sum them, weighted.

    x: (N, d), d the width the experts read (the model's, or a latent's
    the caller projected to: `y` comes back as wide); valid: (N,) bool,
    padding slots False; experts, weights: (N, k) from the router. The
    bank states its expert by its keys: {"gate_up" (G, d, 2f), "down"
    (G, f, d)} a gated expert, `activation(gate) * up` with the gate the
    first half of `gate_up` (default SiLU: a SwiGLU; `jax.nn.relu`: a
    ReGLU), or {"up" (G, d, f), "down" (G, f, d)} an ungated expert of two
    matrices with `activation` between them, which that bank must state.
    `activation` is f32 -> f32 for either bank.
    G >= n_experts groups, this layer's expert e at group
    `first_group + e` (a traced scalar: the bank of every layer, whole).
    `held` = (first, count): the experts this shard holds (default all);
    a pair whose expert lies outside is another shard's and forms no row
    here, and only the held experts' groups, [first_group + first,
    first_group + first + count), need exist in the bank. `max_tokens`: a static bound on how many slots can be valid
    (the scheduler's token budget plus its rows); it sizes the sorted
    pair list, so padding costs no gather either; the list is a whole
    number of row tiles (`row_tile`), the pairs that round it up dead ones.
    Returns (y (N, d) float32, rows (n_experts,) int32: the rows each
    expert took)."""
    n, k = experts.shape
    first, count = held or (0, n_experts)
    # The pair list's ordering goes with the router's part; the gather of
    # pairs, the two products and the combine are the experts'.
    with step_part("moe/route"):
        mine = (valid[:, None] & (experts >= first)
                & (experts < first + count))
        # Pairs that form no row sort behind every expert.
        eid = jnp.where(mine, experts, n_experts).reshape(-1)
        pairs = min(n, max_tokens or n) * k
        tile = row_tile(pairs, jnp.dtype(dtype).itemsize)
        pairs = -(-pairs // tile) * tile
        # Past the slots the list is padded with pairs that form no row.
        eid = jnp.pad(eid, (0, max(0, pairs - n * k)),
                      constant_values=n_experts)
        order = jnp.argsort(eid, stable=True)[:pairs]
        eid_sorted = eid[order]
        # A padding pair reads the last slot's row and weight; `live` masks
        # it.
        order = jnp.minimum(order, n * k - 1)
        token = order // k
        rows = jnp.zeros((n_experts + 1,), jnp.int32).at[eid].add(
            1)[:n_experts]
        gated = "gate_up" in bank
        first_matrix = bank["gate_up" if gated else "up"]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((first_matrix.shape[0],), jnp.int32),
            rows[first:first + count], (first_group + first,))
    with step_part("moe/experts"):
        xs = x[token].astype(dtype)
        hidden = grouped_dot(xs, first_matrix.astype(dtype), sizes)
        if gated:
            gate, up = jnp.split(hidden, 2, axis=-1)
            hidden = (activation or jax.nn.silu)(gate) * up
        else:
            hidden = activation(hidden)
        hidden = hidden.astype(dtype)
        out = grouped_dot(hidden, bank["down"].astype(dtype), sizes)
        # Rows past the last group hold whatever the kernel left there.
        live = (eid_sorted < n_experts)[:, None]
        out = jnp.where(live, out * weights.reshape(-1)[order][:, None], 0.0)
        y = jnp.zeros((n, x.shape[-1]), jnp.float32).at[token].add(out)
    return y, rows
