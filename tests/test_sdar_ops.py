"""What the SDAR family (models/sdar.py) asks of shared code: the paged
kernel's block-causal mask (`mask_block`) at eight query heads a KV head, in
both classes of tile and with `mask_block` 1 the program it always was; the
run class of `ragged_read_by_class`; the walk's rules on the host;
`softmax_topk_route`; the L-position sampler and the three reveal rules."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from tpu_engine.ops import latent_attention as la
from tpu_engine.ops import paged_attention as pa
from tpu_engine.ops.moe import softmax_topk_route
from tpu_engine.runtime.generator import reveal_block, sample_block
from tpu_engine.runtime.scheduler import TickBlock, take_block_from_prev

TPU_INTERPRETER = pltpu.InterpretParams(
    uninitialized_memory="nan", dma_execution_mode="on_wait",
    detect_races=True)


# -- the block mask in the kernel ---------------------------------------------------

@pytest.mark.parametrize("case,path", [
    ("runs-of-4", "pallas-interpreter"),
    ("runs-of-4", "tpu-interpreter"),
    ("chunks-in-tall-tiles", "tpu-interpreter"),
    ("not-aligned-to-blocks", "pallas-interpreter"),
    ("blocks-of-one-are-causal", "pallas-interpreter"),
])
def test_the_block_mask_equals_the_reference(case, path):
    """Through the Pallas interpreter, and through the TPU interpreter,
    which runs a DMA when its semaphore is waited on and leaves memory NaN
    until written: a copy the mask's horizon forgot would show there."""
    interpret = True if path == "pallas-interpreter" else TPU_INTERPRETER
    assert pa.block_mask_parity_check(case, interpret=interpret) < 2e-5


@pytest.mark.parametrize("case,path", [
    ("runs-beside-a-chunk", "pallas-interpreter"),
    ("the-list-is-full", "pallas-interpreter"),
    ("a-chunk-of-one-block-is-a-run", "xla-reference"),
    ("every-row-a-run", "xla-reference"),
])
def test_runs_and_tall_tiles_under_the_block_mask(case, path):
    q_lens, pos0, max_tokens = pa.BLOCK_CLASS_CASES[case]
    operands = pa.class_workload(
        q_lens, pos0, width=64, max_tokens=max_tokens, n_heads=16,
        n_kv_heads=2, d_head=16, block_size=16,
        n_blocks=1 + len(q_lens) * 24, table_len=24, dtype=jnp.float32)
    attn_fn = (pa.ragged_paged_attention_reference
               if path == "xla-reference" else functools.partial(
                   pa.ragged_paged_attention, interpret=True))
    out = jax.jit(functools.partial(
        pa.class_read, width=64, max_tokens=max_tokens, attn_fn=attn_fn,
        mask_block=4))(*operands)
    assert pa.class_read_error(out, operands, mask_block=4) < 2e-5


def test_the_block_mask_differs_from_the_causal_one_where_it_should():
    """Inside a block a query sees the positions after it: the first query
    of a run reads what the causal mask hides, the last reads the same."""
    (q, *rest, qlen), _ = pa.parity_workload(
        "ragged", (4,), n_heads=16, n_kv_heads=2, d_head=16, block_size=16,
        n_blocks=9, table_len=8, dtype=jnp.float32, pos0=(40,))
    causal = pa.ragged_paged_attention_reference(q, *rest, qlen)
    blocks = pa.ragged_paged_attention_reference(q, *rest, qlen,
                                                 mask_block=4)
    assert float(jnp.abs(causal - blocks)[0, 0].max()) > 1e-3
    np.testing.assert_allclose(causal[0, 3], blocks[0, 3], atol=1e-6)


@pytest.mark.parametrize("q_lens,width", [((1, 1, 1, 1), 1),
                                          ((40, 1, 7), 40)])
def test_blocks_of_one_are_the_program_the_kernel_always_was(q_lens, width):
    """`mask_block` 1 traces the same kernel as no `mask_block` at all, to
    the letter: the cells that never state one run the parent's program."""
    shapes = jax.eval_shape(lambda: pa.parity_workload(
        "ragged", q_lens, n_heads=8, n_kv_heads=2, d_head=16, block_size=16,
        n_blocks=33, table_len=8, dtype=jnp.float32)[0])
    assert shapes[0].shape[1] == width

    def text(**read):
        return str(jax.make_jaxpr(functools.partial(
            pa.ragged_paged_attention, interpret=False, **read))(*shapes))

    assert text(mask_block=1) == text()
    assert text(mask_block=4) != text()
    assert "block_mask_read" in text(mask_block=4)
    assert "block_mask_read" not in text()


def test_the_walk_s_rules_on_the_host_follow_the_block_s_end():
    """A tile's horizon rounds up to its last query's block end, capped at
    pos0 + q_len; blocks of one change nothing."""
    pos0, qlen = np.array([36, 0, 250]), np.array([4, 64, 6])
    call = dict(width=64, group=8, kv_heads=2, block_size=16)
    live, lo, hi = pa.walk_tiles(pos0, qlen, mask_block=4, **call)
    assert live.tolist() == [[True, False, False, False], [True] * 4,
                             [True, False, False, False]]
    # 36 + 4 = 40 columns: 3 blocks; tiles of 16 slots: 16, 32, 48, 64
    # columns; 250 + 6 = 256: 16 blocks.
    assert hi[0, 0] == 3 and hi[1].tolist() == [1, 2, 3, 4]
    assert hi[2, 0] == 16 and not lo.any()
    plain = pa.walk_tiles(pos0, qlen, **call)
    same = pa.walk_tiles(pos0, qlen, mask_block=1, **call)
    assert all((a == b).all() for a, b in zip(plain, same))
    # A run of 4 from column 13 under the causal mask stops its first
    # query at 13; under blocks of 4 every query reaches column 16.
    a = pa.walk_tiles(np.array([13]), np.array([2]), width=4, group=8,
                      kv_heads=2, block_size=16)
    b = pa.walk_tiles(np.array([13]), np.array([2]), width=4, group=8,
                      kv_heads=2, block_size=16, mask_block=4)
    assert a[2][0, 0] == 1 and b[2][0, 0] == 1      # capped at pos0 + q_len


def test_the_run_class_is_chosen_by_q_len_alone():
    qlen = jnp.asarray([4, 0, 64, 4, 8])
    plan = la.class_plan(qlen, 64, 8, None, run_slots=4)
    assert plan.short.tolist() == [True, False, False, True, False]
    assert plan.runs.tolist() == [4, 0, 0, 4, 0] and plan.run_slots == 4
    assert int(plan.tall.n_live[0]) == 4 + 1        # tiles of 16 slots
    narrow = la.class_plan(qlen[:2], 4, 8, None, run_slots=4)
    assert narrow.tall is None and narrow.runs.tolist() == [4, 0]
    assert la.class_counts(np.asarray(qlen), 64, 8, 4) == (2, 5)
    old = la.class_plan(jnp.asarray([1, 0, 7]), 64, 8, None)
    assert old.runs is None and old.short.tolist() == [True, False, False]
    assert la.class_counts(np.asarray([1, 0, 7]), 64, 8) == (1, 1)


# -- the router -----------------------------------------------------------------------

@pytest.mark.parametrize("n_experts,top_k", [(8, 2), (128, 8)])
def test_softmax_topk_route_against_a_dense_soft_max(n_experts, top_k):
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 64))
    router = {"kernel": jax.random.normal(jax.random.PRNGKey(1),
                                          (64, n_experts))}
    experts, weights = softmax_topk_route(x, router, top_k)
    logits = np.asarray(x, np.float64) @ np.asarray(router["kernel"],
                                                    np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=-1)[:, :top_k]
    assert (np.asarray(experts) == order).all()
    chosen = np.take_along_axis(probs, order, axis=-1)
    np.testing.assert_allclose(np.asarray(weights),
                               chosen / chosen.sum(-1, keepdims=True),
                               rtol=2e-5)
    assert experts.dtype == jnp.int32 and weights.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-5)


# -- the sampler at L positions and the reveal ----------------------------------------

def _blocks():
    block = jnp.asarray([[-1, -1, -1, -1], [7, -1, 9, -1], [5, 6, -1, -1],
                         [1, 2, 3, 4]])
    x0 = jnp.asarray([[10, 11, 12, 13]] * 4)
    conf = jnp.asarray([[.2, .9, .5, .9], [.1, .3, .1, .3],
                        [.5, .5, .95, .99], [.9, .9, .9, .9]])
    return block, x0, conf


@pytest.mark.parametrize("rule,count,want", [
    ("sequential", [1, 1, 2, 1],
     [[10, -1, -1, -1], [7, 11, 9, -1], [5, 6, 12, 13], [1, 2, 3, 4]]),
    ("sequential", [0, 0, 0, 0],
     [[-1, -1, -1, -1], [7, -1, 9, -1], [5, 6, -1, -1], [1, 2, 3, 4]]),
    # the most confident; ties to the left
    ("low_confidence_static", [1, 1, 1, 1],
     [[-1, 11, -1, -1], [7, 11, 9, -1], [5, 6, -1, 13], [1, 2, 3, 4]]),
    ("low_confidence_static", [2, 2, 1, 0],
     [[-1, 11, -1, 13], [7, 11, 9, 13], [5, 6, -1, 13], [1, 2, 3, 4]]),
    # threshold 0.8: row 0 has two over it (both go, though one is asked);
    # row 1 none (the static choice); row 2 two over it; row 3 none masked
    ("low_confidence_dynamic", [1, 1, 1, 1],
     [[-1, 11, -1, 13], [7, 11, 9, -1], [5, 6, 12, 13], [1, 2, 3, 4]]),
    ("low_confidence_dynamic", [0, 1, 0, 0],
     [[-1, -1, -1, -1], [7, 11, 9, -1], [5, 6, -1, -1], [1, 2, 3, 4]]),
])
def test_the_reveal_rules(rule, count, want):
    block, x0, conf = _blocks()
    got = reveal_block(block, x0, conf, jnp.asarray(count), rule, 0.8)
    assert got.tolist() == want


def test_sample_block_s_arg_max_and_confidence():
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 50)) * 3
    zeros, ones = jnp.zeros((3,)), jnp.ones((3,))
    x0, conf = sample_block(logits.reshape(12, 50), jnp.arange(3),
                            jnp.asarray([0, 8, 40]),
                            zeros, ones, jnp.zeros((3,), jnp.int32), zeros,
                            jnp.ones((3,), bool))
    assert (x0 == logits.argmax(-1)).all() and conf.dtype == jnp.float32
    np.testing.assert_allclose(conf, jax.nn.softmax(logits, -1).max(-1),
                               rtol=1e-5)
    # temperature > 0: a position's draw follows fold_in(seed, position),
    # the rule every path shares: the same whatever block it stands in.
    hot = jnp.full((3,), 0.9)
    logits = jnp.broadcast_to(logits[0, 0], (3, 4, 50))
    a, ca = sample_block(logits.reshape(12, 50), jnp.full((3,), 5),
                         jnp.asarray([0, 4, 2]),
                         hot, ones, jnp.zeros((3,), jnp.int32), zeros,
                         jnp.ones((3,), bool))
    assert a[0, 2] == a[2, 0] and a[0, 3] == a[2, 1]
    assert len({int(t) for t in a.reshape(-1)}) > 1
    np.testing.assert_allclose(
        ca, jnp.take_along_axis(jax.nn.softmax(logits, -1), a[..., None],
                                -1)[..., 0], rtol=1e-5)


def test_a_block_rides_the_device_between_two_steps():
    tokens = jnp.asarray([[3, 4, 5, 6, 7, 8], [-1, -1, -1, -1, 0, 0],
                          [9, -1, 2, -1, 0, 0]])
    prev = jnp.asarray([[1, 1, 1, 1], [20, -1, 21, -1], [0, 0, 0, 0]])
    from_prev = jnp.asarray([False, True, False])
    done = jnp.asarray([False, False, True])
    shown, blk, done = take_block_from_prev(
        tokens, done, prev, jnp.asarray([True, True, False]), from_prev, 4,
        99)
    assert blk.tolist() == [[3, 4, 5, 6], [20, -1, 21, -1], [9, -1, 2, -1]]
    assert shown.tolist() == [[3, 4, 5, 6, 7, 8], [20, 99, 21, 99, 0, 0],
                              [9, 99, 2, 99, 0, 0]]
    assert done.tolist() == [False, True, True]


def test_the_control_block_gains_a_column_on_a_block_decoding_lane_alone():
    plain, blocks = TickBlock(4, [8]), TickBlock(4, [8], reveal=True)
    assert "reveal" not in plain.fields and blocks.cols == plain.cols + 1
    assert list(plain.fields) == [f for f in blocks.fields if f != "reveal"]
