"""Paged KV cache: block pool invariants, radix prefix sharing, and
paged-vs-dense stream equality (runtime.kv_blocks + scheduler
kv_block_size + ops.paged_attention).

Contracts under test:
- pool alloc/free/refcount/COW: blocks free only at refcount 0; a shared
  block is copied, never written through; eviction only ever takes
  tree-only (refcount-1) leaves.
- seeded output streams are identical paged vs dense — greedy AND
  temperature sampling, solo and co-scheduled.
- a shared prompt prefix radix-hits block-granularly: the second request
  skips the matched tokens' prefill (prefix_hit_tokens) and still emits
  the dense path's stream (mid-prompt resume is exact).
- pool pressure evicts only unreferenced radix leaves; live rows keep
  decoding correctly through the churn.
- cancelled (deadline-expired) rows return their blocks.
- the Pallas kernel (interpreter here) matches the XLA gather reference.
"""

import queue as _queue
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.models.transformer import transformer_apply
from tpu_engine.runtime.kv_blocks import BlockPool, PoolExhausted
from tpu_engine.runtime.scheduler import ContinuousGenerator
from tpu_engine.utils.deadline import Deadline, DeadlineExceeded

_ensure_builtin_models_imported()


@pytest.fixture(scope="module")
def spec():
    # max_seq raised past the test prompts' buckets: the dense oracle
    # needs bucket < max_seq to decode (a bucket-sized row is
    # out-of-cache at admission).
    return create_model("gpt2-small-test", max_seq=128)


@pytest.fixture(scope="module")
def params(spec):
    return spec.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def dense(spec, params):
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=4, step_chunk=4, max_seq=128)
    yield s
    s.stop()


@pytest.fixture(scope="module")
def paged(spec, params):
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=4, step_chunk=4, max_seq=128,
                            kv_block_size=16)
    yield s
    s.stop()


def _greedy_ref(params, spec, prompt, n):
    seq = list(prompt)
    out = []
    for _ in range(n):
        logits = transformer_apply(params, jnp.asarray([seq], jnp.int32),
                                   spec.config, dtype=jnp.float32)
        t = int(jnp.argmax(logits[0, len(seq) - 1]))
        out.append(t)
        seq.append(t)
    return out


# -- block pool invariants ----------------------------------------------------

def _pool(spec, blocks=8, bs=16):
    return BlockPool(spec.config, blocks, bs, jnp.float32)


def test_alloc_free_refcount(spec):
    pool = _pool(spec)
    assert pool.free_blocks == 7  # block 0 is the reserved null block
    ids = pool.alloc(3)
    assert 0 not in ids and len(set(ids)) == 3
    assert pool.free_blocks == 4
    assert all(pool.refcount(i) == 1 for i in ids)
    pool.retain(ids[0])
    pool.release(ids[0])
    assert pool.refcount(ids[0]) == 1  # still held once
    pool.release_many(ids)
    assert pool.free_blocks == 7
    assert all(pool.refcount(i) == 0 for i in ids)


def test_alloc_exhaustion_raises_without_consuming(spec):
    pool = _pool(spec, blocks=4)
    ids = pool.alloc(3)
    with pytest.raises(PoolExhausted):
        pool.alloc(1)
    assert pool.free_blocks == 0
    pool.release_many(ids)
    assert pool.free_blocks == 3


def test_null_block_never_allocated_or_freed(spec):
    pool = _pool(spec, blocks=4)
    ids = pool.alloc(3)
    assert 0 not in ids
    pool.release(0)  # permanently pinned: release is a no-op
    assert pool.refcount(0) == 1
    pool.release_many(ids)


def test_copy_on_write(spec):
    pool = _pool(spec)
    # Mark the source block with a sentinel value to verify the copy.
    src = pool.alloc(1)[0]
    pool.caches = type(pool.caches)(
        pool.caches.k.at[:, src].set(7.0), pool.caches.v.at[:, src].set(3.0))
    # Exclusive block: write-through allowed, no copy.
    same, copied = pool.ensure_writable(src)
    assert same == src and not copied
    # Shared block: must copy, swap the writer's reference, keep contents.
    pool.retain(src)  # a second holder (e.g. a radix node)
    new, copied = pool.ensure_writable(src)
    assert copied and new != src
    assert pool.refcount(src) == 1 and pool.refcount(new) == 1
    assert float(pool.caches.k[0, new, 0, 0]) == 7.0
    assert float(pool.caches.v[0, new, 0, 0]) == 3.0
    assert pool.cow_copies == 1


def test_radix_insert_lookup_and_pinning(spec):
    pool = _pool(spec, blocks=8, bs=4)
    prompt = list(range(1, 11))  # 10 tokens -> 2 full blocks + tail
    ids = pool.alloc(3)
    pool.radix.insert(prompt, ids)
    assert pool.radix.nodes == 2  # only FULL blocks are indexed
    assert pool.refcount(ids[0]) == 2 and pool.refcount(ids[1]) == 2
    assert pool.refcount(ids[2]) == 1  # the partial tail stays private
    # Longest-prefix match pins the matched blocks for the caller.
    hit = pool.radix.lookup(prompt[:8] + [99, 98])
    assert hit == ids[:2]
    assert pool.refcount(ids[0]) == 3
    pool.release_many(hit)
    # Divergence inside the first block matches nothing.
    assert pool.radix.lookup([42] * 10) == []


def test_eviction_never_touches_referenced_blocks(spec):
    pool = _pool(spec, blocks=6, bs=4)
    a = pool.alloc(2)
    pool.radix.insert(list(range(1, 9)), a)       # 2 tree nodes
    b = pool.alloc(2)
    pool.radix.insert([7, 7, 7, 7, 8, 8, 8, 8], b)
    # Row releases its own references: a's blocks become tree-only.
    pool.release_many(a)
    # b's blocks stay row-held (refcount 2: row + tree).
    assert pool.free_blocks == 1
    got = pool.alloc(3)  # forces eviction of a's leaves, never b's
    assert pool.refcount(b[0]) == 2 and pool.refcount(b[1]) == 2
    assert set(got).isdisjoint(set(b))
    assert pool.evictions >= 2


# -- paged vs dense stream equality ------------------------------------------

def test_greedy_matches_dense_and_full_forward(dense, paged, spec, params):
    prompt = [5, 9, 3]
    want = _greedy_ref(params, spec, prompt, 6)
    assert dense.generate([prompt], max_new_tokens=6)[0] == want
    assert paged.generate([prompt], max_new_tokens=6)[0] == want


def test_seeded_sampling_matches_dense(dense, paged):
    for seed, temp, top_p, top_k in ((7, 0.8, 1.0, 0), (11, 1.0, 0.9, 0),
                                     (3, 0.7, 1.0, 5)):
        kw = dict(max_new_tokens=8, temperature=temp, seed=seed,
                  top_p=top_p, top_k=top_k)
        d = dense.generate([[5, 9, 3, 2]], **kw)[0]
        p = paged.generate([[5, 9, 3, 2]], **kw)[0]
        assert p == d, (seed, temp, top_p, top_k)


def test_staggered_admission_isolated_paged(dense, paged):
    """Staggered admissions must not perturb rows — dense is the oracle
    (it is itself pinned to the full forward above)."""
    want = [dense.generate([[5, 9, 3]], max_new_tokens=10)[0],
            dense.generate([[7, 2]], max_new_tokens=6)[0],
            dense.generate([[1, 4, 4, 2]], max_new_tokens=8)[0]]
    f1 = paged.submit([5, 9, 3], max_new_tokens=10)
    time.sleep(0.05)
    f2 = paged.submit([7, 2], max_new_tokens=6)
    f3 = paged.submit([1, 4, 4, 2], max_new_tokens=8)
    assert [f1.result(60), f2.result(60), f3.result(60)] == want


def test_oversubscription_returns_blocks(dense, paged):
    prompts = [[i + 1, i + 2] for i in range(9)]
    outs = paged.generate(prompts, max_new_tokens=5)
    assert outs == dense.generate(prompts, max_new_tokens=5)
    st = paged.stats()
    assert st["active"] == 0
    pool = st["kv_pool"]
    # All row-held blocks returned; only radix-owned blocks stay out.
    assert pool["blocks_free"] + pool["radix_nodes"] == pool["blocks_total"]


def test_controls_match_dense(dense, paged):
    kw = dict(max_new_tokens=8, repetition_penalty=1.3, seed=5,
              temperature=0.9)
    assert (paged.generate([[5, 9, 3]], **kw)[0]
            == dense.generate([[5, 9, 3]], **kw)[0])
    kw = dict(max_new_tokens=8, stop_tokens=[7])
    assert (paged.generate([[5, 9, 3]], **kw)[0]
            == dense.generate([[5, 9, 3]], **kw)[0])


# -- radix prefix sharing through the scheduler -------------------------------

def test_shared_prefix_hits_and_matches_dense(dense, spec, params):
    """Two prompts sharing a 32-token prefix: the second admission must
    reuse the first's blocks (prefix_hit_tokens > 0, shared blocks
    appear) and still produce exactly the dense scheduler's stream —
    prefill resumed mid-prompt at the right position."""
    shared = [(i * 7) % 90 + 1 for i in range(32)]
    p1 = shared + [91, 92, 93]
    p2 = shared + [81, 82]
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=4, step_chunk=4, max_seq=128,
                            kv_block_size=16)
    try:
        a = s.generate([p1], max_new_tokens=6)[0]
        before = s.stats()["kv_pool"]
        assert before["radix_nodes"] == 2  # 32 shared tokens = 2 blocks
        b = s.generate([p2], max_new_tokens=6)[0]
        after = s.stats()["kv_pool"]
        assert after["prefix_hit_tokens"] >= before["prefix_hit_tokens"] + 16
        assert a == dense.generate([p1], max_new_tokens=6)[0]
        assert b == dense.generate([p2], max_new_tokens=6)[0]
        # Same-prefix repeat while nothing else runs also shares blocks.
        c = s.generate([p1], max_new_tokens=6)[0]
        assert c == a
    finally:
        s.stop()


def test_shared_prefix_concurrent_rows_share_blocks(dense, spec, params):
    """Co-resident rows with one system prefix: after the first admission
    indexes the prefix, later admissions map onto those blocks (shared
    refcounts > 1 while rows are live) and every stream is correct."""
    shared = [(i * 5) % 90 + 1 for i in range(16)]
    prompts = [shared + [50 + i] for i in range(4)]
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=4, step_chunk=4, max_seq=128,
                            kv_block_size=16)
    try:
        # Admit the prefix owner first so its blocks are indexed (by
        # the tick that ends its prompt)...
        first = s.submit(prompts[0], max_new_tokens=12)
        deadline = time.monotonic() + 60
        while (not s.stats()["kv_pool"]["radix_nodes"]
               and time.monotonic() < deadline):
            time.sleep(0.02)
        rest = [s.submit(p, max_new_tokens=12) for p in prompts[1:]]
        outs = [first.result(60)] + [f.result(60) for f in rest]
        assert outs == dense.generate(prompts, max_new_tokens=12)
        assert s.stats()["kv_pool"]["prefix_hit_tokens"] >= 16
    finally:
        s.stop()


def test_sharing_off_still_correct(dense, spec, params):
    p1 = [(i * 7) % 90 + 1 for i in range(20)]
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=2, step_chunk=4, max_seq=128,
                            kv_block_size=16, prefix_sharing=False)
    try:
        a = s.generate([p1], max_new_tokens=5)[0]
        assert a == dense.generate([p1], max_new_tokens=5)[0]
        st = s.stats()["kv_pool"]
        assert st["radix_nodes"] == 0 and st["prefix_hit_tokens"] == 0
        assert st["blocks_free"] == st["blocks_total"]
    finally:
        s.stop()


def test_eviction_under_scheduler_pressure(dense, spec, params):
    """A pool sized for ~2 resident rows, fed 6 distinct prompts: radix
    leaves from finished rows must evict to make room, live rows must
    never lose blocks, every stream stays correct."""
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=2, step_chunk=4, max_seq=64,
                            kv_block_size=16, kv_blocks=9)
    try:
        # 36-token prompts: bucket 64 = 4 blocks/row, 2 full blocks per
        # prompt stay radix-indexed after completion — two resident rows
        # fill the 8-block pool, so the next admission pair MUST evict
        # earlier prompts' tree-only leaves.
        prompts = [[(i * 13 + j) % 90 + 1 for j in range(36)]
                   for i in range(6)]
        outs = s.generate(prompts, max_new_tokens=5)
        assert outs == dense.generate(prompts, max_new_tokens=5)
        st = s.stats()["kv_pool"]
        assert st["evictions"] > 0  # pressure actually evicted
        assert s.stats().get("pool_starved", 0) == 0  # never truncated
    finally:
        s.stop()


def test_equal_kv_bytes_hold_more_short_rows_than_the_dense_layout(
        dense, spec, params):
    """The pool's reason to exist, as a count: given exactly the KV
    bytes of TWO dense rows (2 x max_seq/16 = 16 blocks + the null
    block), rows that end at 43 tokens reserve at most 3 blocks each,
    so five fit (the slot count is sized to that, as an operator would)
    and the scheduler keeps at least twice as many of them resident at
    once as the dense layout's two slots — and every stream is the
    dense scheduler's. Mixed stepping admits the burst's prompts in its
    first ticks, so the peak does not hang on thread timing."""
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=5, max_seq=128, kv_block_size=16,
                            kv_blocks=2 * (128 // 16) + 1,
                            prefix_sharing=False,
                            prefill_chunk=16)
    try:
        prompts = [[i + 1, i + 2, i + 3] for i in range(8)]
        s.generate(prompts[:1], max_new_tokens=2)  # warm the executables
        futs = [s.submit(p, max_new_tokens=40) for p in prompts]
        peak = 0
        while any(not f.done() for f in futs):
            peak = max(peak, s.stats()["active"])
            time.sleep(0.001)
        outs = [f.result(60) for f in futs]
        assert outs == dense.generate(prompts, max_new_tokens=40)
        assert peak >= 4, peak            # the dense layout holds 2
        st = s.stats()
        assert st.get("pool_starved", 0) == 0   # nobody was truncated
        assert st["kv_pool"]["blocks_free"] == st["kv_pool"]["blocks_total"]
    finally:
        s.stop()


def test_cancelled_rows_return_blocks(spec, params):
    """Deadline-expired rows — before admission and mid-decode — must
    return every block to the pool."""
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=2, step_chunk=2, max_seq=128,
                            kv_block_size=16, prefix_sharing=False)
    try:
        s.generate([[1, 2, 3]], max_new_tokens=2)  # warm the executables
        futs = [s.submit([10 + i, 11, 12], max_new_tokens=64,
                         deadline=Deadline.after_ms(120))
                for i in range(4)]
        outcomes = []
        for f in futs:
            try:
                outcomes.append(("ok", f.result(60)))
            except DeadlineExceeded:
                outcomes.append(("expired", None))
        deadline = time.time() + 10
        while time.time() < deadline:
            st = s.stats()["kv_pool"]
            if (st["blocks_free"] == st["blocks_total"]
                    and s.stats()["active"] == 0):
                break
            time.sleep(0.05)
        st = s.stats()["kv_pool"]
        assert st["blocks_free"] == st["blocks_total"], (outcomes, st)
    finally:
        s.stop()


def test_stop_under_load_releases_everything(spec, params):
    streams = [_queue.Queue() for _ in range(5)]
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=2, step_chunk=2, max_seq=64,
                            kv_block_size=16)
    futs = [s.submit([1 + i, 2, 3], max_new_tokens=40, stream=streams[i])
            for i in range(5)]
    time.sleep(0.3)
    s.stop()
    for f in futs:
        try:
            f.result(timeout=15)
        except RuntimeError:
            pass
    for q in streams:
        items = []
        while True:
            items.append(q.get(timeout=5))
            if items[-1] is None:
                break


# -- kernel parity ------------------------------------------------------------

def test_paged_kernel_matches_reference():
    """A decode-only tick's call: every row one token, the ragged read
    one slot wide (the heads packed into one score tile)."""
    from tpu_engine.ops.paged_attention import ragged_parity_check

    assert ragged_parity_check(q_lens=(1, 1)) < 2e-5
    assert ragged_parity_check(q_lens=(1, 1), n_heads=8, n_kv_heads=2,
                               d_head=16, block_size=8, n_blocks=17,
                               table_len=6) < 2e-5
    assert ragged_parity_check(q_lens=(1, 1), dtype=jnp.bfloat16) < 2e-2


# Head shapes of the one pool layout (L, NB, bs, H_kv*D): the small test
# models' (H_kv*D = 16, no multiple of the 128-lane tile), gpt2-large's
# (G = 1, D = 64) and Mistral's (G = 4, D = 128).
HEAD_SHAPES = {
    "small-HD16": dict(n_heads=4, n_kv_heads=2, d_head=8),
    "G1-D64": dict(n_heads=3, n_kv_heads=3, d_head=64),
    "G4-D128": dict(n_heads=8, n_kv_heads=2, d_head=128),
}


@pytest.mark.parametrize("heads", sorted(HEAD_SHAPES))
@pytest.mark.parametrize("q_lens", [(1, 1, 1), (1, 5, 9)],
                         ids=["width-1", "width-9"])
@pytest.mark.parametrize("kind", ["ragged", "quant_ragged"])
def test_every_read_path_at_every_head_shape(kind, q_lens, heads):
    """Kernel (interpreter) against its XLA reference through the
    (pool, layer) signature, reading the SECOND layer of a two-layer
    pool: a path that ignored the layer index would miss. Width 1 is a
    decode-only tick's call (one query row a head, the heads packed)."""
    from tpu_engine.ops import paged_attention as pa

    err = pa._parity(kind, q_lens, block_size=8, n_blocks=13, table_len=3,
                     dtype=jnp.float32, seed=3, interpret=True,
                     **HEAD_SHAPES[heads])
    assert err < 5e-5


@pytest.mark.parametrize("case", ["ends-on-group-boundary-width-1",
                                  "table-four-times-wider-width-1"])
def test_decode_kernel_walks_each_rows_own_context(case):
    """A decode-only tick's read (the ragged read one slot wide) over
    the workloads the tile walk can get wrong
    (`ops.paged_attention.WALK_CASES`), from a bfloat16 pool as the
    cells serve it (test_mixed_step.py and test_paged_walk.py run the
    float32 ones): contexts ending on, one past and far before a group
    boundary, and a table whose tail is the null block."""
    from tpu_engine.ops import paged_attention as pa

    assert pa.walk_parity_check("ragged", case, interpret=True,
                                dtype=jnp.bfloat16) < 2e-2


@pytest.mark.parametrize("width", [1, 40])
@pytest.mark.parametrize("kind", ["ragged", "quant_ragged"])
def test_a_tick_with_every_row_dead_reads_nothing(kind, width):
    """q_len 0 in every row: no tile is live, the kernel writes zeros
    (and nothing it could have read, garbage or not, shows)."""
    from tpu_engine.ops import paged_attention as pa

    operands, _ = pa.parity_workload(
        kind, (width, 1, width), n_heads=8, n_kv_heads=2, d_head=16,
        block_size=16, n_blocks=61, table_len=20, dtype=jnp.float32)
    out = pa.READ_PATHS[kind][0](*operands[:-1],
                                 jnp.zeros((3,), jnp.int32), interpret=True)
    assert out.shape == operands[0].shape
    assert not np.any(np.asarray(out))


@pytest.mark.parametrize("quant", ["", "int8"])
@pytest.mark.parametrize("step", ["decode", "ragged"])
def test_step_writes_layer_l_into_layer_l_only(spec, params, step, quant):
    """One multi-layer step over a pool full of recognisable bytes: in
    EVERY layer exactly the written slots change (the rows' (block,
    offset) pairs, and the null block that absorbs padding), and every
    other byte of every layer is bit-equal before and after — the layer
    loop carries the whole pool and must not smear a layer's write over
    its neighbours."""
    from tpu_engine.models.transformer import transformer_step_rows_ragged

    cfg = spec.config
    assert cfg.n_layers > 1
    bs, n_blocks = 16, 6
    pool = BlockPool(cfg, n_blocks, bs, jnp.float32, quantize=quant)
    rng = np.random.default_rng(0)

    def noise(x, lo, hi):
        return jnp.asarray(rng.integers(lo, hi, x.shape), x.dtype)

    caches = jax.tree.map(lambda x: noise(x, -100, 100), pool.caches)
    scales = (jax.tree.map(lambda x: noise(x, 1, 9), pool.scales)
              if quant else None)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos0 = jnp.asarray([17, 3], jnp.int32)
    if step == "decode":
        # A decode-only tick: the ragged step one slot wide.
        out = transformer_step_rows_ragged(
            params, jnp.asarray([[5], [9]]), caches, tables, pos0,
            jnp.asarray([1, 1], jnp.int32), cfg, dtype=jnp.float32,
            scales=scales)
        written = {(2, 1), (3, 3)}
    else:
        # Row 0 consumes 3 tokens (columns 17..19 of block 2), row 1 one
        # (column 3 of block 3); its two padding slots hit the null block.
        out = transformer_step_rows_ragged(
            params, jnp.asarray([[5, 6, 7], [9, 0, 0]]), caches, tables,
            pos0, jnp.asarray([3, 1], jnp.int32), cfg, dtype=jnp.float32,
            scales=scales)
        written = {(2, 1), (2, 2), (2, 3), (3, 3), (0, 4), (0, 5)}
    before = list(caches) + (list(scales) if quant else [])
    after = [x for pair in out[1:] for x in pair]
    assert len(after) == len(before)
    for old, new in zip(before, after):
        changed = np.any(np.asarray(old) != np.asarray(new), axis=-1)
        for layer in range(cfg.n_layers):
            got = set(zip(*map(np.ndarray.tolist, np.nonzero(changed[layer]))))
            assert got == written, (layer, got)


def test_paged_kernel_in_scheduler(spec, params, monkeypatch):
    """TPU_ENGINE_PAGED=1 routes decode through the Pallas kernel (the
    interpreter here) — streams must match the XLA reference path."""
    import tpu_engine.ops.paged_attention as pa

    monkeypatch.setenv("TPU_ENGINE_PAGED", "1")
    pa._PAGED_CACHE.clear()
    s = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=2, step_chunk=2, max_seq=64,
                            kv_block_size=16)
    try:
        got = s.generate([[5, 9, 3]], max_new_tokens=4)[0]
    finally:
        s.stop()
        pa._PAGED_CACHE.clear()
    assert got == _greedy_ref(params, spec, [5, 9, 3], 4)


# -- serving integration ------------------------------------------------------

def test_worker_paged_serving_and_observability(spec, params):
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig
    from tpu_engine.utils.metrics import render_prometheus

    engine = InferenceEngine(spec, params=params, dtype="float32",
                             batch_buckets=(1, 2))
    w = WorkerNode(WorkerConfig(node_id="pg1", model="gpt2-small-test",
                                dtype="float32", gen_scheduler="continuous",
                                gen_max_batch_size=4, gen_kv_block_size=16),
                   engine=engine)
    try:
        out = w.handle_generate({"request_id": "r1",
                                 "prompt_tokens": [5, 9, 3],
                                 "max_new_tokens": 4})
        assert out["tokens"] == _greedy_ref(params, spec, [5, 9, 3], 4)
        health = w.get_health()
        pool = health["generator"]["kv_pool"]
        assert pool["blocks_total"] > 0
        body = render_prometheus([health]).decode()
        assert "tpu_engine_kv_blocks_total" in body
        assert "tpu_engine_kv_blocks_free" in body
        # kv_alloc / radix_lookup stage spans joined the trace taxonomy.
        ops = {s["op"] for s in w.tracer.snapshot()}
        assert "kv_alloc" in ops and "radix_lookup" in ops
    finally:
        w.stop()
