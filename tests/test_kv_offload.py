"""Hierarchical host-RAM KV tier (runtime.kv_blocks host_blocks +
scheduler kv_host_blocks).

Contracts under test:
- a demote/promote round trip is BIT-EXACT: the block's K and V come
  back to the device verbatim (dtype-preserving host copies, no math);
- LRU demotion only ever takes tree-only (refcount-1) frontier nodes —
  a live row's or a pinned lookup's block is structurally untouchable;
- promotion defers behind live-row growth: it never evicts and must
  leave the reserve of free blocks intact, else the lookup stops at the
  resident prefix (swap_in_deferred) and the tail recomputes;
- a full host tier makes room by destroying its own LRU demoted leaves;
- insert over a demoted node re-adopts it onto the newcomer's fresh
  device block (host slot freed — the recompute IS the promotion);
- `_recover`/reset voids demoted state via the generation stamp: the
  host tier empties with the pool and stale pins are never released;
- zero-leak accounting: device blocks = free + tree-resident + row-held,
  host blocks used = demoted nodes, across churn;
- scheduler end-to-end (two-path AND mixed): a radix hit on a demoted
  prefix swaps in instead of recomputing, and the stream stays
  byte-identical to an untiered control.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.ops.attention import KVCache
from tpu_engine.runtime.kv_blocks import BlockPool
from tpu_engine.runtime.scheduler import ContinuousGenerator

_ensure_builtin_models_imported()

BS = 16


@pytest.fixture(scope="module")
def spec():
    return create_model("gpt2-small-test", max_seq=128)


@pytest.fixture(scope="module")
def params(spec):
    return spec.init(jax.random.PRNGKey(0))


def _pool(spec, blocks=6, host=4):
    return BlockPool(spec.config, blocks, BS, jnp.float32,
                     host_blocks=host)


def _pattern(pool, base: float):
    shape = (pool.cfg.n_layers, pool.block_size,
             pool.cfg.kv_heads * pool.cfg.d_head)
    return (np.arange(np.prod(shape), dtype=np.float32)
            .reshape(shape) + base)


def _write_block(pool, bid, pat):
    pool.caches = KVCache(pool.caches.k.at[:, bid].set(pat),
                          pool.caches.v.at[:, bid].set(-pat))


def _tree_prefix(pool, n_blocks, base=0.0, prompt0=0):
    """Allocate n blocks with recognizable patterns, index them as one
    radix chain, release the row refs (tree-only)."""
    ids = pool.alloc(n_blocks)
    pats = []
    for j, bid in enumerate(ids):
        pat = _pattern(pool, base + 1000.0 * j)
        _write_block(pool, bid, pat)
        pats.append(pat)
    prompt = list(range(prompt0, prompt0 + n_blocks * pool.block_size))
    pool.radix.insert(prompt, ids)
    pool.release_many(ids)
    return prompt, ids, pats


# -- demote / promote ---------------------------------------------------------

def test_demote_promote_roundtrip_bitexact(spec):
    pool = _pool(spec)
    prompt, ids, pats = _tree_prefix(pool, 2)
    free0 = pool.free_blocks
    assert pool.radix.evict(2) == 2
    assert pool.demotions == 2 and pool.radix.nodes == 2  # nodes survive
    assert pool.free_blocks == free0 + 2
    assert pool.stats()["host"]["blocks_used"] == 2
    got = pool.radix.lookup(prompt, promote_reserve=0)
    assert len(got) == 2
    assert pool.swap_ins == 2 and pool.swap_in_events == 1
    assert pool.swapped_in_tokens == 2 * BS
    for j, bid in enumerate(got):
        assert np.array_equal(np.asarray(pool.caches.k[:, bid]), pats[j])
        assert np.array_equal(np.asarray(pool.caches.v[:, bid]), -pats[j])
    assert pool.stats()["host"]["blocks_used"] == 0
    pool.release_many(got)


def test_no_promote_without_reserve_arg(spec):
    """Direct callers (and the sharing-off path) that pass no
    promote_reserve keep the pre-tier behavior: a demoted node is a
    miss, nothing swaps in."""
    pool = _pool(spec)
    prompt, _, _ = _tree_prefix(pool, 1)
    pool.radix.evict(1)
    assert pool.radix.lookup(prompt) == []
    assert pool.swap_ins == 0 and pool.swap_in_deferred == 0


def test_demotion_never_touches_live_or_pinned(spec):
    pool = _pool(spec)
    prompt, ids, _ = _tree_prefix(pool, 2)
    # A "live row" re-pins the chain (refcount 2 each).
    pinned = pool.radix.lookup(prompt)
    assert pinned == ids
    assert pool.radix.evict(2) == 0
    assert pool.demotions == 0
    # Release the pins: now tree-only, demotable.
    pool.release_many(pinned)
    assert pool.radix.evict(2) == 2
    assert pool.demotions == 2


def test_promotion_defers_behind_reserve(spec):
    pool = _pool(spec, blocks=6, host=4)
    prompt, _, pats = _tree_prefix(pool, 2)
    pool.radix.evict(1)  # demote the TAIL leaf only; head stays resident
    assert pool.demotions == 1
    free = pool.free_blocks
    # Reserve demands every free block stay free: promotion must defer,
    # and the lookup still returns the resident head.
    got = pool.radix.lookup(prompt, promote_reserve=free)
    assert len(got) == 1
    assert pool.swap_in_deferred == 1 and pool.swap_ins == 0
    assert np.array_equal(np.asarray(pool.caches.k[:, got[0]]), pats[0])
    pool.release_many(got)
    # With headroom the same lookup promotes.
    got2 = pool.radix.lookup(prompt, promote_reserve=0)
    assert len(got2) == 2 and pool.swap_ins == 1
    pool.release_many(got2)


def test_promotion_displaces_colder_resident_leaves(spec):
    """A hot demoted prefix may DISPLACE an LRU-colder resident leaf
    (demoting it to the tier — no cached state destroyed) when the free
    list is empty: at idle the radix legitimately holds every block, and
    a swap-in must still be possible. The displaced leaf lands in the
    host tier; nothing is destroyed while the tier has room."""
    pool = _pool(spec, blocks=4, host=4)
    p1, _, pats1 = _tree_prefix(pool, 1, base=0.0, prompt0=0)
    pool.radix.evict(1)
    # Fill the remaining free blocks with a second tree-only chain.
    p2, _, _ = _tree_prefix(pool, pool.free_blocks, base=5e5, prompt0=1000)
    assert pool.free_blocks == 0
    got = pool.radix.lookup(p1, promote_reserve=0)
    assert len(got) == 1 and pool.swap_ins == 1
    assert np.array_equal(np.asarray(pool.caches.k[:, got[0]]), pats1[0])
    assert pool.evictions == 0          # nothing destroyed...
    assert pool.demotions == 2          # ...a colder leaf was demoted
    assert pool.stats()["host"]["blocks_used"] == 1  # the displaced one
    pool.release_many(got)


def test_host_tier_full_evicts_lru_demoted_leaf(spec):
    pool = _pool(spec, blocks=8, host=1)
    p1, _, _ = _tree_prefix(pool, 1, base=0.0, prompt0=0)
    p2, _, _ = _tree_prefix(pool, 1, base=5e5, prompt0=1000)
    pool.radix.evict(1)  # p1's leaf -> the single host slot
    assert pool.demotions == 1 and pool.host_evictions == 0
    pool.radix.evict(1)  # p2's leaf: tier full -> p1's entry destroyed
    assert pool.demotions == 2 and pool.host_evictions == 1
    assert pool.radix.nodes == 1  # only p2's (demoted) node survives
    assert pool.radix.lookup(p1, promote_reserve=0) == []
    got = pool.radix.lookup(p2, promote_reserve=0)
    assert len(got) == 1 and pool.swap_ins == 1
    pool.release_many(got)


def test_insert_readopts_demoted_node(spec):
    pool = _pool(spec)
    prompt, _, _ = _tree_prefix(pool, 1)
    pool.radix.evict(1)
    assert pool.stats()["host"]["blocks_used"] == 1
    # A newcomer recomputed the same tokens into a fresh block: insert
    # re-points the demoted node at it and frees the host slot.
    fresh = pool.alloc(1)
    pat = _pattern(pool, 7e6)
    _write_block(pool, fresh[0], pat)
    pool.radix.insert(prompt, fresh)
    assert pool.stats()["host"]["blocks_used"] == 0
    assert pool.refcount(fresh[0]) == 2  # row + tree
    pool.release_many(fresh)
    got = pool.radix.lookup(prompt, promote_reserve=0)
    assert got == fresh and pool.swap_ins == 0  # resident, no swap needed
    pool.release_many(got)


def test_reset_voids_host_tier_and_generation(spec):
    pool = _pool(spec)
    prompt, _, _ = _tree_prefix(pool, 2)
    pool.radix.evict(2)
    pins = pool.radix.lookup(prompt, promote_reserve=0)
    assert len(pins) == 2
    gen0 = pool.generation
    pool.reset()
    # The stamp is the holders' cue to NOT release stale ids (the
    # scheduler's _discard_item / admission guards compare it).
    assert pool.generation == gen0 + 1
    st = pool.stats()
    assert st["host"]["blocks_used"] == 0
    assert st["blocks_free"] == st["blocks_total"]
    assert int(np.sum(pool._ref[1:])) == 0


def test_zero_leak_accounting_through_churn(spec):
    pool = _pool(spec, blocks=8, host=2)
    p1, _, _ = _tree_prefix(pool, 2, base=0.0, prompt0=0)
    p2, _, _ = _tree_prefix(pool, 2, base=5e5, prompt0=1000)
    pool.radix.evict(2)
    got = pool.radix.lookup(p1, promote_reserve=0) or \
        pool.radix.lookup(p2, promote_reserve=0)
    pool.release_many(got)
    st = pool.stats()
    resident = st["radix_nodes"] - st["host"]["blocks_used"]
    assert st["blocks_free"] + resident == st["blocks_total"]
    assert st["host"]["blocks_used"] <= st["host"]["blocks_total"]
    assert int(np.sum(pool._ref[1:] < 0)) == 0


# -- scheduler end-to-end -----------------------------------------------------

def _churn(g, rng, rounds=4, length=48):
    for _ in range(rounds):
        fp = [int(t) for t in rng.integers(1, 200, length)]
        g.generate([fp], max_new_tokens=4)


@pytest.fixture(scope="module")
def control_stream(spec, params):
    ctrl = ContinuousGenerator(spec, params=params, dtype="float32",
                               n_slots=2, step_chunk=4, max_seq=128,
                               kv_block_size=16)
    shared = [int(t) for t in
              np.random.default_rng(0).integers(1, 200, 32)]
    prompt = shared + [7, 8, 9]
    want = ctrl.generate([prompt], max_new_tokens=8)[0]
    ctrl.stop()
    return prompt, want


def test_swap_in_instead_of_recompute_two_path(spec, params,
                                               control_stream):
    prompt, want = control_stream
    g = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=2, step_chunk=4, max_seq=128,
                            kv_block_size=16, kv_blocks=12,
                            kv_host_blocks=8)
    try:
        assert g.generate([prompt], max_new_tokens=8)[0] == want
        _churn(g, np.random.default_rng(1))
        st = g.stats()["kv_pool"]
        assert st["host"]["demotions"] > 0  # churn demoted cold leaves
        assert g.generate([prompt], max_new_tokens=8)[0] == want
        st2 = g.stats()["kv_pool"]
        assert st2["host"]["swap_ins"] > 0
        assert st2["host"]["swap_in_events"] > 0
        assert st2["prefix_hit_tokens"] > 0  # swap-in counted as a hit
    finally:
        g.stop()


def test_swap_in_mixed_mode(spec, params, control_stream):
    prompt, want = control_stream
    g = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=2, step_chunk=4, max_seq=128,
                            kv_block_size=16, kv_blocks=12,
                            kv_host_blocks=8,
                            prefill_chunk=16)
    try:
        assert g.generate([prompt], max_new_tokens=8)[0] == want
        _churn(g, np.random.default_rng(2), rounds=3)
        assert g.generate([prompt], max_new_tokens=8)[0] == want
        st = g.stats()["kv_pool"]
        assert st["host"]["demotions"] > 0
        assert st["host"]["swap_ins"] > 0
    finally:
        g.stop()


def test_recover_voids_demoted_state(spec, params):
    g = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=2, step_chunk=4, max_seq=128,
                            kv_block_size=16, kv_blocks=12,
                            kv_host_blocks=8)
    try:
        rng = np.random.default_rng(3)
        g.generate([[int(t) for t in rng.integers(1, 200, 40)]],
                   max_new_tokens=4)
        _churn(g, rng, rounds=3)
        assert g.stats()["kv_pool"]["host"]["demotions"] > 0
        gen0 = g._pool.generation
        g._recover(RuntimeError("injected device loss"))
        st = g.stats()["kv_pool"]
        assert g._pool.generation == gen0 + 1
        assert st["host"]["blocks_used"] == 0
        assert st["blocks_free"] == st["blocks_total"]
        assert g.stats().get("recover_invariant_violations", 0) == 0
        # The pool is healthy again: serving continues.
        out = g.generate([[5, 9, 3]], max_new_tokens=4)[0]
        assert len(out) == 4
    finally:
        g.stop()


def test_misconfiguration_is_loud(spec, params):
    with pytest.raises(ValueError, match="kv_host_blocks"):
        ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=2, max_seq=128, kv_host_blocks=4)
    with pytest.raises(ValueError, match="prefix_sharing"):
        ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=2, max_seq=128, kv_block_size=16,
                            kv_host_blocks=4, prefix_sharing=False)


def test_worker_flag_and_health_exposure(spec, params):
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig

    with pytest.raises(RuntimeError, match="kv-host-blocks"):
        WorkerNode(WorkerConfig(node_id="bad", model="gpt2-small-test",
                                gen_kv_host_blocks=4),
                   engine=InferenceEngine("gpt2-small-test", params=params,
                                          dtype="float32"))
    w = WorkerNode(WorkerConfig(node_id="tier", model="gpt2-small-test",
                                gen_kv_block_size=16, gen_kv_blocks=12,
                                gen_kv_host_blocks=8),
                   engine=InferenceEngine("gpt2-small-test", params=params,
                                          dtype="float32"))
    try:
        w.handle_generate({"request_id": "h1",
                           "prompt_tokens": list(range(1, 40)),
                           "max_new_tokens": 2})
        pool = w.get_health()["generator"]["kv_pool"]
        assert pool["host"]["blocks_total"] == 8
        assert "radix_lookups" in pool and "radix_hits" in pool
        # /metrics renders the host-tier family.
        from tpu_engine.utils.metrics import render_prometheus

        body = render_prometheus([w.get_health()]).decode()
        assert "tpu_engine_kv_host_blocks_total" in body
        assert "tpu_engine_kv_radix_lookups_total" in body
    finally:
        w.stop()
