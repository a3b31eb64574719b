"""Tensor-parallel continuous serving: the pjit-sharded model under the
paged scheduler plus the topology-aware gateway ring.

Contracts under test:
- STREAM IDENTITY: greedy AND seeded streams at tp ∈ {1, 2, 4} are
  byte-identical across the plain and speculative paged
  schedulers on the CPU mesh (the logits agree to ~1e-6 — the same
  empirical basis as the mixed-vs-dense identity the engine already
  rests on), with radix prefix hits and the int8 quantized pool
  included;
- ONE dispatch per tick survives sharding: the mixed/spec tick counters
  still satisfy ticks == dispatches at tp > 1 (the SPMD program is one
  dispatch, not one per shard), and the pool's committed sharding is
  unchanged after serving traffic (donation held — XLA never re-laid
  the pool);
- registry capability metadata: every consumer resolves the
  registry-declared TP partition rule; unshardable families (mamba2 /
  state_slab) and invalid knob combinations are LOUD pinned errors at
  the scheduler AND worker layers, never a silent single-device or
  mis-sharded lane;
- migration shard geometry: chains exported from a tp=N pool import
  byte-exactly into an equal-tp pool and are refused BY NAME on a
  mismatched degree (absent stamp = tp 1 — the pre-TP wire format);
- topology-aware ring: lanes labelled with a mesh shape weight their
  virtual nodes by device count (TP=4 beside TP=1 composes), the label
  rides /health additively (absent on tp=1 lanes), and unlabelled
  fleets keep the reference-exact ring.
"""

import queue

import jax
import numpy as np
import pytest

from tpu_engine.core.consistent_hash import ConsistentHash
from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    available_models,
    create_model,
    tp_shardings,
    tp_unshardable_reason,
)
from tpu_engine.parallel.mesh import tp_mesh
from tpu_engine.runtime.kv_blocks import BlockPool
from tpu_engine.runtime.scheduler import ContinuousGenerator, ImportRefused

_ensure_builtin_models_imported()

PROMPTS = [[5, 9, 3, 17], [2, 4, 6, 8, 10, 12], [1] * 20,
           [5, 9, 3, 17, 9, 9]]
# Shared-prefix pair: the radix tree must serve the second prompt's
# prefix from blocks the first filled (block_size 16 → one full block).
SHARED = [[7] * 16 + [3, 1], [7] * 16 + [4, 2, 9]]


@pytest.fixture(scope="module")
def spec():
    return create_model("gpt2-small-test", max_seq=64)


@pytest.fixture(scope="module")
def params(spec):
    return spec.init(jax.random.PRNGKey(0))


def make_gen(spec, params, tp=1, **kw):
    kw.setdefault("kv_block_size", 16)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("n_slots", 4)
    return ContinuousGenerator(spec, params=params, dtype="float32",
                               tp=tp, **kw)


def run_streams(gen, prompts, max_new=10, **kw):
    try:
        return gen.generate(prompts, max_new_tokens=max_new, **kw)
    finally:
        gen.stop()


def pool_leak_free(stats):
    kv = stats["kv_pool"]
    return kv["blocks_free"] + kv["radix_nodes"] >= kv["blocks_total"]


# -- registry capability metadata ---------------------------------------------

def test_every_registered_model_declares_a_tp_rule():
    for name in available_models():
        spec = create_model(name)
        assert spec.tp_rule, f"{name} has no TP partition rule"
        # The rule must RESOLVE (to shardings or a named refusal) —
        # an unknown rule is a registration bug, not a runtime surprise.
        reason = tp_unshardable_reason(spec)
        if reason is not None:
            assert "unknown TP partition rule" not in reason, \
                f"{name}: {reason}"


def test_transformer_rule_places_heads_axis(spec, params):
    mesh = tp_mesh(2)
    sh = tp_shardings(spec, params, mesh)
    # Column-parallel QKV/MLP up (output dim), row-parallel wo/proj
    # (input dim), vocab-sharded head, replicated embeddings/norms.
    assert sh["blocks"]["attn"]["wq"]["kernel"].spec[-1] == "model"
    assert sh["blocks"]["attn"]["wo"]["kernel"].spec[-2] == "model"
    assert sh["blocks"]["mlp"]["fc"]["kernel"].spec[-1] == "model"
    assert sh["blocks"]["mlp"]["proj"]["kernel"].spec[-2] == "model"
    assert sh["head"]["kernel"].spec[-1] == "model"
    assert all(s is None for s in sh["tok_embed"]["table"].spec)
    assert all(s is None for s in sh["blocks"]["ln1"]["scale"].spec)


def test_unshardable_families_refuse_by_name():
    ssd = create_model("ssd-small-test")
    reason = tp_unshardable_reason(ssd)
    assert reason is not None and "conv tail" in reason
    with pytest.raises(RuntimeError, match="cannot be tensor-parallel"):
        tp_shardings(ssd, ssd.init(jax.random.PRNGKey(0)), tp_mesh(2))


def test_scheduler_tp_fences(spec, params):
    # Dense layout cannot shard its pool.
    with pytest.raises(ValueError, match="paged KV cache"):
        ContinuousGenerator(spec, params=params, dtype="float32", tp=2)
    # state_slab family: the pinned per-model refusal.
    ssd = create_model("ssd-small-test")
    with pytest.raises(RuntimeError, match="cannot serve tensor-parallel"):
        ContinuousGenerator(ssd, dtype="float32", tp=2)
    # device and tp are mutually exclusive.
    with pytest.raises(ValueError, match="mutually exclusive"):
        ContinuousGenerator(spec, params=params, dtype="float32", tp=2,
                            kv_block_size=16, device=jax.devices()[0])
    # kv_heads must divide by the degree (gpt2-small-test has 4 heads).
    with pytest.raises(ValueError, match="kv_heads"):
        ContinuousGenerator(spec, params=params, dtype="float32", tp=8,
                            kv_block_size=16)


def test_a_block_budget_one_chip_refuses_serves_the_same_row_at_tp2(
        spec, params):
    """The model-size unlock in pool terms: at 3 blocks a chip (bs 16,
    max_seq 64 = 4 blocks a row) one device cannot hold even one
    full-length row and REFUSES at construction; a tp=2 lane given the
    same bytes a chip (its blocks hold half the heads each, so twice as
    many) holds the row and streams a 40-token prompt to the end,
    byte-identically to an unconstrained single-device lane."""
    per_chip = 3
    with pytest.raises(ValueError, match="cannot hold even one max_seq row"):
        make_gen(spec, params, kv_blocks=per_chip)
    long_prompt = [(i * 7) % 90 + 1 for i in range(40)]
    want = run_streams(make_gen(spec, params,
                                mixed_token_budget=32), [long_prompt],
                       max_new=20)
    gen = make_gen(spec, params, tp=2, kv_blocks=2 * per_chip,
                   mixed_token_budget=32)
    try:
        pool = gen.stats()["kv_pool"]
        assert pool["tp"] == 2 and pool["blocks_total"] == 2 * per_chip - 1
        assert gen.generate([long_prompt], max_new_tokens=20) == want
        st = gen.stats()
        assert st["mixed"]["ticks"] == st["mixed"]["dispatches"] > 0
        assert pool_leak_free(st)
    finally:
        gen.stop()


def test_worker_tp_fences():
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig

    # Unshardable family: pinned RuntimeError naming the model, BEFORE
    # any knob-combination message.
    with pytest.raises(RuntimeError, match="cannot serve tensor-parallel"):
        WorkerNode(WorkerConfig(node_id="w_ssd", model="ssd-small-test",
                                tp=2))
    # Paged continuous scheduler required.
    with pytest.raises(RuntimeError, match="paged KV cache"):
        WorkerNode(WorkerConfig(node_id="w_np", model="gpt2-small-test",
                                tp=2))
    with pytest.raises(RuntimeError, match="--tp must be >= 1"):
        WorkerNode(WorkerConfig(node_id="w_neg", model="gpt2-small-test",
                                tp=0))


# -- stream identity ----------------------------------------------------------

def test_mixed_tp2_streams_identical_single_dispatch(spec, params):
    """The tier-1 smoke: mixed stepping at tp=2 — greedy AND seeded
    streams byte-identical to the tp=1 arm, exactly one compiled ragged
    dispatch per tick, pool sharding stable, zero leaks."""
    base = run_streams(make_gen(spec, params,
                                mixed_token_budget=32), PROMPTS)
    gen = make_gen(spec, params, tp=2,
                   mixed_token_budget=32)
    sharding_before = gen._pool.caches.k.sharding
    try:
        out = gen.generate(PROMPTS, max_new_tokens=10)
        seeded = gen.generate(PROMPTS, max_new_tokens=10,
                              temperature=0.9, seed=[7, 8, 9, 10])
        st = gen.stats()
        assert out == base
        m = st["mixed"]
        assert m["ticks"] == m["dispatches"] > 0
        assert st["tp"] == {"tp": 2, "mesh_shape": {"model": 2},
                            "devices": 2}
        assert st["kv_pool"]["tp"] == 2
        assert pool_leak_free(st)
        # Donation held: the pool kept its committed sharding through
        # every tick (a re-laid pool would have a different sharding).
        assert gen._pool.caches.k.sharding.is_equivalent_to(
            sharding_before, 5)
    finally:
        gen.stop()
    seeded_base = run_streams(
        make_gen(spec, params, mixed_token_budget=32),
        PROMPTS, temperature=0.9, seed=[7, 8, 9, 10])
    assert seeded == seeded_base


@pytest.mark.slow
@pytest.mark.parametrize("tp", [2, 4])
def test_default_budget_tp_streams_identical(spec, params, tp):
    base = run_streams(make_gen(spec, params), PROMPTS)
    gen = make_gen(spec, params, tp=tp)
    try:
        out = gen.generate(PROMPTS, max_new_tokens=10)
        st = gen.stats()
        assert out == base
        assert pool_leak_free(st)
    finally:
        gen.stop()


@pytest.mark.slow
def test_mixed_tp4_streams_identical(spec, params):
    base = run_streams(make_gen(spec, params,
                                mixed_token_budget=32), PROMPTS)
    assert run_streams(make_gen(spec, params, tp=4,
                                mixed_token_budget=32), PROMPTS) == base


@pytest.mark.slow
def test_spec_tp2_streams_identical(spec, params):
    kw = dict(spec_k=2, mixed_token_budget=32)
    base = run_streams(make_gen(spec, params, **kw), PROMPTS)
    gen = make_gen(spec, params, tp=2, **kw)
    try:
        out = gen.generate(PROMPTS, max_new_tokens=10)
        st = gen.stats()
        assert out == base
        assert st["spec"]["ticks"] == st["spec"]["dispatches"] > 0
        assert pool_leak_free(st)
    finally:
        gen.stop()


@pytest.mark.slow
def test_radix_hit_tp2_identical(spec, params):
    """Shared prefixes still share under a sharded pool: the second
    stream's first block comes from the radix tree (prefix_hit_tokens
    > 0) and both streams match the tp=1 arm byte-for-byte."""
    base = run_streams(make_gen(spec, params), SHARED,
                       max_new=8)
    gen = make_gen(spec, params, tp=2)
    try:
        # Serialize so the second admission sees the first's blocks.
        out = [gen.generate([p], max_new_tokens=8)[0] for p in SHARED]
        st = gen.stats()
        assert out == base
        assert st["kv_pool"]["prefix_hit_tokens"] > 0
        assert pool_leak_free(st)
    finally:
        gen.stop()


@pytest.mark.slow
def test_quantized_pool_tp2_deterministic(spec, params):
    """int8 pool under TP: scale arrays shard alongside the payloads,
    streams are deterministic run-to-run and (on this backend) equal to
    the tp=1 quantized arm; zero leaks."""
    kw = dict(kv_quantize="int8")
    base = run_streams(make_gen(spec, params, **kw), PROMPTS)
    gen = make_gen(spec, params, tp=2, **kw)
    try:
        out = gen.generate(PROMPTS, max_new_tokens=10)
        rerun = gen.generate(PROMPTS, max_new_tokens=10)
        st = gen.stats()
        assert out == rerun == base
        # Scales committed to the pool's sharding (heads on the last
        # axis of both).
        assert gen._pool.scales.k.sharding.is_equivalent_to(
            gen._pool.kv_sharding, 4)
        assert pool_leak_free(st)
    finally:
        gen.stop()


# -- migration shard geometry -------------------------------------------------

def test_chain_tp_stamp_and_geometry_refusal(spec):
    cfg = spec.config
    mesh = tp_mesh(2)
    pool_tp = BlockPool(cfg, 8, 16, dtype=np.float32, mesh=mesh)
    pool_one = BlockPool(cfg, 8, 16, dtype=np.float32)
    with pool_tp.lock:
        ids = pool_tp.alloc(2)
        chain = pool_tp.export_chain(ids)
    assert chain["tp"] == 2
    assert pool_tp.verify_chain(chain)
    # Equal geometry: importable.
    assert BlockPool(cfg, 8, 16, dtype=np.float32,
                     mesh=tp_mesh(2)).chain_compatible(chain) is None
    # Mismatched degree: refused BY NAME.
    reason = pool_one.chain_compatible(chain)
    assert reason is not None and "tp=2" in reason \
        and "shard geometry" in reason
    # Pre-TP chains (no stamp) read tp=1 — wire-compat both ways.
    with pool_one.lock:
        ids1 = pool_one.alloc(1)
        old_chain = pool_one.export_chain(ids1)
    assert "tp" not in old_chain
    assert pool_one.chain_compatible(old_chain) is None
    r = pool_tp.chain_compatible(old_chain)
    assert r is not None and "tp=1" in r


@pytest.mark.slow
def test_migration_between_equal_tp_lanes_byte_identical(spec, params):
    """Export a live tp=2 row mid-stream, import it on another tp=2
    lane: the spliced stream equals an uninterrupted run; the same
    snapshot refuses on a tp=1 lane with the geometry named."""
    control = run_streams(make_gen(spec, params),
                          [PROMPTS[0]], max_new=16)[0]
    src = make_gen(spec, params, tp=2)
    dst = make_gen(spec, params, tp=2)
    one = make_gen(spec, params)
    try:
        # Park-after-prefill makes the export deterministic: the row
        # holds (first token emitted, chain complete) until the
        # export-after-prefill command collects it — no race against a
        # fast stream finishing first.
        q: "queue.Queue" = queue.Queue()
        src.submit(PROMPTS[0], max_new_tokens=16, stream=q, tag="mig",
                   handoff=True, handoff_park_s=60.0)
        snap = src.export_row("mig", timeout_s=60, wait_prefill=True)
        assert snap.get("ok"), snap
        assert snap["chain"]["tp"] == 2
        cont = dst.submit_import(
            {k: v for k, v in snap.items() if k != "ok"}).result(120)
        assert cont == control
        with pytest.raises(ImportRefused, match="shard geometry"):
            one.submit_import(
                {k: v for k, v in snap.items() if k != "ok"}).result(120)
    finally:
        src.stop()
        dst.stop()
        one.stop()


# -- topology-aware gateway ring ----------------------------------------------

def test_ring_weights_scale_vnode_share():
    ring = ConsistentHash(50)
    ring.add_node("tp4", weight=4)
    ring.add_node("one_a")
    ring.add_node("one_b")
    keys = [f"k{i}" for i in range(4000)]
    dist = ring.get_distribution(keys)
    assert dist["tp4"] > dist["one_a"] and dist["tp4"] > dist["one_b"]
    assert ring.node_weight("tp4") == 4
    # Re-weighting DOWN drops the extra vnodes in place.
    ring.add_node("tp4", weight=1)
    assert ring.node_weight("tp4") == 1
    ring.remove_node("tp4")
    assert "tp4" not in ring.get_all_nodes()
    # Weight-1 rings are the reference-exact ring, label for label.
    r1, r2 = ConsistentHash(150), ConsistentHash(150)
    r1.add_node("x"), r1.add_node("y")
    r2.add_node("x", weight=1), r2.add_node("y", weight=1)
    assert (r1.get_distribution(keys[:500])
            == r2.get_distribution(keys[:500]))


def test_gateway_topology_labels_and_stats():
    """Local TP lanes label the ring at add_worker; tp=1 lanes stay
    unlabelled (gated /stats and /health — defaults byte-identical)."""
    from tpu_engine.serving.gateway import Gateway
    from tpu_engine.utils.config import GatewayConfig

    class _FakeEngineSpec:
        name = "gpt2-small-test"

    class _FakeEngine:
        spec = _FakeEngineSpec()

    class _FakeWorker:
        def __init__(self, node_id, tp):
            from tpu_engine.utils.config import WorkerConfig

            self.node_id = node_id
            self.engine = _FakeEngine()
            self.config = WorkerConfig(node_id=node_id, tp=tp)

    gw = Gateway([_FakeWorker("w_tp4", 4), _FakeWorker("w_one", 1)],
                 GatewayConfig(virtual_nodes=50))
    try:
        st = gw.get_stats()
        topo = st["topology"]
        assert topo["lanes"] == {"w_tp4": {"tp": 4, "devices": 4,
                                           "mesh_shape": {"model": 4}}}
        assert topo["ring_weights"] == {"w_tp4": 4, "w_one": 1}
        # The TP lane owns the larger hash share on the actual ring.
        dist = gw._ring.get_distribution([f"k{i}" for i in range(2000)])
        assert dist["w_tp4"] > dist["w_one"]
        # remove drops the label with the lane.
        gw.remove_worker("w_tp4")
        assert "topology" not in gw.get_stats()
    finally:
        gw.stop()


def test_normalize_topology_malformed_labels_never_raise():
    """A garbage /health topology label must normalize to None (one
    chip), never raise — an exception on the prober path would read as
    a failed probe and eject a healthy lane."""
    from tpu_engine.serving.gateway import Gateway

    norm = Gateway._normalize_topology
    assert norm(None) is None
    assert norm("tp=4") is None
    assert norm({"devices": "four"}) is None
    assert norm({"devices": 2, "tp": None}) is None
    assert norm({"tp": 1}) is None  # one chip: unlabelled
    assert norm({"tp": 2}) == {"tp": 2, "devices": 2}


def test_worker_tp_device_offset_fence():
    """A mesh slice running past the local devices is a loud startup
    error, never a silent wrap onto another lane's chips."""
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig

    with pytest.raises(RuntimeError, match="device offset"):
        WorkerNode(WorkerConfig(node_id="w_off", model="gpt2-small-test",
                                gen_kv_block_size=16, tp=2,
                                tp_device_offset=7))


def test_gateway_unlabelled_fleet_stats_unchanged():
    from tpu_engine.serving.gateway import Gateway
    from tpu_engine.utils.config import GatewayConfig

    gw = Gateway([], GatewayConfig())
    try:
        assert "topology" not in gw.get_stats()
    finally:
        gw.stop()


def test_prober_applies_topology_label():
    """_apply_topology re-weights every ring the lane is on (the HTTP
    discovery path: labels arrive via /health sweeps, not add_worker)."""
    from tpu_engine.serving.gateway import Gateway
    from tpu_engine.utils.config import GatewayConfig

    gw = Gateway([], GatewayConfig(virtual_nodes=50))
    try:
        gw._clients["lane_a"] = object()
        gw._breakers["lane_a"] = gw._make_breaker()
        gw._ring.add_node("lane_a")
        gw._prefill_ring.add_node("lane_a")
        gw._apply_topology("lane_a", {"tp": 4, "devices": 4})
        assert gw._ring.node_weight("lane_a") == 4
        assert gw._prefill_ring.node_weight("lane_a") == 4
        assert gw.get_stats()["topology"]["lanes"]["lane_a"][
            "devices"] == 4
        # Unchanged label: no-op (updates counter steady).
        n0 = gw.get_stats()["topology"]["updates"]
        gw._apply_topology("lane_a", {"tp": 4, "devices": 4})
        assert gw.get_stats()["topology"]["updates"] == n0
        # Label withdrawn (lane restarted without --tp): back to 1.
        gw._apply_topology("lane_a", None)
        assert gw._ring.node_weight("lane_a") == 1
    finally:
        gw.stop()


@pytest.mark.slow
def test_worker_tp_e2e_health_and_generate(spec, params):
    """A real tp=2 worker lane: /health carries the topology label, the
    generate path serves sharded, streams match a tp=1 lane."""
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig

    def lane(nid, tp, offset=0):
        cfg = WorkerConfig(node_id=nid, model="gpt2-small-test",
                           gen_kv_block_size=16,
                           tp=tp, tp_device_offset=offset)
        return WorkerNode(cfg, engine=InferenceEngine(
            spec, params=params, dtype="float32"))

    w2, w1 = lane("w_tp2", 2, offset=2), lane("w_ref", 1)
    try:
        h = w2.get_health()
        assert h["topology"] == {"tp": 2, "mesh_shape": {"model": 2},
                                 "devices": 2}
        assert "topology" not in w1.get_health()
        # The lane's mesh spans ITS device slice (offset 2), not the
        # first tp devices — in-process TP lanes own disjoint chips.
        assert (list(w2.generator._tp_mesh.devices.flat)
                == jax.devices()[2:4])
        req = {"request_id": "t1", "prompt_tokens": PROMPTS[0],
               "max_new_tokens": 8}
        assert (w2.handle_generate(dict(req))["tokens"]
                == w1.handle_generate(dict(req))["tokens"])
    finally:
        w2.stop()
        w1.stop()
