"""Live stream migration: chain wire format, export/import determinism,
the gateway's migrate-mode drain, and every fallback ladder rung.

DESIGN.md "Live stream migration": ``remove_worker(drain=True)`` with
``migrate_streams`` on EXPORTS each journaled in-flight stream's row —
emitted tokens, sampling state, remaining budget, and its KV block chain
(dtype-preserving bytes + crc32 checksum + generation stamp) — and
resumes it mid-stream on another lane with ZERO re-prefilled tokens,
splicing the continuation byte-identically (the PR 6 positional-fold
argument plus verbatim KV bytes). Every failure — checksum mismatch,
full or dead destination, transfer timeout — lands on the replay resume
with both sides' partial state cleaned up.
"""

import base64
import queue
import threading
import time
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models.transformer import TransformerConfig
from tpu_engine.runtime.kv_blocks import BlockPool, scatter_blocks
from tpu_engine.runtime.scheduler import ImportRefused, StreamMigratedAway
from tpu_engine.serving.gateway import Gateway, _parse_sse
from tpu_engine.serving.resilience import MigrationCounters
from tpu_engine.serving.worker import WorkerNode
from tpu_engine.utils.config import GatewayConfig, WorkerConfig


def _cfg(**kw):
    base = dict(vocab=97, d_model=32, n_layers=2, n_heads=2, d_ff=64,
                max_seq=64)
    base.update(kw)
    return TransformerConfig(**base)


def _fill_blocks(pool, n, seed=0):
    """Allocate ``n`` blocks and scatter deterministic random payloads
    into them (full-precision pools only). Returns the block ids."""
    import jax

    with pool.lock:
        ids = pool.alloc(n)
        L, bs = pool.cfg.n_layers, pool.block_size
        H, D = pool.cfg.kv_heads, pool.cfg.d_head
        rng = np.random.RandomState(seed)
        rk = rng.randn(L, 1, n * bs, H, D).astype(np.float32)
        rv = rng.randn(L, 1, n * bs, H, D).astype(np.float32)
        if pool.quantized:
            from tpu_engine.runtime.kv_blocks import scatter_blocks_quant

            pool.caches, pool.scales = jax.jit(
                scatter_blocks_quant, donate_argnums=(0, 1))(
                pool.caches, pool.scales, jnp.asarray(rk),
                jnp.asarray(rv), jnp.asarray(ids))
        else:
            pool.caches = jax.jit(scatter_blocks, donate_argnums=(0,))(
                pool.caches, jnp.asarray(rk), jnp.asarray(rv),
                jnp.asarray(ids))
    return ids


# -- wire format: round trips, checksums, compatibility -----------------------

@pytest.mark.parametrize("quant", ["", "int8"])
def test_chain_round_trip_bit_exact(quant):
    """export_chain -> import_chain reproduces the exact bytes — bf16
    payloads verbatim, int8 payload + f32 scales copied together (the
    write-once rule survives the wire: nothing requantizes)."""
    cfg = _cfg()
    a = BlockPool(cfg, 8, 4, jnp.bfloat16, quantize=quant)
    b = BlockPool(cfg, 8, 4, jnp.bfloat16, quantize=quant)
    ids = _fill_blocks(a, 3)
    with a.lock:
        chain = a.export_chain(ids)
    assert BlockPool.verify_chain(chain)
    assert chain["quantized"] == (quant == "int8")
    assert chain["generation"] == a.generation
    if quant:
        assert "ks" in chain["blocks"][0] and "vs" in chain["blocks"][0]
    with b.lock:
        assert b.chain_compatible(chain) is None
        ids2 = b.alloc(3)
        b.import_chain(chain, chain["blocks"], ids2)
        chain2 = b.export_chain(ids2)
    assert chain2["checksum"] == chain["checksum"]
    assert chain2["blocks"] == chain["blocks"]


@pytest.mark.parametrize("quant", ["", "int8"])
def test_chain_wire_format_is_the_documented_one(quant):
    """The pool's layout changed on the DEVICE (PR 26: blocks are
    (L, bs, H_kv*D) there), not on the wire: a block's `k`/`v` is still
    the C-order bytes of (L, bs, H_kv, D) at the storage dtype, `ks`/`vs`
    of (L, bs, H_kv) f32, the checksum a crc32 over them in chain order —
    built here with numpy from the values alone — and a chain written by
    hand in that format imports and reads back as those values."""
    import jax
    import ml_dtypes

    from tpu_engine.ops.quant import quantize_kv

    cfg = _cfg(n_heads=4, n_kv_heads=2)     # grouped: H_kv is not H
    L, bs, H, D = cfg.n_layers, 4, cfg.kv_heads, cfg.d_head
    assert H * D != cfg.d_model
    n, seed = 3, 7
    pool = BlockPool(cfg, 8, bs, jnp.bfloat16, quantize=quant)
    ids = _fill_blocks(pool, n, seed=seed)
    # The values _fill_blocks scattered: one dense (L, 1, n*bs, H, D) row.
    rng = np.random.RandomState(seed)
    rows = [rng.randn(L, 1, n * bs, H, D).astype(np.float32)
            for _ in "kv"]
    if quant:
        # The values the int8 pool holds: the one production quantizer
        # (compiled, as the admission scatter runs it) over the row.
        quantized = [jax.jit(quantize_kv)(jnp.asarray(r)) for r in rows]
        rows = [np.asarray(q) for q, _ in quantized] \
            + [np.asarray(sc) for _, sc in quantized]
    else:
        rows = [r.astype(ml_dtypes.bfloat16) for r in rows]
    want = []
    for j in range(n):
        arrays = [r[:, 0, j * bs:(j + 1) * bs] for r in rows]  # (L,bs,H..)
        assert arrays[0].shape == (L, bs, H, D)
        want.append(dict(zip(("k", "v", "ks", "vs"), arrays)))
    with pool.lock:
        chain = pool.export_chain(ids)
    crc = 0
    for entry, arrays in zip(chain["blocks"], want):
        assert sorted(entry) == sorted(arrays)
        for name, arr in arrays.items():
            assert base64.b64decode(entry[name]) == arr.tobytes(), name
            crc = zlib.crc32(arr.tobytes(), crc)
    assert chain["checksum"] == crc
    # The other direction: a chain built by hand in the documented
    # format is accepted and lands as the same values.
    hand = {"version": 1, "dtype": "int8" if quant else "bfloat16",
            "quantized": bool(quant), "block_size": bs, "n_layers": L,
            "kv_heads": H, "d_head": D, "checksum": crc, "generation": 0,
            "blocks": [{name: base64.b64encode(arr.tobytes()).decode()
                        for name, arr in arrays.items()}
                       for arrays in want]}
    other = BlockPool(cfg, 8, bs, jnp.bfloat16, quantize=quant)
    with other.lock:
        assert other.chain_compatible(hand) is None
        assert BlockPool.verify_chain(hand)
        ids2 = other.alloc(n)
        other.import_chain(hand, hand["blocks"], ids2)
        assert other.export_chain(ids2)["blocks"] == chain["blocks"]
    for j, bid in enumerate(ids2):
        got = np.asarray(other.caches.k[:, bid]).reshape(L, bs, H, D)
        assert got.tobytes() == want[j]["k"].tobytes()


def test_chain_export_host_demoted_without_swap_in():
    """A demoted radix leaf exports from its pinned HOST buffers —
    bit-identical to the pre-demotion device bytes, with zero swap-in
    traffic (the pool's swap_ins counter must not move)."""
    cfg = _cfg()
    pool = BlockPool(cfg, 8, 4, jnp.bfloat16, host_blocks=4)
    ids = _fill_blocks(pool, 2)
    tokens = list(range(1, 9))  # two full blocks of 4
    with pool.lock:
        pool.radix.insert(tokens, ids)
        before = pool.export_chain(ids)
        pool.release_many(ids)          # tree-only now
        assert pool.radix.evict(2) == 2  # both demote to the host tier
        nodes = pool.radix.chain_nodes(tokens)
        assert len(nodes) == 2 and all(n.demoted for n in nodes)
        after = pool.export_chain(nodes)
    assert after["blocks"] == before["blocks"]
    assert after["checksum"] == before["checksum"]
    assert pool.swap_ins == 0


def test_chain_checksum_and_geometry_gates():
    cfg = _cfg()
    pool = BlockPool(cfg, 8, 4, jnp.bfloat16)
    ids = _fill_blocks(pool, 2)
    with pool.lock:
        chain = pool.export_chain(ids)
    # Bit flip in a payload -> checksum fails.
    raw = bytearray(base64.b64decode(chain["blocks"][0]["k"]))
    raw[0] ^= 0xFF
    bad = {**chain, "blocks": [dict(chain["blocks"][0],
                                    k=base64.b64encode(bytes(raw)).decode()),
                               chain["blocks"][1]]}
    assert not BlockPool.verify_chain(bad)
    # Geometry mismatches are named, not silently imported.
    other = BlockPool(cfg, 8, 8, jnp.bfloat16)
    assert "block_size" in other.chain_compatible(chain)
    qpool = BlockPool(cfg, 8, 4, jnp.bfloat16, quantize="int8")
    assert qpool.chain_compatible(chain) is not None  # dtype named first


def _rechecksum(chain):
    """Recompute a (possibly doctored) chain's checksum so it is
    SELF-CONSISTENT — the fuzz tests that must be caught by structural
    validation, not the crc."""
    crc = 0
    for entry in chain["blocks"]:
        for name in ("k", "v", "ks", "vs"):
            if name in entry:
                crc = zlib.crc32(base64.b64decode(entry[name]), crc)
    return {**chain, "checksum": crc}


def test_chain_fuzz_truncated_payloads_refused_before_alloc():
    """Truncated payload bytes — with a checksum recomputed to match,
    so only STRUCTURAL validation can catch them — are refused by
    chain_compatible with the byte counts named, before any allocation
    (the import gate runs it first; a raw reshape would crash the
    decode thread and kill every live row)."""
    cfg = _cfg()
    pool = BlockPool(cfg, 8, 4, jnp.bfloat16)
    ids = _fill_blocks(pool, 2)
    with pool.lock:
        chain = pool.export_chain(ids)
    free0 = pool.free_blocks
    for cut in (0, 1, 17):
        raw = base64.b64decode(chain["blocks"][1]["k"])[:cut]
        bad = _rechecksum({**chain, "blocks": [
            chain["blocks"][0],
            dict(chain["blocks"][1],
                 k=base64.b64encode(raw).decode())]})
        assert BlockPool.verify_chain(bad)  # crc is self-consistent...
        reason = pool.chain_compatible(bad)  # ...structure still refuses
        assert reason is not None and str(cut) in reason, (cut, reason)
    # Missing tensor entirely / non-base64 garbage: named, not crashed.
    bad = _rechecksum({**chain, "blocks": [
        {k: v for k, v in chain["blocks"][0].items() if k != "v"}]})
    assert "missing 'v'" in pool.chain_compatible(bad)
    bad = {**chain, "blocks": [dict(chain["blocks"][0], k="!!not-b64!!")]}
    assert "not base64" in pool.chain_compatible(bad)
    assert pool.free_blocks == free0  # pure validation: nothing allocated


def test_chain_fuzz_corrupted_crc_and_garbage():
    """Corrupted checksums and structurally garbage chains refuse via
    verify_chain returning False — never an exception (the gate runs on
    the prefill thread against attacker-shaped bytes)."""
    cfg = _cfg()
    pool = BlockPool(cfg, 8, 4, jnp.bfloat16)
    ids = _fill_blocks(pool, 1)
    with pool.lock:
        chain = pool.export_chain(ids)
    assert not BlockPool.verify_chain({**chain,
                                       "checksum": chain["checksum"] ^ 1})
    assert not BlockPool.verify_chain({**chain, "checksum": "wat"})
    for garbage in ({}, {"blocks": 3}, {"blocks": [None]},
                    {"blocks": [{"k": 5}], "checksum": 0},
                    {"blocks": "nope", "checksum": 0}):
        assert BlockPool.verify_chain(garbage) is False


def test_chain_fuzz_mismatched_geometry_headers():
    """Every geometry/dtype header mismatch is refused with the KEY
    named — cross-dtype or cross-shape imports would reinterpret bytes
    (or requantize), never silently land."""
    cfg = _cfg()
    pool = BlockPool(cfg, 8, 4, jnp.bfloat16)
    ids = _fill_blocks(pool, 1)
    with pool.lock:
        chain = pool.export_chain(ids)
    for key, bogus in (("dtype", "float64"), ("quantized", True),
                       ("block_size", 32), ("n_layers", 7),
                       ("kv_heads", 5), ("d_head", 48)):
        reason = pool.chain_compatible({**chain, key: bogus})
        assert reason is not None and key in reason, (key, reason)
    # Absent header (old/foreign producer) refuses the same way.
    chopped = {k: v for k, v in chain.items() if k != "d_head"}
    assert "d_head" in pool.chain_compatible(chopped)


def test_zero_block_chain_refused_before_alloc(fleet):
    """A snapshot whose chain holds ZERO blocks for a row spanning
    several must resolve ImportRefused on the validation path — before
    any allocation (blocks_free untouched)."""
    src, dst = fleet[0].generator, fleet[1].generator
    q: queue.Queue = queue.Queue()
    src.submit(PROMPT, max_new_tokens=16, stream=q, tag="zb")
    got = []
    while len(got) < 3:
        item = q.get(timeout=60)
        assert item is not None
        got.extend(item)
    snap = src.export_row("zb")
    assert snap["ok"], snap
    empty = _rechecksum({**snap["chain"], "blocks": []})
    free0 = dst.stats()["kv_pool"]["blocks_free"]
    fut = dst.submit_import({**snap, "chain": empty}, tag="zb2")
    with pytest.raises(ImportRefused) as ei:
        fut.result(timeout=30)
    assert "holds 0 blocks" in str(ei.value)
    assert dst.stats()["kv_pool"]["blocks_free"] == free0
    assert dst.stats()["migration"]["import_rejected"] >= 1
    # A chain that is not even an object refuses the same way.
    fut = dst.submit_import({**snap, "chain": "garbage"}, tag="zb3")
    with pytest.raises(ImportRefused):
        fut.result(timeout=30)
    assert _wait(lambda: pool_leak_free(fleet[1]))


def test_migration_counters_schema():
    c = MigrationCounters()
    assert not c.any_nonzero()
    for f in MigrationCounters.FIELDS:
        assert c.get(f) == 0
    c.bump("tokens_migrated", 9)
    assert c.as_dict()["tokens_migrated"] == 9 and c.any_nonzero()
    assert "tokens_migrated" not in MigrationCounters.SPAN_FIELDS


# -- real-model fleet fixtures ------------------------------------------------

GEN_KW = dict(model="gpt2-small-test", dtype="float32",
              gen_scheduler="continuous", gen_step_chunk=2,
              gen_kv_block_size=16, gen_kv_blocks=40,
              gen_prefill_chunk=16, gen_max_batch_size=4)


@pytest.fixture(scope="module")
def fleet():
    """Three in-process lanes sharing one parameter set (the lane-
    uniformity deployments migration assumes — MIGRATION.md)."""
    workers = [WorkerNode(WorkerConfig(node_id=f"w{i}", **GEN_KW))
               for i in range(3)]
    p0 = workers[0].engine.params
    for w in workers[1:]:
        w.apply_weights(p0)
    yield workers
    for w in workers:
        w.stop()


@pytest.fixture(autouse=True)
def _heal_fleet(request):
    yield
    if "fleet" in request.fixturenames:
        for w in request.getfixturevalue("fleet"):
            w.heal()
            w.undrain()


def _wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


def pool_leak_free(worker) -> bool:
    st = worker.generator.stats()
    kp = st["kv_pool"]
    return (st["active"] == 0
            and kp["blocks_free"] + kp["radix_nodes"] >= kp["blocks_total"])


def rid_for(gw, lane, tag="m"):
    return next(f"{tag}{i}" for i in range(4000)
                if gw._ring.get_node(f"{tag}{i}") == lane)


PROMPT = [5, 9, 3, 17, 4, 22, 8]


def _stream_with_drain(gw, req, drain_lane, min_tokens=3,
                       drain_fn=None):
    """Consume a gateway stream on a thread; once ``min_tokens`` are
    relayed, drain ``drain_lane`` (migrate-mode removal) and join.
    Returns (tokens, final_event)."""
    toks, final = [], [None]
    armed = threading.Event()

    def consume():
        for frame in gw.route_generate_stream(dict(req)):
            evt = _parse_sse(frame)
            if evt is None:
                continue
            if evt.get("done"):
                final[0] = evt
                break
            if "tokens" in evt:
                toks.extend(evt["tokens"])
                if len(toks) >= min_tokens:
                    armed.set()

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    assert armed.wait(120), "stream never reached the drain point"
    (drain_fn or (lambda: gw.remove_worker(drain_lane, drain=True)))()
    t.join(timeout=120)
    assert final[0] is not None, "stream never terminated"
    return toks, final[0]


def _migration_spans(gw):
    return [s for s in gw.tracer.snapshot() if s["op"] == "migration"]


def _assert_counters_match_spans(gw):
    mig = gw.get_stats()["migration"]
    expect = sum(mig[f] for f in MigrationCounters.SPAN_FIELDS)
    spans = _migration_spans(gw)
    assert len(spans) == expect, (mig, [s["attrs"] for s in spans])


# -- scheduler-level export/import -------------------------------------------

@pytest.mark.parametrize("params", [
    {},                                                   # greedy
    {"temperature": 0.9, "seed": 11},                     # seeded sampled
    {"temperature": 0.8, "seed": 4, "repetition_penalty": 1.3,
     "stop_tokens": [7], "top_p": 0.9},                   # controls
])
def test_export_import_round_trip_stream_identity(fleet, params):
    """export_row -> submit_import continues the stream byte-identically
    to an uninterrupted run — greedy, seeded, and penalty/stop streams —
    with ZERO re-prefilled tokens on the destination."""
    src, dst, ctl = (w.generator for w in fleet)
    control = fleet[2].handle_generate(
        {"request_id": "ctl", "prompt_tokens": PROMPT,
         "max_new_tokens": 24, **params})["tokens"]
    q: queue.Queue = queue.Queue()
    fut = src.submit(PROMPT, max_new_tokens=24, stream=q, tag="exp1",
                     **{k: v for k, v in params.items()
                        if k != "stop_tokens"},
                     stop_tokens=params.get("stop_tokens"))
    got = []
    while len(got) < 3:
        item = q.get(timeout=60)
        assert item is not None, (got, control)
        got.extend(item)
    pre_prefilled = dst.stats()["kv_pool"]["prefilled_tokens"]
    snap = src.export_row("exp1")
    assert snap["ok"], snap
    while True:  # drain the source's flush + sentinel
        item = q.get(timeout=10)
        if item is None:
            break
        got.extend(item)
    with pytest.raises(StreamMigratedAway) as ei:
        fut.result(timeout=5)
    assert ei.value.retryable and ei.value.migrated
    assert ei.value.tokens_emitted == len(got) == snap["streamed"]

    q2: queue.Queue = queue.Queue()
    fut2 = dst.submit_import(snap, stream=q2, tag="exp1b")
    cont = []
    while True:
        item = q2.get(timeout=60)
        if item is None:
            break
        cont.extend(item)
    assert got + cont == control
    assert fut2.result(timeout=10) == control
    # Zero re-prefilled tokens: the import never ran a prefill window.
    assert dst.stats()["kv_pool"]["prefilled_tokens"] == pre_prefilled
    assert dst.stats()["migration"]["imported_rows"] >= 1
    assert src.stats()["migration"]["exported_rows"] >= 1
    assert _wait(lambda: pool_leak_free(fleet[0]))
    assert _wait(lambda: pool_leak_free(fleet[1]))


def test_export_refusals(fleet):
    gen = fleet[0].generator
    out = gen.export_row("no-such-tag", timeout_s=5.0)
    assert not out["ok"] and "no live row" in out["reason"]
    assert gen.stats().get("migration", {}).get("export_refused", 0) == 0
    # (unknown tags are not counted as refusals — nothing was refused)


def test_import_checksum_mismatch_is_retryable_and_clean(fleet):
    """A corrupted chain is rejected BEFORE any block allocation: the
    future resolves ImportRefused (retryable), the pool is untouched."""
    src, dst = fleet[0].generator, fleet[1].generator
    q: queue.Queue = queue.Queue()
    src.submit(PROMPT, max_new_tokens=20, stream=q, tag="cksum")
    got = []
    while len(got) < 3:
        item = q.get(timeout=60)
        got.extend(item or [])
    snap = src.export_row("cksum")
    assert snap["ok"], snap
    raw = bytearray(base64.b64decode(snap["chain"]["blocks"][0]["k"]))
    raw[0] ^= 0xFF
    snap["chain"]["blocks"][0]["k"] = \
        base64.b64encode(bytes(raw)).decode()
    free0 = dst.stats()["kv_pool"]["blocks_free"]
    fut = dst.submit_import(snap, tag="cksum-b")
    with pytest.raises(ImportRefused, match="checksum"):
        fut.result(timeout=30)
    assert dst.stats()["migration"]["import_rejected"] >= 1
    assert dst.stats()["kv_pool"]["blocks_free"] == free0


def test_import_truncated_payload_with_consistent_checksum_refused(fleet):
    """A chain whose checksum is self-consistent over TRUNCATED payload
    bytes must be refused on the validation path (ImportRefused), never
    crash the decode thread mid-admission — a decode-thread failure
    recovers the pool and kills every live row on the lane."""
    import zlib

    src, dst = fleet[0].generator, fleet[1].generator
    q: queue.Queue = queue.Queue()
    src.submit(PROMPT, max_new_tokens=16, stream=q, tag="trunc")
    got = []
    while len(got) < 3:
        item = q.get(timeout=60)
        got.extend(item or [])
    snap = src.export_row("trunc")
    assert snap["ok"], snap
    # Truncate one payload and RECOMPUTE the checksum over the mangled
    # bytes — verify_chain alone would pass this.
    blk0 = snap["chain"]["blocks"][0]
    blk0["k"] = base64.b64encode(
        base64.b64decode(blk0["k"])[:-8]).decode()
    crc = 0
    for entry in snap["chain"]["blocks"]:
        for name in ("k", "v", "ks", "vs"):
            if name in entry:
                crc = zlib.crc32(base64.b64decode(entry[name]), crc)
    snap["chain"]["checksum"] = crc
    failures0 = dst.stats().get("failures", 0)
    fut = dst.submit_import(snap, tag="trunc-b")
    with pytest.raises(ImportRefused, match="bytes"):
        fut.result(timeout=30)
    # No device-state recovery happened: the lane kept serving.
    assert dst.stats().get("failures", 0) == failures0


def test_import_refused_when_pool_cannot_keep_reserve(fleet):
    """A destination that cannot hold the chain while keeping the
    live-row reserve free refuses the import (retryable) with nothing
    consumed — live rows outrank a resurrected stream."""
    src = fleet[0].generator
    q: queue.Queue = queue.Queue()
    src.submit(PROMPT, max_new_tokens=20, stream=q, tag="full")
    got = []
    while len(got) < 3:
        item = q.get(timeout=60)
        got.extend(item or [])
    snap = src.export_row("full")
    assert snap["ok"], snap
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    tiny = ContinuousGenerator(
        "gpt2-small-test", params=fleet[0].engine.params,
        dtype="float32", n_slots=2, step_chunk=2, prefill_chunk=16,
        kv_block_size=16, kv_blocks=5)  # 4 usable blocks
    try:
        # Occupy the pool with a live row so the reserve rule binds.
        ql: queue.Queue = queue.Queue()
        tiny.submit([1, 2, 3, 4] * 8, max_new_tokens=30, stream=ql,
                    tag="occupant")
        while True:
            item = ql.get(timeout=60)
            if item:
                break
        fut = tiny.submit_import(snap, tag="full-b")
        with pytest.raises(ImportRefused):
            fut.result(timeout=60)
        assert tiny.stats()["migration"]["import_rejected"] >= 1
    finally:
        tiny.stop()


def test_import_geometry_mismatch_refused(fleet):
    src = fleet[0].generator
    q: queue.Queue = queue.Queue()
    src.submit(PROMPT, max_new_tokens=16, stream=q, tag="geo")
    got = []
    while len(got) < 3:
        item = q.get(timeout=60)
        got.extend(item or [])
    snap = src.export_row("geo")
    assert snap["ok"], snap
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    other = ContinuousGenerator(
        "gpt2-small-test", params=fleet[0].engine.params,
        dtype="float32", n_slots=2, step_chunk=2, prefill_chunk=16,
        kv_block_size=8, kv_blocks=20)
    try:
        fut = other.submit_import(snap, tag="geo-b")
        with pytest.raises(ImportRefused, match="block_size"):
            fut.result(timeout=60)
    finally:
        other.stop()


def test_import_radix_readopt_skips_shipped_prefix(fleet):
    """A destination already caching the prompt prefix RE-ADOPTS its own
    radix blocks: fewer chain tokens imported, stream still identical."""
    shared = [(j * 13) % 90 + 1 for j in range(32)]  # two full blocks
    # Warm the destination's radix with the shared prefix.
    fleet[1].handle_generate({"request_id": "warm", "prompt_tokens":
                              shared + [2], "max_new_tokens": 2})
    control = fleet[2].handle_generate(
        {"request_id": "ctl-ra", "prompt_tokens": shared + [5],
         "max_new_tokens": 16})["tokens"]
    src, dst = fleet[0].generator, fleet[1].generator
    q: queue.Queue = queue.Queue()
    src.submit(shared + [5], max_new_tokens=16, stream=q, tag="ra")
    got = []
    while len(got) < 3:
        item = q.get(timeout=60)
        got.extend(item or [])
    snap = src.export_row("ra")
    assert snap["ok"], snap
    while True:
        item = q.get(timeout=10)
        if item is None:
            break
        got.extend(item)
    hits0 = dst.stats()["kv_pool"]["radix_hits"]
    mig0 = dst.stats().get("migration", {}).get("imported_chain_tokens", 0)
    q2: queue.Queue = queue.Queue()
    fut2 = dst.submit_import(snap, stream=q2, tag="ra-b")
    cont = []
    while True:
        item = q2.get(timeout=60)
        if item is None:
            break
        cont.extend(item)
    assert got + cont == control and fut2.result(timeout=10) == control
    st = dst.stats()
    assert st["kv_pool"]["radix_hits"] > hits0
    shipped = st["migration"]["imported_chain_tokens"] - mig0
    # At least the two matched prompt blocks were NOT shipped.
    n_chain_tokens = len(snap["chain"]["blocks"]) * 16
    assert shipped <= n_chain_tokens - 32


# -- gateway-level migrate-mode drain -----------------------------------------

def make_gw(fleet, **kw):
    kw.setdefault("failover_streams", True)
    kw.setdefault("migrate_streams", True)
    kw.setdefault("migrate_timeout_s", 20.0)
    return Gateway(list(fleet), GatewayConfig(**kw))


@pytest.mark.parametrize("params", [
    {},
    {"temperature": 0.9, "seed": 31},
])
def test_migrate_mode_drain_splices_byte_identical(fleet, params):
    gw = make_gw(fleet)
    try:
        control = fleet[2].handle_generate(
            {"request_id": "gctl", "prompt_tokens": PROMPT,
             "max_new_tokens": 32, **params})["tokens"]
        rid = rid_for(gw, "w0", "gd")
        req = {"request_id": rid, "prompt_tokens": PROMPT,
               "max_new_tokens": 32, **params}
        toks, final = _stream_with_drain(gw, req, "w0")
        assert "error" not in final, final
        assert toks == control and final["tokens"] == control
        mig = gw.get_stats()["migration"]
        assert mig["streams_migrated"] >= 1
        assert mig["migration_fallbacks"] == 0
        # Zero replay traffic in a clean migration.
        assert gw.get_stats().get("failover",
                                  {}).get("tokens_replayed", 0) == 0
        _assert_counters_match_spans(gw)
        assert "w0" not in gw.worker_names()
        assert _wait(lambda: all(pool_leak_free(w) for w in fleet))
    finally:
        gw.stop()


def test_fallback_corrupted_transfer_lands_on_replay(fleet):
    """Checksum mismatch at the destination: the continuation segment
    dies retryable and the journal's replay resume completes the stream
    byte-identically — with the fallback counted."""
    gw = make_gw(fleet)
    try:
        src_client = gw._clients["w0"]
        real_migrate = src_client.migrate

        def corrupting_migrate(payload, timeout_s=None):
            out = real_migrate(payload, timeout_s)
            if out.get("ok"):
                blk = out["chain"]["blocks"][0]
                raw = bytearray(base64.b64decode(blk["k"]))
                raw[0] ^= 0xFF
                blk["k"] = base64.b64encode(bytes(raw)).decode()
            return out

        src_client.migrate = corrupting_migrate
        control = fleet[2].handle_generate(
            {"request_id": "cctl", "prompt_tokens": PROMPT,
             "max_new_tokens": 28})["tokens"]
        rid = rid_for(gw, "w0", "ck")
        req = {"request_id": rid, "prompt_tokens": PROMPT,
               "max_new_tokens": 28}
        toks, final = _stream_with_drain(gw, req, "w0")
        assert "error" not in final, final
        assert toks == control and final["tokens"] == control
        mig = gw.get_stats()["migration"]
        assert mig["migration_fallbacks"] >= 1
        _assert_counters_match_spans(gw)
        assert _wait(lambda: all(pool_leak_free(w) for w in fleet))
    finally:
        gw.stop()


def test_fallback_dead_destination_lands_on_replay(fleet):
    """Destination dead at continuation dispatch: import_dispatch_failed
    is counted and the replay resume completes the stream."""
    gw = make_gw(fleet)
    try:
        fleet[2].inject_fault("dest down")
        gw._pick_migration_dest = lambda record, source: "w2"
        control = fleet[1].handle_generate(
            {"request_id": "dctl", "prompt_tokens": PROMPT,
             "max_new_tokens": 28})["tokens"]
        rid = rid_for(gw, "w0", "dd")
        req = {"request_id": rid, "prompt_tokens": PROMPT,
               "max_new_tokens": 28}
        toks, final = _stream_with_drain(gw, req, "w0")
        assert "error" not in final, final
        assert toks == control and final["tokens"] == control
        mig = gw.get_stats()["migration"]
        assert mig["import_dispatch_failed"] >= 1
        assert mig["migration_fallbacks"] >= 1
        _assert_counters_match_spans(gw)
    finally:
        fleet[2].heal()
        gw.stop()


def test_fallback_transfer_timeout_lands_on_replay(fleet):
    """An export that exceeds the per-transfer budget: the orchestrator
    gives up (export_refusals), the relay's handoff wait expires, and
    the replay resume completes the stream."""
    gw = make_gw(fleet, migrate_timeout_s=0.3)
    try:
        src_client = gw._clients["w0"]
        real_migrate = src_client.migrate

        def slow_migrate(payload, timeout_s=None):
            out = real_migrate(payload, timeout_s)
            time.sleep(2.5)  # blow the 0.3 s transfer budget (+1 s slack)
            return out

        src_client.migrate = slow_migrate
        control = fleet[2].handle_generate(
            {"request_id": "tctl", "prompt_tokens": PROMPT,
             "max_new_tokens": 28})["tokens"]
        rid = rid_for(gw, "w0", "tt")
        req = {"request_id": rid, "prompt_tokens": PROMPT,
               "max_new_tokens": 28}
        toks, final = _stream_with_drain(gw, req, "w0")
        assert "error" not in final, final
        assert toks == control and final["tokens"] == control
        mig = gw.get_stats()["migration"]
        assert mig["export_refusals"] >= 1
        assert mig["migration_fallbacks"] >= 1
        _assert_counters_match_spans(gw)
    finally:
        gw.stop()


def test_drain_during_active_failover(fleet):
    """Interplay: a stream's first lane DIES mid-stream (PR 6 replay
    resume moves it), then its NEW lane is drained with migration — the
    twice-moved stream still matches the uninterrupted control."""
    gw = make_gw(fleet)
    try:
        # First segment dies after 3 frames (kill -9 signature): the
        # journal replay-resumes it onto another lane.
        w0_client = gw._clients["w0"]
        orig_stream = w0_client.generate_stream
        calls = {"n": 0}

        def dying_stream(payload):
            calls["n"] += 1
            inner = orig_stream(payload)
            if calls["n"] > 1:
                return inner

            def gen():
                n = 0
                for frame in inner:
                    if n >= 3:
                        inner.close()
                        raise ConnectionResetError("lane died")
                    yield frame
                    n += 1
            return gen()

        w0_client.generate_stream = dying_stream
        control = fleet[2].handle_generate(
            {"request_id": "ictl", "prompt_tokens": PROMPT,
             "max_new_tokens": 32})["tokens"]
        rid = rid_for(gw, "w0", "ip")
        req = {"request_id": rid, "prompt_tokens": PROMPT,
               "max_new_tokens": 32}
        toks, final = [], [None]
        resumed = threading.Event()

        def consume():
            for frame in gw.route_generate_stream(dict(req)):
                evt = _parse_sse(frame)
                if evt is None:
                    continue
                if evt.get("done"):
                    final[0] = evt
                    break
                if "tokens" in evt:
                    toks.extend(evt["tokens"])
                    if (gw.active_streams().get(rid)
                            not in (None, "w0")):
                        resumed.set()

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        assert resumed.wait(120), "stream never resumed off w0"
        new_lane = gw.active_streams().get(rid)
        assert new_lane in ("w1", "w2"), new_lane
        gw.remove_worker(new_lane, drain=True)
        t.join(timeout=120)
        assert final[0] is not None and "error" not in final[0], final[0]
        assert toks == control and final[0]["tokens"] == control
        assert final[0].get("resumed") == 1  # one replay, one migration
        assert gw.get_stats()["migration"]["streams_migrated"] >= 1
        _assert_counters_match_spans(gw)
        assert _wait(lambda: all(pool_leak_free(w) for w in fleet))
    finally:
        gw.stop()


def test_bounded_drain_call_timeout(fleet):
    """Satellite: remove_worker(drain=True) must not hang on a wedged
    lane — the drain call is abandoned after drain_timeout_s, counted
    (drain_failures + span), and removal proceeds."""
    gw = Gateway(list(fleet),
                 GatewayConfig(drain_timeout_s=0.3))
    try:
        blocked = threading.Event()

        class WedgedClient:
            def drain(self):
                blocked.set()
                time.sleep(30)

        gw._clients["w1"] = WedgedClient()
        t0 = time.monotonic()
        gw.remove_worker("w1", drain=True)
        assert time.monotonic() - t0 < 5.0
        assert blocked.is_set()
        assert "w1" not in gw.worker_names()
        mig = gw.get_stats()["migration"]
        assert mig["drain_failures"] == 1
        spans = _migration_spans(gw)
        assert len(spans) == 1
        assert spans[0]["attrs"]["decision"] == "drain_failures"
    finally:
        gw.stop()


def test_defaults_off_schema_and_behavior(fleet):
    """Defaults-off byte compat: no migration block anywhere, no stream
    registry, and remove_worker(drain=True) is today's shed+replay."""
    gw = Gateway(list(fleet), GatewayConfig())
    try:
        assert "migration" not in gw.get_stats()
        # Scheduler-side: a lane that never exported or imported keeps
        # its stats schema byte-identical (the shared fleet has been
        # exercised — use a fresh scheduler).
        from tpu_engine.runtime.scheduler import ContinuousGenerator

        fresh = ContinuousGenerator(
            "gpt2-small-test", params=fleet[0].engine.params,
            dtype="float32", n_slots=2, step_chunk=2, prefill_chunk=16,
            kv_block_size=16, kv_blocks=20)
        try:
            assert fresh.generate([[4, 2, 7]], max_new_tokens=4)
            assert "migration" not in fresh.stats()
        finally:
            fresh.stop()
        it = gw.route_generate_stream(
            {"request_id": "off2", "prompt_tokens": [4, 2, 7],
             "max_new_tokens": 4})
        for _ in it:
            pass
        assert gw.active_streams() == {}
        gw.remove_worker("w2", drain=True)
        assert "w2" not in gw.worker_names()
        assert "migration" not in gw.get_stats()
    finally:
        gw.stop()


def test_worker_admin_migrate_surface(fleet):
    """/admin/migrate contract: unknown streams come back ok=False (the
    orchestrator's fallback needs no exception), missing request_id is a
    client error, and a non-continuous lane refuses loudly."""
    out = fleet[0].handle_migrate_export({"request_id": "nope"})
    assert out["ok"] is False and out["node_id"] == "w0"
    with pytest.raises((KeyError, ValueError)):
        fleet[0].handle_migrate_export({})

    class _NoGenLane:
        generator = None
        node_id = "x"

    out2 = WorkerNode.handle_migrate_export(_NoGenLane(),
                                            {"request_id": "r"})
    assert out2["ok"] is False


@pytest.mark.slow
def test_quantized_migration_round_trip():
    """int8+scale chains migrate verbatim: the continuation equals the
    uninterrupted QUANTIZED control (deterministic per PR 10's
    contract), and host scale-slot accounting stays clean."""
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    kw = dict(n_slots=4, step_chunk=2, prefill_chunk=16,
              kv_block_size=16, kv_blocks=40, kv_quantize="int8",
              dtype="float32")
    A = ContinuousGenerator("gpt2-small-test", **kw)
    B = ContinuousGenerator("gpt2-small-test", params=A.params, **kw)
    C = ContinuousGenerator("gpt2-small-test", params=A.params, **kw)
    try:
        control = C.generate([PROMPT], max_new_tokens=24)[0]
        q: queue.Queue = queue.Queue()
        A.submit(PROMPT, max_new_tokens=24, stream=q, tag="qm")
        got = []
        while len(got) < 3:
            item = q.get(timeout=120)
            got.extend(item or [])
        snap = A.export_row("qm")
        assert snap["ok"], snap
        assert snap["chain"]["quantized"] is True
        while True:
            item = q.get(timeout=10)
            if item is None:
                break
            got.extend(item)
        q2: queue.Queue = queue.Queue()
        fut2 = B.submit_import(snap, stream=q2, tag="qm-b")
        cont = []
        while True:
            item = q2.get(timeout=120)
            if item is None:
                break
            cont.extend(item)
        assert got + cont == control
        assert fut2.result(timeout=10) == control
    finally:
        A.stop()
        B.stop()
        C.stop()


# -- state_slab (SSD/Mamba) chains: the one-pseudo-block wire format ----------


def _ssd_fleet_kw():
    return dict(model="ssd-small-test", dtype="float32",
                gen_scheduler="continuous", gen_step_chunk=2,
                gen_prefill_chunk=16, gen_max_batch_size=4,
                gen_state_rows=8)


@pytest.fixture(scope="module")
def ssd_fleet():
    """Two in-process state_slab lanes sharing one parameter set."""
    workers = [WorkerNode(WorkerConfig(node_id=f"s{i}", **_ssd_fleet_kw()))
               for i in range(2)]
    p0 = workers[0].engine.params
    workers[1].apply_weights(p0)
    yield workers
    for w in workers:
        w.stop()


def slab_leak_free(worker) -> bool:
    st = worker.generator.stats()
    sp = st["state_pool"]
    return (st["active"] == 0
            and sp["rows_free"] == sp["rows_total"]
            and sp["rows_admitted"] == sp["rows_released"])


def test_ssd_state_chain_round_trip_bit_exact():
    """A state row's one-pseudo-block chain exports and imports
    BIT-exactly between same-geometry slab pools, and the paged pool's
    checksum verifier accepts the shape unchanged (shared wire
    format)."""
    from tpu_engine.runtime.kv_blocks import StateSlabPool

    src = StateSlabPool(3, 11, 4)
    rid = src.alloc_row()
    flat = (np.arange(33, dtype=np.float32).reshape(3, 11) * 0.173
            - 2.5)
    src.slab = src.slab.at[:, rid].set(jnp.asarray(flat))
    chain = src.export_row_chain(rid)
    assert BlockPool.verify_chain(chain)  # the PR 11 verifier, verbatim
    dst = StateSlabPool(3, 11, 4)
    assert dst.chain_compatible(chain) is None
    rid2 = dst.alloc_row()
    dst.import_row_chain(chain, rid2)
    assert np.array_equal(np.asarray(dst.slab[:, rid2]), flat)


def test_ssd_state_chain_refusals_named_before_allocation():
    from tpu_engine.runtime.kv_blocks import StateSlabPool

    src = StateSlabPool(2, 7, 3)
    chain = src.export_row_chain(src.alloc_row())
    # Every geometry/family header mismatch is NAMED; a kv_paged pool
    # never accepts a state chain (family key) and vice versa.
    assert "family" in BlockPool(_cfg(), 4, 16,
                                 jnp.float32).chain_compatible(chain)
    assert "state_dim" in StateSlabPool(2, 8, 3).chain_compatible(chain)
    assert "n_layers" in StateSlabPool(3, 7, 3).chain_compatible(chain)
    assert "dtype" in StateSlabPool(
        2, 7, 3, dtype=jnp.bfloat16).chain_compatible(chain)
    # Truncated payload with a SELF-CONSISTENT checksum: refused
    # structurally with byte counts named (never reaches allocation).
    raw = base64.b64decode(chain["blocks"][0]["k"])[:-8]
    trunc = dict(chain, blocks=[{"k": base64.b64encode(raw).decode()}],
                 checksum=zlib.crc32(raw))
    assert StateSlabPool.verify_chain(trunc)  # checksum IS consistent
    reason = src.chain_compatible(trunc)
    assert "48" in reason and "56" in reason  # holds vs expected bytes


@pytest.mark.parametrize("params", [
    {},                                      # greedy
    {"temperature": 0.9, "seed": 1234},      # seeded sampling
])
def test_ssd_stream_migrates_between_lanes_byte_identical(ssd_fleet,
                                                          params):
    """Migration of an SSD stream between lanes splices byte-identically
    through the WORKER surface (/admin/migrate export → migrate_import
    continuation): the state slab ships verbatim, decoding resumes at
    the exported position with zero re-prefill, zero slab leaks on both
    lanes."""
    src, dst = ssd_fleet
    control = src.handle_generate(
        {"request_id": "sc", "prompt_tokens": PROMPT,
         "max_new_tokens": 20, **params})["tokens"]

    toks, final = [], [None]
    armed = threading.Event()
    exported = {}

    def consume():
        stream = src.handle_generate_stream(
            {"request_id": "sm1", "prompt_tokens": PROMPT,
             "max_new_tokens": 20, **params})
        for frame in stream:
            evt = _parse_sse(frame)
            if evt is None:
                continue
            if evt.get("done"):
                final[0] = evt
                break
            if "tokens" in evt:
                toks.extend(evt["tokens"])
                if len(toks) >= 4:
                    armed.set()

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    assert armed.wait(120), "stream never reached the export point"
    exported = src.handle_migrate_export({"request_id": "sm1"})
    t.join(timeout=120)
    assert exported["ok"], exported
    assert exported["chain"]["family"] == "state_slab"
    assert final[0] is not None and final[0].get("migrated") is True

    # Adopt on the destination lane via the migrate_import surface.
    cont = []
    for frame in dst.handle_generate_stream(
            {"request_id": "sm1b", "migrate_import": exported}):
        evt = _parse_sse(frame)
        if evt is None:
            continue
        if evt.get("done"):
            assert "error" not in evt, evt
            spliced = toks + cont
            assert spliced == control
            assert evt["tokens"] == control
            break
        if "tokens" in evt:
            cont.extend(evt["tokens"])
    assert _wait(lambda: slab_leak_free(src) and slab_leak_free(dst))
    assert src.generator.stats()["migration"]["exported_rows"] >= 1
    assert dst.generator.stats()["migration"]["imported_rows"] >= 1
