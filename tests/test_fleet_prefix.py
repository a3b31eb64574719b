"""Fleet-wide KV prefix tier (gateway radix directory + peer block fetch).

Contracts under test (DESIGN.md "Fleet-wide prefix tier"):
- the gateway directory is a bounded LRU hint cache: record/lookup,
  capacity eviction, deeper-entry preference, and per-lane GENERATION
  invalidation (eager sweep + lazy lookup drop);
- the gateway stamps generate-class payloads with a ``prefix_hint``
  naming the owner lane exactly when the directory knows a different
  lane's chain — and never mutates routing itself;
- ``/admin/export_prefix`` serves the longest radix chain matching the
  requested token prefix (partial matches at block grain, bounded by
  max_blocks) and refuses BY NAME while draining;
- a hinted lane splices the peer's chain and the stream stays
  byte-identical to a local-prefill control — greedy, seeded sampling,
  int8 pools, host-demoted chains, and mixed-step admission alike;
- EVERY fallback-ladder rung (peer_unreachable / peer_refused /
  timeout / inflight_capped / checksum_failed / geometry_mismatch /
  stale_generation / pool_full / no_gain) recomputes locally, counts
  exactly once, and never strands or corrupts the stream;
- defaults off = wire-byte-identical: no ``prefix_directory`` /stats
  block, no ``prefix_fetch`` scheduler family, no ``prefix_hint`` in
  dispatched payloads, no ``prefix_fingerprints`` in /health;
- every directory decision has a matching ``prefix_dir`` marker span
  (counters==spans; evictions is the span-free value counter).
"""

import base64
import socket
import threading

import pytest

from tpu_engine.serving.gateway import Gateway
from tpu_engine.serving.prefix_directory import PrefixDirectory
from tpu_engine.serving.resilience import PrefixDirCounters
from tpu_engine.serving.worker import WorkerNode
from tpu_engine.utils.config import GatewayConfig, WorkerConfig


# -- directory unit tests (pure state; no jax) --------------------------------

def test_directory_record_lookup_roundtrip():
    d = PrefixDirectory(capacity=4)
    assert d.lookup("fp0") is None
    assert d.record("fp0", "w1", 3) == 0
    e = d.lookup("fp0")
    assert e == {"lane": "w1", "blocks": 3, "generation": 0}
    # Same-lane refresh overwrites depth either direction.
    d.record("fp0", "w1", 2)
    assert d.lookup("fp0")["blocks"] == 2


def test_directory_keeps_deeper_entry_on_other_lane():
    d = PrefixDirectory(capacity=4)
    d.record("fp", "w1", 3)
    # A shallower claim from another lane must not demote the owner...
    d.record("fp", "w2", 1)
    assert d.lookup("fp")["lane"] == "w1"
    # ...but a deeper one takes it over.
    d.record("fp", "w2", 5)
    assert d.lookup("fp") == {"lane": "w2", "blocks": 5, "generation": 0}


def test_directory_lru_capacity_eviction():
    d = PrefixDirectory(capacity=3)
    for i in range(3):
        d.record(f"fp{i}", "w1", 1)
    assert d.lookup("fp0") is not None  # touch: fp0 becomes most-recent
    assert d.record("fp3", "w1", 1) == 1  # evicts the LRU entry (fp1)
    assert d.lookup("fp1") is None
    assert d.lookup("fp0") is not None
    assert d.stats()["entries"] == 3


def test_directory_generation_invalidation():
    d = PrefixDirectory(capacity=8)
    d.record("a", "w1", 2)
    d.record("b", "w1", 3)
    d.record("c", "w2", 1)
    # Eager sweep drops every w1 entry and bumps the generation.
    assert d.invalidate_lane("w1") == 2
    assert d.lookup("a") is None and d.lookup("b") is None
    assert d.lookup("c") is not None
    assert d.lane_generation("w1") == 1
    # Entries recorded AFTER the bump carry the new generation and live.
    d.record("a", "w1", 2)
    assert d.lookup("a")["generation"] == 1
    # Lazy backstop: an entry stamped with a stale generation dies in
    # lookup even without an eager sweep.
    d._entries["ghost"] = {"lane": "w1", "blocks": 1, "generation": 0}
    assert d.lookup("ghost") is None
    assert "ghost" not in d._entries


def test_prefix_dir_counters_schema():
    c = PrefixDirCounters()
    assert not c.any_nonzero()
    for f in PrefixDirCounters.FIELDS:
        assert c.get(f) == 0
    c.bump("evictions", 4)
    assert c.as_dict()["evictions"] == 4 and c.any_nonzero()
    # evictions is the span-free VALUE counter of the family.
    assert "evictions" not in PrefixDirCounters.SPAN_FIELDS
    for f in ("seeded", "recorded", "invalidations", "hints_attached",
              "lookup_misses"):
        assert f in PrefixDirCounters.SPAN_FIELDS


# -- gateway directory behavior (stub lanes; no jax) --------------------------

class StubLane:
    """Minimal generate-speaking lane capturing dispatched payloads."""

    def __init__(self, node_id):
        self.node_id = node_id
        self.payloads = []

    def handle_generate(self, payload):
        self.payloads.append(dict(payload))
        return {"request_id": payload["request_id"],
                "tokens": [1, 2], "node_id": self.node_id,
                "generate_time_us": 1}

    def get_health(self):
        return {"healthy": True, "node_id": self.node_id}


SHARED = list(range(100, 132))  # two full blocks at block size 16


def _gw(n=3, **cfg_kw):
    lanes = [StubLane(f"w{i}") for i in range(n)]
    return lanes, Gateway(lanes, GatewayConfig(**cfg_kw))


def _rid_for(gw, lane, tag="q"):
    return next(f"{tag}{i}" for i in range(4000)
                if gw._ring.get_node(f"{tag}{i}") == lane)


def _rid_not_for(gw, lane, tag="q"):
    return next(f"{tag}{i}" for i in range(4000)
                if gw._ring.get_node(f"{tag}{i}") != lane)


def test_gateway_records_owner_and_attaches_hint():
    lanes, gw = _gw(prefix_directory=True)
    by_name = {l.node_id: l for l in lanes}
    first = gw._ring.get_node("seed-0")
    # First request: nothing to look up (lookup_misses), owner recorded
    # post-completion.
    r0 = _rid_for(gw, first)
    gw.route_generate({"request_id": r0, "prompt_tokens": list(SHARED),
                       "max_new_tokens": 1})
    assert "prefix_hint" not in by_name[first].payloads[-1]
    pd = gw.get_stats()["prefix_directory"]
    assert pd["recorded"] == 1 and pd["lookup_misses"] == 1
    assert pd["lanes"] == {first: 1}
    # Owner == primary: no hint (the request lands on the blocks
    # already), and no miss is counted either.
    gw.route_generate({"request_id": _rid_for(gw, first, tag="z"),
                       "prompt_tokens": list(SHARED),
                       "max_new_tokens": 1})
    assert "prefix_hint" not in by_name[first].payloads[-1]
    assert gw.get_stats()["prefix_directory"]["lookup_misses"] == 1
    # Same prefix, ring-routed to a DIFFERENT lane: the payload arrives
    # stamped with the owner's hint — routing itself is unchanged.
    r1 = _rid_not_for(gw, first)
    other = gw._ring.get_node(r1)
    gw.route_generate({"request_id": r1, "prompt_tokens": list(SHARED),
                       "max_new_tokens": 1})
    hinted = by_name[other].payloads[-1]
    assert hinted["prefix_hint"]["lane"] == first
    assert hinted["prefix_hint"]["blocks"] == 2
    assert hinted["prefix_hint"]["fingerprint"] == \
        gw._affinity_fingerprint({"prompt_tokens": SHARED})
    assert gw.get_stats()["prefix_directory"]["hints_attached"] == 1
    gw.stop()


def test_gateway_seed_from_health_summaries():
    _, gw = _gw(prefix_directory=True)
    gw._seed_prefix_dir("w1", [{"tokens": list(SHARED), "blocks": 2},
                               {"tokens": [5], "blocks": 1},  # no full block
                               "garbage"])
    fp = gw._affinity_fingerprint({"prompt_tokens": SHARED})
    with gw._lock:
        e = gw._prefix_dir.lookup(fp)
    assert e is not None and e["lane"] == "w1" and e["blocks"] == 2
    pd = gw.get_stats()["prefix_directory"]
    # One seeded bump per SWEEP, not per entry.
    assert pd["seeded"] == 1
    # Re-seeding the identical summary is a no-op (no second bump).
    gw._seed_prefix_dir("w1", [{"tokens": list(SHARED), "blocks": 2}])
    assert gw.get_stats()["prefix_directory"]["seeded"] == 1
    gw.stop()


def test_gateway_remove_worker_invalidates_owner():
    lanes, gw = _gw(prefix_directory=True)
    gw._seed_prefix_dir("w1", [{"tokens": list(SHARED), "blocks": 2}])
    gw.remove_worker("w1")
    fp = gw._affinity_fingerprint({"prompt_tokens": SHARED})
    with gw._lock:
        assert gw._prefix_dir.lookup(fp) is None
    pd = gw.get_stats()["prefix_directory"]
    assert pd["invalidations"] == 1
    # A dispatched request after removal gets no hint (lookup miss).
    rid = _rid_not_for(gw, "w1")
    gw.route_generate({"request_id": rid, "prompt_tokens": list(SHARED),
                       "max_new_tokens": 1})
    served = [l for l in lanes if l.payloads]
    assert all("prefix_hint" not in p for l in served for p in l.payloads)
    gw.stop()


def test_gateway_counters_match_marker_spans():
    _, gw = _gw(prefix_directory=True)
    gw._seed_prefix_dir("w1", [{"tokens": list(SHARED), "blocks": 2}])
    for i in range(3):
        gw.route_generate({"request_id": f"s{i}",
                           "prompt_tokens": SHARED + [i],
                           "max_new_tokens": 1})
    gw.remove_worker("w2")
    pd = gw.get_stats()["prefix_directory"]
    spans = [s for s in gw.tracer.snapshot() if s["op"] == "prefix_dir"]
    by_decision = {}
    for s in spans:
        d = s["attrs"]["decision"]
        by_decision[d] = by_decision.get(d, 0) + 1
    for field in PrefixDirCounters.SPAN_FIELDS:
        assert by_decision.get(field, 0) == pd[field], field
    gw.stop()


def test_gateway_defaults_off_wire_identical():
    lanes, gw = _gw()  # no prefix_directory
    gw.route_generate({"request_id": "r0", "prompt_tokens": list(SHARED),
                       "max_new_tokens": 1})
    gw.route_generate({"request_id": "r1", "prompt_tokens": list(SHARED),
                       "max_new_tokens": 1})
    stats = gw.get_stats()
    assert "prefix_directory" not in stats
    assert all("prefix_hint" not in p for l in lanes for p in l.payloads)
    assert not any(s["op"] == "prefix_dir" for s in gw.tracer.snapshot())
    gw.stop()


# -- real-lane fleet: export, splice identity, fallback rungs -----------------

BS = 16
GEN_KW = dict(model="gpt2-small-test", dtype="float32",
              gen_scheduler="continuous", gen_step_chunk=2,
              gen_kv_block_size=BS, gen_kv_blocks=40,
              gen_prefill_chunk=16, gen_max_batch_size=4,
              gen_prefix_fetch=True)

PROMPT48 = list(range(7, 55))  # three full blocks


def _req(prompt, rid, **kw):
    return dict({"request_id": rid, "prompt_tokens": list(prompt),
                 "max_new_tokens": 8}, **kw)


@pytest.fixture(scope="module")
def owner():
    w = WorkerNode(WorkerConfig(node_id="w0", **GEN_KW))
    yield w
    w.stop()


@pytest.fixture(scope="module")
def _lane_registry(owner):
    return {"w0": owner}


@pytest.fixture(scope="module")
def transport(_lane_registry):
    def fn(hint, payload):
        return _lane_registry[hint["lane"]].handle_export_prefix(payload)
    return fn


@pytest.fixture()
def fetcher(owner, transport, request):
    """A FRESH lane per test (empty radix — every hinted admission is a
    genuine local miss) sharing the owner's weights."""
    w = WorkerNode(WorkerConfig(node_id=f"f-{request.node.name[:24]}",
                                **GEN_KW))
    w.apply_weights(owner.engine.params)
    w.set_prefix_fetch_transport(transport)
    yield w
    w.stop()


@pytest.fixture(scope="module")
def control(owner):
    """Greedy control tokens for PROMPT48 — also seeds the owner's
    radix tree with the three prompt blocks every fetch test pulls."""
    return owner.handle_generate(_req(PROMPT48, "ctl"))["tokens"]


def _pfetch(worker):
    return worker.generator.stats().get("prefix_fetch") or {}


def pool_leak_free(worker) -> bool:
    st = worker.generator.stats()
    kp = st["kv_pool"]
    return (st["active"] == 0
            and kp["blocks_free"] + kp["radix_nodes"] >= kp["blocks_total"])


def test_export_prefix_partial_match_lengths(owner, control):
    gen = owner.generator
    full = gen.export_prefix(PROMPT48)
    assert full["ok"] and full["blocks"] == 3
    assert len(full["chain"]["blocks"]) == 3
    two = gen.export_prefix(PROMPT48[:32])
    assert two["ok"] and two["blocks"] == 2
    # A diverging tail matches only the shared leading blocks.
    partial = gen.export_prefix(PROMPT48[:32] + [999] * 16)
    assert partial["ok"] and partial["blocks"] == 2
    capped = gen.export_prefix(PROMPT48, max_blocks=1)
    assert capped["ok"] and capped["blocks"] == 1
    miss = gen.export_prefix([901, 902, 903] * 8)
    assert not miss["ok"] and "no matching prefix" in miss["reason"]
    short = gen.export_prefix(PROMPT48[:5])  # no full block to match
    assert not short["ok"]


def test_export_prefix_drain_refuses_by_name(owner, control):
    owner.drain()
    try:
        out = owner.handle_export_prefix({"tokens": PROMPT48})
        assert not out["ok"]
        assert out["reason"] == "lane w0 is draining"
        assert out["node_id"] == "w0"
    finally:
        owner.undrain()
    ok = owner.handle_export_prefix({"tokens": PROMPT48})
    assert ok["ok"] and ok["blocks"] == 3


def test_splice_identity_greedy(owner, control, fetcher):
    out = fetcher.handle_generate(
        _req(PROMPT48, "g1", prefix_hint={"lane": "w0", "blocks": 3}))
    assert out["tokens"] == control
    p = _pfetch(fetcher)
    # The last prompt block always recomputes (sampling params are not
    # part of the radix key): 2 of 3 blocks splice, 32 tokens skipped.
    assert p["attempted"] == 1 and p["spliced"] == 1
    assert p["blocks_spliced"] == 2
    assert p["prefill_tokens_skipped_remote"] == 32
    assert pool_leak_free(fetcher)
    # The spliced blocks joined the local radix: a SECOND identical
    # request is now a pure local hit — no second fetch attempt.
    out2 = fetcher.handle_generate(
        _req(PROMPT48, "g2", prefix_hint={"lane": "w0", "blocks": 3}))
    assert out2["tokens"] == control
    assert _pfetch(fetcher)["attempted"] == 1


def test_splice_identity_seeded_sampling(owner, control, fetcher):
    sampled = dict(temperature=0.9, seed=11, max_new_tokens=8)
    want = owner.handle_generate(_req(PROMPT48, "s0", **sampled))["tokens"]
    out = fetcher.handle_generate(
        _req(PROMPT48, "s1", prefix_hint={"lane": "w0", "blocks": 3},
             **sampled))
    assert out["tokens"] == want
    assert _pfetch(fetcher)["spliced"] == 1


def test_splice_identity_small_token_budget(owner):
    """The 16 tokens past the splice prefill over two ticks of eight."""
    kw = dict(GEN_KW, gen_mixed_token_budget=8)
    mx_owner = WorkerNode(WorkerConfig(node_id="mx0", **kw))
    mx_owner.apply_weights(owner.engine.params)
    mx_fetch = WorkerNode(WorkerConfig(node_id="mx1", **kw))
    mx_fetch.apply_weights(owner.engine.params)
    lanes = {"mx0": mx_owner}
    mx_fetch.set_prefix_fetch_transport(
        lambda hint, payload: lanes[hint["lane"]].handle_export_prefix(
            payload))
    try:
        want = mx_owner.handle_generate(_req(PROMPT48, "m0"))["tokens"]
        out = mx_fetch.handle_generate(
            _req(PROMPT48, "m1", prefix_hint={"lane": "mx0", "blocks": 3}))
        assert out["tokens"] == want
        assert _pfetch(mx_fetch)["spliced"] == 1
        assert pool_leak_free(mx_fetch)
    finally:
        mx_owner.stop()
        mx_fetch.stop()


def test_splice_identity_int8_pool(owner):
    kw = dict(GEN_KW, gen_kv_quantize="int8")
    q_owner = WorkerNode(WorkerConfig(node_id="q0", **kw))
    q_owner.apply_weights(owner.engine.params)
    q_fetch = WorkerNode(WorkerConfig(node_id="q1", **kw))
    q_fetch.apply_weights(owner.engine.params)
    lanes = {"q0": q_owner}
    q_fetch.set_prefix_fetch_transport(
        lambda hint, payload: lanes[hint["lane"]].handle_export_prefix(
            payload))
    try:
        want = q_owner.handle_generate(_req(PROMPT48, "q-a"))["tokens"]
        chain = q_owner.generator.export_prefix(PROMPT48)["chain"]
        assert chain["quantized"]
        assert "ks" in chain["blocks"][0]  # scales ride the wire
        out = q_fetch.handle_generate(
            _req(PROMPT48, "q-b", prefix_hint={"lane": "q0", "blocks": 3}))
        assert out["tokens"] == want
        assert _pfetch(q_fetch)["spliced"] == 1
    finally:
        q_owner.stop()
        q_fetch.stop()


def test_splice_identity_host_demoted_chain(owner):
    kw = dict(GEN_KW, gen_kv_host_blocks=8)
    h_owner = WorkerNode(WorkerConfig(node_id="h0", **kw))
    h_owner.apply_weights(owner.engine.params)
    h_fetch = WorkerNode(WorkerConfig(node_id="h1", **GEN_KW))
    h_fetch.apply_weights(owner.engine.params)
    lanes = {"h0": h_owner}
    h_fetch.set_prefix_fetch_transport(
        lambda hint, payload: lanes[hint["lane"]].handle_export_prefix(
            payload))
    try:
        want = h_owner.handle_generate(_req(PROMPT48, "h-a"))["tokens"]
        pool = h_owner.generator._pool
        with pool.lock:
            pool.radix.evict(2)  # demote the two LRU frontier leaves
            demoted = sum(1 for _ in _walk_demoted(pool.radix))
        assert demoted > 0
        out = h_fetch.handle_generate(
            _req(PROMPT48, "h-b", prefix_hint={"lane": "h0", "blocks": 3}))
        assert out["tokens"] == want
        assert _pfetch(h_fetch)["spliced"] == 1
    finally:
        h_owner.stop()
        h_fetch.stop()


def _walk_demoted(radix):
    stack = [radix.root]
    while stack:
        node = stack.pop()
        stack.extend(node.children.values())
        if getattr(node, "host_slot", -1) >= 0:
            yield node


# -- fallback ladder: every rung recomputes locally, counted once -------------

def _assert_rung(fetcher, control, rid, rung, hint=None):
    before = dict(_pfetch(fetcher))
    out = fetcher.handle_generate(
        _req(PROMPT48, rid,
             prefix_hint=hint or {"lane": "w0", "blocks": 3}))
    assert out["tokens"] == control  # the stream NEVER strands
    after = _pfetch(fetcher)
    assert after["attempted"] == before.get("attempted", 0) + 1
    assert after[rung] == before.get(rung, 0) + 1
    assert after["spliced"] == before.get("spliced", 0)
    assert pool_leak_free(fetcher)


def test_rung_peer_unreachable(owner, control, fetcher):
    def dead(hint, payload):
        raise RuntimeError("peer process is gone")
    fetcher.set_prefix_fetch_transport(dead)
    _assert_rung(fetcher, control, "ru-1", "peer_unreachable")


def test_rung_peer_refused_drained_owner(owner, control, fetcher):
    owner.drain()
    try:
        _assert_rung(fetcher, control, "rr-1", "peer_refused")
    finally:
        owner.undrain()


def test_rung_timeout_http_path(owner, control, fetcher):
    class TimedOutClient:
        def export_prefix(self, payload, timeout_s=None):
            raise socket.timeout("timed out")
    fetcher.set_prefix_fetch_transport(None)  # force the HTTP path
    fetcher._prefix_peer_client = lambda addr: TimedOutClient()
    _assert_rung(fetcher, control, "rt-1", "timeout",
                 hint={"lane": "w0", "addr": "h:1", "blocks": 3})


def test_rung_inflight_capped(owner, control, transport, fetcher):
    held = 0
    while fetcher._prefix_fetch_sem.acquire(blocking=False):
        held += 1
    try:
        _assert_rung(fetcher, control, "rc-1", "inflight_capped")
    finally:
        for _ in range(held):
            fetcher._prefix_fetch_sem.release()


def test_rung_checksum_failed(owner, control, transport, fetcher):
    def corrupting(hint, payload):
        out = transport(hint, payload)
        entry = out["chain"]["blocks"][0]
        raw = bytearray(base64.b64decode(entry["k"]))
        raw[0] ^= 0xFF  # same length, wrong bytes
        entry["k"] = base64.b64encode(bytes(raw)).decode("ascii")
        return out
    fetcher.set_prefix_fetch_transport(corrupting)
    _assert_rung(fetcher, control, "rk-1", "checksum_failed")


def test_rung_geometry_mismatch(owner, control, transport, fetcher):
    def wrong_geometry(hint, payload):
        out = transport(hint, payload)
        out["chain"]["block_size"] = 8
        return out
    fetcher.set_prefix_fetch_transport(wrong_geometry)
    _assert_rung(fetcher, control, "rg-1", "geometry_mismatch")


def test_rung_stale_generation(owner, control, transport, fetcher):
    """A pool rebuild landing between the radix snapshot and the splice:
    the foreign chain must NOT be imported into the rebuilt pool
    (stale_generation, no splice). The request itself then dies at
    admission as a pool-rebuild casualty — the PRE-EXISTING
    _StaleAdmission contract, not a fetch regression — and the lane
    keeps serving."""
    pool = fetcher.generator._pool

    def racing_recovery(hint, payload):
        out = transport(hint, payload)
        with pool.lock:
            pool.generation += 1  # a recovery landed mid-fetch
        return out
    fetcher.set_prefix_fetch_transport(racing_recovery)
    with pytest.raises(RuntimeError, match="rebuilt"):
        fetcher.handle_generate(
            _req(PROMPT48, "rs-1",
                 prefix_hint={"lane": "w0", "blocks": 3}))
    p = _pfetch(fetcher)
    assert p["attempted"] == 1 and p["stale_generation"] == 1
    assert p["spliced"] == 0 and p["blocks_spliced"] == 0
    assert pool_leak_free(fetcher)
    # The lane keeps serving: a plain request completes byte-identical.
    fetcher.set_prefix_fetch_transport(transport)
    out = fetcher.handle_generate(_req(PROMPT48, "rs-2"))
    assert out["tokens"] == control


def test_rung_pool_full(owner, control, transport, fetcher):
    pool = fetcher.generator._pool
    orig = pool.can_alloc
    armed = {"on": False}

    def arming(hint, payload):
        out = transport(hint, payload)
        armed["on"] = True  # the NEXT can_alloc is the splice's check
        return out

    def can_alloc(n):
        if armed["on"]:
            armed["on"] = False
            return False
        return orig(n)
    pool.can_alloc = can_alloc
    try:
        fetcher.set_prefix_fetch_transport(arming)
        _assert_rung(fetcher, control, "rp-1", "pool_full")
    finally:
        pool.can_alloc = orig


@pytest.fixture()
def _shallow_owner(owner, _lane_registry):
    """A peer whose radix holds exactly ONE block of PROMPT48 — its
    honest chain cannot beat a fetcher that already matched a block."""
    w = WorkerNode(WorkerConfig(node_id="ng-owner", **GEN_KW))
    w.apply_weights(owner.engine.params)
    w.handle_generate(_req(PROMPT48[:17], "ng-seed"))
    _lane_registry["ng-owner"] = w
    yield w
    _lane_registry.pop("ng-owner", None)
    w.stop()


def test_rung_no_gain_shallow_peer(owner, control, _shallow_owner,
                                   fetcher):
    shallow = _shallow_owner.generator.export_prefix(PROMPT48)
    assert shallow["ok"] and shallow["blocks"] == 1
    # The fetcher also holds the first block; a hint PROMISING two makes
    # the fetch worth attempting, but the peer's one-block chain adds
    # nothing over the local match.
    fetcher.handle_generate(_req(PROMPT48[:17], "ng-warm"))
    before = dict(_pfetch(fetcher))
    out = fetcher.handle_generate(
        _req(PROMPT48, "ng-1", prefix_hint={"lane": "ng-owner",
                                            "blocks": 2}))
    assert out["tokens"] == control
    after = _pfetch(fetcher)
    assert after["attempted"] == before.get("attempted", 0) + 1
    assert after["no_gain"] == before.get("no_gain", 0) + 1
    assert pool_leak_free(fetcher)


def test_self_hint_is_inert(owner, control):
    # A hint naming the serving lane itself: nothing to fetch — not
    # even counted as an attempt.
    before = dict(_pfetch(owner))
    out = owner.handle_generate(
        _req(PROMPT48, "self-1", prefix_hint={"lane": "w0", "blocks": 3}))
    assert out["tokens"] == control
    assert _pfetch(owner).get("attempted", 0) == before.get("attempted", 0)


def test_concurrent_hinted_streams_consistent(owner, control, transport,
                                              fetcher):
    """Two hinted admissions racing on one lane: whichever order the
    prefill thread serves them, both streams land byte-identical and
    the pool stays leak-free (the second is a local hit or a second
    splice — never a corruption)."""
    results = [None, None]

    def run(i):
        results[i] = fetcher.handle_generate(
            _req(PROMPT48, f"cc-{i}",
                 prefix_hint={"lane": "w0", "blocks": 3}))["tokens"]
    threads = [threading.Thread(target=run, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert results[0] == control and results[1] == control
    assert pool_leak_free(fetcher)


# -- defaults off = wire-byte-identical ---------------------------------------

def test_worker_defaults_off_ignores_hint(owner):
    off = WorkerNode(WorkerConfig(
        node_id="off0", **{k: v for k, v in GEN_KW.items()
                           if k != "gen_prefix_fetch"}))
    off.apply_weights(owner.engine.params)
    try:
        want = off.handle_generate(_req(PROMPT48, "off-a"))["tokens"]
        out = off.handle_generate(
            _req(PROMPT48, "off-b",
                 prefix_hint={"lane": "w0", "blocks": 3}))
        assert out["tokens"] == want
        st = off.generator.stats()
        assert "prefix_fetch" not in st
        assert "prefix_fingerprints" not in off.get_health()
        assert off.generator.prefix_fetch is None
    finally:
        off.stop()


def test_fetch_on_but_unused_stats_gated(owner):
    quiet = WorkerNode(WorkerConfig(node_id="quiet0", **GEN_KW))
    try:
        quiet.handle_generate(_req(PROMPT48, "quiet-a"))
        # No hint ever acted on: the scheduler family stays absent.
        assert "prefix_fetch" not in quiet.generator.stats()
        # ...but the /health radix summary IS on (the directory's feed).
        fps = quiet.get_health()["prefix_fingerprints"]
        assert fps and fps[0]["blocks"] == 3
        assert fps[0]["tokens"][:16] == PROMPT48[:16]
    finally:
        quiet.stop()


def test_prefix_fetch_fence_refuses_dense():
    with pytest.raises(RuntimeError, match="--prefix-fetch requires"):
        WorkerNode(WorkerConfig(
            node_id="fence0", model="gpt2-small-test", dtype="float32",
            gen_scheduler="continuous", gen_prefix_fetch=True))


def test_export_prefix_refused_without_paged_sharing(owner):
    out = owner.handle_export_prefix({"tokens": []})
    assert not out["ok"]
    assert "no token prefix" in out["reason"]
    # A scheduler without prefix sharing cannot serve chains — refusal
    # is a named dict, never a raise (the fetcher falls back locally).
    no_share = WorkerNode(WorkerConfig(
        node_id="ns0", **dict(
            {k: v for k, v in GEN_KW.items() if k != "gen_prefix_fetch"},
            gen_prefix_sharing=False)))
    try:
        refused = no_share.generator.export_prefix(PROMPT48)
        assert not refused["ok"]
        assert "prefix sharing" in refused["reason"]
    finally:
        no_share.stop()
