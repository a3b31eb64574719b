"""Native-specific coverage: ctypes layer, FNV parity, batch queue.

The full LRU/ring/breaker semantic suites already run against the native
implementations via tests/impl_params.py parametrization; these tests cover
what is native-only.
"""

import os
import threading
import time

import pytest

from tpu_engine.core import native
from tpu_engine.core.consistent_hash import fnv1a_32

pytestmark = pytest.mark.skipif(not native.available(), reason="libtpucore.so not built")


def test_fnv1a_native_matches_python():
    for key in ["", "a", "foobar", "worker_1#149", "req_12345", "host:8001#0"]:
        assert native.native_fnv1a_32(key) == fnv1a_32(key)


def test_ring_assignment_bit_identical_to_python():
    # Same request → same lane across the native and Python routing paths.
    from tpu_engine.core.consistent_hash import ConsistentHash

    py, nat = ConsistentHash(150), native.NativeConsistentHash(150)
    for n in ["w1", "w2", "w3", "host:8001", "host:8002"]:
        py.add_node(n)
        nat.add_node(n)
    keys = [f"req_{i}" for i in range(300)]
    assert [py.get_node(k) for k in keys] == [nat.get_node(k) for k in keys]
    assert py.get_all_nodes() == nat.get_all_nodes()


def test_lru_binary_safe_keys_and_values():
    c = native.NativeLRUCache(4)
    key = b"\x00\xffkey\x00with\nnuls"
    val = {"output": [1.5, -2.5], "blob": b"\x00\x01\x02"}
    c.put(key, val)
    assert c.get(key) == val


def test_batch_queue_roundtrip_and_tickets():
    q = native.NativeBatchQueue(max_batch=8, timeout_s=0.05)
    t0 = q.push(b"a")
    t1 = q.push(b"b")
    assert (t0, t1) == (0, 1)
    items, timed_out = q.pop_batch()
    assert [p for _, p in items] == [b"a", b"b"]
    assert [t for t, _ in items] == [0, 1]
    assert not timed_out  # queue was non-empty: notify-path semantics


def test_batch_queue_timeout_empty():
    q = native.NativeBatchQueue(max_batch=4, timeout_s=0.05)
    start = time.monotonic()
    items, timed_out = q.pop_batch()
    assert items == [] and timed_out
    assert 0.03 <= time.monotonic() - start < 1.0


def test_batch_queue_respects_max_batch():
    q = native.NativeBatchQueue(max_batch=3, timeout_s=0.05)
    for i in range(7):
        q.push(bytes([i]))
    sizes = []
    for _ in range(3):
        items, _ = q.pop_batch()
        sizes.append(len(items))
    assert sizes == [3, 3, 1]


def test_batch_queue_close_unblocks_and_drains():
    q = native.NativeBatchQueue(max_batch=4, timeout_s=5.0)
    result = {}

    def popper():
        result["first"] = q.pop_batch()
        result["second"] = q.pop_batch()

    t = threading.Thread(target=popper)
    t.start()
    time.sleep(0.05)
    q.push(b"x")
    time.sleep(0.05)
    q.close()
    t.join(timeout=2)
    assert not t.is_alive()
    items, _ = result["first"]
    assert [p for _, p in items] == [b"x"]
    closed_items, _ = result["second"]
    assert closed_items is None  # closed + drained
    assert q.push(b"y") == -1  # push after close rejected


def test_batch_queue_concurrent_producers():
    q = native.NativeBatchQueue(max_batch=32, timeout_s=0.02)
    N = 200

    def producer(base):
        for i in range(N // 4):
            q.push(f"{base}:{i}".encode())

    threads = [threading.Thread(target=producer, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    got = []
    while len(got) < N:
        items, _ = q.pop_batch()
        assert items is not None
        got.extend(items)
    tickets = [t for t, _ in got]
    assert sorted(tickets) == list(range(N))  # every push got a unique ticket
    assert len({p for _, p in got}) == N


def test_ring_node_names_with_newline_roundtrip():
    r = native.NativeConsistentHash(10)
    r.add_node("rack1\nlane0")
    r.add_node("plain")
    assert sorted(r.get_all_nodes()) == ["plain", "rack1\nlane0"]
    assert r.size() == 2


def test_lru_rejects_non_bytes_keys():
    c = native.NativeLRUCache(4)
    with pytest.raises(TypeError):
        c.put("str-key", 1)
    with pytest.raises(TypeError):
        c.get(123)


def test_json_encode_f32_roundtrips():
    """The native %.6g output encoder (miss-path response fragments): six
    significant digits round-trip within 1e-5 relative — beyond bf16's own
    noise — and non-finite values spell exactly what json.dumps emits, so
    json.loads round-trips them."""
    import json

    import numpy as np

    a = np.random.default_rng(1).standard_normal(257).astype(np.float32)
    a *= np.float32(10.0) ** np.random.default_rng(2).integers(-8, 8, 257)
    frag = native.json_encode_f32(a)
    back = np.asarray(json.loads(frag), np.float32)
    rel = np.max(np.abs(back - a) / (np.abs(a) + 1e-30))
    assert rel < 1e-5, rel

    weird = np.asarray([np.nan, np.inf, -np.inf, 0.0, -0.0, 1e-38, 3e38],
                       np.float32)
    got = json.loads(native.json_encode_f32(weird))
    assert np.isnan(got[0]) and got[1] == np.inf and got[2] == -np.inf
    assert native.json_encode_f32(np.zeros(0, np.float32)) == b"[]"


def test_encode_output_fallback_is_full_precision(monkeypatch):
    """Without the native encoder the worker falls back to the plain
    full-precision json.dumps — small magnitudes must NOT round to zero
    (decimal-place rounding would), so fallback and native deployments
    stay within %.6g of each other on the wire."""
    import json

    import numpy as np

    from tpu_engine.core import native as core_native
    from tpu_engine.serving import worker as worker_mod

    # _encode_output imports tpu_engine.core.native at call time — patch
    # the module attribute it will resolve.
    monkeypatch.setattr(core_native, "json_encode_f32", lambda _a: None)
    a = np.asarray([1e-9, -2.5e-30, 3.25, 0.0], np.float32)
    back = np.asarray(json.loads(worker_mod._encode_output(a)), np.float32)
    np.testing.assert_array_equal(back, a)


def test_library_is_rebuilt_when_sources_change(monkeypatch, tmp_path):
    """The library is git-ignored but a copied working tree can carry one
    built from other sources. The loader trusts it only when the hash
    written beside it at build time matches the tracked sources."""
    import shutil

    native_dir = tmp_path / "native"
    shutil.copytree(native._NATIVE_DIR, native_dir)
    monkeypatch.setattr(native, "_NATIVE_DIR", str(native_dir))
    monkeypatch.setattr(native, "_LIB_PATH",
                        str(native_dir / "libtpucore.so"))
    current = native._source_hash()
    assert native._built_from(current)          # copied with its hash
    with open(native_dir / "core.h", "a") as f:
        f.write("\n// edited\n")
    edited = native._source_hash()
    assert edited != current and not native._built_from(edited)
    os.remove(native_dir / "libtpucore.so.sha256")
    assert not native._built_from(current)      # no hash: not trusted
