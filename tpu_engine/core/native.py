"""ctypes bindings for the native C++ runtime core (libtpucore.so).

Exposes ``NativeLRUCache``, ``NativeConsistentHash``, ``NativeCircuitBreaker``
and ``NativeBatchQueue`` with the same Python API as the pure-Python
implementations in ``tpu_engine.core`` so the two are interchangeable (and
are tested against the same suite, see ``tests/impl_params.py``).

The shared library is built from the tracked sources in
``tpu_engine/native`` by ``build.sh`` (plain g++). It is git-ignored, so a
checkout starts without one and a copied working tree may carry one built
from other sources: ``available()`` loads the library only when the
content hash stored beside it matches ``core_api.cc``/``core.h``/
``http_front.h``, and otherwise (re)builds it first. Where it cannot be
built, callers fall back to pure Python.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import pickle
import subprocess
import threading
from typing import Any, List, Optional

from tpu_engine.core.circuit_breaker import CircuitState

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libtpucore.so")
# Everything build.sh compiles: core_api.cc and the headers it includes.
_SOURCES = ("core_api.cc", "core.h", "http_front.h")

_lib = None
_load_lock = threading.Lock()
_load_attempted = False


def _configure(lib: ctypes.CDLL) -> ctypes.CDLL:
    c_size = ctypes.c_size_t
    P = ctypes.c_void_p
    lib.tpu_free.argtypes = [ctypes.c_void_p]
    lib.tpu_lru_create.restype = P
    lib.tpu_lru_create.argtypes = [c_size]
    lib.tpu_lru_destroy.argtypes = [P]
    lib.tpu_lru_get.restype = ctypes.c_int
    lib.tpu_lru_get.argtypes = [P, ctypes.c_char_p, c_size,
                                ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(c_size)]
    lib.tpu_lru_put.argtypes = [P, ctypes.c_char_p, c_size, ctypes.c_char_p, c_size]
    lib.tpu_lru_clear.argtypes = [P]
    lib.tpu_lru_size.restype = c_size
    lib.tpu_lru_size.argtypes = [P]
    lib.tpu_lru_capacity.restype = c_size
    lib.tpu_lru_capacity.argtypes = [P]
    lib.tpu_lru_hits.restype = ctypes.c_uint64
    lib.tpu_lru_hits.argtypes = [P]
    lib.tpu_lru_misses.restype = ctypes.c_uint64
    lib.tpu_lru_misses.argtypes = [P]

    lib.tpu_ring_create.restype = P
    lib.tpu_ring_create.argtypes = [ctypes.c_int]
    lib.tpu_ring_destroy.argtypes = [P]
    lib.tpu_ring_add.argtypes = [P, ctypes.c_char_p]
    lib.tpu_ring_remove.argtypes = [P, ctypes.c_char_p]
    lib.tpu_ring_get.restype = ctypes.c_int
    lib.tpu_ring_get.argtypes = [P, ctypes.c_char_p,
                                 ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(c_size)]
    lib.tpu_ring_all_nodes.restype = ctypes.c_int
    lib.tpu_ring_all_nodes.argtypes = [P, ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(c_size)]
    lib.tpu_ring_num_nodes.restype = c_size
    lib.tpu_ring_num_nodes.argtypes = [P]
    lib.tpu_fnv1a.restype = ctypes.c_uint32
    lib.tpu_fnv1a.argtypes = [ctypes.c_char_p, c_size]

    lib.tpu_breaker_create.restype = P
    lib.tpu_breaker_create.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_double]
    lib.tpu_breaker_destroy.argtypes = [P]
    for fn in ("tpu_breaker_allow", "tpu_breaker_state",
               "tpu_breaker_failures", "tpu_breaker_successes"):
        getattr(lib, fn).restype = ctypes.c_int
        getattr(lib, fn).argtypes = [P]
    lib.tpu_breaker_success.argtypes = [P]
    lib.tpu_breaker_failure.argtypes = [P]

    lib.tpu_bq_create.restype = P
    lib.tpu_bq_create.argtypes = [c_size, ctypes.c_double]
    lib.tpu_bq_destroy.argtypes = [P]
    lib.tpu_bq_push.restype = ctypes.c_longlong
    lib.tpu_bq_push.argtypes = [P, ctypes.c_char_p, c_size]
    lib.tpu_bq_pop_batch.restype = ctypes.c_int
    lib.tpu_bq_pop_batch.argtypes = [P, ctypes.POINTER(ctypes.c_void_p),
                                     ctypes.POINTER(c_size), ctypes.POINTER(ctypes.c_longlong),
                                     ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
    lib.tpu_bq_close.argtypes = [P]
    lib.tpu_bq_size.restype = c_size
    lib.tpu_bq_size.argtypes = [P]

    lib.tpu_front_create.restype = P
    lib.tpu_front_create.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.tpu_front_destroy.argtypes = [P]
    lib.tpu_front_add_lane.argtypes = [P, ctypes.c_char_p, P, P]
    lib.tpu_front_set_lane_enabled.argtypes = [P, ctypes.c_char_p, ctypes.c_int]
    lib.tpu_front_set_handler.argtypes = [P, HANDLER_FN]
    lib.tpu_front_start.restype = ctypes.c_int
    lib.tpu_front_start.argtypes = [P]
    lib.tpu_front_stop.argtypes = [P]
    lib.tpu_front_lane_total.restype = ctypes.c_uint64
    lib.tpu_front_lane_total.argtypes = [P, ctypes.c_char_p]
    lib.tpu_front_lane_hits.restype = ctypes.c_uint64
    lib.tpu_front_lane_hits.argtypes = [P, ctypes.c_char_p]
    lib.tpu_front_reply.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                    ctypes.c_char_p, c_size]
    lib.tpu_front_reply2.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                     ctypes.c_char_p, c_size,
                                     ctypes.c_char_p]
    lib.tpu_send_each.restype = ctypes.c_double
    lib.tpu_send_each.argtypes = [
        ctypes.c_int, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(c_size),
        ctypes.POINTER(ctypes.c_long)]
    lib.tpu_json_encode_f32.restype = c_size
    lib.tpu_json_encode_f32.argtypes = [
        ctypes.c_void_p, c_size, ctypes.POINTER(ctypes.c_void_p)]
    return lib


# void handler(reply_ctx, method, path, body, body_len)
HANDLER_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_char_p,
                              ctypes.c_char_p, ctypes.c_char_p,
                              ctypes.c_size_t)


def _source_hash() -> str:
    digest = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            digest.update(name.encode() + b"\0" + f.read() + b"\0")
    return digest.hexdigest()


def _built_from(source_hash: str) -> bool:
    """True when the library on disk was built from exactly these sources
    (the hash build time wrote beside it)."""
    try:
        with open(_LIB_PATH + ".sha256") as f:
            return os.path.exists(_LIB_PATH) and f.read().strip() == source_hash
    except OSError:
        return False


def _build(source_hash: str) -> bool:
    # Build to a pid-suffixed temp name, then atomically rename: two
    # processes cold-starting together must not interleave g++ output
    # into the same file (a corrupt .so would poison all future runs).
    # The hash lands after the library, so a crash between the two
    # renames leaves a library that is rebuilt, never a stale one trusted.
    tmp_name = f"libtpucore.so.tmp.{os.getpid()}"
    tmp_path = os.path.join(_NATIVE_DIR, tmp_name)
    try:
        subprocess.run(["bash", os.path.join(_NATIVE_DIR, "build.sh"), tmp_name],
                       check=True, capture_output=True, timeout=120)
        os.replace(tmp_path, _LIB_PATH)
        with open(tmp_path, "w") as f:
            f.write(source_hash + "\n")
        os.replace(tmp_path, _LIB_PATH + ".sha256")
        return True
    except (OSError, subprocess.SubprocessError):
        try:
            os.unlink(tmp_path)
        except OSError:
            pass
        return False


def _try_load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    with _load_lock:
        if _lib is not None:
            return _lib
        if _load_attempted:
            return None
        _load_attempted = True
        source_hash = _source_hash()
        if not _built_from(source_hash):
            if os.environ.get("TPU_ENGINE_NO_NATIVE_BUILD") == "1":
                return None
            if not _build(source_hash):
                return None
        try:
            _lib = _configure(ctypes.CDLL(_LIB_PATH))
        except (OSError, AttributeError):
            _lib = None
        return _lib


def available() -> bool:
    return _try_load() is not None


def _take_bytes(lib, ptr: ctypes.c_void_p, length: int) -> bytes:
    try:
        return ctypes.string_at(ptr, length)
    finally:
        lib.tpu_free(ptr)


def json_encode_f32(arr) -> Optional[bytes]:
    """``[a,b,...]`` JSON fragment for a float array via the C encoder
    (%.6g, ~10x faster than json.dumps and GIL-free for the duration).
    None when the native core is absent — callers fall back to a Python
    encode."""
    lib = _try_load()
    if lib is None:
        return None
    import numpy as np

    a = np.ascontiguousarray(arr, dtype=np.float32)
    out = ctypes.c_void_p()
    length = lib.tpu_json_encode_f32(
        a.ctypes.data_as(ctypes.c_void_p), a.size, ctypes.byref(out))
    if not out:
        return None  # allocation failure: let the Python path serve
    return _take_bytes(lib, out, length)


def send_each(fds, bufs):
    """One ``send`` a buffer, each to its socket's descriptor, in order
    and without waiting, in ONE call that holds no interpreter lock
    (``tpu_send_each``): ``(sent, t_done)``, where ``sent[i]`` is the
    bytes socket i took (0: it would have blocked) or ``-errno``, and
    `t_done` the ``time.perf_counter`` at which the last send returned.
    Buffers of one socket must be adjacent: after one that was not taken
    whole the socket's later ones are not offered. None when the native
    core is absent: callers send from Python."""
    lib = _try_load()
    if lib is None:
        return None
    n = len(fds)
    sent = (ctypes.c_long * n)()
    t_done = lib.tpu_send_each(
        n, (ctypes.c_int * n)(*fds), (ctypes.c_char_p * n)(*bufs),
        (ctypes.c_size_t * n)(*map(len, bufs)), sent)
    return list(sent), t_done


class NativeLRUCache:
    """Byte-blob LRU; arbitrary Python values round-trip via pickle.

    Keys must be ``bytes`` — the serving path keys by the serialized input
    tensor. (The pure-Python LRUCache accepts any hashable; restricting the
    native contract to bytes avoids pickle-canonicalization mismatches like
    ``1`` vs ``1.0``, which hash-equal as dict keys but differ as pickles.)
    """

    def __init__(self, capacity: int, raw: bool = False):
        """``raw=True`` stores values as verbatim bytes (no pickle) — the
        contract that lets the native HTTP front read entries directly."""
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self._lib = _try_load()
        if self._lib is None:
            raise RuntimeError("libtpucore.so is not available")
        self._raw = raw
        self._h = self._lib.tpu_lru_create(capacity)

    @property
    def handle(self):
        """The underlying C handle (for tpu_front_add_lane)."""
        return self._h

    def __del__(self):
        lib, h = getattr(self, "_lib", None), getattr(self, "_h", None)
        if lib is not None and h:
            lib.tpu_lru_destroy(h)
            self._h = None

    @staticmethod
    def _key_bytes(key) -> bytes:
        if not isinstance(key, bytes):
            raise TypeError(f"NativeLRUCache keys must be bytes, got {type(key).__name__}")
        return key

    def get(self, key) -> Optional[Any]:
        out = ctypes.c_void_p()
        n = ctypes.c_size_t()
        k = self._key_bytes(key)
        if not self._lib.tpu_lru_get(self._h, k, len(k), ctypes.byref(out), ctypes.byref(n)):
            return None
        blob = _take_bytes(self._lib, out, n.value)
        return blob if self._raw else pickle.loads(blob)

    def put(self, key, value: Any) -> None:
        k = self._key_bytes(key)
        v = value if self._raw else pickle.dumps(value)
        if not isinstance(v, bytes):
            raise TypeError("raw NativeLRUCache values must be bytes")
        self._lib.tpu_lru_put(self._h, k, len(k), v, len(v))

    def clear(self) -> None:
        self._lib.tpu_lru_clear(self._h)

    def size(self) -> int:
        return self._lib.tpu_lru_size(self._h)

    @property
    def capacity(self) -> int:
        return self._lib.tpu_lru_capacity(self._h)

    @property
    def hits(self) -> int:
        return self._lib.tpu_lru_hits(self._h)

    @property
    def misses(self) -> int:
        return self._lib.tpu_lru_misses(self._h)

    def hit_rate(self) -> float:
        from tpu_engine.core.lru_cache import compute_hit_rate

        return compute_hit_rate(self.hits, self.misses)


class NativeConsistentHash:
    def __init__(self, virtual_nodes: int = 150):
        self._lib = _try_load()
        if self._lib is None:
            raise RuntimeError("libtpucore.so is not available")
        self._h = self._lib.tpu_ring_create(virtual_nodes)
        self._virtual_nodes = virtual_nodes

    def __del__(self):
        lib, h = getattr(self, "_lib", None), getattr(self, "_h", None)
        if lib is not None and h:
            lib.tpu_ring_destroy(h)
            self._h = None

    @property
    def virtual_nodes(self) -> int:
        return self._virtual_nodes

    def add_node(self, node: str) -> None:
        self._lib.tpu_ring_add(self._h, node.encode())

    def remove_node(self, node: str) -> None:
        self._lib.tpu_ring_remove(self._h, node.encode())

    def get_node(self, key: str) -> str:
        out = ctypes.c_void_p()
        n = ctypes.c_size_t()
        if not self._lib.tpu_ring_get(self._h, key.encode(), ctypes.byref(out), ctypes.byref(n)):
            raise RuntimeError("hash ring is empty")
        return _take_bytes(self._lib, out, n.value).decode()

    def get_all_nodes(self) -> List[str]:
        out = ctypes.c_void_p()
        n = ctypes.c_size_t()
        self._lib.tpu_ring_all_nodes(self._h, ctypes.byref(out), ctypes.byref(n))
        buf = _take_bytes(self._lib, out, n.value)
        # Repeated <uint32 LE length><bytes> records (see tpu_ring_all_nodes).
        nodes, pos = [], 0
        while pos < len(buf):
            ln = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
            nodes.append(buf[pos:pos + ln].decode())
            pos += ln
        return nodes

    def size(self) -> int:
        return self._lib.tpu_ring_num_nodes(self._h)

    def get_distribution(self, keys) -> dict:
        from tpu_engine.core.consistent_hash import compute_distribution

        return compute_distribution(self, keys)


class NativeCircuitBreaker:
    _STATES = {0: CircuitState.CLOSED, 1: CircuitState.OPEN, 2: CircuitState.HALF_OPEN}

    def __init__(self, failure_threshold: int = 5, success_threshold: int = 2,
                 timeout_seconds: float = 30.0):
        self._lib = _try_load()
        if self._lib is None:
            raise RuntimeError("libtpucore.so is not available")
        self._h = self._lib.tpu_breaker_create(failure_threshold, success_threshold,
                                               float(timeout_seconds))

    def __del__(self):
        lib, h = getattr(self, "_lib", None), getattr(self, "_h", None)
        if lib is not None and h:
            lib.tpu_breaker_destroy(h)
            self._h = None

    def allow_request(self) -> bool:
        return bool(self._lib.tpu_breaker_allow(self._h))

    def record_success(self) -> None:
        self._lib.tpu_breaker_success(self._h)

    def record_failure(self) -> None:
        self._lib.tpu_breaker_failure(self._h)

    @property
    def state(self) -> CircuitState:
        return self._STATES[self._lib.tpu_breaker_state(self._h)]

    @property
    def failure_count(self) -> int:
        return self._lib.tpu_breaker_failures(self._h)

    @property
    def success_count(self) -> int:
        return self._lib.tpu_breaker_successes(self._h)

    def state_name(self) -> str:
        return self.state.value


class NativeBatchQueue:
    """Native MPMC batch queue; the timed PopBatch wait releases the GIL."""

    def __init__(self, max_batch: int, timeout_s: float):
        if max_batch <= 0:
            raise ValueError("max_batch must be positive")
        self._lib = _try_load()
        if self._lib is None:
            raise RuntimeError("libtpucore.so is not available")
        self._max = int(max_batch)
        self._h = self._lib.tpu_bq_create(self._max, float(timeout_s))

    def __del__(self):
        lib, h = getattr(self, "_lib", None), getattr(self, "_h", None)
        if lib is not None and h:
            lib.tpu_bq_destroy(h)
            self._h = None

    def push(self, payload: bytes) -> int:
        """Returns the ticket id, or -1 if the queue is closed."""
        return self._lib.tpu_bq_push(self._h, payload, len(payload))

    def pop_batch(self):
        """Returns (items, timed_out) where items is a list of
        (ticket, payload) — or (None, timed_out) when closed and drained."""
        bufs = (ctypes.c_void_p * self._max)()
        lens = (ctypes.c_size_t * self._max)()
        tickets = (ctypes.c_longlong * self._max)()
        timed_out = ctypes.c_int()
        n = self._lib.tpu_bq_pop_batch(self._h, bufs, lens, tickets, self._max,
                                       ctypes.byref(timed_out))
        if n < 0:
            return None, bool(timed_out.value)
        items = [
            (tickets[i], _take_bytes(self._lib, ctypes.c_void_p(bufs[i]), lens[i]))
            for i in range(n)
        ]
        return items, bool(timed_out.value)

    def close(self) -> None:
        self._lib.tpu_bq_close(self._h)

    def size(self) -> int:
        return self._lib.tpu_bq_size(self._h)


def native_fnv1a_32(key: str) -> int:
    lib = _try_load()
    if lib is None:
        raise RuntimeError("libtpucore.so is not available")
    b = key.encode()
    return lib.tpu_fnv1a(b, len(b))


class NativeHttpFront:
    """The C++ HTTP front door (tpu_engine/native/http_front.h).

    Serves /infer cache hits entirely in C++ (ring lookup + raw-mode LRU
    fetch + response splice, no GIL); everything else — cache misses,
    /generate, health/stats/admin — calls the Python ``fallback`` handler:
    ``fallback(method: str, path: str, body: bytes) -> (status, bytes)``.
    """

    def __init__(self, port: int, fallback, virtual_nodes: int = 150,
                 fake_cached_latency_us: int = 50):
        self._lib = _try_load()
        if self._lib is None:
            raise RuntimeError("libtpucore.so is not available")
        self._h = self._lib.tpu_front_create(port, virtual_nodes,
                                             fake_cached_latency_us)
        self.port = port
        self._lanes: List[str] = []
        lib = self._lib

        def _handler(reply_ctx, method, path, body, body_len):
            ctype = None
            try:
                result = fallback(method.decode(), path.decode(), body or b"")
                # (status, payload) or (status, payload, content_type) —
                # the latter e.g. /metrics' text/plain exposition.
                status, payload = result[0], result[1]
                if len(result) == 3:
                    ctype = result[2]
            except Exception as exc:  # never let an exception cross ctypes
                status, payload = 500, (
                    b'{"error": ' + _json_str(str(exc)) + b"}")
            if ctype is not None:
                lib.tpu_front_reply2(reply_ctx, status, payload,
                                     len(payload), ctype.encode())
            else:
                lib.tpu_front_reply(reply_ctx, status, payload, len(payload))

        # Keep a reference: the C side stores the raw function pointer.
        self._handler_ref = HANDLER_FN(_handler)
        self._lib.tpu_front_set_handler(self._h, self._handler_ref)

    def add_lane(self, name: str, cache: "NativeLRUCache",
                 breaker: "Optional[NativeCircuitBreaker]" = None) -> None:
        if not getattr(cache, "_raw", False):
            raise ValueError("front lanes need raw-mode NativeLRUCache")
        self._lanes.append(name)
        self._lib.tpu_front_add_lane(
            self._h, name.encode(), cache.handle,
            breaker._h if breaker is not None else None)

    def set_lane_enabled(self, name: str, enabled: bool) -> None:
        self._lib.tpu_front_set_lane_enabled(self._h, name.encode(),
                                             1 if enabled else 0)

    def start(self) -> int:
        port = self._lib.tpu_front_start(self._h)
        if port < 0:
            raise OSError(f"native front failed to bind port {self.port}")
        self.port = port
        return port

    def stop(self) -> None:
        if self._h:
            self._lib.tpu_front_stop(self._h)

    def lane_counters(self, name: str):
        n = name.encode()
        return (int(self._lib.tpu_front_lane_total(self._h, n)),
                int(self._lib.tpu_front_lane_hits(self._h, n)))

    def __del__(self):
        lib, h = getattr(self, "_lib", None), getattr(self, "_h", None)
        if lib is not None and h:
            lib.tpu_front_stop(h)
            lib.tpu_front_destroy(h)
            self._h = None


def _json_str(s: str) -> bytes:
    import json

    return json.dumps(s).encode()
