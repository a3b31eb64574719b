"""Minimal threaded JSON-over-HTTP server for the serving endpoints.

Plays the role of cpp-httplib in the reference (vendored at
``/root/reference/external/cpp-httplib``): POST/GET JSON routes with
keep-alive. Python stdlib only — ``ThreadingHTTPServer`` with HTTP/1.1
persistent connections; handlers return ``(status, dict)`` and errors map
to 500 ``{"error": ...}`` exactly like the reference handlers
(``worker_node.cpp:174-186``, ``gateway.cpp:176-188``).
"""

from __future__ import annotations

import json
import socket
import threading
import time
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, Optional, Tuple

from tpu_engine.core import native
from tpu_engine.utils.deadline import ShedError
from tpu_engine.utils.streams import (
    STALLED,
    STOPPED,
    STREAM_STALL_S,
    WOULD_BLOCK,
)
from tpu_engine.utils.tracing import CPU_CLOCK_EVERY

Handler = Callable[[Optional[dict]], Tuple[int, dict]]

LAST_CHUNK = b"0\r\n\r\n"  # ends a chunked body

# How a stream in the writer's hands ended, if not handed back (`_Driven`).
FINISHED, BROKEN = "finished", "broken"


def chunk_frame(chunk: bytes) -> bytes:
    """One event chunk as it goes on the wire under chunked
    transfer-encoding, in one buffer for one send. The single definition
    of the framing: the stream writer and a handler thread both send
    this."""
    return b"%x\r\n%b\r\n" % (len(chunk), chunk)


class _TrackingServer(ThreadingHTTPServer):
    """ThreadingHTTPServer that can sever live keep-alive connections.

    `shutdown()` only stops the accept loop; handler threads blocked on the
    next keep-alive request would keep serving pooled client connections
    after "stop". Tracking the sockets lets stop() half-close them so those
    threads see EOF and exit.
    """

    # socketserver's default listen backlog is 5; benchmark clients open a
    # fresh connection per request at 50+ threads, so SYNs get dropped and
    # retransmitted (1 s tail spikes) without a real backlog.
    request_queue_size = 1024

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._conns = set()
        self._conns_lock = threading.Lock()
        # Requests currently INSIDE a handler (excludes idle keep-alive
        # connections): the graceful-drain wait in JsonHttpServer.stop.
        self.active_requests = 0
        self.active_lock = threading.Lock()
        # Set by stop(): handlers finish their current request, then close
        # the connection — live keep-alive pools converge to zero instead
        # of feeding new requests forever and defeating the drain wait.
        self.draining = False

    def process_request(self, request, client_address):
        with self._conns_lock:
            self._conns.add(request)
        super().process_request(request, client_address)

    def shutdown_request(self, request):
        with self._conns_lock:
            self._conns.discard(request)
        super().shutdown_request(request)

    def close_open_connections(self):
        with self._conns_lock:
            conns = list(self._conns)
        for s in conns:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


def sse_event(payload: dict) -> bytes:
    """One Server-Sent-Events frame. The single definition of the SSE wire
    format — worker streams, cross-host degraded streams, and any future
    framing change (event:/id: lines) all go through here."""
    return b"data: " + json.dumps(payload).encode() + b"\n\n"


class _Driven:
    """One streamed response in the writer's hands, and what its handler
    thread, parked on `ended`, finds when it wakes: `outcome` FINISHED
    (every byte is out, the last chunk too), BROKEN (the iterator raised
    or the reader went away: drop the connection, no last chunk), or why
    the stream is the handler's again, with the bytes still to send in
    `pending`, `exhausted` if they end the body and `failed` if the
    iterator raised after them."""

    __slots__ = ("writer", "sock", "fd", "it", "outbox", "ended", "outcome",
                 "pending", "exhausted", "failed", "recall")

    def __init__(self, writer: "StreamWriter", sock, it):
        self.writer, self.sock, self.fd = writer, sock, sock.fileno()
        self.it, self.outbox = it, it.outbox
        self.ended = threading.Event()
        self.outcome = None
        self.pending = b""
        self.exhausted = self.failed = False
        self.recall = None      # the handler asks for the stream back

    def mark(self):
        """The outbox got an item (`StreamOutbox.put`): ready for the
        next pass; the caller wakes the writer when it has put all."""
        self.writer._ready.append(self)
        return self.writer._wake.set


class StreamWriter:
    """One thread a front server that drives out the token events of
    every stream whose source is an in-process lane's outbox
    (``utils/streams.py``), in one pass a scheduler tick.

    A lane's scheduler puts a tick's fresh tokens into its streams'
    outboxes and wakes this thread once. The pass advances each ready
    stream's event iterator while its outbox has an item (so `next`
    never blocks), frames each chunk (`chunk_frame`), and hands every
    frame to its socket in one send that never waits, all of a pass's
    sends in one call that holds no interpreter lock
    (``core.native.send_each``; a loop of ``socket.send`` where the
    native core is absent). With a handler thread a stream, a tick of
    32 rows woke 32 threads that took the interpreter lock from the
    scheduler's thread in a convoy (PERF.md section 6, PRs 42 and 43);
    now the lock changes hands between two threads.

    A slow or dead reader delays only itself: a socket that would not
    take a whole frame gets its stream handed back, with the bytes not
    yet sent, to the stream's handler thread, which sends them in order
    and iterates the rest as it would any other stream; a send that
    fails ends the stream as a failed `wfile.write` does. The handler
    thread of a driven stream sleeps in `drive` until then.

    The pass makes PR 42's marks (`StreamClock`): `wake` ends where its
    `next` takes an event up, `deliver` where the pass's sends
    returned, one ``stream.deliver`` annotation a pass on this thread's
    line, and this thread's CPU time, read on one pass in
    `CPU_CLOCK_EVERY` and before one that ends a stream, in equal
    shares to the events since the last read. It counts what it sent
    into the lane's `StreamCounts`."""

    def __init__(self, name: str = "stream-writer"):
        self.name = name
        self._ready: deque = deque()
        self._wake = threading.Event()
        self._lock = threading.Lock()   # _driven, _thread, _stopping
        self._driven: set = set()
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        self._passes = 0
        self._cpu_ns = 0
        self._owed: Dict[object, int] = {}  # clock -> events since a read

    # -- the handler thread's side --------------------------------------------

    def drive(self, sock, chunks) -> Optional[_Driven]:
        """Give `chunks` (an `EventStream`) to the writer and sleep until
        the stream has ended or is handed back. None if the writer takes
        no stream any more."""
        s = _Driven(self, sock, chunks)
        box = s.outbox
        with self._lock:
            if self._stopping:
                return None
            self._driven.add(s)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name=self.name, daemon=True)
                self._thread.start()
            # Inside the lock: a writer that stops hands back what it
            # finds in `_driven`, and must find it attached.
            box.attach(s.mark)
        while not s.ended.wait(max(0.05, STREAM_STALL_S - box.idle_s())):
            if s.recall is None and box.idle_s() >= STREAM_STALL_S:
                # Nothing for too long: the iterator says so from a
                # `get` that runs out, on this thread.
                s.recall = STALLED
                s.mark()()
        return s

    def stop(self, timeout_s: float = 5.0) -> None:
        """Hand every stream back to its handler thread and end the
        writer's. Streams that come later are never driven."""
        with self._lock:
            self._stopping = True
            thread = self._thread
        self._wake.set()
        if thread is not None:
            thread.join(timeout_s)

    # -- the writer's thread ----------------------------------------------------

    def _run(self) -> None:
        from jax.profiler import TraceAnnotation

        self._cpu_ns = time.thread_time_ns()
        try:
            while True:
                self._wake.wait()
                self._wake.clear()  # before the drain: a later mark sets it
                if self._stopping:
                    return
                with TraceAnnotation("stream.deliver"):
                    self._pass()
        except Exception:  # a boundary that must let the streams go on
            import traceback

            traceback.print_exc()
        finally:
            with self._lock:
                self._stopping = True
                left = list(self._driven)
            for s in left:
                self._hand_back(s, STOPPED)

    def _pass(self) -> None:
        """Take up every ready stream's waiting events, frame them, send
        them, mark and count them."""
        streams, seen = [], set()
        while self._ready:
            s = self._ready.popleft()
            if s not in seen and s.outcome is None:
                seen.add(s)
                streams.append(s)
        self._passes += 1
        if (self._passes % CPU_CLOCK_EVERY == 0
                or any(s.outbox.ends_next() for s in streams)):
            self._share_cpu()
        taken = []    # (stream, its first frame's index, frames, events)
        bufs, of = [], []
        for s in streams:
            if s.recall is not None:
                self._hand_back(s, s.recall)
                continue
            box, first = s.outbox, len(bufs)
            events = box.taken
            try:
                while box.has_next():
                    if box.taken > events and box.ends_next():
                        # The end waits behind events of this pass: the
                        # iterator records the stream's span on the
                        # `next` that takes it, so that waits for a
                        # pass of its own, after their bytes are out
                        # and marked.
                        s.mark()()
                        break
                    chunk = next(s.it)
                    if chunk:
                        bufs.append(chunk_frame(chunk))
            except StopIteration:
                s.exhausted = True
                bufs.append(LAST_CHUNK)
            except Exception:
                s.failed = True  # as a handler's: what was out stays out
            of.extend([s] * (len(bufs) - first))
            taken.append((s, first, len(bufs) - first, box.taken - events))
        if not taken:
            return
        sent, t_done = self._send_each(of, bufs)
        by_lane: Dict[object, int] = {}
        for s, first, n, events in taken:
            short = next((i for i in range(first, first + n)
                          if sent[i] != len(bufs[i])), None)
            box = s.outbox
            if events and box.clock is not None:
                box.clock.delivered(t_done)
                self._owed[box.clock] = self._owed.get(box.clock, 0) + events
            if short is None:
                by_lane[box.counts] = by_lane.get(box.counts, 0) + events
                if s.failed:
                    self._end(s, BROKEN)
                elif s.exhausted:
                    self._end(s, FINISHED)
            elif sent[short] < 0:
                self._end(s, BROKEN)        # the reader went away
            else:
                s.pending = b"".join(
                    [bufs[short][sent[short]:], *bufs[short + 1:first + n]])
                box.counts.add(would_block=1)
                box.counts.handler_event(WOULD_BLOCK, events)
                self._hand_back(s, WOULD_BLOCK)
        for counts, events in by_lane.items():
            counts.add(writer_events=events, writer_passes=1)

    @staticmethod
    def _send_each(of, bufs):
        """`bufs[i]` to the socket of stream `of[i]`, each in one send
        that never waits: (bytes taken or -errno for each, the
        ``time.perf_counter`` at the last send's return)."""
        out = native.send_each([s.fd for s in of], bufs)
        if out is not None:
            return out
        sent, short = [], None
        for s, buf in zip(of, bufs):
            if s is short:
                sent.append(0)  # behind bytes its socket did not take
                continue
            try:
                n = s.sock.send(buf, socket.MSG_DONTWAIT)
            except (BlockingIOError, InterruptedError):
                n = 0
            except OSError as exc:
                n = -(exc.errno or 1)
            if n != len(buf):
                short = s
            sent.append(n)
        return sent, time.perf_counter()

    def _share_cpu(self) -> None:
        """This thread's CPU time since the last read, in equal shares
        to the events sent since then (a read is a system call, and the
        clock steps by 10 ms on the v5e hosts: PERF.md section 6)."""
        now = time.thread_time_ns()
        total = sum(self._owed.values())
        if total:
            share_us = (now - self._cpu_ns) / 1e3 / total
            for clock, events in self._owed.items():
                clock.add_cpu(share_us * events)
            self._owed.clear()
            self._cpu_ns = now

    def _end(self, s: _Driven, outcome: str) -> None:
        with self._lock:
            self._driven.discard(s)
        s.outcome = outcome
        s.ended.set()

    def _hand_back(self, s: _Driven, reason: str) -> None:
        s.outbox.detach(reason)
        self._end(s, reason)


class JsonHttpServer:
    def __init__(self, port: int, host: str = "0.0.0.0"):
        self._routes: Dict[Tuple[str, str], Handler] = {}
        # (method, prefix) -> handler(body, suffix). Checked only after
        # an exact-route miss, longest prefix first, so parameterized
        # paths (GET /admin/trace/<request_id>) coexist with the exact
        # table without perturbing any registered route.
        self._prefix_routes: Dict[Tuple[str, str], Callable] = {}
        self.host = host
        self.port = port
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    def route(self, method: str, path: str, handler: Handler) -> None:
        self._routes[(method.upper(), path)] = handler

    def route_prefix(self, method: str, prefix: str, handler) -> None:
        """Register a parameterized route: requests whose path starts with
        ``prefix`` (and miss the exact table) invoke ``handler(body,
        suffix)`` where suffix is the remainder of the path."""
        self._prefix_routes[(method.upper(), prefix)] = handler

    # -- lifecycle ------------------------------------------------------------

    def _make_handler(self):
        routes = self._routes
        # Longest prefix first: /admin/trace/raw/ beats /admin/trace/.
        prefix_routes = sorted(self._prefix_routes.items(),
                               key=lambda kv: -len(kv[0][1]))

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # On the handler (StreamRequestHandler), not the server: without
            # TCP_NODELAY the two-write response (headers, body) stalls ~40 ms
            # behind Nagle + the peer's delayed ACK on keep-alive connections.
            disable_nagle_algorithm = True

            def log_message(self, *args):  # silence per-request stderr noise
                pass

            def _respond(self, status: int, payload,
                         content_type: str = "application/json",
                         extra_headers: Optional[Dict[str, str]] = None) -> None:
                # Handlers may return pre-serialized bytes (hot /infer
                # path), a dict, or an ITERATOR of byte chunks (streaming
                # SSE, e.g. /generate/stream) sent with chunked
                # transfer-encoding.
                if (not isinstance(payload, (bytes, bytearray, dict, list,
                                             str, int, float, bool,
                                             type(None)))
                        and hasattr(payload, "__iter__")):
                    self._respond_stream(status, payload)
                    return
                body = (payload if isinstance(payload, (bytes, bytearray))
                        else json.dumps(payload).encode())
                self.send_response(status)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (extra_headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _respond_stream(self, status: int, chunks) -> None:
                """HTTP/1.1 chunked transfer of an event-chunk iterator;
                each chunk goes out at once, framed, in one send (SSE
                consumers read incrementally). An iterator error after
                the headers are out cannot become a 500 — the connection
                closes WITHOUT the terminal 0-chunk so clients see the
                truncation (IncompleteRead) instead of a
                well-formed-but-short stream.

                Who drives the events out is decided by what the iterator
                says of its source. One that names an in-process lane's
                outbox (`EventStream`: every `next` has an item waiting)
                is given to the server's `StreamWriter`, whose one thread
                sends a scheduler tick's events of all such streams in
                one pass, and this thread sleeps until the stream has
                ended: 32 handler threads woken a tick took the
                interpreter lock from the scheduler's in a convoy. Every
                other iterator (a remote lane's relay, a journaled or
                one-shot stream, one whose `next` may block) is iterated
                here, as is the rest of a stream the writer hands back
                (a socket that would block, a stall, a stopping server)
                after the bytes it had not sent."""
                self.send_response(status)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                try:
                    if getattr(chunks, "outbox", None) is not None:
                        left = self.server.stream_writer.drive(
                            self.connection, chunks)
                        if left is not None:
                            if left.outcome == FINISHED:
                                return
                            if left.outcome == BROKEN:
                                raise ConnectionError("stream broke")
                            self.wfile.write(left.pending)
                            if left.failed:
                                raise RuntimeError("stream iterator failed")
                            if left.exhausted:
                                return
                    for chunk in chunks:
                        if not chunk:
                            continue
                        self.wfile.write(chunk_frame(chunk))
                        self.wfile.flush()
                except Exception:
                    # Never re-raise into _dispatch (a second response would
                    # corrupt the chunked framing); drop the connection so
                    # the truncation is detectable, and settle the iterator
                    # now (a lane's releases its admission).
                    self.close_connection = True
                    close = getattr(chunks, "close", None)
                    if close is not None:
                        try:
                            close()
                        except Exception:
                            pass
                    return
                try:
                    self.wfile.write(LAST_CHUNK)
                    self.wfile.flush()
                except OSError:
                    pass  # client went away mid-stream

            def _dispatch(self, method: str) -> None:
                path = self.path.split("?", 1)[0]
                handler = routes.get((method, path))
                if handler is None:
                    for (pm, prefix), ph in prefix_routes:
                        if pm == method and path.startswith(prefix):
                            suffix = path[len(prefix):]
                            handler = (lambda body, _h=ph, _s=suffix:
                                       _h(body, _s))
                            break
                if handler is None:
                    self._respond(404, {"error": f"no route {method} {self.path}"})
                    return
                with self.server.active_lock:
                    self.server.active_requests += 1
                try:
                    body = None
                    if method == "POST":
                        length = int(self.headers.get("Content-Length", 0))
                        raw = self.rfile.read(length) if length else b"{}"
                        body = json.loads(raw)
                        # W3C trace propagation: a `traceparent` HTTP
                        # header (the standard carrier external clients
                        # and meshes emit) joins the payload-field form —
                        # body field wins when both are present, so a
                        # tpu_engine upstream's re-parented context is
                        # never clobbered by a stale edge header.
                        tp = self.headers.get("traceparent")
                        if tp and isinstance(body, dict) \
                                and "traceparent" not in body:
                            body["traceparent"] = tp
                    result = handler(body)
                    # (status, payload) or (status, payload, content_type)
                    # — e.g. /metrics returns Prometheus text exposition.
                    if len(result) == 3:
                        self._respond(result[0], result[1],
                                      content_type=result[2])
                    else:
                        self._respond(result[0], result[1])
                except ShedError as exc:
                    # Resilience layer refusal (expired deadline, overload,
                    # drain): 503 + Retry-After so well-behaved clients back
                    # off, and a machine-readable "kind" so upstream hops
                    # classify without string matching.
                    try:
                        self._respond(
                            503, {"error": str(exc), "kind": exc.kind},
                            extra_headers={"Retry-After": str(max(
                                1, int(exc.retry_after_s + 0.999)))})
                    except Exception:
                        pass
                except (KeyError, ValueError, TypeError) as exc:
                    # Malformed/unsupported request → 400 so gateways can
                    # tell client errors from worker failures (the reference
                    # returns 500 for everything, worker_node.cpp:180-186,
                    # which lets bad clients trip breakers fleet-wide).
                    try:
                        self._respond(400, {"error": str(exc)})
                    except Exception:
                        pass
                except Exception as exc:  # runtime/device failure → 500
                    try:
                        self._respond(500, {"error": str(exc)})
                    except Exception:
                        pass
                finally:
                    with self.server.active_lock:
                        self.server.active_requests -= 1
                    if getattr(self.server, "draining", False):
                        self.close_connection = True

            def do_POST(self):
                self._dispatch("POST")

            def do_GET(self):
                self._dispatch("GET")

        return _Handler

    def start(self, background: bool = True) -> None:
        self._server = _TrackingServer((self.host, self.port), self._make_handler())
        self._server.daemon_threads = True
        if self.port == 0:
            self.port = self._server.server_address[1]
        self._server.stream_writer = StreamWriter(
            f"stream-writer-{self.port}")
        if background:
            self._thread = threading.Thread(
                target=self._server.serve_forever, name=f"http-{self.port}", daemon=True
            )
            self._thread.start()
        else:
            self._server.serve_forever()

    def stop(self, drain_s: float = 10.0) -> None:
        """Stop accepting, then DRAIN: wait up to `drain_s` for requests
        already inside handlers to write their responses before severing
        the remaining (idle keep-alive) connections — a SIGTERM must not
        reset a client mid-/generate."""
        if self._server is not None:
            self._server.draining = True  # keep-alives close after reply
            self._server.shutdown()  # accept loop stops; handlers keep going
            # The streams in the writer's hands go back to their handler
            # threads, which finish them inside the drain wait below.
            self._server.stream_writer.stop()
            deadline = time.monotonic() + drain_s
            while time.monotonic() < deadline:
                with self._server.active_lock:
                    if self._server.active_requests == 0:
                        break
                time.sleep(0.05)
            self._server.close_open_connections()
            self._server.server_close()
            self._server = None
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
