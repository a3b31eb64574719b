"""The front's stream writer (serving/http.py `StreamWriter`): one thread a
front server drives out the token events of every stream whose source is
an in-process lane's outbox, in one pass a scheduler tick.

What must hold whoever drives a stream: the bytes on the socket, the
order of a stream's events, `done` last, the lane's admission released,
the `generate_stream` span's sums. What the writer adds: one send an
event, a reader that stalls or resets delays nobody else, and counters
that say who handed each token event to its socket.
"""

from __future__ import annotations

import http.client
import json
import queue
import socket
import struct
import threading
import time

import pytest

from tpu_engine.serving import http as front
from tpu_engine.serving.http import (
    BROKEN,
    FINISHED,
    LAST_CHUNK,
    StreamWriter,
    chunk_frame,
    sse_event,
)
from tpu_engine.utils.streams import (
    STOPPED,
    UNREGISTERED,
    WOULD_BLOCK,
    EventStream,
    StreamCounts,
    StreamOutbox,
    relay,
)
from tpu_engine.utils.tracing import StreamClock

STREAM_KEYS = ("events", "wake_us_sum", "wake_us_max", "deliver_us_sum",
               "deliver_cpu_us_sum")
N_SLOTS = 8


# -- a lane behind a gateway behind the Python front --------------------------

def _serve(**gateway):
    from tpu_engine.serving.app import serve_combined
    from tpu_engine.utils.config import GatewayConfig, WorkerConfig

    return serve_combined(
        model="gpt2-small-test", lanes=1, port=0,
        worker_config=WorkerConfig(
            model="gpt2-small-test", dtype="float32",
            gen_scheduler="continuous", gen_max_batch_size=N_SLOTS,
            gen_kv_block_size=16, gen_prefill_chunk=16,
            gen_mixed_token_budget=64),
        gateway_config=GatewayConfig(port=0, **gateway), warmup=False,
        native_front=False)


def _stop(served):
    gateway, workers, server = served
    for part in (server, *workers, gateway):
        part.stop()


@pytest.fixture(scope="module")
def served():
    parts = _serve()
    yield parts
    _stop(parts)


def _request(i, n=24):
    return {"request_id": f"w{i}", "prompt_tokens": [3, 5, 7 + i % 40],
            "max_new_tokens": n, "temperature": 0.0, "seed": i}


def _raw_stream(port, payload):
    """The response's bytes as they came off the socket, whole."""
    body = json.dumps(payload).encode()
    with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
        s.sendall(b"POST /generate/stream HTTP/1.1\r\nHost: x\r\n"
                  b"Content-Type: application/json\r\nContent-Length: "
                  + str(len(body)).encode() + b"\r\n\r\n" + body)
        data = b""
        while not data.endswith(LAST_CHUNK):
            got = s.recv(65536)
            assert got, data
            data += got
    head, _, chunked = data.partition(b"\r\n\r\n")
    return head, chunked


def _chunks(chunked):
    """The chunks of a chunked body, and that it is framed to the byte."""
    out, at = [], 0
    while True:
        eol = chunked.index(b"\r\n", at)
        size = int(chunked[at:eol], 16)
        assert chunked[at:eol] == b"%x" % size     # lower case, no padding
        chunk = chunked[eol + 2:eol + 2 + size]
        assert chunked[eol + 2 + size:eol + 4 + size] == b"\r\n"
        at = eol + 4 + size
        if size == 0:
            assert at == len(chunked)
            return out
        out.append(chunk)


def _events(chunks):
    return [json.loads(c.decode().split("data: ", 1)[1]) for c in chunks]


def _stream(port, payload, out=None, key=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/generate/stream", body=json.dumps(payload),
                 headers={"Content-Type": "application/json"})
    data = conn.getresponse().read()
    conn.close()
    events = [json.loads(b[6:]) for b in data.decode().split("\n\n")
              if b.startswith("data: ")]
    if out is not None:
        out[key] = events
    return events


def _counts(worker):
    return worker.generator.stats()["stream"]


def _wait_for(what, timeout_s=30):
    limit = time.monotonic() + timeout_s
    while time.monotonic() < limit:
        if what():
            return True
        time.sleep(0.01)
    return False


def test_the_bytes_on_the_socket_are_the_handler_s_one_send_an_event(
        served, monkeypatch):
    """A writer-driven stream's bytes, off a raw socket, against the same
    request iterated by hand and framed the way `_respond_stream` framed
    a chunk before PR 43 (three writes: size line, chunk, CRLF)."""
    gateway, workers, server = served
    sends = []
    send_each = StreamWriter._send_each

    def recorded(of, bufs):
        sends.extend(bufs)
        return send_each(of, bufs)

    monkeypatch.setattr(StreamWriter, "_send_each", staticmethod(recorded))
    before = _counts(workers[0])
    head, chunked = _raw_stream(server.port, _request(1))
    assert head.startswith(b"HTTP/1.1 200")
    assert b"Transfer-Encoding: chunked" in head
    assert b"Content-Type: text/event-stream" in head
    by_hand = list(workers[0].handle_generate_stream(
        dict(_request(1), request_id="w1")))
    parents = b"".join(b"%x\r\n" % len(c) + c + b"\r\n"
                       for c in by_hand) + b"0\r\n\r\n"
    got, want = _chunks(chunked), _chunks(parents)
    assert len(got) == len(want) >= 3
    assert got[:-1] == want[:-1]           # every token event, to the byte
    done, done_by_hand = _events(got[-1:])[0], _events(want[-1:])[0]
    assert done.pop("generate_time_us") >= 0
    assert done_by_hand.pop("generate_time_us") >= 0
    assert done == done_by_hand and done["done"] is True
    tokens = [t for e in _events(got[:-1]) for t in e["tokens"]]
    assert tokens == done["tokens"] and len(tokens) == 24
    # One send an event, the frame whole; the last chunk a send of its own.
    assert sends == [chunk_frame(c) for c in got] + [LAST_CHUNK]
    assert b"".join(sends) == chunked
    after = _counts(workers[0])
    assert after["writer_events"] - before["writer_events"] == len(got) - 1
    # The request iterated by hand went out on the thread that iterated.
    assert (after["handler_by_reason"].get(UNREGISTERED, 0)
            - before["handler_by_reason"].get(UNREGISTERED, 0)
            ) == len(want) - 1


def test_many_streams_each_get_every_token_once_in_order_done_last(served):
    gateway, workers, server = served
    n = 4 * N_SLOTS                       # four times the lane's slots
    before = _counts(workers[0])
    got = {}
    threads = [threading.Thread(target=_stream, args=(
        server.port, _request(100 + i, 12 + i % 9), got, i), daemon=True)
        for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads) and len(got) == n
    token_events = 0
    for i in range(n):
        events = got[i]
        assert [e.get("done", False) for e in events] == (
            [False] * (len(events) - 1) + [True])
        tokens = [t for e in events[:-1] for t in e["tokens"]]
        assert "error" not in events[-1], events[-1]
        assert events[-1]["tokens"] == tokens and len(tokens) == 12 + i % 9
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=60)
        conn.request("POST", "/generate", body=json.dumps(
            dict(_request(100 + i, 12 + i % 9), request_id=f"b{i}")),
            headers={"Content-Type": "application/json"})
        assert json.loads(conn.getresponse().read())["tokens"] == tokens
        conn.close()
        token_events += len(events) - 1
    after = _counts(workers[0])
    by_writer = after["writer_events"] - before["writer_events"]
    by_handler = after["handler_events"] - before["handler_events"]
    # The counters add up to the token events the clients got, and a
    # writer's pass took more than one stream's at a time.
    assert by_writer + by_handler == token_events
    assert by_handler == 0 and after["would_block"] == before["would_block"]
    passes = after["writer_passes"] - before["writer_passes"]
    assert 0 < passes < by_writer
    assert _wait_for(lambda: workers[0]._admission._depth == 0)


def test_a_driven_stream_s_span_carries_the_sums(served):
    gateway, workers, server = served
    events = _stream(server.port, dict(_request(7, 20), request_id="sp1"))
    n_events = len(events) - 1
    span = next(s for s in workers[0].tracer.snapshot()
                if s["op"] == "generate_stream"
                and s["request_id"] == "sp1")
    attrs = span["attrs"]
    assert set(attrs) == {"ttft_us", *STREAM_KEYS}
    assert attrs["events"] == n_events
    assert 0 <= attrs["wake_us_max"] <= attrs["wake_us_sum"]
    assert 0 < attrs["deliver_us_sum"] <= span["duration_us"] * n_events
    assert 0 <= attrs["deliver_cpu_us_sum"]
    assert 0 < attrs["ttft_us"] <= span["duration_us"]


def test_a_reader_that_resets_releases_its_admission_and_delays_nobody(
        served):
    gateway, workers, server = served
    worker = workers[0]
    assert _wait_for(lambda: worker._admission._depth == 0)
    body = json.dumps(dict(_request(9, 50), request_id="rst")).encode()
    gone = socket.create_connection(("127.0.0.1", server.port), timeout=60)
    gone.sendall(b"POST /generate/stream HTTP/1.1\r\nHost: x\r\n"
                 b"Content-Length: " + str(len(body)).encode()
                 + b"\r\n\r\n" + body)
    assert gone.recv(64)                    # the stream has begun
    # RST, not FIN: the next send to it fails.
    gone.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                    struct.pack("ii", 1, 0))
    gone.close()
    events = _stream(server.port, dict(_request(10, 30), request_id="ok"))
    assert events[-1]["done"] and len(events[-1]["tokens"]) == 30
    assert "error" not in events[-1]
    assert _wait_for(lambda: worker._admission._depth == 0), \
        worker._admission._depth
    assert _wait_for(lambda: worker.generator.stats()["active"] == 0)


def test_a_journaled_stream_is_the_handler_s_and_journals_what_was_sent():
    """`failover_streams`: the gateway's journal iterates the lane's
    stream itself (its `next` may block: a resume, a handoff), so it
    names no outbox and the handler thread drives it; the journal (the
    `done` event's tokens) is what the client received."""
    parts = _serve(failover_streams=True)
    gateway, workers, server = parts
    try:
        head, chunked = _raw_stream(server.port, _request(3, 20))
        events = _events(_chunks(chunked))
        tokens = [t for e in events[:-1] for t in e["tokens"]]
        assert events[-1]["done"] and events[-1]["tokens"] == tokens
        assert len(tokens) == 20
        stream = _counts(workers[0])
        assert stream["writer_events"] == 0 == stream["writer_passes"]
        assert stream["handler_events"] == len(events) - 1
        assert stream["handler_by_reason"] == {UNREGISTERED: len(events) - 1}
        plain = _serve()
        try:
            _, driven = _raw_stream(plain[2].port, _request(3, 20))
            driven_events = _events(_chunks(driven))
            assert [e["tokens"] for e in driven_events[:-1]] == [
                e["tokens"] for e in events[:-1]] or tokens == [
                t for e in driven_events[:-1] for t in e["tokens"]]
            assert _counts(plain[1][0])["writer_events"] == (
                len(driven_events) - 1)
        finally:
            _stop(plain)
    finally:
        _stop(parts)


# -- the writer alone, on made-up streams over socket pairs -------------------

def _made_up(counts, chunk_of=lambda item: sse_event({"tokens": item}),
             at_end=lambda box: None):
    """A lane's event stream as the worker builds it, less the lane: one
    `next` takes one item; the end yields a `done` event."""
    box = StreamOutbox(StreamClock(), counts)

    def events():
        while True:
            item = box.get(timeout=600)
            if item is None:
                at_end(box)     # where the worker records the stream's span
                break
            box.clock.woke(item, box.driven)
            try:
                yield chunk_of(item)
            finally:
                if box.clock.delivered() and not box.driven:
                    counts.handler_event(box.handback or UNREGISTERED)
        yield sse_event({"done": True})

    def watched(it):
        yield from it

    inner = EventStream(events(), box)
    return relay(watched(inner), inner), box


def _pair(sndbuf=None):
    ours, theirs = socket.socketpair()
    if sndbuf:
        ours.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf)
    theirs.settimeout(30)
    return ours, theirs


def _put(box, item):
    wake = box.put(item)
    if wake is not None:
        wake()


def _drive(writer, sock, stream, out):
    out.append(writer.drive(sock, stream))


def _read_chunks(sock, n, then=b""):
    """`n` chunks off a socket, with the time each was whole, and
    nothing after them but `then`."""
    data, out, at = b"", [], 0
    while len(out) < n or len(data) - at < len(then):
        eol = data.find(b"\r\n", at)
        if eol >= 0 and len(out) < n:
            size = int(data[at:eol], 16)
            if len(data) >= eol + 4 + size:
                out.append((time.monotonic(),
                            data[eol + 2:eol + 2 + size]))
                at = eol + 4 + size
                continue
        got = sock.recv(1 << 20)
        assert got, (len(out), n)
        data += got
    assert data[at:] == then
    return out


@pytest.mark.parametrize("native_sends", [True, False])
def test_a_reader_that_stops_reading_gets_its_stream_back_alone(
        native_sends, monkeypatch):
    """Two streams, one whose reader reads nothing: its socket fills, the
    writer hands the stream back with the bytes it could not send, in
    order, and the other stream's events arrive all the while."""
    if native_sends and not front.native.available():
        pytest.skip("no native core here")
    if not native_sends:
        monkeypatch.setattr(front.native, "send_each", lambda fds, bufs: None)
    counts = StreamCounts()
    writer = StreamWriter("w-test")
    big = lambda item: b"x" * 8192 + sse_event({"tokens": item})  # noqa: E731
    stalled, stalled_box = _made_up(counts, big)
    lively, lively_box = _made_up(counts)
    ours_a, theirs_a = _pair(sndbuf=4096)
    ours_b, theirs_b = _pair()
    left_a, left_b = [], []
    threads = [threading.Thread(target=_drive, daemon=True, args=args)
               for args in ((writer, ours_a, stalled, left_a),
                            (writer, ours_b, lively, left_b))]
    try:
        for t in threads:
            t.start()
        assert _wait_for(lambda: stalled_box.driven and lively_box.driven)
        sent_at, n, got = [], 60, []
        lively_reader = threading.Thread(target=lambda: got.extend(
            _read_chunks(theirs_b, n + 1, then=LAST_CHUNK)), daemon=True)
        lively_reader.start()
        for i in range(n):
            sent_at.append(time.monotonic())
            _put(stalled_box, [i])
            _put(lively_box, [i])
            time.sleep(0.002)
        _put(lively_box, None)
        lively_reader.join(30)
        assert len(got) == n + 1
        assert _events([c for _, c in got[:-1]]) == [
            {"tokens": [i]} for i in range(n)]
        # Nobody waited for the reader that reads nothing.
        late = max(t - sent for (t, _), sent in zip(got, sent_at))
        assert late < 0.5, late
        threads[1].join(30)
        assert left_b[0].outcome == FINISHED and not left_b[0].pending
        # The stalled stream is its handler's again, with what was not
        # sent; sent from there, in order, nothing is lost or doubled.
        threads[0].join(30)
        back = left_a[0]
        assert back.outcome == WOULD_BLOCK and back.pending
        assert stalled_box.handback == WOULD_BLOCK and not stalled_box.driven
        assert not back.exhausted and not back.failed
        reader = []
        t = threading.Thread(target=lambda: reader.extend(
            _read_chunks(theirs_a, n + 1)), daemon=True)
        t.start()
        ours_a.sendall(back.pending)
        _put(stalled_box, None)             # now a blocked `get` is told
        for chunk in stalled:               # the handler's own loop
            ours_a.sendall(chunk_frame(chunk))
        t.join(30)
        assert [json.loads(c[8192:].decode().split("data: ", 1)[1])
                for _, c in reader[:-1]] == [{"tokens": [i]}
                                             for i in range(n)]
        snap = counts.snapshot()
        assert snap["would_block"] == 1
        assert snap["writer_events"] + snap["handler_events"] == 2 * n
        assert snap["handler_by_reason"] == {
            WOULD_BLOCK: snap["handler_events"]}
        assert 0 < snap["handler_events"] < n + 1
    finally:
        writer.stop()
        for s in (ours_a, theirs_a, ours_b, theirs_b):
            s.close()


def test_a_reader_that_went_away_breaks_its_stream_alone():
    counts = StreamCounts()
    writer = StreamWriter("w-test")
    gone, gone_box = _made_up(counts)
    lively, lively_box = _made_up(counts)
    ours_a, theirs_a = _pair()
    ours_b, theirs_b = _pair()
    left_a, left_b = [], []
    threads = [threading.Thread(target=_drive, daemon=True, args=args)
               for args in ((writer, ours_a, gone, left_a),
                            (writer, ours_b, lively, left_b))]
    try:
        for t in threads:
            t.start()
        assert _wait_for(lambda: gone_box.driven and lively_box.driven)
        theirs_a.close()
        for i in range(5):
            _put(gone_box, [i])
            _put(lively_box, [i])
            time.sleep(0.005)
        _put(lively_box, None)
        threads[0].join(30)
        assert left_a[0].outcome == BROKEN   # no last chunk is ever sent
        got = _read_chunks(theirs_b, 6, then=LAST_CHUNK)
        assert _events([c for _, c in got]) == [
            *({"tokens": [i]} for i in range(5)), {"done": True}]
        threads[1].join(30)
        assert left_b[0].outcome == FINISHED
    finally:
        writer.stop()
        for s in (ours_a, ours_b, theirs_b):
            s.close()


def test_the_end_is_taken_up_after_the_last_event_s_bytes_are_out():
    """A tick that puts a row's last tokens puts the end behind them.
    The iterator records the stream's span on the `next` that takes the
    end, so the writer takes it on a pass of its own: by then the last
    event is marked delivered, and the sums on the span are whole."""
    counts = StreamCounts()
    writer = StreamWriter("w-test")
    open_at_end = []
    stream, box = _made_up(
        counts, at_end=lambda box: open_at_end.append(box.clock.delivered()))
    ours, theirs = _pair()
    left = []
    t = threading.Thread(target=_drive, daemon=True,
                         args=(writer, ours, stream, left))
    try:
        t.start()
        assert _wait_for(lambda: box.driven)
        _put(box, [1])
        assert box.put([2]) is not None
        _put(box, None)                     # one wake for the two
        got = _read_chunks(theirs, 3, then=LAST_CHUNK)
        assert _events([c for _, c in got]) == [
            {"tokens": [1]}, {"tokens": [2]}, {"done": True}]
        t.join(30)
        assert left[0].outcome == FINISHED
        assert open_at_end == [False]       # the writer had closed it
        assert box.clock.attrs()["events"] == 2
        snap = counts.snapshot()
        assert (snap["writer_events"], snap["handler_events"]) == (2, 0)
    finally:
        writer.stop()
        ours.close()
        theirs.close()


def test_a_stopping_writer_hands_its_streams_back_and_takes_no_more():
    counts = StreamCounts()
    writer = StreamWriter("w-test")
    stream, box = _made_up(counts)
    ours, theirs = _pair()
    left = []
    t = threading.Thread(target=_drive, daemon=True,
                         args=(writer, ours, stream, left))
    try:
        t.start()
        assert _wait_for(lambda: box.driven)
        _put(box, [1])
        assert _events([_read_chunks(theirs, 1)[0][1]]) == [{"tokens": [1]}]
        writer.stop()
        t.join(30)
        assert left[0].outcome == STOPPED and not left[0].pending
        assert not box.driven and box.handback == STOPPED
        _put(box, [2])
        _put(box, None)
        assert _events(list(stream)) == [{"tokens": [2]}, {"done": True}]
        assert counts.snapshot()["handler_by_reason"] == {STOPPED: 1}
        assert counts.snapshot()["writer_events"] == 1
        other, _ = _made_up(counts)
        assert writer.drive(ours, other) is None
    finally:
        ours.close()
        theirs.close()


def test_an_outbox_is_a_queue_until_a_writer_is_attached():
    box = StreamOutbox()
    assert box.put([1]) is None and box.get(timeout=1) == [1]
    assert not box.driven and not box.has_next()
    with pytest.raises(queue.Empty):
        box.get(timeout=0.01)
    got = []
    t = threading.Thread(target=lambda: got.append(box.get(timeout=30)),
                         daemon=True)
    t.start()
    time.sleep(0.05)
    assert box.put([2]) is None              # told the blocked `get`
    t.join(30)
    assert got == [[2]]
    marks, wakes = [], []

    def mark():
        marks.append(1)
        return lambda: wakes.append(1)

    box.put([3])
    box.attach(mark)                         # what waits counts as put now
    assert (marks, wakes) == ([1], [1]) and box.driven and box.has_next()
    wake = box.put(None)
    assert marks == [1, 1] and wakes == [1]  # the caller wakes, once
    wake()
    assert not box.ends_next() and box.get() == [3] and box.taken == 3
    assert box.ends_next()                   # the end is the next item
    assert box.get() is None and box.has_next()   # the end was taken
    box.detach(WOULD_BLOCK)
    assert not box.driven and box.handback == WOULD_BLOCK
    assert box.put([4]) is None


def test_a_stalled_stream_s_timeout_counts_from_the_last_token_taken():
    """A handler that takes a stream over after the writer had it for
    the whole stall period waits no second period."""
    box = StreamOutbox()
    box.put([1])
    assert box.get(timeout=0.2) == [1]
    time.sleep(0.25)
    assert box.idle_s() >= 0.25
    t0 = time.monotonic()
    with pytest.raises(queue.Empty):
        box.get(timeout=0.2)
    assert time.monotonic() - t0 < 0.1
