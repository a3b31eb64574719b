"""A streamed request's way from the lane to whoever drives its events out.

- `StreamOutbox` — the queue a lane's scheduler puts a stream's fresh
  tokens into (`submit(stream=...)`) and its event iterator takes them
  from. Read by a blocked `get` it is a plain queue; `attach`ed to a
  front's stream writer (``serving/http.py`` `StreamWriter`) a `put`
  marks the stream ready there instead of waking a thread, and hands
  the caller the writer's wake to call once it has put all it has.
- `EventStream` — an event-chunk iterator that says which outbox its
  items come from, so that the front can tell a stream it may drive
  from its writer (every `next` has an item waiting) from one it must
  iterate on the request's own thread. `relay` is how a wrapper that
  yields one chunk for one chunk passes that on.
- `StreamCounts` — how a lane's token events went out: by a writer's
  pass or on a handler's thread, and why (`stats()["stream"]`).
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Callable, Dict, Iterator, Optional

# A stream whose lane has put nothing for this long has stalled: its
# reader says so in a terminal error event and ends.
STREAM_STALL_S = 600.0

# Why a token event went out on its request's own thread.
UNREGISTERED = "unregistered"   # no writer was given the stream: a journal
#                                 or a caller iterates it, the native front
WOULD_BLOCK = "would_block"     # its socket would not take a whole frame
STALLED = "stalled"             # nothing came for STREAM_STALL_S
STOPPED = "stopped"             # the front's writer was stopping


class StreamCounts:
    """A lane's token events by who handed them to the socket, counted
    by the writer once a pass and by a handler's thread once an event."""

    FIELDS = ("writer_events", "handler_events", "writer_passes",
              "would_block")

    def __init__(self):
        self._lock = threading.Lock()
        self._n = dict.fromkeys(self.FIELDS, 0)
        self._by_reason: Dict[str, int] = {}

    def add(self, **fields: int) -> None:
        with self._lock:
            for name, n in fields.items():
                self._n[name] += n

    def handler_event(self, reason: str, n: int = 1) -> None:
        with self._lock:
            self._n["handler_events"] += n
            self._by_reason[reason] = self._by_reason.get(reason, 0) + n

    def snapshot(self) -> dict:
        with self._lock:
            return {**self._n, "handler_by_reason": dict(self._by_reason)}


class StreamOutbox:
    """One stream's fresh tokens between the scheduler's `put` and its
    event iterator's `get`: lists of tokens in the order they were put,
    then ``None``, the end of the stream.

    `get(timeout)` blocks as ``queue.Queue.get`` does and raises
    ``queue.Empty``; its timeout counts from when the outbox was last
    emptied, so a reader that takes a stream over late waits no longer
    than one that read it from the start. While a writer is attached no
    thread is blocked in `get`: `put` calls the writer's `mark` and
    returns its `wake`, and the writer calls `next` on the stream's
    iterator only while `has_next()`, so that `get` returns at once.

    `clock` and `counts` ride along for the writer: the stream's
    `StreamClock` (its marks) and the lane's `StreamCounts` (its own
    where no lane counts)."""

    def __init__(self, clock=None, counts: Optional[StreamCounts] = None):
        self.clock = clock
        self.counts = counts if counts is not None else StreamCounts()
        self.taken = 0          # token events taken so far
        self.handback = None    # why a writer gave the stream back
        self._items: deque = deque()
        self._cond = threading.Condition(threading.Lock())
        self._mark: Optional[Callable[[], Callable[[], None]]] = None
        self._ended = False     # the sentinel was taken
        self._t_empty = time.monotonic()

    def put(self, item) -> Optional[Callable[[], None]]:
        """Append `item`. Returns None where a blocked `get` was told,
        else the attached writer's wake: the stream is marked ready
        there, and the caller wakes the writer when it has put all it
        has (the scheduler: once a tick)."""
        with self._cond:
            self._items.append(item)
            mark = self._mark
            if mark is None:
                self._cond.notify()
                return None
        return mark()

    def get(self, timeout: Optional[float] = None):
        with self._cond:
            if not self._items:
                limit = (None if timeout is None
                         else self._t_empty + timeout)
                while not self._items:
                    left = (None if limit is None
                            else limit - time.monotonic())
                    if left is not None and left <= 0:
                        raise queue.Empty
                    self._cond.wait(left)
            item = self._items.popleft()
            if not self._items:
                self._t_empty = time.monotonic()
            if item is None:
                self._ended = True
            else:
                self.taken += 1
            return item

    # -- the writer's side ------------------------------------------------

    @property
    def driven(self) -> bool:
        """A writer's pass, not a blocked `get`, takes the items."""
        return self._mark is not None

    def has_next(self) -> bool:
        """`next` on the stream's iterator will not block in `get`: an
        item waits, or the end was taken and the iterator only has its
        last events and its clean-up left."""
        return bool(self._items) or self._ended

    def ends_next(self) -> bool:
        """The end of the stream is the next item: the iterator records
        the stream's span on the `next` that takes it."""
        items = self._items
        return bool(items) and items[0] is None

    def idle_s(self) -> float:
        """Seconds since the outbox was last emptied; 0 while it holds
        an item."""
        with self._cond:
            return 0.0 if self._items else time.monotonic() - self._t_empty

    def attach(self, mark: Callable[[], Callable[[], None]]) -> None:
        """Give the stream to a writer: `mark()` notes it ready and
        returns the writer's wake. What was put before counts as put
        now."""
        with self._cond:
            self._mark = mark
            self.handback = None
            waiting = bool(self._items)
        if waiting:
            mark()()

    def detach(self, reason: str) -> None:
        """Back to a blocked `get`, which finds what is there."""
        with self._cond:
            self._mark = None
            self.handback = reason
            self._cond.notify_all()


class EventStream:
    """An iterator of event chunks whose items come one for one from
    `outbox`. Everything else about it is the wrapped iterator's."""

    __slots__ = ("_it", "outbox")

    def __init__(self, it: Iterator[bytes], outbox: StreamOutbox):
        self._it = it
        self.outbox = outbox

    def __iter__(self):
        return self

    def __next__(self) -> bytes:
        return next(self._it)

    def close(self) -> None:
        self._it.close()


def relay(it: Iterator[bytes], source) -> Iterator[bytes]:
    """`it`, which yields one chunk for each chunk it takes from
    `source`, saying what `source` says of its outbox."""
    outbox = getattr(source, "outbox", None)
    return it if outbox is None else EventStream(it, outbox)
