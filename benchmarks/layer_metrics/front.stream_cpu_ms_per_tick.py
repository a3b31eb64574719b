"""CPU time the stream handlers' threads spent delivering token events, a
scheduler tick: the sum of the `deliver_cpu_us_sum` attrs of the lanes'
`generate_stream` spans over the window's `mixed.ticks` (the difference of
the lanes' counters), in milliseconds. One event's delivery runs from the
stream queue's `get` having returned to the generator being resumed after
its `yield`: the gateway's relay and journal, the chunk framing, the socket
writes, the flush; the attr is the handler thread's CPU time (user and
kernel) from its first event to the span's record, between whose deliveries
the thread is blocked in the queue's `get`. The Python part of it runs
under the interpreter lock the scheduler's thread needs; the kernel's part
of a socket write does not. To lay beside `sched.host_offcpu_ms`: about
equal or more says the handlers' work is what keeps the scheduler off the
CPU; far below says the lock's hand-off is.

The spans are those run.py hands a reader: of the streams that BEGAN in the
window. One that began before it is left out whole and one that ended after
it is counted whole; in a steady loop the two cancel. A program that does
not sum the deliveries (before PR 42) reads nothing. Layer: HTTP front and
gateway. Moves tokens_per_s."""

from lib.metrics import lane_spans


def compute(run):
    cpu_us = [s["attrs"]["deliver_cpu_us_sum"]
              for s in lane_spans(run, "generate_stream")
              if "deliver_cpu_us_sum" in (s.get("attrs") or {})]
    ticks = 0
    for node, after in run["stats_after"].items():
        before = run["stats_before"].get(node, {}).get("mixed")
        if before and after.get("mixed"):
            ticks += after["mixed"]["ticks"] - before["ticks"]
    return sum(cpu_us) / 1e3 / ticks if cpu_us and ticks else None
