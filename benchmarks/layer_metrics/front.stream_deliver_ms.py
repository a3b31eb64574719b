"""Mean time one token event took downstream of the lane: from the
handler's `get` of the stream queue having returned the tokens to the
generator being resumed after its `yield` (the gateway's relay and journal,
the chunk framing, the socket writes, the flush), over every token event of
the lanes' `generate_stream` spans: the sum of their `deliver_us_sum` attrs
over the sum of their `events`, in milliseconds. Wall time: less the
thread's CPU time (`deliver_cpu_us_sum`, `front.stream_cpu_ms_per_tick`) it
is the wait for the interpreter lock, a slow reader or a full socket. A
program that does not mark the delivery (before PR 42) reads nothing.
Layer: HTTP front and gateway. Moves itl_p95_ms."""

from lib.metrics import lane_spans


def compute(run):
    spans = [s["attrs"] for s in lane_spans(run, "generate_stream")
             if "deliver_us_sum" in (s.get("attrs") or {})]
    events = sum(a["events"] for a in spans)
    return (sum(a["deliver_us_sum"] for a in spans) / events / 1e3
            if events else None)
