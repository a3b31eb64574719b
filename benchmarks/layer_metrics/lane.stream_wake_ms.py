"""Mean time a streamed request's fresh tokens lay in its queue: from the
scheduler's put (`_push_stream`, in the tick's `apply`) to the handler
thread's `get` having returned them, over every token event of the lanes'
`generate_stream` spans: the sum of their `wake_us_sum` attrs over the sum
of their `events`, in milliseconds (`wake_us_max` is on the span too). What
a client's inter-token gap holds beyond the tick's period before a byte is
written; high says the lane starves its handlers of the interpreter lock.
A program that does not mark the put (before PR 42) reads nothing. Layer:
lane and admission. Moves itl_p95_ms."""

from lib.metrics import lane_spans


def compute(run):
    spans = [s["attrs"] for s in lane_spans(run, "generate_stream")
             if "wake_us_sum" in (s.get("attrs") or {})]
    events = sum(a["events"] for a in spans)
    return (sum(a["wake_us_sum"] for a in spans) / events / 1e3
            if events else None)
