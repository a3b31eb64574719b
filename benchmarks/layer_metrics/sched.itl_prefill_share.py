"""Share of the window's inter-token samples whose gap spans a tick that
carried prefill, in percent. The samples are `itl_p95_ms`'s own (an event of
n tokens after a gap counts n times); a gap spans a `mixed_step` span of
width > 1 when the tick's midpoint lies inside it, on one clock through the
window's start. While this stays under 5 the p95 reads a decode tick's
period; above, a prefill tick's (PERF.md, PRs 26, 32 and 35). Layer:
scheduler tick. Moves itl_p95_ms."""

from bisect import bisect_right

from lib.metrics import itl_gaps, lane_spans


def compute(run):
    ticks = lane_spans(run, "mixed_step")
    if not ticks or run.get("window_start") is None:
        return None
    # A span's `ts` is when it was recorded, its end.
    mids = sorted(s.get("start_ts", s["ts"] - s["duration_us"] / 1e6)
                  + s["duration_us"] / 2e6 - run["window_start"]
                  for s in ticks if s["attrs"]["width"] > 1)
    total = spanning = 0
    for t_prev, t, n in itl_gaps(run["records"]):
        total += n
        if bisect_right(mids, t) > bisect_right(mids, t_prev):
            spanning += n
    return 100.0 * spanning / total if total else None
