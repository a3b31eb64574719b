"""From the load generator's records to the end-to-end metrics.

Kept with the benchmark so that no later PR changes the arithmetic it is
judged by. Everything here is plain Python over the records that
lib/loadgen.py wrote; nothing is read from the program under test.

Percentiles are nearest-rank: the smallest sample with at least p% of the
samples at or below it. `highest_percentile` is the choosing-metrics rule:
report the highest percentile that still has ten samples beyond it.
"""

import math


def percentile(values, p):
    """Nearest-rank percentile of a non-empty list; p in (0, 100]."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return ordered[rank - 1]


def highest_percentile(n, beyond=10, ladder=(50, 90, 95, 99, 99.9)):
    """The highest percentile of `ladder` that leaves at least `beyond` of
    n samples above it; None if not even the lowest does."""
    best = None
    for p in ladder:
        if n - math.ceil(p / 100.0 * n) >= beyond:
            best = p
    return best


def lane_spans(run, op):
    """The spans named `op` that the lanes (not the gateway) recorded inside
    the window: what the per-layer readers of scheduler and step read."""
    return [s for node, ring in run["spans"].items() if node != "gateway"
            for s in ring if s["op"] == op]


def ttft_ms(records):
    """Time to first token of every request that got one: from when it was
    DUE (not from when it was sent) to its first token event."""
    return [1e3 * (r["first"] - r["due"]) for r in records
            if r["ok"] and r["first"] is not None]


def itl_gaps(records):
    """(start, end, tokens) of every gap between two token events of a
    request that did not fail: what the inter-token samples are made of."""
    for r in records:
        if r["ok"]:
            events = r["events"]
            for (t_prev, _), (t, n) in zip(events, events[1:]):
                yield t_prev, t, n


def itl_ms(records):
    """The gap between output tokens, one sample per token after a
    request's first event: an event that carries n tokens after a gap g
    gives n samples of g / n."""
    out = []
    for t_prev, t, n in itl_gaps(records):
        out.extend([1e3 * (t - t_prev) / n] * n)
    return out


def window_tokens(records, seconds):
    """Prompt and output tokens the system finished inside [0, seconds):
    an output token counts when its event arrives; a prompt is spread
    evenly over the time from when its request was sent to its first token
    (its prefill is done somewhere in that time) and counts by the part of
    that time inside the window. Token by token and not request by request,
    so that the window's edge cuts a request where it stands: a first token
    that comes 50 ms before or after the edge moves the count by a
    thousandth of its prompt and not by all of it (PERF.md, PR 24: a
    960-token prompt 54 ms from the edge made the rate jump by 3.6%)."""
    total = 0.0
    for r in records:
        if not r["ok"]:
            continue
        first, sent = r["first"], r["sent"]
        if first is not None:
            if first > sent:
                inside = min(first, seconds) - max(sent, 0.0)
                share = max(0.0, inside) / (first - sent)
            else:
                share = 1.0 if 0.0 <= first < seconds else 0.0
            total += share * r["prompt_tokens"]
        total += sum(n for t, n in r["events"] if 0.0 <= t < seconds)
    return total


def lateness_ms(records):
    late = sorted(1e3 * (r["sent"] - r["due"]) for r in records
                  if r["sent"] is not None and r["due"] is not None)
    if not late:
        return {"p50": None, "max": None}
    return {"p50": percentile(late, 50), "max": late[-1]}


def end_to_end(records, seconds, setup_s):
    """Every end-to-end metric this benchmark knows, by name, from one
    window's records; run.py prints those that BENCHMARK.json lists for
    the cell. A metric with no sample is left out."""
    out = {"setup_s": {"value": setup_s, "unit": "s"},
           "tokens_per_s": {"value": window_tokens(records, seconds)
                            / seconds, "unit": "tokens/s"}}
    ttft, itl = ttft_ms(records), itl_ms(records)
    for name, samples, p in (("ttft_p50_ms", ttft, 50),
                             ("ttft_p90_ms", ttft, 90),
                             ("itl_p95_ms", itl, 95)):
        if samples:
            out[name] = {"value": percentile(samples, p), "unit": "ms"}
    if ttft:
        # Every request weighs the same: steadier than a percentile where
        # a window holds some tens of requests.
        out["ttft_mean_ms"] = {"value": sum(ttft) / len(ttft), "unit": "ms"}
    return out


def counts(records):
    attempted = len(records)
    failed = sum(not r["ok"] for r in records)
    return attempted, failed
