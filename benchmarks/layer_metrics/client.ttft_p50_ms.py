"""Median time to first token as the client saw it in the traced run, from
when each request was due, in milliseconds: the same arithmetic as the
end-to-end `ttft_p50_ms`, kept as an unbounded reading in the cells where
about fifty requests a window make the median too unsteady to carry a bound
(PERF.md, section 2). Layer: client. Moves itl_p95_ms (below the knee both
are set by the length of a prefill tick)."""

from lib.metrics import percentile, ttft_ms


def compute(run):
    samples = ttft_ms(run["records"])
    return percentile(samples, 50) if samples else None
