"""Median time to first token as the client saw it in the traced run, from
when each request was due, in milliseconds: the arithmetic of the end-to-end
`ttft_mean_ms` with a median in the mean's place, kept as an unbounded
reading in the cells whose TTFT is judged by its mean: about fifty requests
a window (chat) or some hundred quantised to ticks (docqa, where it was the
judged `ttft_p50_ms` until PR 35) make the median too unsteady to carry a
bound (PERF.md, section 2). Layer: client. Moves ttft_mean_ms."""

from lib.metrics import percentile, ttft_ms


def compute(run):
    samples = ttft_ms(run["records"])
    return percentile(samples, 50) if samples else None
