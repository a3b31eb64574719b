"""Median time a request waited for a free row after the prefill thread
handed it to the decode loop (the `slot_wait` stage spans: `_ready` ->
`_admit`, time parked under pool pressure included), in milliseconds.
Layer: lane and admission. Moves ttft_mean_ms."""

from lib.metrics import lane_spans, percentile


def compute(run):
    spans = [s["duration_us"] for s in lane_spans(run, "slot_wait")]
    return percentile(spans, 50) / 1e3 if spans else None
