"""Median time a request waited in the lane's admission queue before the
scheduler took it (the `queue_wait` stage spans of every lane), in
milliseconds. Layer: lane and admission. Moves ttft_mean_ms."""

from lib.metrics import lane_spans, percentile


def compute(run):
    spans = [s["duration_us"] for s in lane_spans(run, "queue_wait")]
    return percentile(spans, 50) / 1e3 if spans else None
