"""Median time the gateway spent routing one request (its `route` spans),
in milliseconds. Layer: HTTP front and gateway. Moves ttft_mean_ms."""

from lib.metrics import percentile


def compute(run):
    spans = [s["duration_us"] for s in run["spans"].get("gateway", ())
             if s["op"] == "route"]
    return percentile(spans, 50) / 1e3 if spans else None
