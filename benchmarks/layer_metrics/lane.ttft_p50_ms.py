"""Median time to first token as the lane saw it: the `ttft_us` attr of the
`generate_stream` request spans (the lane's receipt of the request -> the
first token event handed to the HTTP writer), in milliseconds. Beside
`client.ttft_p50_ms` it gives the share of the HTTP front and the gateway.
Layer: lane and admission. Moves ttft_mean_ms."""

from lib.metrics import lane_spans, percentile


def compute(run):
    spans = [s["attrs"]["ttft_us"] for s in lane_spans(run, "generate_stream")
             if "ttft_us" in (s.get("attrs") or {})]
    return percentile(spans, 50) / 1e3 if spans else None
