"""Median host time of one decode-only tick (`mixed_step` spans of width
1: from before the dispatch to after the host has the sampled tokens), in
milliseconds. Layer: step function. Moves tokens_per_s."""

from lib.metrics import lane_spans, percentile


def compute(run):
    spans = [s["duration_us"] for s in lane_spans(run, "mixed_step")
             if s["attrs"]["width"] == 1]
    return percentile(spans, 50) / 1e3 if spans else None
