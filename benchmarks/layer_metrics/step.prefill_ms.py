"""Median host time of one tick that carried a prefill chunk (`mixed_step`
spans of width > 1), in milliseconds. Layer: step function. Moves
itl_p95_ms: below the knee the tail of the gaps between tokens is a prefill
tick."""

from lib.metrics import lane_spans, percentile


def compute(run):
    spans = [s["duration_us"] for s in lane_spans(run, "mixed_step")
             if s["attrs"]["width"] > 1]
    return percentile(spans, 50) / 1e3 if spans else None
