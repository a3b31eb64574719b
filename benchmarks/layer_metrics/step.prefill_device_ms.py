"""Median time of the step program in one tick that carried a prefill
chunk: `dispatch_us` + `wait_us` of the `mixed_step` spans of width > 1, in
milliseconds (`step.prefill_ms` less the scheduler's host work).
Layer: step function. Moves itl_p95_ms."""

from lib.metrics import lane_spans, percentile


def compute(run):
    spans = [s["attrs"]["dispatch_us"] + s["attrs"]["wait_us"]
             for s in lane_spans(run, "mixed_step")
             if s["attrs"]["width"] > 1 and "wait_us" in s["attrs"]]
    return percentile(spans, 50) / 1e3 if spans else None
