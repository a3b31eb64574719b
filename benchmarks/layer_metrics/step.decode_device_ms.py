"""Median time of the step program in one decode-only tick: `dispatch_us` +
`wait_us` of the `mixed_step` spans of width 1 (the call of the compiled
step until the host has its results; forming the batch and applying the
results are left out, unlike `step.decode_ms`), in milliseconds.
Layer: step function. Moves tokens_per_s."""

from lib.metrics import lane_spans, percentile


def compute(run):
    spans = [s["attrs"]["dispatch_us"] + s["attrs"]["wait_us"]
             for s in lane_spans(run, "mixed_step")
             if s["attrs"]["width"] == 1 and "wait_us" in s["attrs"]]
    return percentile(spans, 50) / 1e3 if spans else None
