"""Median time of the step program in one decode-only tick of a lane whose
rows own a state row beside their blocks: `dispatch_us` + `wait_us` of the
`mixed_step` spans of width 1 that carry `gdn_step_rows` (the call of the
compiled step until the host has its results; forming the batch and
applying the results are left out, unlike `step.hybrid_decode_ms`), in
milliseconds: `step.decode_device_ms`'s span, for the cell that metric's
list does not name. Layer: step function. Moves tokens_per_s."""

from lib.metrics import lane_spans, percentile


def compute(run):
    spans = [s["attrs"]["dispatch_us"] + s["attrs"]["wait_us"]
             for s in lane_spans(run, "mixed_step")
             if s["attrs"].get("width") == 1 and "wait_us" in s["attrs"]
             and "gdn_step_rows" in s["attrs"]]
    return percentile(spans, 50) / 1e3 if spans else None
