"""Median host time of one tick of a block-decoding lane that carried NO
prompt chunk (`mixed_step` spans with `run_width` and `width` 1: runs of
`block_length` tokens alone), in milliseconds: the tick a block's passes
ride. Five of them are four tokens' gap. Layer: step function. Moves
itl_p95_ms."""

from lib.metrics import lane_spans, percentile


def compute(run):
    spans = [s["duration_us"] for s in lane_spans(run, "mixed_step")
             if "run_width" in s["attrs"] and s["attrs"]["width"] == 1]
    return percentile(spans, 50) / 1e3 if spans else None
