"""Median host time of one decode-only tick of a lane whose rows own a
Mamba-2 state row AND a K/V chain in every layer (`mixed_step` spans of
width 1 that carry `ssd_step_rows`: every row through the one-step form of
the recurrence and the packed G = 5 read of its context, in all six layers;
from before the dispatch to after the host has the sampled tokens), in
milliseconds: `step.decode_ms`'s span, for the cell that metric's list does
not name. On a lane that runs a tick ahead this is the tick's period less
the loop's time (PERF.md section 3). Layer: step function. Moves
tokens_per_s."""

from lib.metrics import lane_spans, percentile


def compute(run):
    spans = [s["duration_us"] for s in lane_spans(run, "mixed_step")
             if s["attrs"].get("width") == 1
             and "ssd_step_rows" in s["attrs"]]
    return percentile(spans, 50) / 1e3 if spans else None
