"""Median period of a decode-only tick: the `period_us` attr of the
`mixed_step` spans of width 1 that carry one (from the scheduler's
`begin()` of the tick enqueued before to this tick's `begin()`: one whole
iteration of the loop, the number a decode-bound cell's `tokens_per_s`
follows), in milliseconds. The phases that tile it are on the spans too:
`form_us + dispatch_us` of the tick before, `wait_us + apply_us` of the
tick before that, this tick's `loop_us`. A tick that follows an idle lane
carries none; a program that does not mark the period (before PR 42) reads
nothing. Layer: scheduler tick. Moves tokens_per_s."""

from lib.metrics import lane_spans, percentile


def compute(run):
    periods = [s["attrs"]["period_us"] for s in lane_spans(run, "mixed_step")
               if s["attrs"]["width"] == 1 and "period_us" in s["attrs"]]
    return percentile(periods, 50) / 1e3 if periods else None
