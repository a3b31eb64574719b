"""Median time the scheduler's loop took between two ticks: the `loop_us`
attr of the `mixed_step` spans that carry one (the `loop.admit` stretch
before the tick's `form`: exports, pool growth, admission, expiry and the
tail of the tick before, whose parts are on the span as `loop_exports_us`,
`loop_capacity_us`, `loop_admit_us`, `loop_expire_us`), in milliseconds.
The fifth phase of a tick's period: `step.decode_ms` is the other four. A
tick that follows an idle lane carries none; a program that does not mark
the loop (before PR 42) reads nothing. Layer: scheduler tick. Moves
tokens_per_s."""

from lib.metrics import lane_spans, percentile


def compute(run):
    loops = [s["attrs"]["loop_us"] for s in lane_spans(run, "mixed_step")
             if "loop_us" in s["attrs"]]
    return percentile(loops, 50) / 1e3 if loops else None
