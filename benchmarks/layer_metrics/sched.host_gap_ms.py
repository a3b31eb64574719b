"""Median time the device had nothing queued because of the host, between
two back-to-back ticks: the `gap_us` attr of the `mixed_step` spans that
carry one (from the end of the previous tick's wait for the device to this
tick's call of the step: applying results, the loop between ticks, forming
the batch), in milliseconds. A tick that follows an idle lane carries none.
Layer: scheduler tick. Moves tokens_per_s."""

from lib.metrics import lane_spans, percentile


def compute(run):
    gaps = [s["attrs"]["gap_us"] for s in lane_spans(run, "mixed_step")
            if "gap_us" in s["attrs"]]
    return percentile(gaps, 50) / 1e3 if gaps else None
