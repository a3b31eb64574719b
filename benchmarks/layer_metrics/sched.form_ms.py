"""Median time the scheduler took to form a tick: the `form_us` attr of the
`mixed_step` spans (from the tick's `begin()` to its `dispatch` mark: the
two passes over the rows, the control block's packing and the host→device
transfer of what the step takes per row, `runtime/scheduler.py`
`_tick_mixed`), in milliseconds. The first of the four phases
`step.decode_ms` sums; where a decode tick's period is the host's
(`sched.decode_period_ms` beside a high `device.idle_host`) it is the part
that PR 47 found to be fifteen small transfers. Every tick carries the attr
since PR 25; a program without the tick clock reads nothing. Layer:
scheduler tick. Moves itl_p95_ms."""

from lib.metrics import lane_spans, percentile


def compute(run):
    forms = [s["attrs"]["form_us"] for s in lane_spans(run, "mixed_step")
             if "form_us" in s["attrs"]]
    return percentile(forms, 50) / 1e3 if forms else None
