"""Median run time ON THE DEVICE of a decode-only tick's program (width 1),
in milliseconds: from the trace's "XLA Modules" line (lib/xplane_scopes.py
`modules`), the runs wholly inside the traced slice of the programs named
`tick_w1` (`tick_w1_r4` on a lane that decodes by blocks of 4): since PR 55
a tick's program is named for its width (`jit_tick_w1`, `jit_tick_w256`).
This is the device's own step time: `step.*_device_ms` reads the time the
host was blocked. Read beside `sched.decode_period_ms`: on a device-bound
lane that runs ahead the two agree. A slice in which no such program ran,
and a program before PR 55 (every program `jit_mixed_step`), read nothing.
Layer: step function. Moves tokens_per_s."""

from lib.xplane_scopes import run_ms


def compute(run):
    return run_ms(run, lambda kind, width: kind == "tick" and width == 1)
