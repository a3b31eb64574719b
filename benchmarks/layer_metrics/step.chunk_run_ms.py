"""Median run time ON THE DEVICE of a chunk tick's program (width above 1),
in milliseconds: from the trace's "XLA Modules" line (lib/xplane_scopes.py
`modules`), the runs wholly inside the traced slice of the programs named
`tick_w<width>` with a width above 1 (`tick_w256`): since PR 55 a tick's
program is named for its width. This is the device's own step time:
`step.prefill_device_ms` reads the time the host was blocked. Read beside
`step.prefill_ms`. A slice in which no such program ran, and a program
before PR 55 (every program `jit_mixed_step`), read nothing.
Layer: step function. Moves itl_p95_ms."""

from lib.xplane_scopes import run_ms


def compute(run):
    return run_ms(run, lambda kind, width: kind == "tick" and width > 1)
