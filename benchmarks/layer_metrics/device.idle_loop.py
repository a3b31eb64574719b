"""Share of the traced slice in which no operation ran on the device AND the
scheduler's thread was in its loop between two ticks (the `loop.admit`
annotation on the trace's host plane), in percent of the slice: the part
of `device.idle_host` that `tick.form` and `tick.apply` do not hold, from
lib/host_phases.py's `idle_by_phase`. Where every tick is enqueued behind a
running one (`sched.overlap_tick_share`) and the device's tick outlasts
the host's work, the loop hides and this reads near 0; where the host sets
the pace it is what `sched.loop_ms` costs the device. Layer: device. Moves
tokens_per_s.

As `device.idle_host`: the annotations are read from the newest .xplane.pb
under benchmarks/out/*.trace, unless the run object brings them reduced as
`run["host_phases"]`."""

from lib import host_phases, host_threads


def compute(run):
    phases = host_threads.of_run(run, "host_phases", host_phases)
    if not phases or host_phases.LOOP not in phases.get("idle_by_phase", {}):
        return None
    return (100.0 * phases["idle_by_phase"][host_phases.LOOP]
            / run["trace"]["window_s"])
