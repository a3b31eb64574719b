"""Share of the traced slice in which no operation ran on the device AND the
scheduler was forming a batch, applying results or between two ticks
(`tick.form`, `tick.apply`, `loop.admit` annotations on the trace's host
plane), in percent of the slice. `device.idle` less this is idle inside
`tick.dispatch` and `tick.wait`: launch latency and bubbles between
operations. Layer: device. Moves tokens_per_s.

`run["trace"]` carries no host plane, so the annotations are read from the
newest .xplane.pb under benchmarks/out/*.trace (lib/host_phases.py), unless
the run object brings them reduced as `run["host_phases"]`."""

import os

from lib import host_phases

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "out")


def compute(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    phases = run.get("host_phases")
    if phases is None:
        path = host_phases.newest_xplane(OUT)
        phases = host_phases.reduce_file(path) if path else None
    if not phases:
        return None
    if abs(phases.get("window_s", trace["window_s"])
           - trace["window_s"]) > 1e-9:
        return None     # the newest file is not the one `trace` was read from
    return 100.0 * phases["idle_host_s"] / trace["window_s"]
