"""From the same .xplane.pb as lib/xplane_reduce.py: what the host was doing
while the device ran nothing.

The program's scheduler marks every tick with jax.profiler.TraceAnnotation
(tpu_engine/utils/tracing.py, `TickClock`): `tick`, its four contiguous
children `tick.form`, `tick.dispatch`, `tick.wait`, `tick.apply`, and
`loop.admit` for the loop's work before a tick. They land on the host plane
of the trace, on the device planes' clock.

  host plane      the plane whose name starts with HOST_PLANE_PREFIX
                  ("/host:CPU"); every line (one a thread) is read, and an
                  event counts when its name is one of ANNOTATIONS
  host phases     HOST_PHASES: the phases in which the host alone decides
                  when the device gets its next operation (forming the
                  batch, applying the results, the loop between ticks).
                  Inside `tick.dispatch` and `tick.wait` a step is enqueued
                  or running: an idle device there is launch latency or a
                  bubble between operations, not the scheduler
  idle            per device plane, as xplane_reduce: the gaps between the
                  first operation's start and the last one's end in which no
                  operation ran (idle_gaps, imported from there)
  idle_host_s     the part of those gaps that lies inside a host phase

A program without the annotations (every commit before PR 25) gives no
events: `reduce_file` then returns None, and the reader leaves the metric
out. With several lanes in one process the phases of all scheduler threads
are read as one set, so a gap on one chip may be put down to a neighbour's
phase: an upper bound there, exact on one chip.
"""

import bisect
import glob
import os

from lib.xplane_reduce import (DEVICE_PLANE_PREFIX, OP_LINE, find_xplane,
                               idle_gaps, read_planes, union_ns)

HOST_PLANE_PREFIX = "/host:CPU"
TICK = "tick"
LOOP = "loop.admit"
TICK_PHASES = ("tick.form", "tick.dispatch", "tick.wait", "tick.apply")
ANNOTATIONS = (TICK, *TICK_PHASES, LOOP)
HOST_PHASES = ("tick.form", "tick.apply", LOOP)


def newest_xplane(out_dir):
    """The newest .xplane.pb of any `<cell>.trace` directory under
    `out_dir` (run.py clears a cell's directory before each traced run), or
    None."""
    found = [p for p in (find_xplane(d) for d in
                         glob.glob(os.path.join(out_dir, "*.trace")))
             if p is not None]
    return max(found, key=os.path.getmtime) if found else None


def read_annotations(path, host_prefix=HOST_PLANE_PREFIX):
    """{annotation name: [(start_ns, end_ns), ...], sorted} over every line
    of the host plane; names that are not the program's are dropped."""
    out = {}
    for events in read_planes(path, host_prefix, "").values():
        for name, start, dur in events:
            if name in ANNOTATIONS:
                out.setdefault(name, []).append((start, start + dur))
    return {name: sorted(spans) for name, spans in out.items()}


def overlap_ns(gaps, intervals):
    """Total length of the parts of `gaps` that lie inside `intervals`.
    `gaps` are sorted and do not overlap each other (idle_gaps: tens of
    thousands in a slice); `intervals` are few and may overlap."""
    ends = [g1 for _, g1 in gaps]
    inside = []
    for s, e in intervals:
        k = bisect.bisect_right(ends, s)       # the first gap that ends after s
        while k < len(gaps) and gaps[k][0] < e:
            inside.append((max(gaps[k][0], s), min(gaps[k][1], e)))
            k += 1
    return union_ns(inside)


def reduce_planes(device_planes, annotations):
    """Averages over the device planes that ran an op (as xplane_reduce):
    window_s, idle_s, idle_host_s, and idle_by_phase {annotation: seconds of
    idle inside it}; `ticks` is the number of `tick` annotations read. None
    if the trace carries no annotation or no device op."""
    used = [ev for ev in device_planes.values() if ev]
    if not used or not annotations.get(TICK):
        return None
    n = len(used)
    window = idle = idle_host = 0.0
    by_phase = {name: 0.0 for name in (*TICK_PHASES, LOOP)}
    host = [span for name in HOST_PHASES
            for span in annotations.get(name, ())]
    for events in used:
        intervals = [(s, s + d) for _, s, d in events]
        gaps = idle_gaps(intervals)
        window += (max(e for _, e in intervals)
                   - min(s for s, _ in intervals))
        idle += sum(end - start for start, end in gaps)
        idle_host += overlap_ns(gaps, host)
        for name in by_phase:
            by_phase[name] += overlap_ns(gaps, annotations.get(name, ()))
    return {"planes": n, "ticks": len(annotations[TICK]),
            "window_s": window / 1e9 / n, "idle_s": idle / 1e9 / n,
            "idle_host_s": idle_host / 1e9 / n,
            "idle_by_phase": {name: ns / 1e9 / n
                              for name, ns in by_phase.items()}}


def reduce_file(path, device_prefix=DEVICE_PLANE_PREFIX, op_line=OP_LINE,
                host_prefix=HOST_PLANE_PREFIX):
    return reduce_planes(read_planes(path, device_prefix, op_line),
                         read_annotations(path, host_prefix))


if __name__ == "__main__":
    # By hand: cd benchmarks && python3 -m lib.host_phases <file.xplane.pb>
    import json
    import sys

    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
