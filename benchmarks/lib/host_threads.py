"""From the same .xplane.pb as lib/host_phases.py: what the host's OTHER
threads were doing while the device ran nothing, and which of the loop's
statements the scheduler's thread was in.

lib/host_phases.py lays the device's idle gaps against the scheduler
thread's own phases (`tick.form` ... `loop.admit`). Since PR 42 the program
puts two more kinds of jax.profiler.TraceAnnotation on the host plane, on
the device planes' clock (tpu_engine/utils/tracing.py):

  stream.deliver     one an event of a streamed request, on the HANDLER's
                     thread (`StreamClock`): from the stream queue's `get`
                     having returned the tokens to the generator being
                     resumed after its `yield` (the gateway's relay, the
                     chunk framing, the socket writes, the flush). With 32
                     streams there are 32 such threads, and they share the
                     interpreter lock with the scheduler's
  loop.admit.<part>  the loop's statements between two ticks, children of
                     `loop.admit` on the scheduler's thread (`TickClock.
                     loop_part`): exports, capacity, admit, expire

Every line of the host plane (one a thread) is read, and an event counts
when its name is one of NAMES. For each name:

  events    how many were read
  sum_s     their durations added up (thread-seconds: 32 threads that
            deliver at once count 32 times)
  union_s   the time in which at least one was open
  idle_s    the part of the device's idle gaps (xplane_reduce.idle_gaps, per
            device plane, averaged over the planes that ran an op) that lies
            inside that union: the idle time in which at least one thread
            was inside such an annotation

A program without these annotations (every commit before PR 42) gives no
events and an empty `by_name`: the readers then leave their metrics out.
With several lanes in one process the threads of all lanes are read as one
set, as in lib/host_phases.py: an upper bound there, exact on one chip.
"""

import os

from lib.host_phases import HOST_PLANE_PREFIX, newest_xplane, overlap_ns
from lib.xplane_reduce import (DEVICE_PLANE_PREFIX, OP_LINE, idle_gaps,
                               read_planes, union_ns)

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "out")
STREAM = "stream.deliver"
LOOP_PARTS = tuple("loop.admit." + part for part in
                   ("exports", "capacity", "admit", "expire"))
NAMES = (STREAM, *LOOP_PARTS)


def read_annotations(path, names=NAMES, host_prefix=HOST_PLANE_PREFIX):
    """{annotation name: [(start_ns, end_ns), ...], sorted} over every line
    of the host plane; names not in `names` are dropped."""
    out = {}
    for events in read_planes(path, host_prefix, "").values():
        for name, start, dur in events:
            if name in names:
                out.setdefault(name, []).append((start, start + dur))
    return {name: sorted(spans) for name, spans in out.items()}


def reduce_planes(device_planes, annotations):
    """window_s and idle_s as lib/host_phases.py has them (averages over the
    device planes that ran an op) and, by annotation name, `events`,
    `sum_s`, `union_s` and `idle_s` (above). None if no device op ran."""
    used = [ev for ev in device_planes.values() if ev]
    if not used:
        return None
    n = len(used)
    window = idle = 0.0
    idle_in = dict.fromkeys(annotations, 0.0)
    for events in used:
        intervals = [(s, s + d) for _, s, d in events]
        gaps = idle_gaps(intervals)
        window += (max(e for _, e in intervals)
                   - min(s for s, _ in intervals))
        idle += sum(end - start for start, end in gaps)
        for name, spans in annotations.items():
            idle_in[name] += overlap_ns(gaps, spans)
    return {"planes": n, "window_s": window / 1e9 / n,
            "idle_s": idle / 1e9 / n,
            "by_name": {name: {"events": len(spans),
                               "sum_s": sum(e - s for s, e in spans) / 1e9,
                               "union_s": union_ns(spans) / 1e9,
                               "idle_s": idle_in[name] / 1e9 / n}
                        for name, spans in annotations.items()}}


def reduce_file(path, device_prefix=DEVICE_PLANE_PREFIX, op_line=OP_LINE,
                host_prefix=HOST_PLANE_PREFIX, names=NAMES):
    return reduce_planes(read_planes(path, device_prefix, op_line),
                         read_annotations(path, names, host_prefix))


def of_run(run, key, reducer):
    """What a reader of the traced slice's host plane starts from:
    `run[key]` where the run object brings the reduction, else
    `reducer.reduce_file` of the newest .xplane.pb under
    benchmarks/out/*.trace (`run["trace"]` carries no host plane). None
    where nothing was traced, no op ran, or the newest file is not the one
    `run["trace"]` was read from (their windows differ)."""
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    reduced = run.get(key)
    if reduced is None:
        path = newest_xplane(OUT)
        reduced = reducer.reduce_file(path) if path else None
    if not reduced or abs(reduced.get("window_s", trace["window_s"])
                          - trace["window_s"]) > 1e-9:
        return None
    return reduced


if __name__ == "__main__":
    # By hand: cd benchmarks && python3 -m lib.host_threads <file.xplane.pb>
    import json
    import sys

    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
