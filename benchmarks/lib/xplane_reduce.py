"""From the profiler's trace (.xplane.pb) to device busy time, idle gaps and
time per operation.

Read with jax.profiler.ProfileData and nothing else. A trace has planes
(one per device, one for the host's threads), each with lines, each with
events that carry a start and a duration in nanoseconds on one clock.

  device planes   names that start with DEVICE_PLANE_PREFIX ("/device:TPU:")
  op line         the line named OP_LINE ("XLA Ops"): one event per
                  operation the device ran. The other lines of a device
                  plane ("XLA Modules", "Steps", ...) cover the same time
                  again at a coarser grain and are not added to it.

names    the op line's events are named by the whole HLO instruction; the
         reducer keeps the instruction's name without its number and its
         result's type, as in "%_paged_call f32[32,20,256,64]", so the 36
         copies of one layer-sized buffer add up under one name
self     an op such as `while` lasts as long as the ops of its body, which
         the line lists too; the time per op is SELF time, an event's
         duration less the events nested inside it, so nothing counts twice
busy     the union of the op intervals of a plane (overlaps counted once)
idle     the window minus busy; the window of a plane runs from its first
         op's start to its last op's end
gaps     the longest intervals in which no op ran, each labelled with what
         the host was doing; the program's ticks carry no TraceAnnotation
         yet, so every gap is "host:unattributed" (PERF.md, Open questions)

Averages over planes are over the planes that ran at least one op.
"""

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
UNATTRIBUTED = "host:unattributed"


def find_xplane(directory):
    """The newest .xplane.pb under a profiler log directory, or None."""
    found = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def read_planes(path, plane_prefix=DEVICE_PLANE_PREFIX, line_prefix=OP_LINE):
    """{plane name: [(event name, start_ns, duration_ns), ...]} for every
    plane whose name starts with `plane_prefix`, from the lines whose name
    starts with `line_prefix`."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(plane_prefix):
            continue
        events = []
        for line in plane.lines:
            if not line.name.startswith(line_prefix):
                continue
            events.extend((e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events)
        planes[plane.name] = events
    return planes


def describe(path, top=40):
    """Plane and line names with event counts, and the commonest event
    names of each line: what one looks at by hand before writing a name
    pattern."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            totals = {}
            for e in line.events:
                n, s = totals.get(e.name, (0, 0.0))
                totals[e.name] = (n + 1, s + float(e.duration_ns))
            ranked = sorted(totals.items(), key=lambda kv: -kv[1][1])[:top]
            lines.append({"line": line.name, "events": sum(
                n for n, _ in totals.values()),
                "top": [[name, n, s / 1e9] for name, (n, s) in ranked]})
        out.append({"plane": plane.name, "lines": lines})
    return out


def short_name(event_name):
    """'%fusion.163 = bf16[32,256,1280]{2,1,0:T(8,128)} fusion(...)' ->
    '%fusion bf16[32,256,1280]': the instruction's name without its number,
    and its result's type ('(tuple)' for a tuple). A name that is no HLO
    instruction is kept, cut to 80 characters."""
    name, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name[:80]
    name = re.sub(r"\.\d+$", "", name)
    if rest.startswith("("):
        return f"{name} (tuple)"[:80]
    end = min((i for i in (rest.find("{"), rest.find(" ")) if i >= 0),
              default=len(rest))
    return f"{name} {rest[:end]}"[:80]


def self_times(events):
    """[(name, self_ns)] for the events of ONE line: each event's duration
    less the durations of the events that start and end inside it."""
    out, stack = [], []          # stack: [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack and end <= stack[-1][1]:
            stack[-1][2] -= dur              # nested: the parent's loss
        stack.append([name, end, dur])
    out.extend((name, own) for name, _, own in stack)
    return out


def union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def idle_gaps(intervals):
    """The (start, end) intervals between the first op's start and the last
    op's end in which no op ran."""
    gaps, cur_end = [], None
    for start, end in sorted(intervals):
        if cur_end is not None and start > cur_end:
            gaps.append((cur_end, start))
        cur_end = end if cur_end is None else max(cur_end, end)
    return gaps


def reduce_planes(planes, top_ops=10, top_gaps=5):
    """The numbers the per-layer metrics and the breakdown are read from.

    busy_s, window_s: averaged over the planes that ran an op. op_seconds:
    {short op name: self seconds}, summed over planes and divided by their
    number (the time one chip spent in that op). gaps: the longest idle gaps of
    any plane, as [label, seconds]."""
    used = {name: ev for name, ev in planes.items() if ev}
    if not used:
        return {"planes": 0, "busy_s": 0.0, "window_s": 0.0,
                "op_seconds": {}, "device_ops": [], "idle_gaps": []}
    n = len(used)
    busy = window = 0.0
    op_ns, gaps = {}, []
    for events in used.values():
        intervals = [(s, s + d) for _, s, d in events]
        busy += union_ns(intervals)
        window += (max(e for _, e in intervals)
                   - min(s for s, _ in intervals))
        for name, own in self_times(events):
            name = short_name(name)
            op_ns[name] = op_ns.get(name, 0.0) + own
        gaps.extend(end - start for start, end in idle_gaps(intervals))
    op_seconds = {name: ns / 1e9 / n for name, ns in op_ns.items()}
    ranked = sorted(op_seconds.items(), key=lambda kv: -kv[1])
    return {
        "planes": n,
        "busy_s": busy / 1e9 / n,
        "window_s": window / 1e9 / n,
        "op_seconds": op_seconds,
        "device_ops": [[name, s] for name, s in ranked[:top_ops]],
        "idle_gaps": [[UNATTRIBUTED, g / 1e9]
                      for g in sorted(gaps, reverse=True)[:top_gaps]],
    }


def reduce_file(path, **kwargs):
    return reduce_planes(read_planes(path), **kwargs)


if __name__ == "__main__":
    # Look at a trace by hand: python3 benchmarks/lib/xplane_reduce.py <file>
    import json
    import sys

    print(json.dumps(describe(sys.argv[1]), indent=1))
