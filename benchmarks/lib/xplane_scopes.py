"""From the same .xplane.pb as lib/xplane_reduce.py: the device's time by
PART of the step, and the run time of each tick program by name.

lib/xplane_reduce.py sums the "XLA Ops" line by an op's NAME, which for
everything but a Pallas call is what XLA called a fusion. Since PR 55 the
program opens a named scope around each part of a step
(tpu_engine/utils/tracing.py `STEP_PARTS`, `step_part`), and the profiler
keeps an op's scope path in the op's METADATA, as the stat `tf_op`:
`jit(tick_w256)/while/body/closed_call/attn/read/dot_general:`. A tick's
program is named for its width (`tick_name`), which the "XLA Modules" line
shows as `jit_tick_w256(<program id>)`.

jax.profiler.ProfileData hands out an event's name, start, duration and the
EVENT's stats, none of its metadata's; no `xplane_pb2` is installed. So the
metadata is read here from the file's bytes, by protobuf's wire format: five
messages, the field numbers below (tsl/profiler/protobuf/xplane.proto). A
plane's `lines` are skipped by their length: ProfileData gives the events,
and an event's `name` IS its metadata's name (the whole HLO instruction),
which joins the two. Should two programs of one plane hold the same
instruction text under different parts, the op's time goes to the part
first read; `collisions` counts such names (0 in every cell's trace).

  parts     {path: {"self_s", "flops", "bytes", "ops"}}, every path of
            STEP_PARTS and UNSCOPED: an op's SELF time, as
            xplane_reduce.self_times reckons it, put to the part its path
            names: the vocabulary's components of the path, outermost
            first, whole components only (`attn` then `read`; a transform's
            wrapper as in `vmap(reveal)` is taken off; `attn_like/...` is
            no part). An op the compiler names itself, its path dropped,
            goes to the part COMPILER_NAMED gives it (XLA's own grouped
            product, `ragged-dot-*`, to `moe/experts`). Any other op whose
            path holds no part goes to UNSCOPED: the
            `while` and `conditional` shells, whose self time is what their
            bodies leave; copies and parameter moves XLA adds between
            programs' ops. A fusion that spans two parts goes to the part
            of its root op. `flops` and `bytes` are the metadata's `flops`
            and `bytes_accessed` (the COMPILER's count: a Pallas call
            reports what its cost estimate says or nothing, a padded slot
            counts as work) times the op's runs, for reading by hand: no
            metric divides them by a peak. `ops` is the events counted.
            Seconds are averaged over the planes that ran an op.
  busy_s    as xplane_reduce: the union of the op intervals, averaged over
            the planes that ran an op. sum(parts) = the sum of self times.
  window_s  as xplane_reduce: which file the readers were handed.
  unscoped  [[op, path, self_s]] of the ops under no part, by the
            instruction's name without its number (`%copy-done`) and its
            path without the program (`while/body/dynamic_slice`; "" for
            an op with no path at all), longest first: what UNSCOPED holds.
  longest   [[op, part, self_s]] of the twenty longest ops, named as
            `breakdown.device_ops` names them (xplane_reduce.short_name),
            each with its part: what a fusion of the ledger's list IS.
  modules   {program name without "jit_" and its id: [run durations in ms]}
            from the "XLA Modules" line: every run the line holds. A run
            starts a fraction of a microsecond before its first op, and
            the line holds a run the slice cut at its start by no event
            at all (batch's slice, PR 55: the runs add up to busy less one
            run's remnant), so what it holds are whole runs.

A trace in which no op's path holds a part (a program before PR 55, a CPU
rehearsal) gives None, and every reader then leaves its metric out. With
parts in the trace, a part no op ran under reads 0.0.

By hand: cd benchmarks && python3 -m lib.xplane_scopes <file.xplane.pb>
"""

import os
import re
import struct

from lib.host_phases import newest_xplane
from lib.xplane_reduce import (DEVICE_PLANE_PREFIX, OP_LINE, read_planes,
                               self_times, short_name, union_ns)

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "out")

# tpu_engine/utils/tracing.py STEP_PARTS, spelt here as lib/host_phases.py
# spells the tick's phases: the benchmark names what it reads.
STEP_PARTS = (
    "embed", "plan",
    "attn/qkv", "attn/write", "attn/read", "attn/out",
    "mixer/in", "mixer/chunk", "mixer/step", "mixer/out",
    "mlp",
    "moe/route", "moe/experts", "moe/shared",
    "head",
    "sample", "sample/reveal",
)
UNSCOPED = "unscoped"
MODULE_LINE = "XLA Modules"
PATH_STAT = "tf_op"
COLLIDED = "collided"
# Ops the TPU compiler makes itself and NAMES itself, their scope path
# dropped: XLA expands `lax.ragged_dot` (`ops/moe.py` `grouped_dot`, traced
# under `moe/experts`) into custom calls whose whole path reads
# `ragged-dot-none` / `ragged-dot-metadata` (seen in solve's first traced
# run, PR 55: 59 % of busy stood under no part). A kernel of the repo's own
# keeps its path, as the Pallas calls do, and needs no line here.
COMPILER_NAMED = (("ragged-dot", "moe/experts"),)
# {top-level part: its children}; `attn` alone is no part, `sample` is.
_CHILDREN = {}
for _part in STEP_PARTS:
    _top, _, _child = _part.partition("/")
    _CHILDREN.setdefault(_top, set()).update({_child} if _child else ())
# `vmap(reveal)`, `jvp(vmap(attn))`: a transform wraps the scope's name.
_WRAPPED = re.compile(r"^(?:\w+\()+([^()]+)\)+$")
# `jit_tick_w256(1234)` -> `tick_w256`; `tick_w1`, `tick_w1_r4`,
# `tick_w256`, `spec_w5` -> kind, width.
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
_TICK = re.compile(r"^(tick|spec)_w(\d+)(?:_r\d+)?$")


def compiler_named(path):
    """The part of an op whose path the compiler replaced by a name of its
    own (COMPILER_NAMED), or None."""
    return next((part for prefix, part in COMPILER_NAMED
                 if path.startswith(prefix)), None)


def part_of(path):
    """The part a scope path names, or None: its first component that is a
    top-level part and, where that part has children, the first later
    component that is one of them. `tf_op` ends `:<type>`."""
    names = []
    for component in (path.rpartition(":")[0] or path).split("/"):
        wrapped = _WRAPPED.match(component)
        names.append(wrapped.group(1) if wrapped else component)
    for k, name in enumerate(names):
        if name not in _CHILDREN:
            continue
        child = next((c for c in names[k + 1:] if c in _CHILDREN[name]),
                     None)
        part = name if child is None else f"{name}/{child}"
        return part if part in STEP_PARTS else None
    return None


# -- the wire format ----------------------------------------------------------

def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: an int for a varint,
    the bytes for a fixed or length-delimited field (a memoryview's slice:
    nothing is copied until a string is decoded)."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"wire type {wire} is not in an XSpace")
        yield number, wire, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_entry(entry):
    """The value of a map<int64, message> entry (key 1, value 2)."""
    return next((v for n, _, v in fields(entry) if n == 2), b"")


def _stat_metadata(entry):
    """XStatMetadata: id 1, name 2."""
    ident, name = 0, ""
    for number, _, value in fields(_map_entry(entry)):
        if number == 1:
            ident = value
        elif number == 2:
            name = _text(value)
    return ident, name


def _stat(stat, names):
    """XStat -> (its name, its value): metadata_id 1, double 2, uint64 3,
    int64 4, str 5, bytes 6, ref 7 (a stat metadata's id whose NAME is the
    value). A value of a type not known here reads None."""
    name, out = None, None
    for number, _, value in fields(stat):
        if number == 1:
            name = names.get(value)
        elif number == 2:
            out, = struct.unpack("<d", value)
        elif number in (3, 4):
            out = value
        elif number == 5:
            out = _text(value)
        elif number == 6:
            out = bytes(value)
        elif number == 7:
            out = names.get(value)
    return name, out


def _event_metadata(entry, names):
    """XEventMetadata -> (name, {stat name: value}): name 2, stats 5."""
    name, stats = "", {}
    for number, _, value in fields(_map_entry(entry)):
        if number == 2:
            name = _text(value)
        elif number == 5:
            key, out = _stat(value, names)
            if key is not None:
                stats[key] = out
    return name, stats


def read_op_stats(path, plane_prefix=DEVICE_PLANE_PREFIX):
    """{plane name: {event metadata's name: {stat name: value}}} for every
    plane whose name starts with `plane_prefix`. XSpace.planes 1; XPlane
    name 2, lines 3 (skipped), event_metadata 4, stat_metadata 5. Of two
    metadata with one name (the same instruction text in two programs) the
    first is kept, marked COLLIDED if the other names another part."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, _, plane in fields(space):
        if number != 1:
            continue
        name, events, names = "", [], {}
        for field, _, value in fields(plane):
            if field == 2:
                name = _text(value)
            elif field == 4:
                events.append(value)
            elif field == 5:
                ident, stat_name = _stat_metadata(value)
                names[ident] = stat_name
        if not name.startswith(plane_prefix):
            continue
        ops = out[name] = {}
        for event in events:
            op, stats = _event_metadata(event, names)
            first = ops.setdefault(op, stats)
            if (first is not stats and part_of(first.get(PATH_STAT) or "")
                    != part_of(stats.get(PATH_STAT) or "")):
                first[COLLIDED] = True
    return out


# -- the reduction ------------------------------------------------------------

def module_name(event_name):
    """`jit_tick_w256(1234)` -> `tick_w256`."""
    return _MODULE.match(event_name).group(1)


def tick_width(module):
    """(kind, width) of a tick program's name (`tick_w1_r4` -> ("tick",
    1)), or None for another program."""
    found = _TICK.match(module)
    return (found.group(1), int(found.group(2))) if found else None


def reduce_planes(op_planes, op_stats, module_planes=None):
    """The module doc's dict from read_planes' events of the op line,
    read_op_stats' metadata and read_planes' events of the module line.
    None if no op ran or no op's path holds a part."""
    used = {name: ev for name, ev in op_planes.items() if ev}
    if not used:
        return None
    n = len(used)
    parts = {part: {"self_s": 0.0, "flops": 0.0, "bytes": 0.0, "ops": 0}
             for part in (*STEP_PARTS, UNSCOPED)}
    busy = window = 0.0
    scoped = False
    modules, unscoped, by_op = {}, {}, {}
    for plane, events in used.items():
        stats = op_stats.get(plane, {})
        intervals = [(s, s + d) for _, s, d in events]
        busy += union_ns(intervals)
        window += (max(e for _, e in intervals)
                   - min(s for s, _ in intervals))
        placed = {}      # an instruction's text -> where its runs go
        for name, own in self_times(events):
            if name not in placed:
                meta = stats.get(name, {})
                path = meta.get(PATH_STAT) or ""
                part = part_of(path)
                # Only a path the PROGRAM stated says that the trace holds
                # parts: a parent's `ragged-dot` does not.
                scoped = scoped or part is not None
                part = part or compiler_named(path)
                short = short_name(name)
                placed[name] = (
                    part or UNSCOPED, float(meta.get("flops") or 0),
                    float(meta.get("bytes_accessed") or 0), short,
                    None if part else (short.split()[0],
                                       _without_program(path)))
            part, flops, moved, short, kind = placed[name]
            into = parts[part]
            into["self_s"] += own / 1e9 / n
            into["flops"] += flops / n
            into["bytes"] += moved / n
            into["ops"] += 1
            by_op[short, part] = by_op.get((short, part), 0.0) + own / 1e9 / n
            if kind is not None:
                unscoped[kind] = unscoped.get(kind, 0.0) + own / 1e9 / n
        for name, _, dur in (module_planes or {}).get(plane, ()):
            modules.setdefault(module_name(name), []).append(dur / 1e6)
    if not scoped:
        return None
    return {"planes": n, "busy_s": busy / 1e9 / n,
            "window_s": window / 1e9 / n, "parts": parts,
            "modules": modules,
            "collisions": sum(1 for stats in op_stats.values()
                              for meta in stats.values()
                              if meta.get(COLLIDED)),
            "unscoped": sorted(([op, path, s] for (op, path), s
                                in unscoped.items()), key=lambda r: -r[2]),
            "longest": sorted(([op, part, s] for (op, part), s
                               in by_op.items()), key=lambda r: -r[2])[:20]}


def _without_program(path):
    """`jit(tick_w1)/while/body/add:` -> `while/body/add`; "" for an op
    with no path at all."""
    path = path.rpartition(":")[0] or path
    return path.partition("/")[2] if path.startswith("jit(") else path


def reduce_file(path, device_prefix=DEVICE_PLANE_PREFIX, op_line=OP_LINE,
                module_line=MODULE_LINE):
    return reduce_planes(read_planes(path, device_prefix, op_line),
                         read_op_stats(path, device_prefix),
                         read_planes(path, device_prefix, module_line))


# -- what the readers under layer_metrics/ share ------------------------------

def of_run(run):
    """The reduction a `step.*_busy` or `step.*_run_ms` reader starts from:
    `run["scopes"]` where the run object brings it, else `reduce_file` of
    the newest .xplane.pb under benchmarks/out/*.trace, kept on the run
    object for the next reader (a 3 s slice's file takes seconds to read).
    None where nothing was traced, no op ran, the trace holds no part, or
    the newest file is not the one `run["trace"]` was read from (their
    windows differ)."""
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    if "scopes" not in run:
        path = newest_xplane(OUT)
        run["scopes"] = (reduce_file(path) if path else None) or {}
    reduced = run["scopes"]
    if not reduced or abs(reduced["window_s"] - trace["window_s"]) > 1e-9:
        return None
    return reduced


def busy_share(run, *prefixes):
    """Self seconds under the parts that are one of `prefixes` or lie under
    one (`attn` holds `attn/read`), over busy_s, in percent. None without a
    part in the trace; 0.0 where no op ran under these."""
    scopes = of_run(run)
    if not scopes or not scopes["busy_s"]:
        return None
    seconds = sum(p["self_s"] for name, p in scopes["parts"].items()
                  if any(name == pre or name.startswith(pre + "/")
                         for pre in prefixes))
    return 100.0 * seconds / scopes["busy_s"]


def run_ms(run, wanted):
    """Median run, in ms, of the tick programs whose (kind, width) `wanted`
    accepts; None where no such program ran inside the slice."""
    from lib.metrics import percentile

    scopes = of_run(run)
    if not scopes:
        return None
    runs = [ms for module, durations in scopes["modules"].items()
            if (found := tick_width(module)) and wanted(*found)
            for ms in durations]
    return percentile(runs, 50) if runs else None


def table(reduced):
    """The table by part and the modules' medians, as lines of text."""
    from lib.metrics import percentile

    busy = reduced["busy_s"]
    lines = [f"busy_s {busy:.6f}  window_s {reduced['window_s']:.6f}  "
             f"planes {reduced['planes']}  "
             f"collisions {reduced['collisions']}",
             f"{'part':<14}{'self_s':>10}{'% busy':>8}{'GFLOP':>12}"
             f"{'GB':>10}{'ops':>9}"]
    total = 0.0
    for part, p in reduced["parts"].items():
        total += p["self_s"]
        lines.append(f"{part:<14}{p['self_s']:>10.4f}"
                     f"{100 * p['self_s'] / busy:>8.2f}"
                     f"{p['flops'] / 1e9:>12.1f}{p['bytes'] / 1e9:>10.2f}"
                     f"{p['ops']:>9d}")
    lines.append(f"{'sum':<14}{total:>10.4f}{100 * total / busy:>8.2f}")
    lines.append("the longest ops (op, part, self_s, % busy):")
    for op, part, seconds in reduced["longest"]:
        lines.append(f"  {op:<48} {part:<14} {seconds:>9.4f}"
                     f"{100 * seconds / busy:>7.2f}")
    lines.append("unscoped, its ten longest (op, path, self_s, % busy):")
    for op, path, seconds in reduced["unscoped"][:10]:
        lines.append(f"  {op:<28} {path or '-':<36} {seconds:>9.4f}"
                     f"{100 * seconds / busy:>7.2f}")
    lines.append(f"{'program':<24}{'runs':>6}{'median ms':>12}"
                 f"{'sum s':>10}")
    for module, runs in sorted(reduced["modules"].items()):
        lines.append(f"{module:<24}{len(runs):>6d}"
                     f"{percentile(runs, 50):>12.3f}"
                     f"{sum(runs) / 1e3:>10.4f}")
    return lines


if __name__ == "__main__":
    import sys
    import time

    began = time.monotonic()
    found = reduce_file(sys.argv[1])
    print("\n".join(table(found)) if found else
          "no op of this trace carries a part of STEP_PARTS")
    print(f"read in {time.monotonic() - began:.2f} s")
    sys.exit(0 if found else 1)
