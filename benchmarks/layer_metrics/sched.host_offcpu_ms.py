"""Mean time a tick's scheduler thread spent OFF the CPU in the three
phases in which it makes no call that blocks by design: the mean
`form_offcpu_us`, plus the mean `apply_offcpu_us`, plus the mean
`loop_offcpu_us` of the `mixed_step` spans that carry each (each the
phase's wall time less the thread's own CPU time, `time.thread_time_ns`,
never below 0), in milliseconds. Time there is time the thread waited for
the interpreter lock (held by a stream handler, or by the collector) or for
a core. Beside `sched.loop_ms` it says whether the loop's time is the
loop's own statements or somebody else's; `front.stream_cpu_ms_per_tick` is
what the handlers did meanwhile.

Means of each attr, not the median of a tick's sum as the other `sched.*`
readers take: the program reads the thread's CPU clock on one loop iteration
in eight (a read is a system call, 45 us on the v5e hosts), so a span
carries the attrs of the phases that fell into such an iteration or none;
and that clock ticks in steps of 10 ms there (PERF.md, PR 42), so one phase
of a few milliseconds reads 0 or 10 ms of CPU time: the program carries
the CPU time beyond a stretch's wall time to the phase's next stretches, so
that the attrs' mean over a window is the phases' mean wall time less CPU
time. A program without the marks (before PR 42) reads nothing. Layer:
scheduler tick. Moves tokens_per_s."""

from lib.metrics import lane_spans

PARTS = ("form_offcpu_us", "apply_offcpu_us", "loop_offcpu_us")


def compute(run):
    ticks = [s["attrs"] for s in lane_spans(run, "mixed_step")]
    read = [[a[k] for a in ticks if k in a] for k in PARTS]
    if not all(read):
        return None
    return sum(sum(values) / len(values) for values in read) / 1e3
