"""Share of the traced slice in which no operation ran on the device AND at
least one stream handler's thread was delivering a token event (inside a
`stream.deliver` annotation, on any line of the trace's host plane), in
percent of the slice: idle gaps within the union of those annotations over
all threads (lib/host_threads.py). Beside `device.idle_loop`: most of it
says the device waits while the handlers run; little of it says the
handlers are not what the scheduler's loop waits for. Not a part of
`device.idle_host` (a handler delivers inside `tick.dispatch` and
`tick.wait` too), but never more than `device.idle`. A trace without the
annotation (a program before PR 42) reads nothing. Layer: device. Moves
tokens_per_s.

As `device.idle_host`: read from the newest .xplane.pb under
benchmarks/out/*.trace, unless the run object brings the reduction as
`run["host_threads"]`."""

from lib import host_threads


def compute(run):
    threads = host_threads.of_run(run, "host_threads", host_threads)
    if not threads or host_threads.STREAM not in threads["by_name"]:
        return None
    return (100.0 * threads["by_name"][host_threads.STREAM]["idle_s"]
            / run["trace"]["window_s"])
