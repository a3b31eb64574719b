"""Milliseconds a second the interpreter's garbage collector paused the
process inside the window: the difference of the lanes' `gc.seconds` (a
process-wide sum of the pauses of every generation, so every lane of one
process reports the same: the largest difference is taken, not the sum)
over the window's seconds. The collector runs with the interpreter lock
held, so the scheduler's thread waits every pause out whoever set it off;
a tick that held one carries `gc_us`. No collection reads 0; a program that
does not count them (before PR 42) reads nothing. Layer: step function.
Moves itl_p95_ms (a full collection inside a tick is one long gap)."""


def compute(run):
    paused = [after["gc"]["seconds"] - before["gc"]["seconds"]
              for node, after in run["stats_after"].items()
              for before in (run["stats_before"].get(node, {}),)
              if "gc" in after and "gc" in before]
    return 1e3 * max(paused) / run["seconds"] if paused else None
