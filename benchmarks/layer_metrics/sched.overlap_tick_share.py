"""Share of the window's mixed ticks that were enqueued while the tick
before's results were not yet read, in percent: the difference of the
lanes' `mixed.overlapped_ticks` over the difference of `mixed.ticks`. Such
a tick is already queued when the running one ends, so the host's work
between two ticks costs the device nothing. 0 says the lane read every
tick's results before it formed the next (it must where an export waits,
a row is parked, or the lane speculates); a program that does not count
them reads nothing. Layer: scheduler tick. Moves tokens_per_s."""


def compute(run):
    ticks = overlapped = 0
    for node, after in run["stats_after"].items():
        before = run["stats_before"].get(node, {}).get("mixed")
        mixed = after.get("mixed")
        if not mixed or not before or "overlapped_ticks" not in mixed:
            continue
        ticks += mixed["ticks"] - before["ticks"]
        overlapped += (mixed["overlapped_ticks"]
                       - before.get("overlapped_ticks", 0))
    return 100.0 * overlapped / ticks if ticks else None
