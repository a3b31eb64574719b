"""Host→device arrays the scheduler made while it formed a tick, a tick of
the window: the difference of the lanes' `mixed.form_transfers`
(`ContinuousGenerator.stats()["mixed"]`, counted where each array is made)
over the difference of `mixed.ticks`. A transfer costs the host a quarter
of a millisecond on a v5e whatever it holds, so this count times that is
the floor of `sched.form_ms`: 1 says a tick's per-row inputs went as one
control block, 15 to 18 that each went alone (before PR 47). A program that
does not count them reads nothing, as does a window without a tick. Layer:
scheduler tick. Moves itl_p95_ms."""


def compute(run):
    ticks = transfers = 0
    for node, after in run["stats_after"].items():
        before = run["stats_before"].get(node, {}).get("mixed")
        mixed = after.get("mixed")
        if not mixed or not before or "form_transfers" not in mixed:
            continue
        ticks += mixed["ticks"] - before["ticks"]
        transfers += (mixed["form_transfers"]
                      - before.get("form_transfers", 0))
    return transfers / ticks if ticks else None
