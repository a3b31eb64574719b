"""Rows an expert took in a tick in which it took any: the window
difference of the lanes' `stats()["moe"]` `assignments` over
`experts_touched` ((layer, expert) pairs with at least one row, summed over
ticks). At a handful of rows the grouped product streams an expert's 17 MB
for a few matrix-vector products and is weight-bound; the chip's ridge is
near 240 rows. Layer: expert layer. Moves tokens_per_s."""


def compute(run):
    assignments = touched = 0
    for node, after in run["stats_after"].items():
        before = run["stats_before"][node]
        if "moe" not in after or "moe" not in before:
            continue
        assignments += (after["moe"]["assignments"]
                        - before["moe"]["assignments"])
        touched += (after["moe"]["experts_touched"]
                    - before["moe"]["experts_touched"])
    return assignments / touched if touched else None
