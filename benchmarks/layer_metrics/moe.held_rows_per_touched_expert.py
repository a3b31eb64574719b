"""Rows a HELD expert took in a tick in which it took any: the window
difference of the lanes' `stats()["moe"]` `assignments_held` over
`experts_touched` ((layer, expert) pairs with at least one row, summed over
ticks; on a lane that holds a share only its own experts can be touched).
At ~4 rows the grouped product streams an expert's 14.2 MB for four
matrix-vector products and is weight-bound: the chip's ridge is near 240
rows, and the deployment's experts would see twice these. Layer: expert
layer. Moves tokens_per_s."""


def compute(run):
    held = touched = 0
    for node, after in run["stats_after"].items():
        before = run["stats_before"][node]
        if ("assignments_held" not in after.get("moe", {})
                or "moe" not in before):
            continue
        held += (after["moe"]["assignments_held"]
                 - before["moe"]["assignments_held"])
        touched += (after["moe"]["experts_touched"]
                    - before["moe"]["experts_touched"])
    return held / touched if touched else None
