"""Rows an expert took in a tick in which it took any: the window
difference of the lanes' `stats()["moe"]` pairs that formed a row HERE
(`assignments_held` on a lane that holds a share of the experts and counts
it, else `assignments`) over `experts_touched` ((layer, expert) pairs with
at least one row, summed over ticks; on a lane that holds a share only its
own experts can be touched). At a handful of rows the grouped product
streams an expert's matrices for a few matrix-vector products and is
weight-bound; the chip's ridge is near 240 rows. Layer: expert layer.
Moves tokens_per_s."""


def compute(run):
    rows = touched = 0
    for node, after in run["stats_after"].items():
        before = run["stats_before"][node]
        if "moe" not in after or "moe" not in before:
            continue
        key = ("assignments_held" if "assignments_held" in after["moe"]
               else "assignments")
        rows += after["moe"][key] - before["moe"][key]
        touched += (after["moe"]["experts_touched"]
                    - before["moe"]["experts_touched"])
    return rows / touched if touched else None
