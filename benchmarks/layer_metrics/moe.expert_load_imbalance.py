"""How unevenly the router loaded the experts a lane holds over the window:
in each expert layer the busiest held expert's rows over the mean held
expert's, averaged over the expert layers (1.0 is perfect balance). From the
window difference of the lanes' `stats()["moe"]["rows_by_expert"]`, which
the step counts (valid slots only), over the experts the configuration says
the lane holds (`sizes(run["config"])["experts"]["held"]`,
lib/roofline_sizes.py: all of them, or `held_first`, `held_count`; the
others' rows are another chip's and read 0 here, which would count as
imbalance). Layer: expert layer. Moves tokens_per_s: the busiest expert's
row tiles are the grouped product's longest group."""

from lib.roofline_sizes import sizes


def compute(run):
    size = sizes(run["config"])["experts"]
    if not size:
        return None
    first, count = size["held"]
    ratios = []
    for node, after in run["stats_after"].items():
        before = run["stats_before"][node]
        if "moe" not in after or "moe" not in before:
            continue
        for rows_a, rows_b in zip(after["moe"]["rows_by_expert"],
                                  before["moe"]["rows_by_expert"]):
            rows = [a - b for a, b in zip(rows_a, rows_b)][first:first + count]
            if sum(rows):
                ratios.append(max(rows) * len(rows) / sum(rows))
    return sum(ratios) / len(ratios) if ratios else None
