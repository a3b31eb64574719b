"""How unevenly the router loaded the experts over the window: in each
expert layer the busiest expert's rows over the mean expert's, averaged
over the expert layers (1.0 is perfect balance). From the window
difference of the lanes' `stats()["moe"]["rows_by_expert"]`, which the
step counts (valid slots only). Layer: expert layer. Moves tokens_per_s:
the busiest expert's row tiles are the grouped product's longest group."""


def compute(run):
    ratios = []
    for node, after in run["stats_after"].items():
        before = run["stats_before"][node]
        if "moe" not in after or "moe" not in before:
            continue
        for rows_a, rows_b in zip(after["moe"]["rows_by_expert"],
                                  before["moe"]["rows_by_expert"]):
            rows = [a - b for a, b in zip(rows_a, rows_b)]
            if sum(rows):
                ratios.append(max(rows) * len(rows) / sum(rows))
    return sum(ratios) / len(ratios) if ratios else None
