"""How unevenly a soft-max router loaded 128 whole experts over the window:
in each expert layer the busiest expert's rows over the mean expert's,
averaged over the layers (1.0 is perfect balance). From the window
difference of the lanes' `stats()["moe"]["rows_by_expert"]`, on a run whose
configuration decodes by blocks. Layer: expert layer. Moves tokens_per_s:
the busiest expert's row tiles are the grouped product's longest group."""

from lib.roofline_sdar import counted


def compute(run):
    ratios = []
    for before, after in counted(run, "moe", "rows_by_expert"):
        for rows_a, rows_b in zip(after["rows_by_expert"],
                                  before["rows_by_expert"]):
            rows = [a - b for a, b in zip(rows_a, rows_b)]
            if sum(rows):
                ratios.append(max(rows) * len(rows) / sum(rows))
    return sum(ratios) / len(ratios) if ratios else None
