"""How unevenly the router loaded the experts a lane HOLDS over the window:
in each expert layer the busiest held expert's rows over the mean held
expert's, averaged over the expert layers (1.0 is perfect balance). From the
window difference of the lanes' `stats()["moe"]["rows_by_expert"]` over the
held experts alone (`kwargs.held_first`, `held_count`: the others' rows are
another chip's and read 0 here, which `moe.expert_load_imbalance` would
count as imbalance), on a run whose configuration states a latent. Layer:
expert layer. Moves tokens_per_s: the busiest expert's row tiles are the
grouped product's longest group."""

from lib.roofline_nemotron_h import latent_moe_lanes, sizes


def compute(run):
    ratios = []
    for before, after in latent_moe_lanes(run):
        first, count = sizes(run["config"])["held"]
        for rows_a, rows_b in zip(after["rows_by_expert"],
                                  before["rows_by_expert"]):
            rows = [a - b for a, b in zip(rows_a, rows_b)][first:first + count]
            if sum(rows):
                ratios.append(max(rows) * len(rows) / sum(rows))
    return sum(ratios) / len(ratios) if ratios else None
