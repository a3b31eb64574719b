"""Share of the window's mixed ticks whose sampler took its filtered body
(the whole-vocabulary sort), in percent: the difference of the lanes'
`mixed.sample_filtered_ticks` over the difference of `mixed.ticks`. The
scheduler counts a tick's body from the controls of the rows whose sample
is real, as the compiled step chooses it on the device. 0 says the cell's
traffic never reaches the sort; a program that does not count the bodies
reads nothing. Layer: step function. Moves tokens_per_s."""


def compute(run):
    ticks = filtered = 0
    for node, after in run["stats_after"].items():
        before = run["stats_before"].get(node, {}).get("mixed")
        mixed = after.get("mixed")
        if not mixed or not before or "sample_filtered_ticks" not in mixed:
            continue
        ticks += mixed["ticks"] - before["ticks"]
        filtered += (mixed["sample_filtered_ticks"]
                     - before["sample_filtered_ticks"])
    return 100.0 * filtered / ticks if ticks else None
