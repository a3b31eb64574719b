"""Share of the device's busy time spent sorting in a routed model under
greedy traffic, in percent: the trace's operations whose name carries
`sort`, over the union of all operation intervals. No tick's sampler sorts (the
load generator sends temperature 0: `step.sampler_sort_tick_share` reads 0
in every cell), so every sort is an expert layer's: the router's top 22 of
512 scores a token and the tick's 5,632 (token, expert) pairs ordered by
expert, five layers a tick. 2.75 times the pairs a token of the widest
router before it. Layer: expert layer. Moves tokens_per_s."""

from lib.roofline_moe_mla import busy_share


def compute(run):
    return busy_share(run, "sort")
