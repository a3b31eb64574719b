"""Share of the device's busy time spent sorting in a routed model under
greedy traffic, in percent: the trace's operations whose name carries
`sort`, over the union of all operation intervals, on a run whose
configuration states a latent for its experts. No tick's sampler sorts (the
load generator sends temperature 0: `step.sampler_sort_tick_share` reads 0
in every cell), so every sort is an expert layer's: the router's top 22 of
512 scores a token and the tick's 5,632 (token, expert) pairs ordered by
expert, five layers a tick. 2.75 times the pairs a token of the widest
router before it. Layer: expert layer. Moves tokens_per_s."""

from lib.roofline_nemotron_h import SORT, busy_share, states_a_latent


def compute(run):
    return busy_share(run, SORT) if states_a_latent(run) else None
