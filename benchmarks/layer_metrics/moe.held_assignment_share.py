"""Of the (token, expert) pairs the lanes routed in the window, the share
that went to an expert the lane HOLDS and so formed a row there, in
percent: the window difference of `stats()["moe"]` `assignments_held` over
`assignments`. A lane that holds half of a balanced router's experts reads
about 50; far from the held share of the experts, the router's bias or the
traffic favours one chip's experts. Layer: expert layer. Moves
tokens_per_s."""


def compute(run):
    routed = held = 0
    for node, after in run["stats_after"].items():
        before = run["stats_before"][node].get("moe")
        moe = after.get("moe")
        if not moe or not before or "assignments_held" not in moe:
            continue
        routed += moe["assignments"] - before["assignments"]
        held += moe["assignments_held"] - before["assignments_held"]
    return 100.0 * held / routed if routed else None
