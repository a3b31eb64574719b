"""Share of the traced slice in which no operation ran on the device, in
percent: 1 - (union of operation intervals) / slice, averaged over the
chips used. Layer: device. Moves tokens_per_s."""


def compute(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
