"""Share of the device's busy time spent sorting, in percent: the trace's
operations whose name carries `sort`, over the union of all operation
intervals. A step sorts where its sampler filters (`runtime/generator.py`
`_sample`'s third body: a row's whole vocabulary, for top_p / top_k /
min_p) and, in a routed model, where the expert layer orders a tick's
(token, expert) pairs, which is small beside it. A trace with no such
operation reads 0.0, not nothing: that no tick sorted is the reading.
Layer: step function. Moves tokens_per_s."""

# XLA names the instruction `%sort`; its result here is a tuple (values
# and indices), so the trace's row is "%sort (tuple)".
PATTERN = "sort"


def compute(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if PATTERN in name.lower())
    return 100.0 * seconds / trace["busy_s"]
