"""Mean time a request spent in ticks that gave its row no prompt tokens
while it was prefilling: the `starved_us` attr of the window's `prefill`
spans (the token budget goes to the lowest-numbered prefilling row), in
milliseconds. The mean and not the median, because the finding is a few
requests that wait for tens of seconds among many that wait for none.
Layer: scheduler tick. Moves ttft_mean_ms."""

from lib.metrics import lane_spans


def compute(run):
    starved = [s["attrs"]["starved_us"] for s in lane_spans(run, "prefill")
               if "starved_us" in (s.get("attrs") or {})]
    return sum(starved) / len(starved) / 1e3 if starved else None
