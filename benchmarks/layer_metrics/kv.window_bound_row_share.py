"""Of the rows the window's ticks fed, the share whose first new column lay
at or past the sliding window, in percent: the `mixed_step` spans'
`rows_past_window` over their `rows_fed`, summed over the window. Such a
row's window layers read `window` columns whatever its context and give a
block back every block of tokens; a row short of the window reads all it
has and gives nothing back. It says whether a cell works the mechanism:
near 0 the window layers are full-attention layers with a second pool.
Layer: KV pool. Moves tokens_per_s."""

from lib.metrics import lane_spans


def compute(run):
    past = fed = 0
    for span in lane_spans(run, "mixed_step"):
        attrs = span.get("attrs") or {}
        if "rows_past_window" in attrs:
            past += attrs["rows_past_window"]
            fed += attrs["rows_fed"]
    return 100.0 * past / fed if fed else None
