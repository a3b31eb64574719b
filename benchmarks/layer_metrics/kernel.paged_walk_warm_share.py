"""Share of the paged read's live query tiles whose first group of blocks
was already in flight when the tile began, in percent: `walk_warm_tiles`
over `walk_live_tiles`, summed over the `mixed_step` spans that carry
both. The scheduler counts them on the host where it forms a tick
(`tpu_engine/ops/paged_attention.py` `walk_counts`, the kernel's own
rules): a tile is warm where the grid step right before it is live too,
since that step's last fold started the fetch (PR 48); a cold tile waits
for its first group with nothing to fold. 97 says 31 of a decode tick's 32
rows follow a live row; a lane with one or two live rows of 32 reads low,
and its read is a small share of its step. A program that does not count
(before PR 48), a family whose step is its own, and a window without a
tick read nothing. Layer: kernels. Moves tokens_per_s."""

from lib.metrics import lane_spans


def compute(run):
    live = warm = 0
    for span in lane_spans(run, "mixed_step"):
        attrs = span["attrs"]
        if "walk_live_tiles" in attrs and "walk_warm_tiles" in attrs:
            live += attrs["walk_live_tiles"]
            warm += attrs["walk_warm_tiles"]
    return 100.0 * warm / live if live else None
