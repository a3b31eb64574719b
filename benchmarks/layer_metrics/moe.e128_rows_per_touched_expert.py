"""Rows an expert of a layer of 128 whole experts took in a tick in which it
took any: the window difference of the lanes' `stats()["moe"]`
`assignments` over `experts_touched` ((layer, expert) pairs with at least
one row, summed over ticks), on a run whose configuration decodes by blocks
(every expert is held, so every pair forms a row). A tick of 64 runs of 4
tokens brings 2,048 pairs a layer: 16 rows for an expert's 9.4 MB, 32 beside
a 256-token chunk, where the chip's ridge is near 240. Layer: expert layer.
Moves tokens_per_s."""

from lib.roofline_sdar import counted


def compute(run):
    pairs = touched = 0
    for before, after in counted(run, "moe", "experts_touched"):
        pairs += after["assignments"] - before["assignments"]
        touched += after["experts_touched"] - before["experts_touched"]
    return pairs / touched if touched else None
