"""Rows a HELD expert of a LatentMoE layer took in a tick in which it took
any: the window difference of the lanes' `stats()["moe"]`
`assignments_held` over `experts_touched` ((layer, expert) pairs with at
least one row, summed over ticks), on a run whose configuration states a
latent (`moe.held_rows_per_touched_expert`'s counters, for the cell that
metric's list does not name). A tick's 256 tokens bring 5,632 pairs of which
a quarter form rows over 128 held experts: ~11 rows for an expert's 11.0 MB,
where the chip's ridge is near 240 and the deployment's four chips' ticks
would bring 44. Layer: expert layer. Moves tokens_per_s."""

from lib.roofline_nemotron_h import latent_moe_lanes


def compute(run):
    held = touched = 0
    for before, after in latent_moe_lanes(run):
        held += after["assignments_held"] - before["assignments_held"]
        touched += after["experts_touched"] - before["experts_touched"]
    return held / touched if touched else None
