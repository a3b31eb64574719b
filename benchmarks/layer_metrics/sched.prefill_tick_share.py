"""Share of the window's scheduler ticks that carried prefill (`mixed_step`
spans with width > 1), in percent: every such tick stalls the decoding rows
for a wide step. Layer: scheduler tick. Moves itl_p95_ms."""

from lib.metrics import lane_spans


def compute(run):
    widths = [s["attrs"]["width"] for s in lane_spans(run, "mixed_step")]
    if not widths:
        return None
    return 100.0 * sum(w > 1 for w in widths) / len(widths)
