"""Decoding rows per scheduler tick over the window: the difference of the
lanes' `mixed.decode_tokens` over the difference of `mixed.ticks`.
Layer: scheduler tick. Moves tokens_per_s."""


def compute(run):
    ticks = tokens = 0
    for node, after in run["stats_after"].items():
        before = run["stats_before"][node]
        if "mixed" not in after:
            continue
        ticks += after["mixed"]["ticks"] - before["mixed"]["ticks"]
        tokens += (after["mixed"]["decode_tokens"]
                   - before["mixed"]["decode_tokens"])
    return tokens / ticks if ticks else None
