"""Peak share of the K/V pool's blocks that were held during the window, in
percent, on a block-decoding lane: (blocks_total - blocks_free) /
blocks_total, the fullest lane of the fullest sample. The pool is sized for
64 rows at the traffic's longest context (2048 + 256 tokens); the rows'
contexts reach a fraction of it, and a row's last block of 4 tokens is
rewritten by every pass where an autoregressive row appends a token. Layer:
KV pool. Moves tokens_per_s."""

from lib.roofline_sdar import decodes_by_blocks


def compute(run):
    if not decodes_by_blocks(run):
        return None
    peak = None
    for sample in run["pool_samples"]:
        for pool in sample["kv_pool"].values():
            if not pool:
                continue
            held = 1.0 - pool["blocks_free"] / pool["blocks_total"]
            peak = held if peak is None else max(peak, held)
    return None if peak is None else 100.0 * peak
