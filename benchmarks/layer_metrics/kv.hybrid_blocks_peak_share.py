"""Peak share of the K/V pool's blocks that were held during the window, in
percent, on a lane whose pool holds the full-attention layers alone beside
a state pool: (blocks_total - blocks_free) / blocks_total, the fullest lane
of the fullest sample (`kv.blocks_peak_share`'s counter, for the cell that
metric's list does not name). The full layers keep every block of a row, so
this is what bounds the contexts the lane can hold. Layer: KV pool. Moves
tokens_per_s."""


def compute(run):
    peak = None
    for sample in run["pool_samples"]:
        for pool in sample["kv_pool"].values():
            if not pool or "state_bytes_held" not in pool:
                continue
            held = 1.0 - pool["blocks_free"] / pool["blocks_total"]
            peak = held if peak is None else max(peak, held)
    return None if peak is None else 100.0 * peak
