"""Peak share of the KV pool's blocks that were held during the window, in
percent: (blocks_total - blocks_free) / blocks_total, the fullest lane of
the fullest sample (the pool's counters are sampled every half second;
blocks the radix tree retains count as held). Layer: KV pool. Moves
tokens_per_s."""


def compute(run):
    peak = None
    for sample in run["pool_samples"]:
        for pool in sample["kv_pool"].values():
            if not pool or not pool.get("blocks_total"):
                continue
            held = 1.0 - pool["blocks_free"] / pool["blocks_total"]
            peak = held if peak is None else max(peak, held)
    return None if peak is None else 100.0 * peak
