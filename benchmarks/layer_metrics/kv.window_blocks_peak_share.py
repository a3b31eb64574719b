"""Peak share of the sliding-window layers' pool that was held during the
window, in percent, on a lane whose rows hold blocks of two kinds:
`window_blocks_held` / `window_blocks_total`, the fullest lane of the
fullest sample (the pool's counters are sampled every half second). The
pool is sized so that it can never run out: a slot's share is the window,
a chunk and a block of tokens. It reads near 100 where every row is far
past the window, and low where the contexts are short of it and the pool's
memory is held for nothing. Layer: KV pool. Moves tokens_per_s."""


def compute(run):
    peak = None
    for sample in run["pool_samples"]:
        for pool in sample["kv_pool"].values():
            if not pool or not pool.get("window_blocks_total"):
                continue
            held = pool["window_blocks_held"] / pool["window_blocks_total"]
            peak = held if peak is None else max(peak, held)
    return None if peak is None else 100.0 * peak
