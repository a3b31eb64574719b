"""Peak share of the K/V pool's blocks that were held during the window, in
percent, on a lane whose pool is DEEPER than its model (a plane a (pass,
layer): a block of 16 tokens is 25 MB, and the pool's 320 blocks are every
row's worst case): (blocks_total - blocks_free) / blocks_total, the fullest
lane of the fullest sample (`kv.blocks_peak_share`'s counter, for the cell
that metric's list does not name). Read on a lane whose `stats()["mixed"]`
carries `kv_planes`; nothing elsewhere. Layer: KV pool. Moves
tokens_per_s."""


def compute(run):
    looped = {node for node, stats in run["stats_after"].items()
              if stats.get("mixed", {}).get("kv_planes")}
    peak = None
    for sample in run["pool_samples"]:
        for node, pool in sample["kv_pool"].items():
            if not pool or node not in looped:
                continue
            held = 1.0 - pool["blocks_free"] / pool["blocks_total"]
            peak = held if peak is None else max(peak, held)
    return None if peak is None else 100.0 * peak
