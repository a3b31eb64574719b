"""Peak share of the full-attention layers' pool that was held during the
window, in percent, on a lane whose rows hold blocks of two kinds:
`full_blocks_held` / `blocks_total`, the fullest lane of the fullest
sample (the pool's counters are sampled every half second). The full
layers keep every block of a row, so this is what bounds the contexts the
lane can hold. Layer: KV pool. Moves tokens_per_s."""


def compute(run):
    peak = None
    for sample in run["pool_samples"]:
        for pool in sample["kv_pool"].values():
            if not pool or "full_blocks_held" not in pool:
                continue
            held = pool["full_blocks_held"] / pool["blocks_total"]
            peak = held if peak is None else max(peak, held)
    return None if peak is None else 100.0 * peak
