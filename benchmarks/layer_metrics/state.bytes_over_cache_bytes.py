"""Bytes of fixed state held over bytes of cache held (K and V, or a
latent), at the block pool's fullest sample, on a lane whose rows own both:
`state_bytes_held` / `kv_bytes_held` of the pool's counters (sampled every
half second, one reading of the two). How much of what the rows hold no
longer grows with their length: at full depth a hybrid's point. Layer:
state pool. Moves tokens_per_s."""


def compute(run):
    fullest = None
    for sample in run["pool_samples"]:
        for pool in sample["kv_pool"].values():
            if not pool or not pool.get("kv_bytes_held"):
                continue
            if fullest is None or (pool["kv_bytes_held"]
                                   > fullest["kv_bytes_held"]):
                fullest = pool
    if fullest is None:
        return None
    return fullest["state_bytes_held"] / fullest["kv_bytes_held"]
