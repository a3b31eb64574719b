"""Tokens a sliding-window layer holds over tokens a full-attention layer
holds, a ratio, at the sample where the full layers held most (the pool's
counters are sampled every half second): `window_blocks_held` over
`full_blocks_held`, blocks of the same size, one kind a pool. 1.0 means the
window layers gave nothing back; with contexts far over the window it
reads near (window + a chunk) / context. Layer: KV pool. Moves
tokens_per_s."""


def compute(run):
    best = None
    for sample in run["pool_samples"]:
        for pool in sample["kv_pool"].values():
            if not pool or not pool.get("full_blocks_held"):
                continue
            if best is None or pool["full_blocks_held"] > best[0]:
                best = (pool["full_blocks_held"], pool["window_blocks_held"])
    return None if best is None else best[1] / best[0]
