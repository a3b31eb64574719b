"""Bytes of recurrent state held over bytes of LATENT held, at the latent
pool's fullest sample, on a lane whose rows own both: `state_bytes_held` /
`kv_bytes_held` of the pool's counters (sampled every half second, one
reading of the two), of a pool whose blocks hold a latent. Where
olmo-hybrid-7b-12l.digest reads 0.068 (46 KB of K/V a token outgrow 21 MB
of state within 500 tokens), a row here holds 8.98 MB of state beside 1,280
B a token: the fixed state is the larger part until 7 k tokens. Layer: state
pool. Moves tokens_per_s."""

from lib.roofline_kimi_linear import holds_latent


def compute(run):
    fullest = None
    for sample in run["pool_samples"]:
        for pool in sample["kv_pool"].values():
            if (not pool or not holds_latent(pool)
                    or not pool.get("kv_bytes_held")):
                continue
            if fullest is None or (pool["kv_bytes_held"]
                                   > fullest["kv_bytes_held"]):
                fullest = pool
    if fullest is None:
        return None
    return fullest["state_bytes_held"] / fullest["kv_bytes_held"]
