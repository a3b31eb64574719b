"""Bytes of recurrent state held over bytes of K/V held, at the K/V pool's
fullest sample, on a lane whose rows own both in EVERY layer:
`state_bytes_held` / `kv_bytes_held` of the pool's counters (sampled every
half second, one reading of the two), of a pool whose blocks hold this
configuration's 4 KV heads. Where olmo-hybrid-7b-12l.digest reads 0.07 and
kimi-linear-48b-a3b-5l.reason 2.6, a row here holds 25.5 MB of state beside
12,288 B a token: the fixed state is the larger part until 2 k tokens, the
lane's limit. Layer: state pool. Moves tokens_per_s."""

from lib.roofline_falcon_h1 import holds_ssd


def compute(run):
    fullest = None
    for sample in run["pool_samples"]:
        for pool in sample["kv_pool"].values():
            if (not holds_ssd(pool, run["config"])
                    or not pool.get("kv_bytes_held")):
                continue
            if fullest is None or (pool["kv_bytes_held"]
                                   > fullest["kv_bytes_held"]):
                fullest = pool
    if fullest is None:
        return None
    return fullest["state_bytes_held"] / fullest["kv_bytes_held"]
