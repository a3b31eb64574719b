"""Bytes of recurrent state held over bytes of K/V held, at the K/V pool's
fullest sample, on a lane whose rows own a state in five layers and a chain
in one: `state_bytes_held` / `kv_bytes_held` of the pool's counters (sampled
every half second, one reading of the two), of a pool whose blocks hold this
configuration's 2 KV heads. A row holds 21.6 MB of state beside 1,024 B a
token: the fixed state is the larger part until 21 k tokens, past the lane's
limit (falcon-h1-34b-6l.converse reads 3.4, its chain 12 times as wide).
Layer: state pool. Moves tokens_per_s."""

from lib.roofline_nemotron_h import holds_ssd


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
