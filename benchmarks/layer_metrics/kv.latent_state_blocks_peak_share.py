"""Peak share of the latent pool's blocks that were held during the window,
in percent, on a lane whose pool holds the MLA layers alone beside a state
pool: (blocks_total - blocks_free) / blocks_total, the fullest lane of the
fullest sample (`kv.hybrid_blocks_peak_share`'s counter, of a pool whose
blocks hold a latent). The pool is sized for 128 rows at the traffic's
longest context (11,264 tokens); the rows' contexts reach a fraction of it.
Layer: KV pool. Moves tokens_per_s."""

from lib.roofline_kimi_linear import holds_latent


def compute(run):
    peak = None
    for sample in run["pool_samples"]:
        for pool in sample["kv_pool"].values():
            if (not pool or "state_bytes_held" not in pool
                    or not holds_latent(pool)):
                continue
            held = 1.0 - pool["blocks_free"] / pool["blocks_total"]
            peak = held if peak is None else max(peak, held)
    return None if peak is None else 100.0 * peak
