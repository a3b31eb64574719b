"""Peak share of the K/V pool's blocks that were held during the window, in
percent, on a lane where ONE layer of eleven keeps a block chain beside five
that keep a state row: (blocks_total - blocks_free) / blocks_total, the
fullest lane of the fullest sample (`kv.ssd_blocks_peak_share`'s counter, of
a pool whose blocks hold this configuration's 2 KV heads). The pool is sized
for 64 rows at the traffic's longest context (8192 + 512 tokens and a chunk
more); the rows' contexts reach a fraction of it. Layer: KV pool. Moves
tokens_per_s."""

from lib.roofline_nemotron_h import holds_ssd


def compute(run):
    peak = None
    for sample in run["pool_samples"]:
        for pool in sample["kv_pool"].values():
            if not holds_ssd(pool, run["config"]):
                continue
            held = 1.0 - pool["blocks_free"] / pool["blocks_total"]
            peak = held if peak is None else max(peak, held)
    return None if peak is None else 100.0 * peak
