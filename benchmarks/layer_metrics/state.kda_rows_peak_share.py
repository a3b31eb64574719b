"""Peak share of the state pool's rows that were held, in percent, on a
lane whose rows own a channel-gated recurrent state beside a LATENT chain:
`rows_peak` / `rows_total` of `stats()["state_pool"]` at the window's end,
the fullest lane (`state.rows_peak_share`'s counter, for the cell that
metric's list does not name: a lane that reports `kv_bytes_held` over a
pool whose blocks hold a latent). A row costs the same 8.98 MB at token 1
and token 11,000: this is slots in use. Layer: state pool. Moves
tokens_per_s."""

from lib.roofline_kimi_linear import holds_latent


def compute(run):
    shares = []
    for stats in run["stats_after"].values():
        pool, blocks = stats.get("state_pool"), stats.get("kv_pool") or {}
        if (pool and pool.get("rows_total") and "rows_peak" in pool
                and holds_latent(blocks)):
            shares.append(pool["rows_peak"] / pool["rows_total"])
    return 100.0 * max(shares) if shares else None
