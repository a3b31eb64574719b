"""Peak share of the state pool's rows that were held, in percent, on a
lane whose rows own a Mamba-2 state in 5 of 11 layers beside a K/V chain in
one: `rows_peak` / `rows_total` of `stats()["state_pool"]` at the window's
end, the fullest lane (`state.ssd_rows_peak_share`'s counter, for the cell
that metric's list does not name: a lane whose blocks hold this
configuration's 2 KV heads). A row costs the same 21.6 MB at token 1 and
token 8704: this is slots in use. Layer: state pool. Moves tokens_per_s."""

from lib.roofline_nemotron_h import holds_ssd


def compute(run):
    shares = []
    for stats in run["stats_after"].values():
        pool = stats.get("state_pool")
        if (pool and pool.get("rows_total") and "rows_peak" in pool
                and holds_ssd(stats.get("kv_pool"), run["config"])):
            shares.append(pool["rows_peak"] / pool["rows_total"])
    return 100.0 * max(shares) if shares else None
