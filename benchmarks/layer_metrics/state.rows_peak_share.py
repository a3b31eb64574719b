"""Peak share of the state pool's rows that were held, in percent, on a
lane whose rows own a fixed state (a recurrence's, or a short convolution's
tail) beside their block chain: `rows_peak` / `rows_total` of
`stats()["state_pool"]` at the window's end (the pool keeps its own peak;
the null row is in neither number), the fullest lane. A row costs the same
bytes at token 1 and token 8000, so this is slots in use, not context held.
Layer: state pool. Moves tokens_per_s."""


def compute(run):
    shares = [pool["rows_peak"] / pool["rows_total"]
              for pool in (stats.get("state_pool")
                           for stats in run["stats_after"].values())
              if pool and pool.get("rows_total") and "rows_peak" in pool]
    return 100.0 * max(shares) if shares else None
