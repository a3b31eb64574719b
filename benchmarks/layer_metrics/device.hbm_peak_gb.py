"""Peak device memory in use on the fullest chip after the window
(`device.memory_stats()["peak_bytes_in_use"]`), in GB of 1e9 bytes.
Layer: device. Moves tokens_per_s (memory freed is room for a larger pool
and more rows)."""


def compute(run):
    peak = run["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
