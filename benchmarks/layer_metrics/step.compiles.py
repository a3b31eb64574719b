"""XLA compilations inside the window: the difference of the lanes'
`compile.count` (a process-wide count, so every lane of one process reports
the same: the largest difference is taken, not the sum). A warm window
compiles nothing and reads 0; a program that does not count reads nothing.
Layer: step function. Moves itl_p95_ms (a compile inside the window is one
tick of seconds)."""


def compute(run):
    counts = [after["compile"]["count"] - before["compile"]["count"]
              for node, after in run["stats_after"].items()
              for before in (run["stats_before"].get(node, {}),)
              if "compile" in after and "compile" in before]
    return max(counts) if counts else None
