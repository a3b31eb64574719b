#!/usr/bin/env python3
"""A set of runs of one cell, one after the other, and each metric's spread.

    python3 benchmarks/sets.py --workload <cell> --seeds 11,12,13 \\
        [--seconds 51] [--trace 0] [--out chiprun_out/<file>.jsonl]

How a bound is derived (PERF.md, section 2): two such sets of one tree, a
seed a run, and for each metric the wider set's spread, the distance between
the first and the third quartile (`statistics.quantiles(values, n=4)`) as a
share of the median. This file starts run.py once a seed, as the check does,
keeps every run's last line with its seed, and prints each metric's readings,
median and spread. Stdlib only: it never touches the chip its children use.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values):
    """(median, IQR / median) of three readings or more."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return median, (q3 - q1) / median


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", default="51")
    ap.add_argument("--trace", default="0", choices=("0", "1"))
    ap.add_argument("--out", help="append every run's last line here")
    args = ap.parse_args(argv)

    readings, bad = {}, 0
    for seed in args.seeds.split(","):
        began = time.monotonic()
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             args.workload, "--seed", seed, "--seconds", args.seconds,
             "--trace", args.trace], capture_output=True, text=True)
        lines = proc.stdout.strip().splitlines()
        line = json.loads(lines[-1]) if proc.returncode == 0 and lines else {}
        sound = bool(line.get("correct")) and line.get("failed") == 0
        bad += not sound
        kept = {"workload": args.workload, "seed": int(seed),
                "trace": int(args.trace), "rc": proc.returncode,
                "took_s": round(time.monotonic() - began, 1), "line": line}
        if not sound:
            kept["stderr"] = proc.stderr[-2000:]
            kept["stdout"] = proc.stdout[-2000:]
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(kept) + "\n")
        print(json.dumps({k: v for k, v in kept.items() if k != "line"}),
              flush=True)
        for name, m in line.get("metrics", {}).items():
            readings.setdefault(name, []).append(m["value"])
    for name, values in readings.items():
        out = {"metric": name, "values": values}
        if len(values) >= 3:
            out["median"], out["spread"] = spread(values)
        print(json.dumps(out), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
