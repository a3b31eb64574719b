"""Shared by the benchmark's tests: where the benchmark lives, importable."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
DATA = os.path.join(ROOT, "tests", "benchmarks", "data")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
