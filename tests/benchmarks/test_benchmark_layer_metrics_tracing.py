"""Folded into test_benchmark_layer_metrics.py by PR 27: the pins of PR 25's
eight readers, their `RUN` and their cases are cases of that file's
parametrised tests now, each still counted.

This file stays, empty of tests, for one reason: the
`pytest_collection_modifyitems` hook in tests/conftest.py imports `WANT`
from here at every collection, and a `benchmark` PR may change nothing
outside the benchmark's own directories. The PR that may touch
tests/conftest.py deletes the hook and this file together (PERF.md,
section 7)."""

WANT = {}
