"""A dialect only the tests name, found beside their benchmark file and not
in benchmarks/references/: the way a reference reaches run.py before its PR
is merged. The equations are benchmarks/references/mamba2.py's."""

from references.mamba2 import forward  # noqa: F401
