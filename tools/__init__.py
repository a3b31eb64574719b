# Makes tools/ importable so diagnostics.py can reuse the
# fault-injection harness's launch/stream helpers instead of re-deriving
# them. The scripts themselves still run standalone (python3 tools/...).
