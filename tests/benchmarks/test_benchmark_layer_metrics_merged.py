"""A merged per-layer reader is ONE file listed for every cell whose step
runs its kind of kernel, pool or counter (PR 68). Here, for every (merged
reader, cell on its list) in BENCHMARK.json: a test file beside this one
names the cell (`CELL`, or `CELLS`), holds a made-up run at that cell's
configuration (`RUN`) and the values computed from it by hand (`WANT`), and
the reader returns them to 1e-9. So no cell is appended to a merged list
without a pin at its own sizes, which is where a copy a family had its pin
before; and the configuration that comes next brings its pins as a file of
its own, `test_benchmark_layer_metrics_<family>.py`, found by its `CELL`
(`bench_paths.pins`): nothing here names a cell or a file."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import load_benchmark, pins, reader  # noqa: E402

MERGED = ["kernel.paged_attn_busy", "kernel.paged_attn_roofline",
          "kernel.moe_experts_busy", "kernel.moe_experts_roofline",
          "kernel.state_step_busy", "kernel.state_step_roofline",
          "kernel.state_chunk_busy", "kernel.state_chunk_roofline",
          "moe.rows_per_touched_expert", "moe.expert_load_imbalance",
          "state.rows_peak_share", "state.bytes_over_cache_bytes",
          "kv.blocks_peak_share", "step.decode_ms"]
LISTS = {m["name"]: m["workloads"] for m in load_benchmark()["per_layer"]
         if m["name"] in MERGED}
PINS = pins()


def test_every_merged_reader_is_listed_and_every_cell_has_its_pins():
    assert sorted(LISTS) == sorted(MERGED)
    unpinned = {cell for cells in LISTS.values() for cell in cells} \
        - set(PINS)
    assert not unpinned, "no test_benchmark_layer_metrics_*.py names " \
        f"{sorted(unpinned)} as its CELL"


@pytest.mark.parametrize("name, cell", [
    (name, cell) for name in MERGED for cell in LISTS.get(name, [])
    if cell in PINS])
def test_a_merged_reader_reads_the_family_s_hand_computed_value(name, cell):
    module = PINS[cell]
    assert name in module.WANT, f"{module.__name__}.py pins no {name}"
    assert reader(name)(module.RUN) == pytest.approx(module.WANT[name],
                                                      rel=1e-9)
