"""The new cell's metrics: `lfm2-24b-a2b-9l.assist` arrived when the
per-layer list was FULL (128 of 128 entries), so it brings no reader of its
own and is appended to the lists of the nine accepted readers by part and by
run that find something in its step (not `step.decode_run_ms`: nearly
every tick of the cell carries a chunk, and a traced slice holds no width-1
run; not `step.mixer_chunk_busy`: nothing runs under that part) (tests/benchmarks/
test_benchmark_reference_lfm2.py holds the lists); here the cell's small
double is rehearsed through run.py on the CPU, and the readers by part are
held on a step that opens no op under a part."""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH, DATA, ROOT  # noqa: E402

from lib import xplane_scopes  # noqa: E402

CELL = "lfm2-24b-a2b-9l.assist"
LISTED = ["step.attn_busy", "step.attn_read_busy", "step.ffn_busy",
          "step.moe_experts_busy", "step.mixer_busy", "step.head_busy",
          "step.sample_busy", "step.unscoped_busy", "step.chunk_run_ms"]
# What this file's readers are: nothing new (conftest.py takes the names a
# test_benchmark_layer_metrics_*.py lists off the pinned file's).
WANT = []


def test_the_rehearsal_lists_every_metric_of_the_new_cell():
    """run.py --trace 1 on the CPU at the small size, a cell list of its own
    with the ten keyless per-layer metrics and the nine accepted readers by
    part and by run: the span and counter metrics print, what only a device
    trace gives is left out."""
    cells = os.path.join(DATA, "BENCHMARK.lfm2.test.json")
    with open(cells) as f:
        listed = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    want = [m["name"] for m in real["per_layer"]
            if CELL in m.get("workloads", [CELL])]
    assert [m["name"] for m in listed["per_layer"]] == want
    assert len(want) == 19 and want[10:] == LISTED
    assert [m["name"] for m in listed["end_to_end"]] == [
        m["name"] for m in real["end_to_end"]
        if CELL in m.get("workloads", [CELL])] == [
        "itl_p95_ms", "tokens_per_s", "setup_s"]
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"),
         "--benchmark-file", cells, "--workload", "lfm2.closed",
         "--seed", str(2**31 + 60), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=dict(os.environ, TPU_ENGINE_PLATFORM="cpu"),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    device_only = {"device.idle", "device.idle_host", "device.hbm_peak_gb",
                   *LISTED}
    assert set(got) == set(want) - device_only
    assert got["step.compiles"] == {"value": 0, "unit": "compilations"}


def _reader(name):
    import importlib.util

    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "under_test_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


def _run(parts, modules):
    """A run object whose trace was reduced to `parts` (self seconds) and
    `modules` (runs in ms), one second busy of a two-second slice."""
    every = {part: {"self_s": 0.0, "flops": 0.0, "bytes": 0.0, "ops": 0}
             for part in (*xplane_scopes.STEP_PARTS, xplane_scopes.UNSCOPED)}
    for part, seconds in parts.items():
        every[part] = dict(every[part], self_s=seconds, ops=1)
    return {"trace": {"busy_s": 1.0, "window_s": 2.0},
            "scopes": {"busy_s": 1.0, "window_s": 2.0, "parts": every,
                       "modules": modules}}


def test_the_listed_readers_read_the_cell_s_step_on_a_hand_made_trace():
    """The parts this family's step opens (tests/test_lfm2.py holds them):
    the experts 0.78 s of one busy second, the conv operators' three parts
    0.04 together, attention 0.07 of which the read 0.03; `mixer/chunk`
    holds no op, so `step.mixer_chunk_busy` reads 0.0 and the cell is NOT on
    its list."""
    run = _run({"moe/experts": 0.78, "moe/route": 0.02, "mlp": 0.01,
                "mixer/in": 0.02, "mixer/step": 0.01, "mixer/out": 0.01,
                "attn/qkv": 0.02, "attn/write": 0.01, "attn/read": 0.03,
                "attn/out": 0.01, "head": 0.05, "sample": 0.01,
                xplane_scopes.UNSCOPED: 0.02},
               {"tick_w1": [17.0, 19.0, 18.0],
                "tick_w256": [30.0, 34.0, 32.0]})
    want = {"step.attn_busy": 7.0, "step.attn_read_busy": 3.0,
            "step.ffn_busy": 81.0, "step.moe_experts_busy": 78.0,
            "step.mixer_busy": 4.0, "step.head_busy": 5.0,
            "step.sample_busy": 1.0, "step.unscoped_busy": 2.0,
            "step.chunk_run_ms": 32.0}
    assert sorted(want) == sorted(LISTED)
    for name, value in want.items():
        assert abs(_reader(name)(run) - value) < 1e-9, name
    assert _reader("step.mixer_chunk_busy")(run) == 0.0


def test_the_listed_readers_are_null_proof():
    """No op under a part reads 0.0 and not an error; no trace at all, and a
    slice in which no tick program ran, read nothing."""
    empty = _run({}, {})
    for name in LISTED:
        got = _reader(name)(empty)
        assert got is None if name.endswith("_run_ms") else got == 0.0, name
    untraced = {"trace": None}
    assert all(_reader(name)(untraced) is None for name in LISTED)
