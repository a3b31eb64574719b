"""run.py --trace 1 end to end on the CPU with the per-layer metrics of PR 25
listed (its own cell list: BENCHMARK.tracing.test.json, the same test
configurations and traffic files as BENCHMARK.test.json under cell names of
its own, so that the two rehearsals can run at once without sharing a file
under benchmarks/out): every reader of the program's new spans and counters
returns a value, and what only a device trace gives is left out."""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH, DATA, ROOT  # noqa: E402

RUN = os.path.join(BENCH, "run.py")
CELLS = os.path.join(DATA, "BENCHMARK.tracing.test.json")
NEW_FROM_SPANS_AND_COUNTERS = {
    "sched.budget_wait_ms", "lane.slot_wait_ms", "lane.ttft_p50_ms",
    "step.compiles"}


def test_the_tracing_cell_list_only_appends_to_the_tests_own():
    with open(CELLS) as f:
        tracing = json.load(f)
    with open(os.path.join(DATA, "BENCHMARK.test.json")) as f:
        base = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    n = len(base["per_layer"])
    renamed = json.loads(json.dumps(base).replace('"small.', '"tracing.'))
    assert tracing["per_layer"][:n] == renamed["per_layer"]
    assert {k: v for k, v in tracing.items() if k != "per_layer"} == {
        k: v for k, v in renamed.items() if k != "per_layer"}
    added = {m["name"]: m for m in tracing["per_layer"][n:]}
    assert set(added) == NEW_FROM_SPANS_AND_COUNTERS | {"device.idle_host"}
    listed = {m["name"]: m for m in real["per_layer"]}
    for name, m in added.items():
        for key in ("unit", "better", "source", "layer", "moves"):
            assert m[key] == listed[name][key], (name, key)


def test_traced_run_reads_every_new_span_and_counter_metric():
    env = dict(os.environ, TPU_ENGINE_PLATFORM="cpu")
    proc = subprocess.run(
        [sys.executable, RUN, "--benchmark-file", CELLS, "--workload",
         "tracing.closed", "--seed", str(2**31 + 5), "--seconds", "2",
         "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert NEW_FROM_SPANS_AND_COUNTERS <= set(got)
    # The slice was traced, on a CPU: no device plane, so no device number,
    # and the annotations alone do not make one.
    assert not {"device.idle", "device.idle_host"} & set(got)
    assert "device.idle_host found nothing to read" in proc.stderr
    # The window is warm: the step programs were compiled in set-up.
    assert got["step.compiles"] == {"value": 0, "unit": "compilations"}
    # A tick's spans are read (the three readers of the phases' marks,
    # `sched.host_gap_ms` and `step.*_device_ms`, went with PR 68).
    assert 0 < got["step.decode_ms"]["value"]
    assert 0 < got["step.prefill_ms"]["value"]
    assert got["lane.slot_wait_ms"]["value"] > 0
    assert got["sched.budget_wait_ms"]["value"] >= 0
    # The lane's first token lies inside the client's.
    assert 0 < got["lane.ttft_p50_ms"]["value"]
    # The traced slice does hold the program's annotations.
    from lib import host_phases, xplane_reduce
    path = xplane_reduce.find_xplane(
        os.path.join(BENCH, "out", "tracing.closed.trace"))
    marks = host_phases.read_annotations(path)
    assert set(marks) == set(host_phases.ANNOTATIONS)
    assert len(marks["tick"]) > 10
