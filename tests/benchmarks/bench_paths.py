"""Shared by the benchmark's tests: where the benchmark lives, importable,
what BENCHMARK.json lists for a cell, and a rehearsal's list of cells."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
DATA = os.path.join(ROOT, "tests", "benchmarks", "data")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def load_benchmark():
    """BENCHMARK.json as it is committed."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def listed(bench, cell, kind="per_layer"):
    """The metrics of `kind` that `bench` lists for `cell`, in its order."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def read_without_a_device(bench, cell):
    """The per-layer metrics `bench` lists for `cell` that a rehearsal on the
    CPU prints: every reader of spans and counters. What a device trace
    gives, and the device's memory, are left out there and said so."""
    return {m["name"] for m in listed(bench, cell)
            if m["source"] != "device_trace"} - {"device.hbm_peak_gb"}


def rehearsal_cells(directory, family, cell):
    """The list of cells a rehearsal runs `benchmarks/run.py` on, written
    under `directory`; its path. data/BENCHMARK.<family>.test.json holds the
    cut-down configuration and its workload; what is measured there is what
    BENCHMARK.json lists for `cell` today, `end_to_end` and `per_layer`, each
    entry without its `workloads` (every workload of the file reports it).
    run.py looks for traffic and references beside the list it is given."""
    with open(os.path.join(DATA, f"BENCHMARK.{family}.test.json")) as f:
        cells = json.load(f)
    real = load_benchmark()
    for kind in ("end_to_end", "per_layer"):
        cells[kind] = [{k: v for k, v in m.items() if k != "workloads"}
                       for m in listed(real, cell, kind)]
    for beside in ("traffic", "references"):
        os.symlink(os.path.join(DATA, beside),
                   os.path.join(str(directory), beside))
    path = os.path.join(str(directory), f"BENCHMARK.{family}.test.json")
    with open(path, "w") as f:
        json.dump(cells, f, indent=1)
    return path


def pins():
    """{cell: the module that pins the per-layer readers at that cell's
    configuration}: every test_benchmark_layer_metrics*.py beside this file
    that names its `CELL` (or `CELLS`) holds a made-up run `RUN` and the
    values computed by hand from it, `WANT`. A configuration that comes
    later brings such a file of its own and is found here by that alone."""
    import glob
    import importlib

    found = {}
    for path in sorted(glob.glob(os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "test_benchmark_layer_metrics*.py"))):
        module = importlib.import_module(os.path.basename(path)[:-3])
        for cell in getattr(module, "CELLS", [getattr(module, "CELL", None)]):
            if cell:
                found[cell] = module
    return found


def reader(metric):
    """`compute` of benchmarks/layer_metrics/<metric>.py."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + metric.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", metric + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute
