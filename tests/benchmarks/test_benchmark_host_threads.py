"""lib/host_threads.py and the `device.idle_stream` reader on a made-up set
of planes: one device plane with three ops (and a second for the average),
a host plane's annotations as `read_annotations` hands them over. The
reading of a file is tried on the recorded trace lib/host_phases.py's test
uses (recorded before PR 42: it holds `tick.*` and `loop.admit`, none of
this library's own names)."""

import importlib.util
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH  # noqa: E402

from lib import host_phases as H  # noqa: E402
from lib import host_threads as T  # noqa: E402

TRACE = os.path.join(BENCH, "tests", "data", "cpu_annotated_ticks.xplane.pb")
CPU = dict(device_prefix="/host:CPU", op_line="tf_XLAPjRtCpuClient")

# Ops at [0, 10), [20, 30), [50, 60): idle (10, 20) and (30, 50), 30 of 60.
DEVICE = {"/device:TPU:0": [("%op", 0.0, 10.0), ("%op", 20.0, 10.0),
                            ("%op", 50.0, 10.0)]}
# Three deliveries on two threads, two of them overlapping; one part of the
# loop. Union of the deliveries: (5, 25) and (40, 45).
ANNOTATIONS = {T.STREAM: [(5.0, 12.0), (11.0, 25.0), (40.0, 45.0)],
               "loop.admit.admit": [(31.0, 33.0)]}


def _reader():
    path = os.path.join(BENCH, "layer_metrics", "device.idle_stream.py")
    spec = importlib.util.spec_from_file_location("idle_stream_reader", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


def test_names_are_the_programs():
    sys.path.insert(0, os.path.dirname(BENCH))
    from tpu_engine.utils import tracing

    assert T.LOOP_PARTS == tuple("loop.admit." + p
                                 for p in tracing.LOOP_PARTS)
    assert T.NAMES == (T.STREAM, *T.LOOP_PARTS)
    assert not set(T.NAMES) & set(H.ANNOTATIONS)


def test_sum_union_and_idle_inside_by_name():
    out = T.reduce_planes(DEVICE, ANNOTATIONS)
    assert (out["planes"], out["window_s"], out["idle_s"]) == (
        1, pytest.approx(60e-9), pytest.approx(30e-9))
    stream = out["by_name"][T.STREAM]
    assert stream["events"] == 3
    assert stream["sum_s"] == pytest.approx((7 + 14 + 5) * 1e-9)
    assert stream["union_s"] == pytest.approx((20 + 5) * 1e-9)
    # Idle inside the union: all of (10, 20), and (40, 45) of (30, 50).
    assert stream["idle_s"] == pytest.approx((10 + 5) * 1e-9)
    part = out["by_name"]["loop.admit.admit"]
    assert (part["events"], part["idle_s"]) == (1, pytest.approx(2e-9))
    assert stream["idle_s"] <= out["idle_s"]
    assert stream["union_s"] <= stream["sum_s"]


def test_planes_are_averaged_and_an_idle_plane_is_left_out():
    two = dict(DEVICE, **{"/device:TPU:1": [("%op", 0.0, 60.0)],
                          "/device:TPU:2": []})
    out = T.reduce_planes(two, ANNOTATIONS)
    assert out["planes"] == 2
    assert out["window_s"] == pytest.approx(60e-9)
    assert out["idle_s"] == pytest.approx(15e-9)
    assert out["by_name"][T.STREAM]["idle_s"] == pytest.approx(7.5e-9)
    # What the threads did does not depend on the device planes.
    assert out["by_name"][T.STREAM]["union_s"] == pytest.approx(25e-9)


def test_nothing_to_read_is_an_empty_set_or_nothing():
    assert T.reduce_planes(DEVICE, {})["by_name"] == {}
    assert T.reduce_planes({"/device:TPU:0": []}, ANNOTATIONS) is None
    assert T.reduce_planes({}, ANNOTATIONS) is None


def test_a_file_is_read_over_every_line_of_the_host_plane_by_name():
    # The program's names of PR 25, asked for by name: as host_phases reads.
    names = ("tick.form", "loop.admit")
    got = T.read_annotations(TRACE, names)
    want = H.read_annotations(TRACE)
    assert got == {name: want[name] for name in names}
    # This library's own names: the recording is older than they are.
    assert T.read_annotations(TRACE) == {}
    out = T.reduce_file(TRACE, **CPU)
    assert out["by_name"] == {} and out["planes"] == 1
    phases = H.reduce_file(TRACE, **CPU)
    assert out["window_s"] == pytest.approx(phases["window_s"], rel=1e-12)
    assert out["idle_s"] == pytest.approx(phases["idle_s"], rel=1e-12)
    # ... and idle inside `loop.admit` is what host_phases makes of it.
    named = T.reduce_file(TRACE, names=names, **CPU)
    assert named["by_name"]["loop.admit"]["idle_s"] == pytest.approx(
        phases["idle_by_phase"]["loop.admit"], rel=1e-9)
    assert named["by_name"]["loop.admit"]["events"] == 5
    assert T.reduce_file(TRACE) is None          # no TPU plane in it


def test_the_reader_is_the_share_of_the_slice():
    compute = _reader()
    threads = T.reduce_planes(DEVICE, ANNOTATIONS)
    run = {"trace": {"busy_s": 30e-9, "window_s": 60e-9},
           "host_threads": threads}
    assert compute(run) == pytest.approx(25.0)
    idle = 100 * (1 - run["trace"]["busy_s"] / run["trace"]["window_s"])
    assert 0 < compute(run) <= idle
    # No handler delivered inside the slice, but the program marks them.
    quiet = T.reduce_planes(DEVICE, {T.STREAM: [(0.0, 5.0)]})
    assert compute(dict(run, host_threads=quiet)) == 0.0
    # A program that does not (the parent's): nothing, and no exception.
    older = T.reduce_planes(DEVICE, {"loop.admit.admit": [(31.0, 33.0)]})
    assert compute(dict(run, host_threads=older)) is None
    assert compute(dict(run, host_threads={})) is None
    assert compute(dict(run, trace=None)) is None
    assert compute(dict(run, trace={"busy_s": 0.0, "window_s": 0.0})) is None
    # A reduction of another file than `trace` is not put beside it.
    other = dict(run["trace"], window_s=61e-9)
    assert compute(dict(run, trace=other)) is None
