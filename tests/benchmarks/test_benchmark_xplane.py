"""The trace reducer on a small recorded trace (four ticks of one jitted
matmul captured on the CPU: the arithmetic is the same for any plane)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH  # noqa: E402

from lib import xplane_reduce as X  # noqa: E402

TRACE = os.path.join(BENCH, "tests", "data", "cpu_four_ticks.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    return X.read_planes(TRACE, plane_prefix="/host:CPU",
                         line_prefix="tf_XLAPjRtCpuClient")


def _sweep_union(intervals):
    """Union length by counting open intervals at every edge: another
    algorithm than the reducer's merge."""
    edges = sorted([(s, 1) for s, _ in intervals]
                   + [(e, -1) for _, e in intervals],
                   key=lambda x: (x[0], -x[1]))
    total, depth, last = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            total += t - last
        depth, last = depth + step, t
    return total


def test_fixture_holds_the_four_ticks(planes):
    assert list(planes) == ["/host:CPU"]
    events = planes["/host:CPU"]
    assert len(events) == 36
    assert sum(name == "dot_general.1" for name, _, _ in events) == 4


def test_busy_window_and_op_sums_are_pinned(planes):
    out = X.reduce_planes(planes)
    events = planes["/host:CPU"]
    intervals = [(s, s + d) for _, s, d in events]
    assert out["planes"] == 1
    assert out["window_s"] == pytest.approx(16514713e-9, rel=1e-9)
    assert out["busy_s"] == pytest.approx(_sweep_union(intervals) / 1e9)
    # Self time: each op's four durations less the "end: <op>" marker that
    # the CPU runtime nests at its end.
    assert out["op_seconds"]["dot_general.1"] == pytest.approx(
        (348380 - (1249 + 402 + 916 + 425)) * 1e-9)
    assert out["op_seconds"]["wrapped_reduce-window"] == pytest.approx(
        (12918 + 12651 + 12725 + 12198 - (176 + 276 + 187 + 231)) * 1e-9)
    assert sum(out["op_seconds"].values()) == pytest.approx(out["busy_s"])
    assert out["device_ops"][0][0] == "dot_general.1"
    assert 0 < out["busy_s"] < out["window_s"]


def test_idle_is_the_window_less_busy_and_gaps_lie_between_ticks(planes):
    out = X.reduce_planes(planes, top_gaps=3)
    gaps = out["idle_gaps"]
    assert [label for label, _ in gaps] == [X.UNATTRIBUTED] * 3
    # Four ticks about 5.4 ms apart: the three long gaps are between them.
    assert all(0.004 < g < 0.007 for _, g in gaps)
    all_gaps = X.reduce_planes(planes, top_gaps=10**6)["idle_gaps"]
    assert sum(g for _, g in all_gaps) == pytest.approx(
        out["window_s"] - out["busy_s"])


@pytest.mark.parametrize("intervals,union,gaps", [
    ([(0, 10)], 10, []),
    ([(0, 10), (5, 15)], 15, []),
    ([(0, 10), (20, 30)], 20, [(10, 20)]),
    ([(20, 30), (0, 10), (2, 4), (10, 12)], 22, [(12, 20)]),
    ([], 0, []),
])
def test_union_and_gaps(intervals, union, gaps):
    assert X.union_ns(intervals) == union
    assert X.idle_gaps(intervals) == gaps


def test_two_planes_average_and_an_empty_plane_is_not_counted():
    planes = {"/device:TPU:0": [("a", 0.0, 10.0), ("b", 20.0, 10.0)],
              "/device:TPU:1": [("a", 0.0, 30.0)],
              "/device:TPU:2": []}
    out = X.reduce_planes(planes)
    assert out["planes"] == 2
    assert out["busy_s"] == pytest.approx(25e-9)
    assert out["window_s"] == pytest.approx(30e-9)
    assert out["op_seconds"] == {"a": pytest.approx(20e-9),
                                 "b": pytest.approx(5e-9)}


def test_no_device_plane_reads_as_nothing():
    assert X.read_planes(TRACE) == {}
    out = X.reduce_planes({})
    assert out["busy_s"] == 0.0 and out["device_ops"] == []


def test_describe_lists_planes_lines_and_top_events():
    listing = X.describe(TRACE)
    host = next(p for p in listing if p["plane"] == "/host:CPU")
    line = next(ln for ln in host["lines"]
                if ln["line"].startswith("tf_XLAPjRtCpuClient"))
    assert line["events"] == 36 and line["top"][0][0] == "dot_general.1"


def test_self_time_takes_nested_events_from_their_parent():
    events = [("while", 0.0, 100.0), ("a", 10.0, 30.0), ("b", 50.0, 40.0),
              ("b.inner", 60.0, 10.0), ("c", 120.0, 5.0)]
    assert sorted(X.self_times(events)) == sorted(
        [("while", 30.0), ("a", 30.0), ("b", 30.0), ("b.inner", 10.0),
         ("c", 5.0)])
    out = X.reduce_planes({"/device:TPU:0": events})
    assert sum(out["op_seconds"].values()) == pytest.approx(out["busy_s"])


@pytest.mark.parametrize("name,want", [
    ("%fusion.163 = bf16[32,256,1280]{2,1,0:T(8,128)(2,1)} fusion(f32[3] %x)",
     "%fusion bf16[32,256,1280]"),
    ("%while.3 = (s32[]{:T(128)}, bf16[32,1,1280]{2,0,1}) while(%tuple.55)",
     "%while (tuple)"),
    ("%p.1 = s32[] parameter(0)", "%p s32[]"),
    ("dot_general.1", "dot_general.1"),
])
def test_short_names(name, want):
    assert X.short_name(name) == want
