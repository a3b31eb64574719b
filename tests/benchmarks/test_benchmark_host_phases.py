"""lib/host_phases.py and the `device.idle_host` reader on a small recorded
trace: four ticks of one jitted matmul on the CPU, each marked by the
program's own `TickClock` (admit, begin, 0.8 ms of "forming", dispatch,
wait, 0.3 ms of "applying", end) under jax.profiler with the Python tracer
off. On the CPU the ops sit on a line of the host plane, so the plane and
line are passed as they are to xplane_reduce; the arithmetic is the same
for a device plane."""

import importlib.util
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH  # noqa: E402

from lib import host_phases as H  # noqa: E402
from lib import xplane_reduce as X  # noqa: E402

TRACE = os.path.join(BENCH, "tests", "data", "cpu_annotated_ticks.xplane.pb")
OLD_TRACE = os.path.join(BENCH, "tests", "data", "cpu_four_ticks.xplane.pb")
CPU = dict(device_prefix="/host:CPU", op_line="tf_XLAPjRtCpuClient")


@pytest.fixture(scope="module")
def annotations():
    return H.read_annotations(TRACE)


@pytest.fixture(scope="module")
def ops():
    return X.read_planes(TRACE, "/host:CPU", "tf_XLAPjRtCpuClient")


def _inside_by_sampling(gaps, intervals, step=100.0):
    """Length of gaps inside intervals by stepping through the gaps every
    100 ns: another algorithm than the library's clipping and union."""
    total = 0.0
    for g0, g1 in gaps:
        t = g0 + step / 2
        while t < g1:
            if any(s <= t < e for s, e in intervals):
                total += step
            t += step
    return total


def test_fixture_holds_four_ticks_with_their_phases(annotations):
    assert set(annotations) == set(H.ANNOTATIONS)
    assert len(annotations["tick"]) == 4
    assert len(annotations["loop.admit"]) == 5     # one after the last tick
    for phase in H.TICK_PHASES:
        assert len(annotations[phase]) == 4
    for k, (start, end) in enumerate(annotations["tick"]):
        kids = [annotations[p][k] for p in H.TICK_PHASES]
        assert start <= kids[0][0] and kids[-1][1] <= end
        for a, b in zip(kids, kids[1:]):
            assert a[1] <= b[0] < a[1] + 25000     # contiguous to 25 us
    # The recording slept 0.8 ms in `form` and 0.3 ms in `apply`.
    assert all(e - s > 800e3 for s, e in annotations["tick.form"])
    assert all(e - s > 300e3 for s, e in annotations["tick.apply"])


def test_idle_inside_host_phases_is_pinned(annotations, ops):
    out = H.reduce_planes(ops, annotations)
    intervals = [(s, s + d) for _, s, d in ops["/host:CPU"]]
    gaps = X.idle_gaps(intervals)
    assert out["planes"] == 1 and out["ticks"] == 4
    assert out["window_s"] == pytest.approx(7317269e-9, rel=1e-9)
    assert out["idle_s"] == pytest.approx(6212827e-9, rel=1e-9)
    assert out["idle_host_s"] == pytest.approx(5589209e-9, rel=1e-9)
    host = [span for name in H.HOST_PHASES for span in annotations[name]]
    assert out["idle_host_s"] * 1e9 == pytest.approx(
        _inside_by_sampling(gaps, host), abs=100.0 * 2 * len(gaps))
    # The phases do not overlap, so idle splits over them; what is left
    # over lies between two annotations (microseconds a boundary).
    by_phase = out["idle_by_phase"]
    assert set(by_phase) == {*H.TICK_PHASES, "loop.admit"}
    assert out["idle_host_s"] == pytest.approx(
        sum(by_phase[name] for name in H.HOST_PHASES))
    assert sum(by_phase.values()) <= out["idle_s"]
    assert out["idle_s"] - sum(by_phase.values()) < 100e-6
    # Most of this fixture's idle time is the sleeps in form and apply.
    assert by_phase["tick.form"] > 4 * 0.6e-3
    assert by_phase["tick.apply"] > 4 * 0.25e-3
    assert out["idle_host_s"] <= out["idle_s"]


def test_reduce_file_takes_the_reducer_s_plane_and_line_arguments():
    out = H.reduce_file(TRACE, **CPU)
    assert out["idle_host_s"] == pytest.approx(5589209e-9, rel=1e-9)
    # No plane of that name (the default: a TPU's), or a trace recorded
    # before the program marked its ticks: nothing, and no exception.
    assert H.reduce_file(TRACE) is None
    assert H.reduce_file(OLD_TRACE, **CPU) is None
    assert H.read_annotations(OLD_TRACE) == {}


@pytest.mark.parametrize("gaps,intervals,inside", [
    ([(0, 10)], [(2, 5)], 3),
    ([(0, 10)], [(2, 5), (4, 8)], 6),             # overlapping phases
    ([(0, 10), (20, 30)], [(5, 25)], 10),
    ([(0, 10)], [(10, 20)], 0),
    ([(0, 10)], [], 0),
    ([], [(0, 10)], 0),
])
def test_overlap(gaps, intervals, inside):
    assert H.overlap_ns(gaps, intervals) == inside


def test_newest_xplane_is_the_newest_of_any_cell(tmp_path):
    assert H.newest_xplane(str(tmp_path)) is None
    for age, cell in ((200, "a.chat"), (100, "b.batch")):
        d = tmp_path / f"{cell}.trace" / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        f = d / "vm.xplane.pb"
        f.write_bytes(b"")
        os.utime(f, (1e9 - age, 1e9 - age))
    assert H.newest_xplane(str(tmp_path)).endswith(
        os.path.join("b.batch.trace", "plugins", "profile", "t",
                     "vm.xplane.pb"))


def test_the_reader_is_the_share_of_the_slice():
    path = os.path.join(BENCH, "layer_metrics", "device.idle_host.py")
    spec = importlib.util.spec_from_file_location("idle_host_reader", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    phases = H.reduce_file(TRACE, **CPU)
    run = {"trace": {"busy_s": phases["window_s"] - phases["idle_s"],
                     "window_s": phases["window_s"]},
           "host_phases": phases}
    value = module.compute(run)
    assert value == pytest.approx(100 * 5589209 / 7317269)
    idle = 100 * (1 - run["trace"]["busy_s"] / run["trace"]["window_s"])
    assert 0 < value <= idle
    assert module.compute(dict(run, trace=None)) is None
    # Phases reduced from another file than `trace` (a stale one under
    # benchmarks/out) are not put beside it.
    other = dict(run["trace"], window_s=run["trace"]["window_s"] + 1e-3)
    assert module.compute(dict(run, trace=other)) is None
