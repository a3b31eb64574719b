"""Bench-harness failure story.

A run that dies mid-way (the driver kills a hung process and records only
rc=1) must not discard what it measured: bench.py records each completed
sub-measurement to a run-stamped BENCH_partial.json immediately and
attaches the partials to the error JSON line, so a hang after scenario 1
still ships scenario 1's numbers. What it must never do is carry on
without the chip: a failed device probe ends the run non-zero, and no
number from the CPU is written into a device metric's field. The
reference harness (/root/reference/benchmark.py:54-76) has no failure
story at all — a crashed run prints nothing.
"""

import contextlib
import io
import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


@pytest.fixture(autouse=True)
def _isolate_partial(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "_PARTIAL_PATH",
                        str(tmp_path / "BENCH_partial.json"))
    bench._PARTIAL.clear()
    yield
    bench._PARTIAL.clear()


def test_record_partial_writes_incrementally():
    bench.record_partial("serving", {"throughput_req_s": 100.0})
    bench.record_partial("miss_path", {"p50_ms": 7.0})
    on_disk = json.load(open(bench._PARTIAL_PATH))
    assert on_disk["serving"]["throughput_req_s"] == 100.0
    assert on_disk["miss_path"]["p50_ms"] == 7.0
    assert "ts" in on_disk


def test_error_line_carries_partials(monkeypatch):
    bench.record_partial("serving", {"throughput_req_s": 100.0})

    def wedge():
        raise RuntimeError("device probe hung >120s")

    monkeypatch.setattr(bench, "_main", wedge)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = bench.main()
    line = json.loads(buf.getvalue())
    assert rc == 1
    assert line["metric"] == "bench_error"
    assert line["partial"]["serving"]["throughput_req_s"] == 100.0


def test_failed_device_probe_fails_the_run(monkeypatch):
    """The opposite of a fallback: when the probe finds no usable chip,
    the run ends non-zero with the probe's error on the one JSON line —
    it does not flip to the CPU, shrink the model and report numbers."""
    assert not hasattr(bench, "device_fallback")
    monkeypatch.delenv("TPU_ENGINE_PLATFORM", raising=False)

    def no_chip():
        raise RuntimeError("device probe failed: no TPU found")

    monkeypatch.setattr(bench, "probe_device", no_chip)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--quick"])
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = bench.main()
    line = json.loads(buf.getvalue())
    assert rc == 1
    assert line["metric"] == "bench_error" and "no TPU" in line["error"]
    assert "TPU_ENGINE_PLATFORM" not in os.environ   # nothing flipped
    assert "partial" not in line                     # nothing measured


def test_device_probe_requires_a_tpu_backend(monkeypatch):
    """The real probe, in this chipless sandbox: without
    TPU_ENGINE_PLATFORM it must fail by name (JAX itself drops to the
    CPU silently); with TPU_ENGINE_PLATFORM=cpu the operator asked for
    the host backend and the probe passes."""
    monkeypatch.delenv("TPU_ENGINE_PLATFORM", raising=False)
    with pytest.raises(RuntimeError, match="no TPU found"):
        bench.probe_device()
    monkeypatch.setenv("TPU_ENGINE_PLATFORM", "cpu")
    bench.probe_device()


def test_scenarios_are_the_infer_harness_and_each_fails_without_a_chip(
        monkeypatch, capsys):
    """The surface: `--help` offers the reference's `/infer` load, its
    mixed-shape config and the miss-path sweep, and nothing else (the
    CPU arms are gone; `/generate` is benchmarks/run.py's). Each of them
    ends non-zero with ONE JSON line naming it when the probe fails."""
    monkeypatch.setattr(sys, "argv", ["bench.py", "--help"])
    with pytest.raises(SystemExit) as done:
        bench._main()
    assert done.value.code == 0
    usage = " ".join(capsys.readouterr().out.split())
    offered = re.search(r"--scenario \{([^}]*)\}", usage).group(1)
    assert offered.split(",") == ["infer", "mixed", "miss-sweep"]
    assert "--no-compute" not in usage

    def no_chip():
        raise RuntimeError("device probe failed: no TPU found")

    monkeypatch.setattr(bench, "probe_device", no_chip)
    for scenario in offered.split(","):
        monkeypatch.setattr(
            sys, "argv", ["bench.py", "--scenario", scenario, "--quick"])
        assert bench.main() == 1
        (out,) = capsys.readouterr().out.splitlines()
        line = json.loads(out)
        assert line["metric"] == "bench_error"
        assert line["scenario"] == scenario and "partial" not in line


def test_error_line_without_partials_stays_clean(monkeypatch):
    # Metadata-only partials (the scenario stamp _main writes before any
    # measurement) must not masquerade as surviving numbers.
    bench.record_partial("scenario", "infer")
    monkeypatch.setattr(
        bench, "_main",
        lambda: (_ for _ in ()).throw(RuntimeError("early failure")))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = bench.main()
    line = json.loads(buf.getvalue())
    assert rc == 1 and "partial" not in line
