"""The twelve per-layer readers PR 55 lists (`step.*_busy` by PART of the
step, `step.decode_run_ms` / `step.chunk_run_ms` by tick program) on a
made-up run, on the chip's recorded fixture, and on a parent's run, which
opens no part.

`WANT` is this file's part of the table of pins: the hook in
tests/conftest.py joins every `test_benchmark_layer_metrics_*.py`'s `WANT`
to the table test_benchmark_layer_metrics.py holds the `per_layer` list to."""

import importlib.util
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH, DATA, ROOT  # noqa: E402

from lib import xplane_reduce as X  # noqa: E402
from lib import xplane_scopes as S  # noqa: E402

TRACE = os.path.join(DATA, "scopes.toy_hybrid.xplane.pb")


def _reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_under_test_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.compute


def _part(self_s):
    return {"self_s": self_s, "flops": 0.0, "bytes": 0.0, "ops": 1}


# A hybrid with experts and a block's reveal in one made-up slice: 2 s busy
# of a 3 s window; seconds by part.
SECONDS = {"embed": 0.01, "plan": 0.03, "attn/qkv": 0.10, "attn/write": 0.04,
           "attn/read": 0.30, "attn/out": 0.06, "mixer/in": 0.08,
           "mixer/chunk": 0.24, "mixer/step": 0.20, "mixer/out": 0.08,
           "mlp": 0.16, "moe/route": 0.05, "moe/experts": 0.36,
           "moe/shared": 0.03, "head": 0.14, "sample": 0.02,
           "sample/reveal": 0.06, S.UNSCOPED: 0.04}
SCOPES = {"planes": 1, "busy_s": 2.0, "window_s": 3.0, "collisions": 0,
          "parts": {part: _part(s) for part, s in SECONDS.items()},
          "modules": {"tick_w1": [5.0, 5.2, 5.4, 9.0], "tick_w1_r4": [5.1],
                      "tick_w256": [40.0, 44.0, 42.0], "tick_w16": [8.0],
                      "spec_w5": [1.0], "mixed_step": [3.0],
                      "_where": [0.01]}}
RUN = {"trace": {"busy_s": 2.0, "window_s": 3.0, "op_seconds": {}},
       "scopes": SCOPES}
WANT = {
    "step.attn_busy": 100 * 0.50 / 2.0,
    "step.attn_read_busy": 100 * 0.30 / 2.0,
    "step.ffn_busy": 100 * (0.16 + 0.05 + 0.36 + 0.03) / 2.0,
    "step.moe_experts_busy": 100 * 0.36 / 2.0,
    "step.mixer_busy": 100 * 0.60 / 2.0,
    "step.mixer_chunk_busy": 100 * 0.24 / 2.0,
    "step.head_busy": 100 * 0.14 / 2.0,
    "step.sample_busy": 100 * 0.08 / 2.0,
    "step.reveal_busy": 100 * 0.06 / 2.0,
    "step.unscoped_busy": 100 * 0.04 / 2.0,
    # tick_w1 and tick_w1_r4 are both width 1; tick_w16 is not
    "step.decode_run_ms": 5.2,
    # every width above 1: 8, 40, 42, 44 (lib/metrics.py's percentile)
    "step.chunk_run_ms": 40.0,
}
SHARES = sorted(name for name in WANT if name.endswith("_busy"))
TOP_LEVEL = ["step.attn_busy", "step.ffn_busy", "step.mixer_busy",
             "step.head_busy", "step.sample_busy", "step.unscoped_busy"]


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _reader(name)(RUN) == pytest.approx(WANT[name])


def test_the_top_level_shares_and_unscoped_cover_the_step():
    """... but for `embed` and `plan`, which no metric of their own reads:
    the shares add to the sum of self times over busy less theirs."""
    total = sum(_reader(name)(RUN) for name in TOP_LEVEL)
    assert total == pytest.approx(
        100 * (sum(SECONDS.values()) - 0.01 - 0.03) / 2.0)


@pytest.mark.parametrize("name", SHARES)
def test_a_part_no_op_ran_under_reads_zero_not_nothing(name):
    scopes = dict(SCOPES, parts={part: _part(0.0) for part in SECONDS}
                  | {"embed": _part(2.0)})
    assert _reader(name)(dict(RUN, scopes=scopes)) == 0.0


@pytest.mark.parametrize("name", ["step.decode_run_ms", "step.chunk_run_ms"])
def test_a_slice_without_such_a_tick_reads_nothing(name):
    wrong = {"step.decode_run_ms": {"tick_w256": [40.0]},
             "step.chunk_run_ms": {"tick_w1": [5.0], "tick_w1_r4": [5.0]}}
    scopes = dict(SCOPES, modules=dict(wrong[name], spec_w5=[1.0]))
    assert _reader(name)(dict(RUN, scopes=scopes)) is None


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("run", [
    {"trace": None},                                   # nothing was traced
    {"trace": {"busy_s": 0.0, "window_s": 0.0}},       # no op ran
    {"trace": {"busy_s": 2.0, "window_s": 3.0}, "scopes": {}},  # no part
    # the newest file is another run's: its window is not this trace's
    {"trace": {"busy_s": 2.0, "window_s": 2.5}, "scopes": SCOPES},
], ids=["untraced", "idle", "parent", "stale"])
def test_the_readers_find_nothing_where_there_is_nothing(name, run):
    assert _reader(name)(dict(run)) is None


def test_a_parent_s_trace_is_read_once_and_every_reader_leaves_its_metric_out(
        monkeypatch, tmp_path):
    """A run object that brings no reduction: the first reader reads the
    newest trace, finds no part in it (a program before PR 55) and says so
    on the run object; the others do not read the file again."""
    calls = []
    monkeypatch.setattr(S, "newest_xplane", lambda out: str(tmp_path / "x"))
    monkeypatch.setattr(S, "reduce_file",
                        lambda path: calls.append(path))     # -> None
    run = {"trace": {"busy_s": 2.0, "window_s": 3.0}}
    assert [_reader(name)(run) for name in sorted(WANT)] == [None] * 12
    assert len(calls) == 1 and run["scopes"] == {}
    # ... and with no trace file at all nothing is opened
    monkeypatch.setattr(S, "newest_xplane", lambda out: None)
    assert _reader("step.head_busy")({"trace": run["trace"]}) is None
    assert len(calls) == 1


def test_the_readers_read_the_newest_trace_of_the_run(monkeypatch):
    """As run.py leaves it: `trace` is lib/xplane_reduce.py's reduction of
    the file the readers then open themselves, once."""
    calls = []
    reduce_file = S.reduce_file
    monkeypatch.setattr(S, "newest_xplane", lambda out: TRACE)
    monkeypatch.setattr(S, "reduce_file",
                        lambda path: calls.append(path) or reduce_file(path))
    run = {"trace": X.reduce_file(TRACE)}
    got = {name: _reader(name)(run) for name in sorted(WANT)}
    assert calls == [TRACE]
    want = reduce_file(TRACE)
    busy = want["busy_s"]
    assert got["step.mixer_chunk_busy"] == pytest.approx(
        100 * want["parts"]["mixer/chunk"]["self_s"] / busy)
    assert got["step.moe_experts_busy"] == got["step.reveal_busy"] == 0.0
    assert got["step.decode_run_ms"] > 0 and got["step.chunk_run_ms"] > 0
    # Every part but `embed` and `plan` lies under a top-level metric.
    covered = sum(got[name] for name in TOP_LEVEL)
    rest = sum(want["parts"][p]["self_s"] for p in ("embed", "plan"))
    assert covered + 100 * rest / busy == pytest.approx(100.0, abs=0.1)
    # A part holds its kernel: the Pallas calls by name, as `kernel.*_busy`
    # reads them, are no more than their parts.
    by_name = run["trace"]["op_seconds"]

    def kernel(pattern):
        return 100 * sum(s for n, s in by_name.items() if pattern in n) / busy

    assert 0 < kernel("gdn_chunk") <= got["step.mixer_chunk_busy"]
    assert 0 < kernel("_paged_call") <= got["step.attn_read_busy"]
    assert kernel("gdn_") <= got["step.mixer_busy"]


def test_the_twelve_are_listed_together_with_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["traffic"]: w["name"] for w in bench["workloads"]}
    # The ten cells there were when the twelve were listed (PR 55): a cell
    # that came later joins the lists its step opens, and is not held here.
    every = [w["name"] for w in bench["workloads"]]
    every = every[:every.index(cells["reply"]) + 1]
    first = [m["name"] for m in bench["per_layer"]].index("step.attn_busy")
    last = bench["per_layer"][first:first + 12]
    assert [m["name"] for m in last] == [
        "step.attn_busy", "step.attn_read_busy", "step.ffn_busy",
        "step.moe_experts_busy", "step.mixer_busy", "step.mixer_chunk_busy",
        "step.head_busy", "step.sample_busy", "step.reveal_busy",
        "step.unscoped_busy", "step.decode_run_ms", "step.chunk_run_ms"]
    assert sorted(m["name"] for m in last) == sorted(WANT)
    by_name = {m["name"]: m for m in last}
    for m in last:
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
        assert (m["layer"], m["source"], m["better"]) == (
            "step function", "device_trace", "lower")
        assert m["unit"] == ("ms" if m["name"].endswith("_ms") else "%")
        assert m["moves"] == ("itl_p95_ms" if m["name"]
                              == "step.chunk_run_ms" else "tokens_per_s")
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))

    def listed(name):
        return [cell for cell in by_name[name]["workloads"] if cell in every]

    for name in ("step.attn_busy", "step.attn_read_busy", "step.ffn_busy",
                 "step.head_busy", "step.sample_busy", "step.unscoped_busy"):
        assert listed(name) == every
    assert listed("step.moe_experts_busy") == [
        cells[t] for t in ("solve", "repo", "reason", "agents", "reply")]
    assert listed("step.mixer_busy") == [
        cells[t] for t in ("digest", "reason", "converse", "agents")]
    # reason's slice holds no chunk tick (PERF.md section 7)
    assert listed("step.mixer_chunk_busy") == [
        cells[t] for t in ("digest", "converse", "agents")]
    assert listed("step.chunk_run_ms") == [
        cell for cell in every if cell != cells["reason"]]
    # docqa's, repo's and agents' slices hold no tick without a chunk
    assert listed("step.decode_run_ms") == [
        cells[t] for t in ("chat", "batch", "solve", "digest", "reason",
                           "converse", "reply")]
    assert listed("step.reveal_busy") == [cells["reply"]]
    # each cell that reads a part's share lists the kernel it is read beside
    for metric, kernels in (
            ("step.mixer_chunk_busy", ("kernel.state_chunk_busy",)),
            ("step.moe_experts_busy", ("kernel.moe_experts_busy",))):
        beside = {cell for m in bench["per_layer"] if m["name"] in kernels
                  for cell in m["workloads"] if cell in every}
        assert set(listed(metric)) == beside
