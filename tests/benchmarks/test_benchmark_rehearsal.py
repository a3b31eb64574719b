"""run.py end to end on the CPU at test-only sizes (kept apart from
benchmarks/configs/): the contract's last line, the device printed as the
CPU it was, both loops, both attention dialects, a recurrent family that
arrives as new files only, and the refusals."""

import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH, DATA, ROOT  # noqa: E402

RUN = os.path.join(BENCH, "run.py")
CELLS = os.path.join(DATA, "BENCHMARK.test.json")


def _run(*args, platform="cpu", timeout=420):
    env = dict(os.environ, BENCH_RUN="7")
    env.pop("TPU_ENGINE_PLATFORM", None)
    if platform:
        env["TPU_ENGINE_PLATFORM"] = platform
    return subprocess.run([sys.executable, RUN, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def cells():
    with open(CELLS) as f:
        return json.load(f)


def _listed(cells, kind, cell):
    return {m["name"] for m in cells[kind]
            if cell in m.get("workloads", [cell])}


def test_untraced_open_loop_run_prints_the_contract_s_last_line(cells):
    proc = _run("--benchmark-file", CELLS, "--workload", "small.open",
                "--seed", str(2**31 + 17), "--seconds", "2", "--trace", "0")
    line = _last_line(proc)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    # What decided `correct`, each number beside its limit, comes last in
    # the line and as the last lines of standard error.
    assert set(line["compared"]) == {"worst_gap_in_logit_std", "exact_share",
                                     "repeat_identical"}
    gap = line["compared"]["worst_gap_in_logit_std"]
    assert 0.0 <= gap["value"] <= gap["at_most"]
    assert proc.stderr.strip().splitlines()[-1].startswith(
        "benchmark: correct: repeat_identical ")
    assert line["correct"] is True
    assert line["attempted"] == 12 and line["failed"] == 0
    assert set(line["metrics"]) == _listed(cells, "end_to_end", "small.open")
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0, name
    assert line["metrics"]["setup_s"]["unit"] == "s"
    # The device is what JAX ran on: a CPU rehearsal says so.
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert "memory_peak_bytes" in line["device"]
    # The run's records and counters stay behind, under a number of its own.
    runs = os.path.join(BENCH, "out", "runs")
    kept = sorted(f for f in os.listdir(runs) if f.startswith("small.open."))
    with open(os.path.join(runs, kept[-1])) as f:
        run = json.load(f)
    assert run["seed"] == 2**31 + 17 and run["trace"] == 0
    assert len(run["records"]) == 12 and "worker_1" in run["counted"]
    assert run["end_to_end"]["ttft_mean_ms"] == line["metrics"]["ttft_mean_ms"]


def test_traced_closed_loop_run_reports_layers_and_no_device_number(cells):
    proc = _run("--benchmark-file", CELLS, "--workload", "small.closed",
                "--seed", "5", "--seconds", "2", "--trace", "1")
    line = _last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    wanted = _listed(cells, "per_layer", "small.closed")
    got = set(line["metrics"])
    assert got <= wanted
    # Spans and counters are readable anywhere; what only a device trace
    # gives is left out on a CPU, never filled in.
    assert {"gateway.route_ms", "lane.queue_wait_ms", "step.decode_ms",
            "step.prefill_ms", "sched.decode_rows_per_tick",
            "sched.prefill_tick_share", "sched.itl_prefill_share",
            "kv.blocks_peak_share"} == got
    assert not {"device.idle", "kernel.paged_attn_busy"} & got
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["device"]["platform"] == "cpu"
    # Every metric is also printed by name and unit on an earlier line.
    assert "step.decode_ms = " in proc.stdout


def test_a_closed_loop_in_waves_runs_from_its_traffic_file_alone(cells):
    """`"wave": true` in the traffic file and nothing else: the warm-up and
    the window both send in waves of as many requests as the lane has
    slots, and the traced run says what share of the gaps between tokens
    lay across a prefill tick."""
    line = _last_line(_run("--benchmark-file", CELLS, "--workload",
                           "small.wave", "--seed", str(2**31 + 41),
                           "--seconds", "2", "--trace", "1"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8 and line["attempted"] % 4 == 0
    assert 0.0 < line["metrics"]["sched.itl_prefill_share"]["value"] < 100.0
    assert "sched.prefill_tick_share" in line["metrics"]
    runs = os.path.join(BENCH, "out", "runs")
    kept = sorted(f for f in os.listdir(runs) if f.startswith("small.wave."))
    with open(os.path.join(runs, kept[-1])) as f:
        records = json.load(f)["records"]
    assert [r["i"] for r in records] == list(range(len(records)))
    waves = [records[n:n + 4] for n in range(0, len(records), 4)]
    for before, after in zip(waves, waves[1:]):
        assert min(r["sent"] for r in after) >= max(
            r["done"] for r in before)


def test_a_family_the_harness_has_no_word_for_runs_from_new_files_only(cells):
    """The registry's recurrent test model on the slab pool: a configuration
    file, a reference found beside the benchmark file, an entry. Every
    metric listed for the cell is read but what only a device trace gives:
    the readers bound to the paged kernel and the block pool name their
    cells and are not asked."""
    proc = _run("--benchmark-file", CELLS, "--workload", "small.slab",
                "--seed", str(2**31 + 29), "--seconds", "2", "--trace", "1")
    line = _last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    wanted = _listed(cells, "per_layer", "small.slab")
    assert not {"kv.blocks_peak_share", "kernel.paged_attn_busy"} & wanted
    assert set(line["metrics"]) == wanted - {"device.idle",
                                             "device.hbm_peak_gb"}
    said = [ln for ln in proc.stderr.splitlines()
            if "found nothing to read" in ln]
    assert sorted(ln.split()[1] for ln in said) == ["device.hbm_peak_gb",
                                                    "device.idle"]
    details = json.loads(next(ln for ln in proc.stdout.splitlines()
                              if ln.startswith('{"correct"')))
    assert details["positions"] == 24 and details["exact_share"] == 1.0


def test_a_reference_with_a_dropped_term_reads_not_correct():
    """The same served recurrence, judged by the control beside the
    benchmark file: the run goes to its end and says `correct` false."""
    line = _last_line(_run("--benchmark-file", CELLS, "--workload",
                           "small.slab-dropped", "--seed", str(2**31 + 29),
                           "--seconds", "1", "--trace", "0"))
    assert line["correct"] is False
    assert line["failed"] == 0 and line["attempted"] >= 3


def test_a_configuration_whose_reference_file_is_missing_is_refused(tmp_path):
    with open(CELLS) as f:
        cells = json.load(f)
    with open(os.path.join(ROOT, cells["configs"][0]["file"])) as f:
        config = json.load(f)
    config["reference"]["dialect"] = "no-such-dialect"
    (tmp_path / "config.json").write_text(json.dumps(config))
    cells["configs"][0]["file"] = str(tmp_path / "config.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(cells))
    shutil.copytree(os.path.join(DATA, "traffic"), tmp_path / "traffic")
    proc = _run("--benchmark-file", str(tmp_path / "BENCHMARK.json"),
                "--workload", "small.open", timeout=60)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
    assert "references/no-such-dialect.py" in proc.stderr


def test_no_accelerator_means_no_result():
    proc = _run("--benchmark-file", CELLS, "--workload", "small.open",
                "--seed", "1", "--seconds", "1", "--trace", "0",
                platform=None, timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_unknown_cell_and_closed_loop_sweep_are_refused():
    proc = _run("--benchmark-file", CELLS, "--workload", "no.such.cell",
                timeout=60)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
    proc = _run("--benchmark-file", CELLS, "--sweep", "small.closed",
                "--rates", "1", timeout=60)
    assert proc.returncode != 0
    assert "closed loop" in proc.stderr
