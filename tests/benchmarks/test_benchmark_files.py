"""BENCHMARK.json and the files it names: the allowed characters and
lengths, every cell's files, every per-layer metric's reader, and that the
harness itself names no cell, configuration, traffic mix or metric."""

import ast
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    assert len(bench["command"]) <= 32
    assert all(_one_line(word) for word in bench["command"])
    for path in bench["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path)
        assert not path.startswith("/") and ".." not in path.split("/")
        assert os.path.isdir(os.path.join(ROOT, path))


def test_names_units_and_whys(bench):
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[kind]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((kind in ("end_to_end", "per_layer") and "metric"
                          or kind, entry["name"]))
    assert len(names) == len(set(names)), "a name is used twice"
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for entry in bench["configs"] + bench["workloads"]:
        assert _one_line(entry["why"]), entry["name"]
    for config in bench["configs"]:
        assert _one_line(config["source"])
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert len(config["reduced"]) <= 16
        assert all(NAME.match(key) for key in config["reduced"])


TRAFFIC_KEYS = {"loop", "rate_per_s", "clients", "wave", "lead_ms", "block",
                "pool", "prompt_tokens", "output_tokens", "sharing", "warmup_s",
                "warmup_max_new_tokens", "drain_s", "who", "why"}


@pytest.mark.parametrize("name", sorted(os.listdir(os.path.join(BENCH,
                                                                "traffic"))))
def test_a_traffic_file_sets_only_what_the_generator_reads(name):
    """The order of sizes and arrivals, the stagger, the sampling and the
    traced slice are constants of the harness: a traffic file that could
    choose its own order could flatter the change it arrives with."""
    with open(os.path.join(BENCH, "traffic", name)) as f:
        traffic = json.load(f)
    assert set(traffic) <= TRAFFIC_KEYS, set(traffic) - TRAFFIC_KEYS
    assert traffic["loop"] in ("open", "closed")
    assert ("rate_per_s" in traffic) == (traffic["loop"] == "open")
    assert ("clients" in traffic) == (traffic["loop"] == "closed")
    if "wave" in traffic:
        # A wave is one block of the plan: each covers the sizes anew.
        assert traffic["wave"] is True and traffic["loop"] == "closed"
        assert traffic["block"] == traffic["clients"]
    if "lead_ms" in traffic:
        # Only a wave has a first request; the lead stays well inside a
        # tick, or the others would miss the wave's second.
        assert traffic.get("wave") is True and 0 < traffic["lead_ms"] <= 50
    assert _one_line(traffic["who"], 600) and _one_line(traffic["why"], 600)


def test_end_to_end_metrics(bench):
    metrics = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in metrics and metrics["setup_s"]["bound"] <= 0.1
    for m in metrics.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_which_cells_each_end_to_end_metric_is_judged_in(bench):
    """PR 35: no time to first token is judged by a median (the ~115 TTFTs
    of a docqa window are quantised to ticks and a few that slip move it by
    a whole one); the three metrics every cell reports keep no list, so that
    a later cell joins them as data."""
    metrics = {m["name"]: m for m in bench["end_to_end"]}
    assert "ttft_p50_ms" not in metrics
    assert metrics["ttft_mean_ms"]["workloads"] == [
        "gpt2-large.chat", "mistral-7b-v0.2-8l.docqa"]
    for name in ("itl_p95_ms", "tokens_per_s", "setup_s"):
        assert "workloads" not in metrics[name], name
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    # The median stays where it explains, beside the judged mean.
    assert per_layer["client.ttft_p50_ms"]["workloads"] == [
        "gpt2-large.chat", "mistral-7b-v0.2-8l.docqa"]
    for name in ("gateway.route_ms", "lane.queue_wait_ms",
                 "sched.budget_wait_ms", "lane.ttft_p50_ms"):
        assert per_layer[name]["moves"] == "ttft_mean_ms", name
        assert per_layer[name]["workloads"] == ["mistral-7b-v0.2-8l.docqa"]


def test_cells_and_their_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert 1 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 4)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        assert w["config"] in configs
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert data["source"] == c["source"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        for key in ("factory", "kwargs", "serving", "reference", "correct"):
            assert key in data, (c["name"], key)
        dialect = data["reference"]["dialect"]
        with open(os.path.join(BENCH, "references", dialect + ".py")) as f:
            tree = ast.parse(f.read())
        assert any(isinstance(n, ast.FunctionDef) and n.name == "forward"
                   for n in tree.body), dialect
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def test_a_metric_bound_to_a_mechanism_names_its_cells(bench):
    """A per-layer metric without a `workloads` key is asked of every cell
    a later PR adds, whatever its family. One that reads a kernel or the
    block pool finds nothing in a cell without that mechanism, so it lists
    the cells that have it (PERF.md, section 3)."""
    for m in bench["per_layer"]:
        if m["name"].startswith(("kernel.", "kv.")):
            assert m.get("workloads"), m["name"]
    without = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    assert sorted(without) == [
        "device.hbm_peak_gb", "device.idle", "device.idle_host",
        "sched.decode_rows_per_tick",
        "sched.itl_prefill_share", "sched.prefill_tick_share",
        "step.compiles", "step.prefill_ms"]


def test_every_cell_reports_what_the_contract_asks(bench):
    for w in bench["workloads"]:
        def listed(kind):
            return [m["name"] for m in bench[kind]
                    if w["name"] in m.get("workloads", [w["name"]])]
        e2e = listed("end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2
        assert listed("per_layer")
        for m in bench["per_layer"]:
            if w["name"] in m.get("workloads", [w["name"]]):
                assert m["moves"] in e2e, (m["name"], w["name"])


def test_per_layer_metrics_have_readers_and_one_spelling_per_layer(bench):
    # 72 since PR 68 (a reader a KIND of kernel, pool and counter); what is
    # left under the contract's 128 is the room of the PRs that add readers.
    assert 1 <= len(bench["per_layer"]) <= 128
    assert sorted(m["name"] + ".py" for m in bench["per_layer"]) == sorted(
        name for name in os.listdir(os.path.join(BENCH, "layer_metrics"))
        if name.endswith(".py"))
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _one_line(m["layer"])
        path = os.path.join(BENCH, "layer_metrics", m["name"] + ".py")
        assert os.path.isfile(path), path
        with open(path) as f:
            tree = ast.parse(f.read())
        assert any(isinstance(n, ast.FunctionDef) and n.name == "compute"
                   for n in tree.body), path
    layers = {m["layer"] for m in bench["per_layer"]}
    assert len({layer.lower() for layer in layers}) == len(layers)


def _code_outside_docstrings(path):
    """The file's text with its docstrings (the module's, a function's, a
    class's) blanked: its code and its comments."""
    with open(path) as f:
        source = f.read()
    lines = source.splitlines()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, (ast.Module, ast.FunctionDef, ast.ClassDef)) \
                and ast.get_docstring(node, clean=False) is not None:
            doc = node.body[0]
            for n in range(doc.lineno - 1, doc.end_lineno):
                lines[n] = ""
    return "\n".join(lines)


def test_no_reader_names_a_cell(bench):
    """A reader, or a library it counts with, that branched on which cell
    or configuration it serves would have to be edited for the next one
    (ISSUE 68): every size comes from what `run["config"]` STATES
    (lib/roofline_sizes.py). A docstring may say where a number was seen."""
    names = [e["name"] for kind in ("configs", "workloads")
             for e in bench[kind]]
    for folder in ("layer_metrics", "lib"):
        for file_name in sorted(os.listdir(os.path.join(BENCH, folder))):
            if not file_name.endswith(".py"):
                continue
            code = _code_outside_docstrings(
                os.path.join(BENCH, folder, file_name))
            for name in names:
                assert name not in code, f"{folder}/{file_name}: {name!r}"


def test_widths_are_the_sources(bench):
    """Every published width is as the source has it; only depth is cut."""
    with open(os.path.join(BENCH, "configs", "gpt2-large.json")) as f:
        g = json.load(f)
    assert (g["n_layer"], g["n_embd"], g["n_head"], g["n_positions"],
            g["vocab_size"]) == (36, 1280, 20, 1024, 50257)
    assert g["kwargs"] == {"n_layers": 36, "d_model": 1280, "n_heads": 20,
                           "d_ff": 5120, "vocab": 50257, "max_seq": 1024}
    with open(os.path.join(BENCH, "configs", "mistral-7b-v0.2-8l.json")) as f:
        m = json.load(f)
    assert (m["hidden_size"], m["intermediate_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["vocab_size"], m["max_position_embeddings"],
            m["rope_theta"], m["sliding_window"]) == (
        4096, 14336, 32, 8, 32000, 32768, 1000000.0, None)
    assert list(m["reduced"]) == ["num_hidden_layers"]
    k = m["kwargs"]
    assert (k["d_model"], k["d_ff"], k["n_heads"], k["n_kv_heads"],
            k["vocab"], k["max_seq"], k["rope_theta"]) == (
        4096, 14336, 32, 8, 32000, 32768, 1000000.0)
    assert k["n_layers"] == m["num_hidden_layers"] == 8


def test_the_harness_names_no_cell_configuration_or_metric(bench):
    with open(os.path.join(BENCH, "run.py")) as f:
        text = f.read()
    named = [e["name"] for kind in ("configs", "workloads", "per_layer")
             for e in bench[kind]]
    named += [w["traffic"] for w in bench["workloads"]]
    for name in named:
        assert name not in text, f"run.py names {name!r}"


def test_the_load_generator_is_stdlib_only():
    with open(os.path.join(BENCH, "lib", "loadgen.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert not imported & {"jax", "jaxlib", "numpy", "tpu_engine", "lib"}


def test_peaks_table_has_the_chip_and_its_source():
    with open(os.path.join(BENCH, "lib", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9 and v5e["source"]


def test_a_cell_is_asked_for_every_metric_without_a_key(bench):
    """run.py's own reading of the list: the `workloads` keys are the one
    way to say which cell has which metric. A metric without a key is every
    cell's; one with a key is the listed cells' and nobody else's."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "run_under_test", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    path = os.path.join(ROOT, "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"], path)
        for kind in ("end_to_end", "per_layer"):
            assert [m["name"] for m in cell[kind]] == [
                m["name"] for m in bench[kind]
                if w["name"] in m.get("workloads", [w["name"]])]
    docqa = {m["name"] for m in
             run.load_cell("mistral-7b-v0.2-8l.docqa", path)["per_layer"]}
    assert "step.compiles" in docqa and "kernel.paged_attn_busy" in docqa
    # Its every tick carries a chunk, whose FLOPs the span cannot count.
    assert "kernel.paged_attn_roofline" not in docqa
