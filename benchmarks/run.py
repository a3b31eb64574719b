#!/usr/bin/env python3
"""One run of one cell of the benchmark, on the machine it is started on.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of BENCHMARK.json's `workloads`: a configuration
(benchmarks/configs/<config>.json) under a traffic mix
(benchmarks/traffic/<traffic>.json). This file names no cell, configuration,
traffic mix or per-layer metric: all of them are found by name.

What one run does, in order (everything before the window is `setup_s`):

 1. decide the platform as `serve` does (a TPU, or refuse) and turn on the
    compile cache in <checkout>/.jax_cache;
 2. register the configuration and bring up `serve_combined`: HTTP front,
    gateway, one lane a chip, weights from --seed;
 3. decide `correct` against the configuration's plain reference, the file
    benchmarks/references/<reference.dialect>.py (lib/reference.py has the
    contract; serving the sample also compiles the two step programs the
    window uses), then warm the cell's own traffic for a few seconds
    through the load generator;
 4. measure: the load generator, a stdlib child process, offers the cell's
    traffic for --seconds and writes one record per request;
 5. print every metric by name, then ONE last line of JSON:
    --trace 0 the cell's end-to-end metrics, --trace 1 its per-layer metrics
    (from spans, counters and a profiler trace of a slice of the window).

    python3 benchmarks/run.py --sweep <cell> --rates 2,3,4 --seconds 30

brings the server up once and offers each rate in turn (open-loop cells):
how the fixed rate of a cell was found.

The run object handed to benchmarks/layer_metrics/<metric>.py `compute(run)`:
  stats_before, stats_after  {lane: ContinuousGenerator.stats()} at the
                             window's two ends
  spans         {lane or "gateway": [span dicts recorded inside the window]}
  pool_samples  [{"t", "kv_pool": {lane: pool stats}}] every half second
  trace         lib/xplane_reduce.reduce_planes(...) of the traced slice,
                or None
  records       the load generator's records
  peaks         lib/peaks.json's entry for this device kind
  device        the `device` object of the last line
  seconds       the window's length
  window_start  the window's start on the clock of the spans' `start_ts`:
                a record's times count from it
  config        the configuration file's dict: the sizes a reader counts
                operations and bytes from (lib/roofline.py has the counting)
  cell          the cell's entry of `workloads`
  slice         {"begin", "end"}: the traced slice on the clock of the spans'
                `start_ts` (time.time()), from the profiler's start having
                returned to its stop being called, so that a tick wholly
                inside it ran wholly inside the trace; None if nothing was
                traced
A reader that finds nothing to read returns None and the metric is left out.

Every run leaves benchmarks/out/runs/<cell>.<n>.json behind: its seed, what
the lanes counted, its metrics and every request's record, so that a run that
reads far off can be taken apart afterwards.
"""

import argparse
from concurrent.futures import ThreadPoolExecutor
import importlib.util
import json
import os
import random
import shutil
import subprocess
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")
WARM_MAX_PROMPT_TOKENS = 300   # warm-up prompts are cut to this: two chunks
TRACE_SLICE_S = 3.0            # the profiled slice in the window's middle
sys.path.insert(0, HERE)     # lib.* for this file and the metric readers
sys.path.insert(0, ROOT)     # tpu_engine, the system under test

from lib import metrics as M  # noqa: E402  (stdlib only)


def say(**fields):
    print(json.dumps(fields), flush=True)


def phase(name):
    """Set-up is most of what a run costs: say when each part of it ended,
    in seconds since the process started."""
    say(phase=name, at_s=round(time.monotonic() - T_START, 3))


def fail(message):
    """No result line: the driver reads a non-zero exit as 'did not run'."""
    print(f"benchmark: {message}", file=sys.stderr, flush=True)
    sys.exit(1)


# -- what BENCHMARK.json says about the cell ----------------------------------

def load_cell(name, bench_file):
    """The cell's entry, its configuration and traffic files, and the
    metrics BENCHMARK.json lists for it. A traffic file is looked for in
    benchmarks/traffic/, then beside `bench_file` (the tests' own cells);
    the configuration's reference in references/, the same way."""
    with open(bench_file) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        fail(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)

    def listed(kind):
        return [m for m in bench[kind]
                if "workloads" not in m or name in m["workloads"]]

    def found(kind, file_name):
        path = next((p for p in (os.path.join(d, kind, file_name) for d in
                                 (HERE, os.path.dirname(bench_file)))
                     if os.path.exists(p)), None)
        if path is None:
            fail(f"no file {kind}/{file_name} in benchmarks/ or beside "
                 f"{os.path.basename(bench_file)}")
        return path

    traffic_path = found("traffic", cell["traffic"] + ".json")
    with open(traffic_path) as f:
        traffic = json.load(f)
    return {"cell": cell, "config": config, "traffic": traffic,
            "traffic_path": traffic_path,
            "reference_path": found(
                "references", config["reference"]["dialect"] + ".py"),
            "end_to_end": listed("end_to_end"),
            "per_layer": listed("per_layer")}


def load_file(path, function):
    """`function` of the Python file at `path`: a per-layer reader's
    `compute`, a reference's `forward`. Files are found by a name that
    BENCHMARK.json or a configuration gives, never imported by name here."""
    stem = os.path.basename(path)[:-len(".py")]
    spec = importlib.util.spec_from_file_location(
        "benchmark_file_" + stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, function)


# -- the device ---------------------------------------------------------------

def device_info(chips):
    import jax

    devices = jax.devices()
    if len(devices) < chips:
        fail(f"the cell needs {chips} chip(s); JAX sees {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


def load_peaks(kind, platform):
    with open(os.path.join(HERE, "lib", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        if platform == "tpu":
            fail(f"device kind {kind!r} is not in lib/peaks.json")
        return None          # a CPU rehearsal has no peaks and no rooflines
    return table[kind]


# -- the load generator -------------------------------------------------------

def offer(cell, served, seed, seconds, tag, lead_s=1.0, rate=None,
          caps=None):
    """Run the load generator to its end; returns (records, its summary,
    the window's start on time.monotonic() and on time.time())."""
    os.makedirs(OUT, exist_ok=True)
    out_path = os.path.join(OUT, f"{cell['cell']['name']}.{tag}.jsonl")
    drain = float(cell["traffic"].get("drain_s", 30))
    t0 = time.monotonic() + lead_s       # the child needs time to start
    wall0 = time.time() + lead_s
    argv = [sys.executable, os.path.join(HERE, "lib", "loadgen.py"),
            "--traffic", cell["traffic_path"], "--seed", str(seed),
            "--port", str(served.port), "--seconds", str(seconds),
            "--vocab", str(served.vocab), "--t0", repr(t0),
            "--out", out_path, "--tag", tag, "--drain", str(drain)]
    if rate is not None:
        argv += ["--rate", str(rate)]
    for flag, value in (caps or {}).items():
        argv += [flag, str(value)]
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE, text=True)
    try:
        stdout, _ = proc.communicate(timeout=lead_s + seconds + drain + 60)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        fail("the load generator did not end in time")
    if proc.returncode != 0:
        fail(f"the load generator exited {proc.returncode}")
    with open(out_path) as f:
        records = [json.loads(line) for line in f]
    return records, json.loads(stdout.strip().splitlines()[-1]), t0, wall0


# -- correctness --------------------------------------------------------------

def decide_correct(cell, served, seed):
    """Serve the seeded sample greedily, compare with the plain reference;
    the same greedy request twice must give the same tokens."""
    from lib import reference

    spec = cell["config"]["correct"]
    rng = random.Random(seed ^ 0x5EED)
    new = int(spec["new_tokens"])
    prompts = [[rng.randrange(served.vocab) for _ in range(length)]
               for length in spec["prompt_lens"]]
    # All at once, as rows of the same ticks: one after another they would
    # cost a prefill and `new` decode ticks each, in every run's set-up.
    with ThreadPoolExecutor(len(prompts)) as pool:
        samples = list(zip(prompts, pool.map(
            lambda kp: served.generate(f"correct-{kp[0]}", kp[1], new),
            enumerate(prompts))))
    # The repeat runs alone both times, so the two dispatches are the same.
    short = [rng.randrange(served.vocab)
             for _ in range(int(spec["repeat_prompt_len"]))]
    few = int(spec["repeat_new_tokens"])
    repeat_same = (served.generate("repeat-1", short, few)
                   == served.generate("repeat-2", short, few))
    params = served.workers[0].engine.params
    ok, details = reference.check_served(
        load_file(cell["reference_path"], "forward"), params,
        cell["config"]["reference"], samples,
        float(spec["tolerance_in_logit_std"]),
        float(spec["min_exact_share"]), int(spec["pad_to"]))
    details["repeat_identical"] = repeat_same
    return bool(ok and repeat_same), details


def compared(details):
    """Each number `correct` compared, beside its limit."""
    return {"worst_gap_in_logit_std": {
                "value": details["worst_gap_in_logit_std"],
                "at_most": details["tolerance"]},
            "exact_share": {"value": details["exact_share"],
                            "at_least": details["min_exact_share"]},
            "repeat_identical": {"value": int(details["repeat_identical"]),
                                 "at_least": 1}}


# -- set-up -------------------------------------------------------------------

def bring_up(cell, seed):
    from tpu_engine.serving.cli import select_platform

    select_platform()          # a TPU or refuse; places the compile cache
    device = device_info(int(cell["cell"]["chips"]))
    phase("device")
    peaks = load_peaks(device["kind"], device["platform"])
    from lib.sut import Served

    served = Served(cell["config"], int(cell["cell"]["chips"]), seed)
    phase("served")
    return served, device, peaks


def warm(cell, served, seed):
    """A few seconds of the cell's own arrivals, outside the window, with
    every prompt and output cut short so that it ends soon: both step
    widths with several rows live. Any failure there fails the run."""
    traffic = cell["traffic"]
    seconds = float(traffic.get("warmup_s", 0))
    if seconds <= 0:
        return
    caps = {"--cap-new-tokens": int(traffic.get("warmup_max_new_tokens", 8)),
            "--cap-prompt-tokens": WARM_MAX_PROMPT_TOKENS}
    records, summary, _, _ = offer(cell, served, seed + 1, seconds, "warm",
                                   caps=caps)
    if summary["failed"] or not records:
        fail(f"warm-up traffic failed: {summary}")
    if not served.wait_idle(120):
        fail("the lanes did not go idle after the warm-up")


def counted(before, after):
    """What each lane's scheduler and pool counted over the window: printed
    on an earlier line of every run, so that a run that reads far off can be
    told from its neighbours by what the program did in it."""
    out = {}
    for node, b in before.items():
        a = after[node]
        out[node] = {k: a[k] - b[k] for k in ("admitted", "completed")}
        for group, keys in (("mixed", ("ticks", "prefill_tokens",
                                       "decode_tokens")),
                            ("kv_pool", ("evictions", "cow_copies",
                                         "radix_hits"))):
            if group in a:
                out[node].update({k: a[group][k] - b[group][k] for k in keys})
    return out


def keep_run(name, **run):
    """The run's records and counters, under the next free number."""
    runs = os.path.join(OUT, "runs")
    os.makedirs(runs, exist_ok=True)
    n = sum(1 for f in os.listdir(runs) if f.startswith(name + "."))
    with open(os.path.join(runs, f"{name}.{n:03d}.json"), "w") as f:
        json.dump(run, f)


def spans_inside(spans, wall0, seconds):
    return {node: [s for s in ring
                   if wall0 <= s.get("start_ts", s["ts"]) < wall0 + seconds]
            for node, ring in spans.items()}


def tick_extremes(spans, wall0, top=3):
    """The window's slowest scheduler ticks and the longest waits between
    two ticks, per lane, as [seconds into the window, milliseconds]: kept
    with every run, traced or not (the span ring is always on), so that a
    run that lost seconds says whether a tick or the host between ticks
    took them."""
    out = {}
    for node, ring in spans.items():
        ticks = sorted((s.get("start_ts", s["ts"]), s["duration_us"])
                       for s in ring if s["op"] == "mixed_step")
        gaps = [(a + da / 1e6, (b - a) * 1e3 - da / 1e3)
                for (a, da), (b, _) in zip(ticks, ticks[1:])]

        def longest(pairs):
            return [[round(t - wall0, 3), round(ms, 3)] for t, ms in
                    sorted(pairs, key=lambda p: -p[1])[:top]]

        if ticks:
            out[node] = {"slowest_ticks": longest(
                             [(t, d / 1e3) for t, d in ticks]),
                         "longest_waits": longest(gaps)}
    return out


# -- one measured run ---------------------------------------------------------

def measure(cell, served, device, peaks, seed, seconds, trace):
    from lib.sut import Sampler

    sampler, trace_dir = None, None
    before = served.generator_stats()
    if trace:
        # The traced run also samples the pool and profiles a slice in the
        # middle of the window; the untraced run does neither.
        trace_dir = os.path.join(OUT, f"{cell['cell']['name']}.trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        slice_s = min(TRACE_SLICE_S, seconds / 2)
        begin = time.monotonic() + 1.0 + (seconds - slice_s) / 2
        sampler = Sampler(served, trace_dir, begin, begin + slice_s)
        sampler.start()
    records, summary, t0, wall0 = offer(cell, served, seed, seconds, "window")
    after = served.generator_stats()
    if sampler is not None:
        sampler.stop()
    device = dict(device, memory_peak_bytes=served.memory_peak_bytes())
    attempted, failed = M.counts(records)
    result = {"attempted": attempted, "failed": failed, "device": device}
    n_ttft = len(M.ttft_ms(records))
    tally = counted(before, after)
    window_spans = spans_inside(served.spans(), wall0, seconds)
    extremes = tick_extremes(window_spans, wall0)
    say(loadgen=summary, window_s=seconds, attempted=attempted,
        failed=failed, lateness_ms=M.lateness_ms(records),
        setup_s=t0 - T_START, counted=tally, ticks=extremes,
        ttft_samples=n_ttft,
        highest_percentile_with_ten_beyond=M.highest_percentile(n_ttft))
    # Set-up: from the start of this process to the window's start.
    values = M.end_to_end(records, seconds, t0 - T_START)
    keep_run(cell["cell"]["name"], seed=seed, trace=int(trace),
             seconds=seconds, counted=tally, ticks=extremes,
             end_to_end=values, records=records)

    if not trace:
        result["metrics"] = {m["name"]: values[m["name"]]
                             for m in cell["end_to_end"]
                             if m["name"] in values}
        return result

    from lib import xplane_reduce

    reduced = None
    path = xplane_reduce.find_xplane(trace_dir)
    if path is not None:
        reduced = xplane_reduce.reduce_file(path)
    run = {"stats_before": before, "stats_after": after,
           "spans": window_spans,
           "pool_samples": [s for s in sampler.samples
                            if t0 <= s["t"] < t0 + seconds],
           "trace": reduced, "records": records, "peaks": peaks,
           "device": device, "seconds": seconds, "window_start": wall0,
           "config": cell["config"], "cell": cell["cell"],
           "slice": sampler.traced}
    result["metrics"] = {}
    for m in cell["per_layer"]:
        value = load_file(os.path.join(HERE, "layer_metrics",
                                       m["name"] + ".py"), "compute")(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            # The check refuses a line that lacks a metric listed for the
            # cell: a metric this traffic never feeds needs a `workloads` key.
            print(f"benchmark: {m['name']} found nothing to read in "
                  f"{cell['cell']['name']}", file=sys.stderr, flush=True)
    if reduced is not None and reduced["busy_s"] > 0:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    return result


def run_cell(args):
    cell = load_cell(args.workload, args.benchmark_file)
    served, device, peaks = bring_up(cell, args.seed)
    try:
        correct, details = decide_correct(cell, served, args.seed)
        say(correct=correct, **details)
        phase("correct")
        warm(cell, served, args.seed)
        phase("warm")
        result = measure(cell, served, device, peaks, args.seed,
                         args.seconds, bool(args.trace))
    finally:
        served.stop()
    for name, m in result["metrics"].items():
        print(f"{name} = {m['value']} {m['unit']}", flush=True)
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": result["metrics"],
            "device": result["device"]}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    # What decided `correct` comes last, here and on standard error: the
    # check keeps the end of each where a run reads not correct.
    line["compared"] = compared(details)
    print(json.dumps(line), flush=True)
    for name, pair in line["compared"].items():
        print(f"benchmark: correct: {name} {json.dumps(pair)}",
              file=sys.stderr, flush=True)
    return 0


def run_sweep(args):
    """One set-up, several offered rates in turn: for each, the end-to-end
    metrics and whether the backlog grew (requests still unfinished at the
    window's end, first half against second half of the window's TTFT)."""
    cell = load_cell(args.sweep, args.benchmark_file)
    if cell["traffic"]["loop"] != "open":
        fail("a sweep offers rates; the cell's traffic is a closed loop")
    served, device, _ = bring_up(cell, args.seed)
    try:
        correct, details = decide_correct(cell, served, args.seed)
        say(correct=correct, device=device, **details)
        warm(cell, served, args.seed)
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            records, summary, _, _ = offer(cell, served, args.seed + k,
                                           args.seconds, f"sweep{k}",
                                           rate=rate)
            values = M.end_to_end(records, args.seconds, 0.0)
            half = args.seconds / 2
            early = M.ttft_ms([r for r in records if r["due"] < half])
            late = M.ttft_ms([r for r in records if r["due"] >= half])
            unfinished = sum(1 for r in records if r["done"] is None
                             or r["done"] > args.seconds)
            say(rate_per_s=rate, requests=len(records),
                failed=summary["failed"],
                unfinished_at_window_end=unfinished,
                ttft_p50_first_half_ms=M.percentile(early, 50)
                if early else None,
                ttft_p50_second_half_ms=M.percentile(late, 50)
                if late else None,
                late_max_ms=summary["late_max_ms"],
                **{n: v["value"] for n, v in values.items()
                   if n != "setup_s"})
            if not served.wait_idle(180):
                fail("the lanes did not go idle between rates")
    finally:
        served.stop()
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", help="a cell whose rate is to be found")
    ap.add_argument("--rates", default="", help="--sweep: rates, comma-"
                    "separated, offered in turn")
    ap.add_argument("--benchmark-file",
                    default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="the list of cells (the tests have their own)")
    args = ap.parse_args(argv)
    if bool(args.workload) == bool(args.sweep):
        ap.error("give --workload or --sweep")
    return run_sweep(args) if args.sweep else run_cell(args)


if __name__ == "__main__":
    sys.exit(main())
