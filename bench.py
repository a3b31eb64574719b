#!/usr/bin/env python3
"""End-to-end serving benchmark — the reference's headline harness, reproduced.

Mirrors /root/reference/benchmark.py: a closed-loop multithreaded client
POSTs `{request_id, input_data}` JSON to the gateway `/infer` endpoint
(10,000 requests, 50 threads, 10 distinct input vectors — the reference's
published 522.64 req/s run, README.md:274-300). The serving stack under
test is the TPU-native combined process: HTTP front door → hash-ring lane
selection → LRU cache → dynamic batcher → shape-bucketed XLA executables.

The server runs in a SEPARATE process (its own GIL) so the client load
generator doesn't share an interpreter with the serving path.

Prints exactly ONE JSON line to stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}
All progress/diagnostics go to stderr.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import socket
import statistics
import subprocess
import sys
import threading
import time
from typing import Optional, Sequence, Tuple

BASELINE_REQ_S = 522.64  # reference README.md:283 (BASELINE.md)
REPO = os.path.dirname(os.path.abspath(__file__))

# Peak dense bf16 FLOP/s per chip, by device_kind substring (public specs).
# MFU figures are computed against these. A kind that is not in the table
# is an error: a catch-all would hand it another chip's peak.
PEAK_BF16_FLOPS = (
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6e", 918e12), ("trillium", 918e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
)


def chip_peak_flops() -> tuple:
    """(device_kind, peak bf16 FLOP/s) of the chip JAX runs on."""
    import jax

    kind = jax.devices()[0].device_kind
    lk = kind.lower()
    for sub, peak in PEAK_BF16_FLOPS:
        if sub in lk:
            return kind, peak
    raise KeyError(f"no peak FLOP/s on record for device_kind {kind!r}: "
                   f"add it to PEAK_BF16_FLOPS with its source")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# Every completed sub-measurement lands here AND in a RUN-STAMPED
# partial artifact immediately — so a device hang mid-run (the driver
# kills the hung process and records only rc=1) still leaves every
# number measured before it, both on disk and attached to the error
# JSON line main() prints.
# Run-stamped (scenario + timestamp + pid) so concurrent runs never
# clobber each other, and REMOVED on a completed run — only aborted
# runs leave a partial behind (a stale fixed-name BENCH_partial.json
# used to sit at the repo root forever).
_PARTIAL: dict = {}
_PARTIAL_PATH = None  # set on first write (run-stamped)


def _partial_path() -> str:
    global _PARTIAL_PATH
    if _PARTIAL_PATH is None:
        stamp = time.strftime("%Y%m%d_%H%M%S")
        _PARTIAL_PATH = os.path.join(
            REPO, f"BENCH_partial.{_SCENARIO}.{stamp}.{os.getpid()}.json")
    return _PARTIAL_PATH


def record_partial(name: str, data) -> None:
    _PARTIAL[name] = data
    _PARTIAL["ts"] = time.strftime("%Y-%m-%d %H:%M:%S")
    try:
        with open(_partial_path(), "w") as f:
            json.dump(_PARTIAL, f, indent=2)
    except OSError as exc:  # a read-only checkout must not kill the bench
        log(f"partial artifact write failed: {exc}")


def cleanup_partial() -> None:
    """Remove this run's partial artifact — called once the run emitted
    its final line (an ABORTED run keeps its partials for forensics)."""
    if _PARTIAL_PATH is not None and os.path.exists(_PARTIAL_PATH):
        try:
            os.remove(_PARTIAL_PATH)
        except OSError:
            pass


def free_port() -> int:
    from tpu_engine.utils.net import free_port as _fp

    return _fp()


def wait_ready(port: int, timeout_s: float = 600.0, proc=None) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            # Server died before listening — most commonly the free_port()
            # probe-then-close race (utils/net.py documents it: another
            # process can bind the probed port first). Distinct error type
            # so launch_ready retries with a FRESH port instead of
            # polling a corpse for 10 minutes.
            raise ChildProcessError(
                f"server exited rc={proc.returncode} before ready")
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=2)
            conn.request("GET", "/stats")
            resp = conn.getresponse()
            resp.read()
            conn.close()
            if resp.status == 200:
                return
        except OSError:
            pass
        time.sleep(0.5)
    raise TimeoutError(f"server on port {port} not ready after {timeout_s}s")


class LoadGen:
    """Closed-loop load: T threads, each a persistent keep-alive connection,
    issuing its share of N requests back-to-back (reference benchmark.py:49-76).

    The client is raw sockets with precomputed request bytes — http.client's
    per-request object churn was the measured bottleneck at >8k req/s (the
    server's hit path is GIL-free C++, so client CPU directly caps the
    recorded number). Semantics unchanged: one outstanding request per
    thread, no pipelining."""

    def __init__(self, port: int, n_requests: int, n_threads: int,
                 distinct_inputs: int = 10, input_offset: int = 0):
        self.port = port
        self.n_requests = n_requests
        self.n_threads = n_threads
        # Reference workload: input cycles through 10 distinct small vectors
        # (benchmark.py:23) — the ~99.7% cache hit rate is a workload property.
        # Stored as (head, tail) byte fragments: request i's body is
        # head + str(i) + tail, with Content-Length patched per request.
        # `input_offset` shifts the vectors into a disjoint numeric range —
        # a warm-up pass must not pre-populate the cache with the measured
        # run's inputs (the cache keys on input bytes alone).
        self._frags = []
        for i in range(input_offset, input_offset + distinct_inputs):
            body = json.dumps({
                "request_id": "req_@",
                "input_data": [float(i), float(i + 1), float(i + 2)],
            })
            head, tail = body.split("req_@")
            self._frags.append((head.encode() + b"req_", tail.encode()))
        self.latencies_ms: list[list[float]] = [[] for _ in range(n_threads)]
        self.failures = [0] * n_threads

    def _connect(self) -> socket.socket:
        s = socket.create_connection(("127.0.0.1", self.port), timeout=30)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return s

    def _worker(self, tid: int, start_idx: int, count: int) -> None:
        lat = self.latencies_ms[tid]
        lat_append = lat.append
        perf = time.perf_counter
        frags = self._frags
        n_frags = len(frags)
        prefix = (b"POST /infer HTTP/1.1\r\nHost: b\r\n"
                  b"Content-Type: application/json\r\nContent-Length: ")
        sock = self._connect()
        buf = b""
        for k in range(count):
            i = start_idx + k
            head, tail = frags[i % n_frags]
            ib = str(i).encode()
            body = head + ib + tail
            req = prefix + str(len(body)).encode() + b"\r\n\r\n" + body
            t0 = perf()
            try:
                sock.sendall(req)
                # Headers (server always sends Content-Length, no chunking).
                while True:
                    j = buf.find(b"\r\n\r\n")
                    if j >= 0:
                        break
                    chunk = sock.recv(65536)
                    if not chunk:
                        raise OSError("connection closed")
                    buf += chunk
                cl_at = buf.find(b"Content-Length: ", 0, j)
                cl_end = buf.find(b"\r\n", cl_at)
                total = j + 4 + int(buf[cl_at + 16:cl_end])
                while len(buf) < total:
                    chunk = sock.recv(65536)
                    if not chunk:
                        raise OSError("connection closed")
                    buf += chunk
                ok = buf.startswith(b"HTTP/1.1 200")
                buf = buf[total:]
            except (OSError, ValueError):
                ok = False
                buf = b""
                try:
                    sock.close()
                except OSError:
                    pass
                try:
                    sock = self._connect()
                except OSError:
                    pass
            if ok:
                lat_append((perf() - t0) * 1e3)
            else:
                self.failures[tid] += 1
        sock.close()

    def run(self) -> dict:
        per = self.n_requests // self.n_threads
        extra = self.n_requests % self.n_threads
        threads = []
        idx = 0
        t_start = time.perf_counter()
        for tid in range(self.n_threads):
            count = per + (1 if tid < extra else 0)
            th = threading.Thread(target=self._worker, args=(tid, idx, count))
            idx += count
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        wall_s = time.perf_counter() - t_start
        lats = sorted(x for chunk in self.latencies_ms for x in chunk)
        n_ok = len(lats)
        n_fail = sum(self.failures)

        def pct(p: float) -> float:
            if not lats:
                return 0.0
            return lats[min(len(lats) - 1, int(p / 100.0 * len(lats)))]

        return {
            "requests": self.n_requests,
            "success": n_ok,
            "failed": n_fail,
            "success_rate": n_ok / max(1, self.n_requests),
            "wall_s": round(wall_s, 3),
            "throughput_req_s": round(n_ok / wall_s, 2) if wall_s > 0 else 0.0,
            "latency_ms": {
                "mean": round(statistics.fmean(lats), 3) if lats else 0.0,
                "p50": round(pct(50), 3),
                "p90": round(pct(90), 3),
                "p95": round(pct(95), 3),
                "p99": round(pct(99), 3),
                "max": round(lats[-1], 3) if lats else 0.0,
            },
        }


def scrape_stats(port: int) -> dict:
    out = {}
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        conn.request("GET", "/health")
        resp = conn.getresponse()
        health = json.loads(resp.read())
        conn.close()
        out["cache_hit_rate"] = health.get("cache_hit_rate")
        bp = health.get("batch_processor", {})
        out["avg_batch_size"] = bp.get("avg_batch_size")
    except Exception as exc:  # stats are best-effort
        log(f"stats scrape failed: {exc}")
    return out


def scrape_trace_stages(port: int) -> Optional[dict]:
    """Per-stage latency attribution from the server's tracing layer
    (GET /trace "stages"): where did the wall time go — queue wait,
    batch formation, device compute, serialization? Emitted into the
    BENCH json so the perf trajectory carries attributable numbers, not
    just end-to-end req/s. Count-weighted means aggregate across lanes;
    per-stage p99 reports the worst lane (cross-lane percentiles cannot
    be merged from summaries)."""
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        conn.request("GET", "/trace")
        resp = conn.getresponse()
        trace = json.loads(resp.read())
        conn.close()
    except Exception as exc:  # tracing scrape is best-effort
        log(f"trace scrape failed: {exc}")
        return None
    stages = trace.get("stages")
    if not stages:
        return None
    agg: dict = {}
    for lane_stages in stages.values():
        for op, s in lane_stages.items():
            a = agg.setdefault(op, {"count": 0, "_sum": 0.0, "p99_us": 0})
            a["count"] += s["count"]
            a["_sum"] += s["mean_us"] * s["count"]
            a["p99_us"] = max(a["p99_us"], s["p99_us"])
    out = {"stages": {}}
    for op, a in sorted(agg.items()):
        out["stages"][op] = {
            "count": a["count"],
            "mean_us": round(a["_sum"] / max(1, a["count"]), 1),
            "p99_us": a["p99_us"],
        }
    qw = out["stages"].get("queue_wait")
    dc = out["stages"].get("device_compute")
    if qw and dc and dc["mean_us"] > 0:
        # The headline attribution ratio: >1 means requests spend longer
        # waiting for a batch slot than computing — batching policy, not
        # the device, is the next thing to tune.
        out["queue_wait_vs_device_compute"] = round(
            qw["mean_us"] / dc["mean_us"], 3)
    return out


def stop_server(proc: Optional[subprocess.Popen]) -> None:
    """terminate -> bounded wait -> kill; shared by every launcher site."""
    if proc is None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


def launch_server(model: str, port: int, lanes: int,
                  mixed: bool = False,
                  pipeline_depth: Optional[int] = None,
                  batch_buckets: Optional[str] = None) -> subprocess.Popen:
    # The compile cache is placed by the child's own entry point
    # (cli.main: from JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "tpu_engine.serving.cli", "serve",
           "--model", model, "--port", str(port), "--lanes", str(lanes),
           "--warmup"]
    if mixed:
        cmd += ["--shape-buckets", "320x320x3,480x480x3,640x640x3"]
    if pipeline_depth is not None:
        cmd += ["--pipeline-depth", str(pipeline_depth)]
    if batch_buckets is not None:
        cmd += ["--batch-buckets", batch_buckets]
    log(f"launching server: {' '.join(cmd)}")
    return subprocess.Popen(cmd, cwd=REPO, env=env,
                            stdout=sys.stderr, stderr=sys.stderr)


def launch_ready(model: str, lanes: int, attempts: int = 3,
                 **launch_kw) -> Tuple[int, subprocess.Popen]:
    """Pick a free port, launch, wait ready — retrying the WHOLE pick+
    launch on an early exit. free_port() can only probe: the kernel may
    hand the same port to another process between the probe close and the
    server's bind, so the consumer (here), not the prober, owns the
    retry."""
    last: Exception = RuntimeError("unreachable")
    for attempt in range(attempts):
        port = free_port()
        proc = launch_server(model, port, lanes, **launch_kw)
        try:
            wait_ready(port, proc=proc)
            return port, proc
        except ChildProcessError as exc:
            last = exc
            log(f"launch attempt {attempt + 1}/{attempts} failed ({exc}); "
                "retrying on a fresh port")
        except BaseException:
            stop_server(proc)
            raise
    raise RuntimeError(f"server failed to launch after {attempts} "
                       f"attempts: {last}")


def run_miss_path_sweep(model: str = "resnet50",
                        depths: Sequence[int] = (4, 8, 16),
                        n_requests: int = 3000, n_threads: int = 50) -> dict:
    """Miss-path (all-distinct inputs, zero cache hits) throughput vs
    submit/collect pipeline depth: if the gap between the batch time and
    the device step is un-overlapped dispatch round-trips, deeper
    pipelining closes it; if it is host work, it won't. Full HTTP serving
    path, one server process per depth."""
    out: dict = {"model": model, "n_requests": n_requests,
                 "threads": n_threads}
    for depth in depths:
        port, proc = launch_ready(model, 0, pipeline_depth=depth)
        try:
            # Warm in a DISJOINT input range: warm vectors in the cache
            # would serve the measured run's first requests as hits.
            LoadGen(port, 200, 8, distinct_inputs=200,
                    input_offset=10_000_000).run()
            r = LoadGen(port, n_requests, n_threads,
                        distinct_inputs=n_requests).run()
            out[f"depth{depth}"] = {
                "throughput_req_s": r["throughput_req_s"],
                "p50_ms": r["latency_ms"]["p50"],
                "p99_ms": r["latency_ms"]["p99"],
                "success_rate": round(r["success_rate"], 4),
            }
        finally:
            stop_server(proc)
    return out


def run_cache_test(port: int, n: int = 100) -> dict:
    """Reference benchmark.py's cache-effectiveness A/B (its :180-220):
    n distinct inputs (miss phase), then the same n again (hit phase)."""
    import random

    rnd = random.Random(1234)
    inputs = [[rnd.uniform(0, 100) for _ in range(3)] for _ in range(n)]

    def phase(tag):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        lats = []
        for i, vec in enumerate(inputs):
            body = json.dumps({"request_id": f"cache_{tag}_{i}",
                               "input_data": vec})
            t0 = time.perf_counter()
            conn.request("POST", "/infer", body=body,
                         headers={"Content-Type": "application/json"})
            conn.getresponse().read()
            lats.append((time.perf_counter() - t0) * 1e3)
        conn.close()
        return statistics.fmean(lats)

    # Same request_id per input across phases so both route to one lane.
    miss_ms = phase("x")
    hit_ms = phase("x")
    return {
        "miss_avg_ms": round(miss_ms, 3),
        "hit_avg_ms": round(hit_ms, 3),
        "speedup": round(miss_ms / max(hit_ms, 1e-9), 2),
    }


def run_generate_bench(port: int, n_requests: int = 16, max_new: int = 32,
                       n_threads: int = 8) -> dict:
    """Autoregressive decode throughput: concurrent /generate requests,
    reports generated tokens/s (BASELINE config 5 workload)."""
    import random

    rnd = random.Random(7)
    prompts = [[rnd.randrange(1, 200) for _ in range(rnd.randrange(4, 24))]
               for _ in range(n_requests)]
    tokens_out = [0] * n_threads
    fails = [0] * n_threads

    def worker(tid):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        for i in range(tid, n_requests, n_threads):
            body = json.dumps({"request_id": f"gen_{i}",
                               "prompt_tokens": prompts[i],
                               "max_new_tokens": max_new})
            try:
                conn.request("POST", "/generate", body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                data = json.loads(resp.read())
                if resp.status == 200:
                    tokens_out[tid] += len(data["tokens"])
                else:
                    fails[tid] += 1
            except (OSError, http.client.HTTPException):
                fails[tid] += 1
                conn.close()
                conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        conn.close()

    # Warm the compiled prefill/decode executables before timing.
    warm = threading.Thread(target=worker, args=(0,))
    warm.start()
    warm.join()
    tokens_out[0] = 0

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(tid,))
               for tid in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    total = sum(tokens_out)
    return {
        "tokens": total,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total / wall, 2) if wall > 0 else 0.0,
        "failed": sum(fails),
    }


def run_compute_bench(model: str = "resnet50", batch: int = 32,
                      iters: int = 30, dtype: str = "bfloat16") -> dict:
    """Device-compute benchmark with honest attribution.

    Two timed loops:
    - **device loop**: inputs pre-staged on device, outputs not read until
      the end (one forced scalar materialization). Per-iter time = executable +
      per-dispatch stream overhead; `mfu` is computed from THIS number and
      XLA's own cost analysis, so it reflects the device, not the host.
    - **e2e loop**: full `batch_predict` path with pre-generated distinct
      host inputs (RNG hoisted out of the loop) — staging + transfer +
      readback included; reported separately as `e2e_step_ms` /
      `host_overhead_ms`, never folded into MFU."""
    import numpy as np

    from tpu_engine.runtime.engine import InferenceEngine

    eng = InferenceEngine(model, dtype=dtype, batch_buckets=(batch,))
    wire = eng._wire_buckets[-1]  # full-width: the honest worst-case feed
    t0 = time.perf_counter()
    exe = eng._compiled(batch, wire=wire)
    compile_s = time.perf_counter() - t0

    flops_per_exec = None
    try:
        ca = exe.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        flops_per_exec = float(ca.get("flops", 0.0)) or None
    except Exception as exc:
        log(f"cost_analysis unavailable: {exc}")

    rng = np.random.default_rng(0)
    n_in = eng.input_size
    host_batches = [
        [rng.standard_normal(n_in).astype(np.float32) for _ in range(batch)]
        for _ in range(iters)
    ]

    # -- device loop: a few distinct pre-staged buffers, round-robin -------
    import jax

    staged = [eng._stage_wire(host_batches[k % iters][:batch], batch, wire)
              for k in range(min(4, iters))]
    y = exe(eng.params, staged[0])
    _ = np.asarray(jax.tree_util.tree_leaves(y)[0])[:1]  # hard sync (warm)
    t0 = time.perf_counter()
    for k in range(iters):
        y = exe(eng.params, staged[k % len(staged)])
    _ = np.asarray(jax.tree_util.tree_leaves(y)[0]).ravel()[:1]  # hard sync
    device_wall = time.perf_counter() - t0
    device_step_ms = device_wall / iters * 1e3

    # -- e2e loop: full miss path, distinct inputs, RNG pre-hoisted --------
    eng.batch_predict(host_batches[0])  # warm the e2e path
    t0 = time.perf_counter()
    for hb in host_batches:
        eng.batch_predict(hb)
    e2e_wall = time.perf_counter() - t0
    e2e_step_ms = e2e_wall / iters * 1e3

    kind, peak = chip_peak_flops()
    achieved = (flops_per_exec / (device_step_ms / 1e3)
                if flops_per_exec else None)
    return {
        "model": model,
        "batch": batch,
        "iters": iters,
        "device_step_ms": round(device_step_ms, 3),
        "e2e_step_ms": round(e2e_step_ms, 3),
        "host_overhead_ms": round(e2e_step_ms - device_step_ms, 3),
        "samples_per_s": round(batch / (e2e_step_ms / 1e3), 2),
        "device_samples_per_s": round(batch / (device_step_ms / 1e3), 2),
        "compile_s": round(compile_s, 2),
        "flops_per_batch": flops_per_exec,
        "achieved_tflops": round(achieved / 1e12, 2) if achieved else None,
        "device_kind": kind,
        "peak_tflops": round(peak / 1e12, 1),
        "mfu": round(achieved / peak, 4) if achieved else None,
    }


def run_decode_compute(model: str = "gpt2", batch: int = 8,
                       max_new: int = 64, dtype: str = "bfloat16",
                       quantize: bool = False, fused: bool = False) -> dict:
    """On-chip decode throughput: tokens/s/chip through the KV-cache decode
    loop, with decode MFU ≈ tokens/s x 2 x params / peak (decode is
    HBM-bandwidth-bound; low MFU is expected and honest). `quantize` runs
    the same loop over int8 weight-only params (ops.quant) — decode streams
    every weight per step, so int8 halves its HBM bytes. `fused` runs the
    single-dispatch whole-loop mode (zero per-chunk host syncs — the
    honest device-capability number on a high-latency dispatch link)."""
    import numpy as np

    from tpu_engine.models.registry import create_model, _ensure_builtin_models_imported
    from tpu_engine.ops.nn import count_params
    from tpu_engine.runtime.generator import Generator

    _ensure_builtin_models_imported()
    spec = create_model(model)
    params = None
    if quantize:
        import jax

        from tpu_engine.ops.quant import quantize_params

        params = quantize_params(spec.init(jax.random.PRNGKey(0)))
    gen = Generator(spec, params=params, dtype=dtype, batch_buckets=(batch,))
    n_params = count_params(gen.params)

    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(1, 1000, size=12)]
               for _ in range(batch)]
    t0 = time.perf_counter()
    # Compile with the measured max_new (fused caches one executable per
    # output-capacity bucket; a 4-token warm compile would miss it).
    gen.generate(prompts, max_new_tokens=max_new, fused=fused)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = gen.generate(prompts, max_new_tokens=max_new, temperature=0.0,
                       fused=fused)
    wall = time.perf_counter() - t0
    tokens = sum(len(o) for o in out)
    kind, peak = chip_peak_flops()
    tok_s = tokens / wall
    flops_s = tok_s * 2.0 * n_params  # matmul fwd ≈ 2*N FLOPs/token
    return {
        "model": model,
        "batch": batch,
        "max_new_tokens": max_new,
        "quantize": "int8" if quantize else None,
        "fused": fused,
        "tokens_per_s": round(tok_s, 2),
        "wall_s": round(wall, 3),
        "compile_s": round(compile_s, 2),
        "n_params": n_params,
        "device_kind": kind,
        "decode_mfu": round(flops_s / peak, 4),
    }


def run_decode_ab(model: str = "gpt2", n_requests: int = 24,
                  max_new: int = 32, mean_gap_ms: float = 40.0,
                  dtype: str = "bfloat16") -> dict:
    """Continuous vs batch-to-completion decode under Poisson arrivals:
    same model/params/workload, reports tokens/s and
    per-request latency for both schedulers."""
    import random

    import jax
    import numpy as np

    from tpu_engine.models.registry import create_model, _ensure_builtin_models_imported
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig

    _ensure_builtin_models_imported()
    spec = create_model(model)
    params = spec.init(jax.random.PRNGKey(0))
    rnd = random.Random(42)
    prompts = [[rnd.randrange(1, 1000) for _ in range(rnd.randrange(4, 24))]
               for _ in range(n_requests)]
    gaps = [rnd.expovariate(1000.0 / mean_gap_ms) / 1000.0
            for _ in range(n_requests)]

    results = {}
    for sched in ("batch", "continuous"):
        cfg = WorkerConfig(model=model, node_id=f"ab-{sched}", dtype=dtype,
                           gen_scheduler=sched, batch_buckets=(1,))
        engine = InferenceEngine(spec, params=params, dtype=dtype,
                                 batch_buckets=(1,))
        w = WorkerNode(cfg, engine=engine)
        try:
            # Warm compiles outside the timed window.
            w.handle_generate({"request_id": "warm", "prompt_tokens": [1, 2, 3],
                               "max_new_tokens": 4})
            lats = [None] * n_requests
            threads = []

            def issue(i):
                t0 = time.perf_counter()
                w.handle_generate({"request_id": f"ab_{i}",
                                   "prompt_tokens": prompts[i],
                                   "max_new_tokens": max_new})
                lats[i] = (time.perf_counter() - t0) * 1e3

            t0 = time.perf_counter()
            for i in range(n_requests):
                time.sleep(gaps[i])
                th = threading.Thread(target=issue, args=(i,))
                th.start()
                threads.append(th)
            for th in threads:
                th.join()
            wall = time.perf_counter() - t0
            lat_sorted = sorted(lats)
            results[sched] = {
                "tokens_per_s": round(n_requests * max_new / wall, 2),
                "wall_s": round(wall, 3),
                "latency_p50_ms": round(lat_sorted[len(lats) // 2], 1),
                "latency_p95_ms": round(lat_sorted[int(0.95 * len(lats))
                                                   - 1], 1),
            }
        finally:
            w.stop()
    cont, bat = results["continuous"], results["batch"]
    results["continuous_speedup"] = round(
        cont["tokens_per_s"] / max(bat["tokens_per_s"], 1e-9), 3)
    return results


def run_spec_ab(model: str = "gpt2", batch: int = 8, max_new: int = 64,
                k: int = 4, dtype: str = "bfloat16") -> dict:
    """Speculative vs plain batch decode: same target params, greedy, batch
    workload. Two drafts bracket the win envelope — the target itself
    (acceptance 1: the machinery's best case) and a random-init distilgpt2
    (acceptance ~0: pure overhead floor). Real drafts (imported distilgpt2
    weights vs gpt2) land between; with the whole round loop compiled
    on-device, the speculative path also removes every per-chunk host sync
    the plain scheduler pays (runtime/speculative.py)."""
    import jax
    import numpy as np

    from tpu_engine.models.registry import (create_model,
                                            _ensure_builtin_models_imported)
    from tpu_engine.runtime.generator import Generator
    from tpu_engine.runtime.speculative import SpeculativeGenerator

    _ensure_builtin_models_imported()
    spec = create_model(model)
    params = spec.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(1, 1000, size=12)]
               for _ in range(batch)]

    def timed(gen):
        t0 = time.perf_counter()
        gen.generate(prompts, max_new_tokens=max_new)     # compile + warm
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = gen.generate(prompts, max_new_tokens=max_new)
        wall = time.perf_counter() - t0
        toks = sum(len(o) for o in out)
        return out, {"tokens_per_s": round(toks / wall, 2),
                     "wall_s": round(wall, 3),
                     "compile_s": round(compile_s, 2)}

    def prefix_match(got, want):
        # Strict equality is too brittle under bf16: the windowed verify
        # and the sequential decode are different reductions, and a
        # near-tied argmax can legitimately flip (after which the streams
        # diverge). Report the mean fraction of the stream matching up to
        # the first divergence instead (1.0 under f32, tested).
        fracs = []
        for g, w in zip(got, want):
            n = min(len(g), len(w)) or 1
            i = 0
            while i < n and g[i] == w[i]:
                i += 1
            fracs.append(i / n)
        return round(sum(fracs) / len(fracs), 3)

    plain = Generator(spec, params=params, dtype=dtype,
                      batch_buckets=(batch,))
    want, plain_r = timed(plain)

    results = {"model": model, "batch": batch, "max_new_tokens": max_new,
               "k": k, "plain_batch": plain_r}
    from tpu_engine.ops.quant import quantize_params

    # int8_self_draft is the deployable no-second-checkpoint draft: the
    # TARGET's weights quantized int8 draft the bf16 target. The draft
    # step reads half the weight HBM bytes (decode is weight-bound on
    # chip) yet almost never flips the argmax, so acceptance stays near
    # k+1 — a real speedup, unlike the same-cost self_draft upper bound
    # or the random floor.
    drafts = [("self_draft", spec, params),
              ("int8_self_draft", create_model(model),
               quantize_params(params)),
              ("random_distilgpt2", create_model("distilgpt2"), None)
              if model == "gpt2" else
              ("random_same_arch", create_model(model), None)]
    for name, dspec, dparams in drafts:
        sg = SpeculativeGenerator(spec, dspec, params=params,
                                  draft_params=dparams, k=k, dtype=dtype,
                                  batch_buckets=(batch,))
        got, r = timed(sg)
        r["greedy_prefix_match_frac"] = prefix_match(got, want)
        r["mean_tokens_per_round"] = sg.last_stats.get(
            "mean_tokens_per_round")
        r["speedup_vs_plain"] = round(
            r["tokens_per_s"] / max(plain_r["tokens_per_s"], 1e-9), 3)
        results[name] = r
    return results


def run_prefill_mfu(model: str = "gpt2", batch: int = 8, seq: int = 1024,
                    iters: int = 10, dtype: str = "bfloat16") -> dict:
    """Transformer-prefill MFU — the matmul-dense flagship: prefill is
    back-to-back (B*S, d) x (d, *) matmuls, the shape the MXU was built
    for, where a CNN's small-channel early convs are not. Pure device loop (inputs pre-staged, one hard
    sync at the end), FLOPs from XLA's own cost analysis."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                            create_model)
    from tpu_engine.models.transformer import init_caches, transformer_prefill

    _ensure_builtin_models_imported()
    spec = create_model(model, max_seq=seq)
    cfg = spec.config
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype]
    params = spec.init(jax.random.PRNGKey(0))

    def prefill(p, tokens, caches):
        return transformer_prefill(p, tokens, caches, cfg, dtype=dt)

    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab, (batch, seq)), jnp.int32)
    caches = init_caches(cfg, batch, seq, dt)
    t0 = time.perf_counter()
    exe = jax.jit(prefill).lower(params, tokens, caches).compile()
    compile_s = time.perf_counter() - t0
    flops = None
    try:
        ca = exe.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        flops = float(ca.get("flops", 0.0)) or None
    except Exception as exc:
        log(f"cost_analysis unavailable: {exc}")

    logits, _ = exe(params, tokens, caches)
    _ = np.asarray(logits).ravel()[:1]  # hard sync (warm)
    t0 = time.perf_counter()
    for _k in range(iters):
        logits, _ = exe(params, tokens, caches)
    _ = np.asarray(logits).ravel()[:1]
    step_ms = (time.perf_counter() - t0) / iters * 1e3

    kind, peak = chip_peak_flops()
    achieved = flops / (step_ms / 1e3) if flops else None
    return {
        "model": model, "batch": batch, "seq": seq, "dtype": dtype,
        "device_kind": kind,
        "compile_s": round(compile_s, 2),
        "device_step_ms": round(step_ms, 3),
        "prefill_tokens_per_s": round(batch * seq / (step_ms / 1e3), 1),
        "flops_per_step": flops,
        "achieved_tflops": round(achieved / 1e12, 2) if achieved else None,
        "mfu": round(achieved / peak, 4) if achieved else None,
    }


def run_longcontext_prefill(model: str = "gpt2",
                            seqs: Sequence[int] = (4096, 8192),
                            batch: int = 1, iters: int = 5,
                            xla_arm_max_seq: int = 4096) -> dict:
    """Long-context serving proof: gpt2 wired through
    the GENERATOR's flash prefill at S4k-8k — the sequences whose S^2
    score temps kill the unfused path. Measures prefill tok/s through the
    real serving entry (Generator.generate, prompt-bucketed, two decode
    steps so the path is the production one, prefill dominating). The XLA
    arm (TPU_ENGINE_FLASH=0) runs only to `xla_arm_max_seq` — at S8192 it
    cannot compile on a 16 GB chip (44 GB of S^2 temps, PERF.md)."""
    import os

    import numpy as np

    from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                            create_model)
    from tpu_engine.runtime.generator import Generator

    _ensure_builtin_models_imported()
    max_seq = max(seqs)
    rng = np.random.default_rng(3)
    out: dict = {"model": model, "batch": batch}
    prior_flash = os.environ.get("TPU_ENGINE_FLASH")  # restore, don't pop:
    # clobbering a caller-forced mode would silently change attention
    # selection for every stage that runs after this one.
    for attn, label in (("auto", "flash"), ("0", "xla")):
        os.environ["TPU_ENGINE_FLASH"] = attn
        try:
            # Fresh generator per arm: the attention choice is baked at
            # trace time.
            spec = create_model(model, max_seq=max_seq)
            gen = Generator(spec, dtype="bfloat16", batch_buckets=(batch,),
                            prompt_buckets=tuple(seqs), max_seq=max_seq)
            for s in seqs:
                if label == "xla" and s > xla_arm_max_seq:
                    out[f"xla_S{s}"] = "skipped: S^2 temps exceed HBM"
                    continue
                plen = s - 2  # prompt bucket s, two decode steps inside it
                prompts = [[int(t) for t in rng.integers(1, 1000, plen)]
                           for _ in range(batch)]
                t0 = time.perf_counter()
                gen.generate(prompts, max_new_tokens=2)  # compile + warm
                compile_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                for _k in range(iters):
                    gen.generate(prompts, max_new_tokens=2)
                wall = (time.perf_counter() - t0) / iters
                out[f"{label}_S{s}"] = {
                    "prefill_tokens_per_s": round(batch * plen / wall, 1),
                    "wall_s": round(wall, 3),
                    "compile_s": round(compile_s, 2),
                }
        finally:
            if prior_flash is None:
                os.environ.pop("TPU_ENGINE_FLASH", None)
            else:
                os.environ["TPU_ENGINE_FLASH"] = prior_flash
    return out


def run_mixed_shape_bench(port: int, n_requests: int = 2000,
                          n_threads: int = 16) -> dict:
    """Mixed-shape load (BASELINE config 4): yolov8n requests cycling three
    resolutions with distinct payloads, stressing the (shape, batch)
    executable cache under concurrent traffic."""
    import random

    rnd = random.Random(9)
    shapes = [(320, 320, 3), (480, 480, 3), (640, 640, 3)]
    lat = [[] for _ in range(n_threads)]
    fails = [0] * n_threads

    def worker(tid):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        for i in range(tid, n_requests, n_threads):
            shape = shapes[i % len(shapes)]
            # Tiny distinct payload; engine zero-pads to the true shape —
            # wire cost stays client-bound, device cost is the real shape.
            body = json.dumps({
                "request_id": f"mix_{i}",
                "input_data": [rnd.random() for _ in range(16)],
                "shape": list(shape),
            })
            t0 = time.perf_counter()
            try:
                conn.request("POST", "/infer", body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                if resp.status == 200:
                    lat[tid].append((time.perf_counter() - t0) * 1e3)
                else:
                    fails[tid] += 1
            except (OSError, http.client.HTTPException):
                fails[tid] += 1
                conn.close()
                conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.close()

    # Warm every (shape, batch) bucket before timing.
    warm = threading.Thread(target=worker, args=(0,))
    warm.start()
    warm.join()
    lat[0] = []

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lats = sorted(x for chunk in lat for x in chunk)
    return {
        "requests": n_requests,
        "shapes": [list(s) for s in shapes],
        "throughput_req_s": round(len(lats) / wall, 2),
        "p50_ms": round(lats[len(lats) // 2], 2) if lats else None,
        "p99_ms": round(lats[int(0.99 * len(lats)) - 1], 2) if lats else None,
        "failed": sum(fails),
    }


def run_paged_ab(model: str = "gpt2-small-test", n_requests: int = 16,
                 max_new: int = 96, shared_max_new: int = 16,
                 prompt_len: int = 8, shared_prefix_len: int = 64,
                 mean_gap_ms: float = 15.0, dtype: str = "float32",
                 block_size: int = 16, dense_slots: int = 2,
                 max_seq: int = 512) -> dict:
    """Dense vs paged KV cache at EQUAL KV memory budget (the tentpole
    A/B). Two arms:

    - **capacity**: a burst of short prompts against (a) the dense
      scheduler (`dense_slots` rows of max_seq each) and (b) the paged
      scheduler given exactly the same KV bytes as a block pool
      (`dense_slots * ceil(max_seq/bs)` blocks), with its slot count
      sized to what those blocks can hold concurrently at this
      workload's row footprint. Reports the peak concurrently-admitted
      rows each sustained — paged rows reserve blocks for the tokens
      they actually hold, so the same HBM admits several times more
      short rows.
    - **shared-prefix**: Poisson arrivals of prompts sharing one
      system-prompt prefix, paged with radix sharing on vs off. Reports
      prefill-token savings (prefix_hit_tokens vs prefilled_tokens) and
      tokens/s.

    Runs on the CPU mesh (tiny default model, max_seq overridden on the
    spec: the capacity and sharing ratios are layout/workload
    properties, not model-size properties); the on-chip campaign re-runs
    it against gpt2 on the device."""
    import random

    import jax

    from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                            create_model)
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    _ensure_builtin_models_imported()
    spec = create_model(model, max_seq=max_seq)
    params = spec.init(jax.random.PRNGKey(0))
    step_chunk = 8
    width = -(-max_seq // block_size)
    kv_blocks = dense_slots * width + 1  # == dense KV bytes (+ null block)
    # Worst-case blocks one capacity-arm row pins (prompt + generation +
    # one chunk of headroom): the pool admits this many rows at once.
    per_row_blocks = -(-(prompt_len + max_new + step_chunk) // block_size)
    paged_slots = max(1, (kv_blocks - 1) // per_row_blocks)
    rnd = random.Random(42)

    def run_burst(gen, prompts, new_tokens, gaps=None):
        peak = [0]
        stop_flag = threading.Event()

        def sampler():
            while not stop_flag.is_set():
                peak[0] = max(peak[0], gen.stats()["active"])
                time.sleep(0.002)

        th = threading.Thread(target=sampler, daemon=True)
        th.start()
        t0 = time.perf_counter()
        futs = []
        for i, p in enumerate(prompts):
            if gaps:
                time.sleep(gaps[i])
            futs.append(gen.submit(p, max_new_tokens=new_tokens))
        outs = [f.result(600) for f in futs]
        wall = time.perf_counter() - t0
        stop_flag.set()
        th.join(timeout=1)
        toks = sum(len(o) for o in outs)
        short = sum(1 for o in outs if len(o) < new_tokens)
        return {"requests": len(prompts), "wall_s": round(wall, 3),
                "tokens": toks, "truncated_rows": short,
                "tokens_per_s": round(toks / wall, 2) if wall else 0.0,
                "peak_concurrent_rows": peak[0]}

    results = {"model": model, "max_seq": max_seq,
               "block_size": block_size, "dense_slots": dense_slots,
               "paged_slots_equal_budget": paged_slots,
               "kv_blocks_equal_budget": kv_blocks}
    # A few distinct prompts cycled (the reference benchmark's own
    # workload shape): admission cost is then prefix-cache/radix-cheap on
    # both arms, so the burst measures RESIDENCY capacity, not the CPU
    # mesh's serial prefill throughput.
    distinct = [[rnd.randrange(1, 200) for _ in range(prompt_len)]
                for _ in range(4)]
    prompts = [distinct[i % len(distinct)] for i in range(n_requests)]

    dense = ContinuousGenerator(spec, params=params, dtype=dtype,
                                n_slots=dense_slots, step_chunk=step_chunk,
                                max_seq=max_seq)
    try:
        dense.generate(distinct, max_new_tokens=2)  # warm compiles+cache
        results["dense"] = run_burst(dense, prompts, max_new)
    finally:
        dense.stop()
    record_partial("paged_ab_dense", results["dense"])
    paged = ContinuousGenerator(spec, params=params, dtype=dtype,
                                n_slots=paged_slots, step_chunk=step_chunk,
                                max_seq=max_seq, kv_block_size=block_size,
                                kv_blocks=kv_blocks)
    try:
        paged.generate(distinct, max_new_tokens=2)
        results["paged"] = run_burst(paged, prompts, max_new)
        results["paged"]["kv_pool"] = {
            k: paged.stats()["kv_pool"][k]
            for k in ("blocks_total", "blocks_free", "evictions")}
    finally:
        paged.stop()
    results["capacity_gain"] = round(
        results["paged"]["peak_concurrent_rows"]
        / max(1, results["dense"]["peak_concurrent_rows"]), 2)
    record_partial("paged_ab_capacity", {
        k: results[k] for k in ("dense", "paged", "capacity_gain")})

    # Shared-prefix Poisson arm: radix sharing on vs off, same arrivals.
    shared = [rnd.randrange(1, 200) for _ in range(shared_prefix_len)]
    sp = [shared + [rnd.randrange(1, 200) for _ in range(6)]
          for _ in range(n_requests)]
    gaps = [rnd.expovariate(1000.0 / mean_gap_ms) / 1000.0
            for _ in range(n_requests)]
    for label, sharing in (("paged_shared_prefix", True),
                           ("paged_no_sharing", False)):
        g = ContinuousGenerator(spec, params=params, dtype=dtype,
                                n_slots=paged_slots, step_chunk=step_chunk,
                                max_seq=max_seq, kv_block_size=block_size,
                                kv_blocks=kv_blocks,
                                prefix_sharing=sharing)
        try:
            # Warm the full prefill path AND (sharing arm) the resumed
            # mid-prompt window widths, so the timed burst measures the
            # steady state, not one-time XLA compiles.
            g.generate([sp[0]], max_new_tokens=2)
            g.generate([shared + [1, 2, 3]], max_new_tokens=2)
            r = run_burst(g, sp, shared_max_new, gaps=gaps)
            pool = g.stats()["kv_pool"]
            r["kv_pool"] = {k: pool[k] for k in
                            ("prefix_hit_tokens", "prefilled_tokens",
                             "prefix_savings_frac", "blocks_shared",
                             "radix_nodes", "evictions")}
            results[label] = r
        finally:
            g.stop()
        record_partial(label, results[label])
    results["prefill_token_savings_frac"] = \
        results["paged_shared_prefix"]["kv_pool"]["prefix_savings_frac"]
    return results


def run_quant_ab(model: str = "gpt2-small-test", n_requests: int = 24,
                 max_new: int = 96, shared_prefix_len: int = 32,
                 prompt_tail: int = 6,
                 dtype: str = "bfloat16", block_size: int = 16,
                 bf16_rows: int = 3, max_seq: int = 256,
                 model_kwargs: Optional[dict] = None) -> dict:
    """bf16 vs int8 KV block pool at EQUAL KV byte budget (the
    --kv-quantize tentpole A/B, in the paged-ab shape). Three arms, all
    paged with radix prefix sharing ON and a shared-prefix burst so the
    prefix-skip machinery stays engaged:

    - **bf16** (defaults-off): today's pool, sized to ``bf16_rows`` rows
      of max_seq. Run twice — the repeat must be byte-identical (the
      defaults-off arm IS pre-quantization behavior) and its /stats
      kv_pool must carry no `quantized` key.
    - **int8**: the same KV bytes as a quantized pool — about 2x the
      blocks (payload halves; the per-slot f32 scales cost 4/(D+4) of
      the win, so ~1.88x at d_head 64) — with its slot count sized to
      what those blocks hold at this workload's row footprint. Run
      twice — quantized greedy streams must be deterministic across
      repeats. The headline is peak concurrently-admitted rows:
      capacity_gain = int8 peak / bf16 peak, bar >= 1.8x.

    The default model override (d_model 128, n_heads 2) gives the tiny
    test config a SERVING-SHAPED d_head of 64 — at the test model's
    native d_head 16 the scale overhead would mask the byte win that
    real models (d_head 64-128) actually see; the on-chip campaign runs
    the same A/B against gpt2 (d_head 64) on the device."""
    import random

    import jax

    from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                            create_model)
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    _ensure_builtin_models_imported()
    if model_kwargs is None:
        model_kwargs = ({"d_model": 128, "n_heads": 2}
                        if model == "gpt2-small-test" else {})
    spec = create_model(model, max_seq=max_seq, **model_kwargs)
    params = spec.init(jax.random.PRNGKey(0))
    cfg = spec.config
    # Small decode chunks: rows live many chunks, so the burst's
    # steady-state concurrency is bound by SLOT capacity (the thing the
    # A/B measures), not by the serial admission rate of the host mesh.
    step_chunk = 2
    width = -(-max_seq // block_size)
    bf16_blocks = bf16_rows * width + 1
    # Equal BYTE budget, not equal block count: the quantized pool gets
    # however many int8+scale blocks fit in the bf16 arm's KV bytes —
    # sized by the POOL'S OWN layout formulas, never a re-derivation.
    import jax.numpy as jnp

    from tpu_engine.runtime.kv_blocks import (dense_block_bytes,
                                              quant_block_bytes)

    dense_bpb = dense_block_bytes(
        cfg, block_size,
        {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype])
    quant_bpb = quant_block_bytes(cfg, block_size)
    budget_bytes = (bf16_blocks - 1) * dense_bpb
    quant_blocks = budget_bytes // quant_bpb + 1
    prompt_len = shared_prefix_len + prompt_tail
    per_row_blocks = -(-(prompt_len + max_new + step_chunk) // block_size)
    bf16_slots = max(1, (bf16_blocks - 1) // per_row_blocks)
    quant_slots = max(1, (quant_blocks - 1) // per_row_blocks)
    rnd = random.Random(7)
    shared = [rnd.randrange(1, 200) for _ in range(shared_prefix_len)]
    prompts = [shared + [rnd.randrange(1, 200) for _ in range(prompt_tail)]
               for _ in range(n_requests)]

    def run_burst(gen, new_tokens):
        peak = [0]
        stop_flag = threading.Event()

        def sampler():
            while not stop_flag.is_set():
                peak[0] = max(peak[0], gen.stats()["active"])
                time.sleep(0.002)

        th = threading.Thread(target=sampler, daemon=True)
        th.start()
        t0 = time.perf_counter()
        futs = [gen.submit(p, max_new_tokens=new_tokens) for p in prompts]
        outs = [f.result(600) for f in futs]
        wall = time.perf_counter() - t0
        stop_flag.set()
        th.join(timeout=1)
        toks = sum(len(o) for o in outs)
        return outs, {"requests": len(prompts), "wall_s": round(wall, 3),
                      "tokens": toks,
                      "tokens_per_s": round(toks / wall, 2) if wall else 0.0,
                      "peak_concurrent_rows": peak[0]}

    def run_arm(quantize: str, n_slots: int, kv_blocks: int):
        gen = ContinuousGenerator(
            spec, params=params, dtype=dtype, n_slots=n_slots,
            step_chunk=step_chunk, max_seq=max_seq,
            kv_block_size=block_size, kv_blocks=kv_blocks,
            kv_quantize=quantize)
        try:
            # Warm compiles + the resumed mid-prompt window widths so the
            # timed bursts measure steady state, not one-time XLA work.
            gen.generate([prompts[0]], max_new_tokens=2)
            gen.generate([shared + [1, 2, 3]], max_new_tokens=2)
            streams1, r1 = run_burst(gen, max_new)
            streams2, r2 = run_burst(gen, max_new)
            pool = gen.stats()["kv_pool"]
            r1["repeat_identical"] = streams1 == streams2
            r1["kv_pool"] = {k: pool[k] for k in
                             ("blocks_total", "block_size",
                              "prefix_savings_frac", "radix_hits")
                             if k in pool}
            for k in ("quantized", "bytes_per_block",
                      "dense_bytes_per_block", "capacity_multiplier"):
                if k in pool:
                    r1["kv_pool"][k] = pool[k]
            r1["stats_has_quantized_key"] = "quantized" in pool
            r1["peak_concurrent_rows"] = max(r1["peak_concurrent_rows"],
                                             r2["peak_concurrent_rows"])
        finally:
            gen.stop()
        return streams1, r1

    results = {"model": model, "model_kwargs": model_kwargs,
               "max_seq": max_seq, "block_size": block_size,
               "dtype": dtype, "d_head": cfg.d_head,
               "kv_byte_budget": int(budget_bytes),
               "bf16": {"kv_blocks": bf16_blocks, "n_slots": bf16_slots},
               "int8": {"kv_blocks": int(quant_blocks),
                        "n_slots": quant_slots}}
    bf16_streams, bf16_r = run_arm("", bf16_slots, bf16_blocks)
    results["bf16"].update(bf16_r)
    record_partial("quant_ab_bf16", results["bf16"])
    int8_streams, int8_r = run_arm("int8", quant_slots, int(quant_blocks))
    results["int8"].update(int8_r)
    record_partial("quant_ab_int8", results["int8"])

    results["capacity_gain"] = round(
        results["int8"]["peak_concurrent_rows"]
        / max(1, results["bf16"]["peak_concurrent_rows"]), 2)
    agree = [a == b for a, b in zip(int8_streams, bf16_streams)]
    tok_agree = [sum(x == y for x, y in zip(a, b)) / max(1, len(a))
                 for a, b in zip(int8_streams, bf16_streams)]
    results["streams_identical_to_bf16_frac"] = round(
        sum(agree) / len(agree), 3)
    results["token_agreement_frac"] = round(
        sum(tok_agree) / len(tok_agree), 4)
    results["checks_passed"] = bool(
        results["capacity_gain"] >= 1.8
        and results["int8"]["repeat_identical"]          # deterministic
        and results["bf16"]["repeat_identical"]          # defaults-off
        and not results["bf16"]["stats_has_quantized_key"]
        and results["int8"]["stats_has_quantized_key"]
        and results["bf16"]["kv_pool"]["prefix_savings_frac"] > 0
        and results["int8"]["kv_pool"]["prefix_savings_frac"] > 0)
    return results


def run_recurrent_ab(att_model: str = "gpt2-small-test",
                     ssd_model: str = "ssd-small-test",
                     n_requests: int = 12, max_new: int = 16,
                     seq_sweep=(46, 110, 238), att_rows_budget: int = 3,
                     block_size: int = 16, max_seq: int = 256,
                     n_slots: int = 16, mixed_budget: int = 32,
                     quick: bool = False) -> dict:
    """Attention (kv_paged) vs SSD (state_slab) at EQUAL HBM budget —
    the O(1)-state tentpole A/B. One byte budget, sized to
    ``att_rows_budget`` full-length attention rows, provisions BOTH
    arms' pools: the attention arm gets that many KV blocks, the SSD
    arm however many fixed-size state rows fit in the same bytes. A
    saturating greedy burst of ``n_requests`` streams runs at each
    SEQUENCE LENGTH in ``seq_sweep`` (prompt lengths; +max_new decode
    tokens each) and the headline is PEAK CONCURRENT ROWS vs length:

    - attention rows allocate their prompt bucket's blocks AT
      admission, so the pool binds exactly there: peak rows FALL as
      sequences lengthen (excess admissions defer, the PR 3 parking);
    - SSD rows need exactly ONE state row forever, so peak rows are
      CONSTANT in sequence length — "KV capacity" became "state
      capacity", and it does not depreciate with context.

    Both arms run MIXED stepping so a row occupies its slot from
    admission through prefill and decode (concurrency measures pool
    capacity, not the host mesh's serial admission rate), and the
    sweep lengths are chosen so prompt+decode never outgrows the
    admission-time bucket — the pool binds at ADMISSION, never by
    mid-stream starvation (starved early completions would poison the
    determinism check). Every burst runs twice (streams must be
    byte-identical run to run, both arms) and every pool must account
    for every block/row after each burst (zero slab leaks — rows_free
    == rows_total on the SSD arm, blocks free+radix-held == total on
    the attention arm). CPU mesh; the artifact carries the device
    stamp like every in-process A/B."""
    import random

    import jax
    import jax.numpy as jnp

    from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                            create_model)
    from tpu_engine.runtime.kv_blocks import dense_block_bytes
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    _ensure_builtin_models_imported()
    if quick:
        seq_sweep = (seq_sweep[0], seq_sweep[-1])
        n_requests = min(n_requests, 8)
    att_spec = create_model(att_model, max_seq=max_seq)
    ssd_spec = create_model(ssd_model, max_seq=max_seq)
    att_params = att_spec.init(jax.random.PRNGKey(0))
    ssd_params = ssd_spec.init(jax.random.PRNGKey(0))
    # Equal BYTE budget from the pools' OWN layout formulas (never a
    # re-derivation): att_rows_budget full-length attention rows.
    width = -(-max_seq // block_size)
    dense_bpb = dense_block_bytes(att_spec.config, block_size,
                                  jnp.bfloat16)
    budget_bytes = att_rows_budget * width * dense_bpb
    att_blocks = budget_bytes // dense_bpb + 1  # +1: the null block
    # The SSD row cost comes from the pool's own layout formula.
    from tpu_engine.models.ssd import ssd_state_dim
    ssd_row_bytes = ssd_spec.config.n_layers \
        * ssd_state_dim(ssd_spec.config) * 4
    ssd_rows = budget_bytes // ssd_row_bytes + 1  # +1: the null row
    rnd = random.Random(11)

    def run_burst(gen, prompts):
        peak = [0]
        stop_flag = threading.Event()

        def sampler():
            while not stop_flag.is_set():
                peak[0] = max(peak[0], gen.stats()["active"])
                time.sleep(0.002)

        th = threading.Thread(target=sampler, daemon=True)
        th.start()
        t0 = time.perf_counter()
        futs = [gen.submit(p, max_new_tokens=max_new) for p in prompts]
        outs = [f.result(600) for f in futs]
        wall = time.perf_counter() - t0
        stop_flag.set()
        th.join(timeout=1)
        toks = sum(len(o) for o in outs)
        return outs, {"wall_s": round(wall, 3), "tokens": toks,
                      "tokens_per_s": round(toks / wall, 2) if wall
                      else 0.0,
                      "peak_concurrent_rows": peak[0]}

    def sweep_arm(arm: str):
        per_len = {}
        deterministic = True
        leaks_clean = True
        complete = True
        for plen in seq_sweep:
            prompts = [[rnd.randrange(1, 200) for _ in range(plen)]
                       for _ in range(n_requests)]
            if arm == "ssd":
                gen = ContinuousGenerator(
                    ssd_spec, params=ssd_params, dtype="float32",
                    n_slots=n_slots, max_seq=max_seq,
                    prefill_chunk=block_size, mixed_step=True,
                    mixed_token_budget=mixed_budget,
                    state_rows=int(ssd_rows))
            else:
                gen = ContinuousGenerator(
                    att_spec, params=att_params, dtype="bfloat16",
                    n_slots=n_slots, max_seq=max_seq,
                    prefill_chunk=block_size, mixed_step=True,
                    mixed_token_budget=mixed_budget,
                    kv_block_size=block_size, kv_blocks=int(att_blocks),
                    prefix_sharing=False)
            try:
                gen.generate([prompts[0][:8]], max_new_tokens=2)  # warm
                s1, r1 = run_burst(gen, prompts)
                s2, r2 = run_burst(gen, prompts)
                deterministic &= s1 == s2
                # Full-length streams only: a starved early completion
                # would mean the pool bound mid-stream, not at
                # admission — the A/B's sizing contract.
                complete &= all(len(o) == max_new for o in s1 + s2)
                r1["peak_concurrent_rows"] = max(
                    r1["peak_concurrent_rows"],
                    r2["peak_concurrent_rows"])
                st = gen.stats()
                if arm == "ssd":
                    pool = st["state_pool"]
                    r1["pool"] = {k: pool[k] for k in
                                  ("rows_total", "rows_free",
                                   "bytes_per_row")}
                    leaks_clean &= (pool["rows_free"]
                                    == pool["rows_total"])
                else:
                    pool = st["kv_pool"]
                    r1["pool"] = {k: pool[k] for k in
                                  ("blocks_total", "blocks_free",
                                   "radix_nodes")}
                    leaks_clean &= (pool["blocks_free"]
                                    + pool["radix_nodes"]
                                    >= pool["blocks_total"])
            finally:
                gen.stop()
            per_len[plen] = r1
        return {"per_seq_len": per_len,
                "streams_deterministic": deterministic,
                "streams_complete": complete,
                "pools_leak_free": leaks_clean}

    ssd_res = sweep_arm("ssd")
    att_res = sweep_arm("att")
    ssd_peaks = [ssd_res["per_seq_len"][s]["peak_concurrent_rows"]
                 for s in seq_sweep]
    att_peaks = [att_res["per_seq_len"][s]["peak_concurrent_rows"]
                 for s in seq_sweep]
    longest = seq_sweep[-1]
    results = {
        "att_model": att_model, "ssd_model": ssd_model,
        "max_seq": max_seq, "block_size": block_size,
        "n_slots": n_slots, "n_requests": n_requests,
        "hbm_byte_budget": int(budget_bytes),
        "att": {"kv_blocks": int(att_blocks),
                "bytes_per_block": int(dense_bpb), **att_res},
        "ssd": {"state_rows": int(ssd_rows),
                "bytes_per_row": int(ssd_row_bytes), **ssd_res},
        "seq_sweep": list(seq_sweep),
        "ssd_peak_rows": ssd_peaks,
        "att_peak_rows": att_peaks,
        # The capacity story at the longest length: constant-state rows
        # vs linearly-depreciating KV rows on the same HBM.
        "capacity_gain_at_longest": round(
            ssd_peaks[-1] / max(1, att_peaks[-1]), 2),
    }
    results["checks_passed"] = bool(
        # SSD peak concurrent rows constant in sequence length...
        len(set(ssd_peaks)) == 1
        # ...while the attention arm's fall as streams lengthen...
        and att_peaks[-1] < att_peaks[0]
        # ...and the SSD arm holds more rows at the longest length.
        and ssd_peaks[-1] > att_peaks[-1]
        and ssd_res["streams_deterministic"]
        and att_res["streams_deterministic"]
        and ssd_res["streams_complete"]
        and att_res["streams_complete"]
        and ssd_res["pools_leak_free"]
        and att_res["pools_leak_free"]
        # The sweep actually saturated the SSD arm (peak == the burst).
        and ssd_peaks[-1] == min(n_requests, n_slots))
    return results


def run_tp_ab(model: str = "gpt2-small-test", tp: int = 4,
              blocks_per_device: int = 12, n_requests: int = 24,
              short_prompt_len: int = 18, long_prompt_len: int = 230,
              max_new: int = 12, block_size: int = 16,
              max_seq: int = 256, single_max_seq: int = 64,
              n_slots: int = 16, quick: bool = False) -> dict:
    """Tensor-parallel serving A/B at EQUAL PER-DEVICE HBM budget (the
    TP tentpole): every arm gets ``blocks_per_device`` KV blocks per
    chip — the TP arm's pool is tp x that many blocks sharded over its
    mesh, the single-device arm exactly that many on its one chip.

    Two facets, both provable on the CPU mesh:

    - MODEL-SIZE UNLOCK: at this per-device budget a single-device lane
      cannot hold even ONE ``max_seq`` KV row — the engine REFUSES
      OUTRIGHT at construction (the pinned "cannot hold even one
      max_seq row" ValueError; recorded verbatim), and its weights sit
      whole on the chip. The TP arm serves the exact same model +
      max_seq (params sharded by the registry rule, pool tp x deeper)
      and completes a ``long_prompt_len``-token stream — the "models
      too big for one chip" unlock, in pool terms. Per-device param
      bytes are measured from the PLACED tree's real shard shapes.
    - CAPACITY: a saturating burst of short greedy streams on the TP
      arm vs a single-device arm that — to exist at all at this budget
      — must shrink its context window to ``single_max_seq``. Peak
      concurrent rows (sampled from stats) scale with the pooled
      blocks.

    Every burst runs twice (streams byte-identical run to run), the TP
    arm's short streams must equal the single arm's BYTE-FOR-BYTE
    (cross-geometry stream identity — the same fold_in(seed, position)
    + paged-layout argument as every other identity in this engine),
    mixed ticks == dispatches on the sharded arm (one SPMD dispatch per
    tick), and every pool accounts for every block after each burst.
    Short prompts are sized so prompt + max_new + the decode horizon
    fits the admission bucket — the pools bind at ADMISSION (deferred
    admissions, deterministic), never by mid-stream starvation (whose
    early completions are timing-dependent and would poison the
    determinism check). Streams must run FULL length on both arms.
    CPU mesh; on-chip rerun pending like r06-r15."""
    import random

    import jax
    import numpy as _np

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported, create_model)
    from tpu_engine.runtime.kv_blocks import dense_block_bytes
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    _ensure_builtin_models_imported()
    if quick:
        n_requests = min(n_requests, 12)
        tp = min(tp, 2)
    spec = create_model(model, max_seq=max_seq)
    params = spec.init(jax.random.PRNGKey(0))
    import jax.numpy as _jnp

    bpb = dense_block_bytes(spec.config, block_size, _jnp.float32)
    rnd = random.Random(17)
    short_prompts = [[rnd.randrange(1, 200)
                      for _ in range(short_prompt_len)]
                     for _ in range(n_requests)]
    long_prompt = [rnd.randrange(1, 200) for _ in range(long_prompt_len)]

    def param_bytes_per_device(tree) -> int:
        total = 0
        for leaf in jax.tree.leaves(tree):
            sh = getattr(leaf, "sharding", None)
            if sh is None:
                total += leaf.size * leaf.dtype.itemsize
                continue
            shard = sh.shard_shape(leaf.shape)
            total += int(_np.prod(shard)) * leaf.dtype.itemsize
        return int(total)

    def run_burst(gen, prompts):
        peak = [0]
        stop_flag = threading.Event()

        def sampler():
            while not stop_flag.is_set():
                peak[0] = max(peak[0], gen.stats()["active"])
                time.sleep(0.002)

        th = threading.Thread(target=sampler, daemon=True)
        th.start()
        t0 = time.perf_counter()
        futs = [gen.submit(p, max_new_tokens=max_new) for p in prompts]
        outs = [f.result(600) for f in futs]
        wall = time.perf_counter() - t0
        stop_flag.set()
        th.join(timeout=1)
        toks = sum(len(o) for o in outs)
        return outs, {"wall_s": round(wall, 3), "tokens": toks,
                      "tokens_per_s": round(toks / wall, 2) if wall
                      else 0.0,
                      "peak_concurrent_rows": peak[0]}

    def leak_free(gen) -> bool:
        kv = gen.stats()["kv_pool"]
        return kv["blocks_free"] + kv["radix_nodes"] >= kv["blocks_total"]

    results = {
        "model": model, "tp": tp, "block_size": block_size,
        "max_seq": max_seq, "single_max_seq": single_max_seq,
        "blocks_per_device": blocks_per_device,
        "kv_budget_bytes_per_device": int(blocks_per_device * bpb),
        "n_requests": n_requests, "max_new": max_new,
    }

    # -- facet 1: the model+KV footprint a single chip refuses ---------
    refusal = None
    try:
        ContinuousGenerator(
            spec, params=params, dtype="float32", n_slots=n_slots,
            max_seq=max_seq, prefill_chunk=block_size, mixed_step=True,
            kv_block_size=block_size,
            kv_blocks=blocks_per_device + 1,  # +1: the null block
            prefix_sharing=False)
    except ValueError as exc:
        refusal = str(exc)
    results["single_device_refusal"] = refusal
    ok_refused = refusal is not None and "max_seq row" in refusal

    tp_gen = ContinuousGenerator(
        spec, params=params, dtype="float32", n_slots=n_slots,
        max_seq=max_seq, prefill_chunk=block_size, mixed_step=True,
        kv_block_size=block_size, kv_blocks=tp * blocks_per_device + 1,
        prefix_sharing=False, tp=tp)
    try:
        results["tp_param_bytes_per_device"] = param_bytes_per_device(
            tp_gen.params)
        results["single_param_bytes_per_device"] = \
            param_bytes_per_device(params)
        tp_gen.generate([short_prompts[0][:8]], max_new_tokens=2)  # warm
        long1 = tp_gen.generate([long_prompt], max_new_tokens=max_new)
        long2 = tp_gen.generate([long_prompt], max_new_tokens=max_new)
        s1, r1 = run_burst(tp_gen, short_prompts)
        s2, r2 = run_burst(tp_gen, short_prompts)
        st = tp_gen.stats()
        m = st["mixed"]
        results["tp_arm"] = {
            "kv_blocks": tp * blocks_per_device,
            "long_stream_tokens": len(long1[0]),
            "ticks": m["ticks"], "dispatches": m["dispatches"],
            **r1,
        }
        results["tp_arm"]["peak_concurrent_rows"] = max(
            r1["peak_concurrent_rows"], r2["peak_concurrent_rows"])
        tp_deterministic = (s1 == s2 and long1 == long2)
        tp_single_dispatch = m["ticks"] == m["dispatches"]
        tp_leaks = leak_free(tp_gen)
        tp_long_complete = len(long1[0]) == max_new
    finally:
        tp_gen.stop()

    # -- identity reference: an UNCONSTRAINED single-device lane -------
    # (ample blocks — exists only to prove the TP arm's streams are
    # byte-identical to single-device serving; the budget-constrained
    # single arm below cannot serve max_seq=256 at all).
    ref_gen = ContinuousGenerator(
        spec, params=params, dtype="float32", n_slots=n_slots,
        max_seq=max_seq, prefill_chunk=block_size, mixed_step=True,
        kv_block_size=block_size, prefix_sharing=False)
    try:
        ref_long = ref_gen.generate([long_prompt], max_new_tokens=max_new)
        ref_short, _ = run_burst(ref_gen, short_prompts)
    finally:
        ref_gen.stop()
    streams_identical = (s1 == ref_short and long1 == ref_long)

    # -- facet 2: capacity at equal per-device budget ------------------
    # The single-device arm only exists at this budget by SHRINKING its
    # context window (single_max_seq) — the honest comparison point.
    single_gen = ContinuousGenerator(
        spec, params=params, dtype="float32", n_slots=n_slots,
        max_seq=single_max_seq, prefill_chunk=block_size,
        mixed_step=True, kv_block_size=block_size,
        kv_blocks=blocks_per_device + 1, prefix_sharing=False)
    try:
        single_gen.generate([short_prompts[0][:8]], max_new_tokens=2)
        t1, q1 = run_burst(single_gen, short_prompts)
        t2, q2 = run_burst(single_gen, short_prompts)
        single_deterministic = t1 == t2
        single_leaks = leak_free(single_gen)
        # Full-length streams only: the pool must have bound at
        # admission (parked), never by mid-stream starvation.
        streams_complete = (all(len(o) == max_new for o in t1 + t2)
                            and all(len(o) == max_new for o in s1 + s2))
        results["single_arm"] = {
            "kv_blocks": blocks_per_device, "max_seq": single_max_seq,
            **q1,
        }
        results["single_arm"]["peak_concurrent_rows"] = max(
            q1["peak_concurrent_rows"], q2["peak_concurrent_rows"])
    finally:
        single_gen.stop()

    tp_peak = results["tp_arm"]["peak_concurrent_rows"]
    single_peak = results["single_arm"]["peak_concurrent_rows"]
    results["peak_rows_gain"] = round(tp_peak / max(1, single_peak), 2)
    results["param_bytes_per_device_ratio"] = round(
        results["single_param_bytes_per_device"]
        / max(1, results["tp_param_bytes_per_device"]), 2)
    results["checks_passed"] = bool(
        # The single chip provably refuses the model+KV footprint...
        ok_refused
        # ...the TP arm serves it to completion at the same per-device
        # budget...
        and tp_long_complete
        # ...byte-identically to single-device serving...
        and streams_identical
        # ...with exactly one SPMD dispatch per tick...
        and tp_single_dispatch
        # ...deterministically on both arms, full-length streams
        # (admission-bound pools, no starved early completions), zero
        # blocks leaked...
        and tp_deterministic and single_deterministic
        and streams_complete
        and tp_leaks and single_leaks
        # ...and more concurrent rows on the pooled blocks.
        and tp_peak > single_peak)
    return results


def run_mixed_ab(model: str = "gpt2-small-test", n_short: int = 12,
                 n_long: int = 4, max_new: int = 40, long_max_new: int = 4,
                 short_prompt_len: int = 8, long_prompt_len: int = 440,
                 mean_gap_ms: float = 25.0, dtype: str = "float32",
                 block_size: int = 16, max_seq: int = 512,
                 step_chunk: int = 8, prefill_chunk: int = 256,
                 mixed_budget: int = 16, n_slots: int = 4,
                 model_kwargs: Optional[dict] = None,
                 repeats: int = 2) -> dict:
    """Mixed stepping vs the two-thread paged scheduler under long-prompt
    interference (the --mixed-step tentpole A/B). Workload: Poisson
    arrivals of short decode-heavy requests with long prompts injected
    between them — the pattern whose admission prefills head-of-line
    block decode dispatches in the two-path scheduler. Both arms run the
    SAME paged pool, prompts, seeds, and arrival gaps; only the stepping
    differs. Reports, per arm:

    - ITL p50/p99 over the short rows' token inter-arrival gaps (each
      delivery's gap is charged to its first token, 0 to the rest —
      exactly what a streaming client sees), TTFT p50/p99, tokens/s;
    - device dispatches per generated token, from the scheduler's own
      counters (baseline: decode chunks + admission dispatches; mixed:
      the per-tick ragged dispatch);
    - one-dispatch-per-tick asserted from the mixed stats (ticks and
      dispatches are counted at different code sites).

    A seeded-identity check reruns two prompts on a DENSE scheduler and
    requires byte-identical streams from the mixed arm. CPU mesh by
    default; the on-chip campaign's `mixed` stage reruns it on the
    device."""
    import random

    import jax

    from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                            create_model)
    from tpu_engine.runtime.scheduler import ContinuousGenerator

    _ensure_builtin_models_imported()
    # The registry test model's default geometry is dispatch-overhead-
    # dominated on CPU (a 16-wide tick costs less than a scheduler
    # wakeup), which buries the admission-interference signal in noise —
    # by default the scenario sizes it up (d256 x 4 layers) so compute,
    # not jitter, is measured. `model_kwargs={}` keeps the tiny
    # geometry (the --quick smoke).
    if model_kwargs is None and model == "gpt2-small-test":
        model_kwargs = dict(d_model=256, n_layers=4, n_heads=8,
                            d_ff=1024, vocab=2048)
    spec = create_model(model, max_seq=max_seq, **(model_kwargs or {}))
    params = spec.init(jax.random.PRNGKey(0))
    rnd = random.Random(42)
    width = -(-max_seq // block_size)
    kv_blocks = n_slots * width + 1

    # One interleaved arrival schedule: a long prompt after every
    # n_short//n_long short requests. (kind, prompt, max_new, seed)
    shorts = [[rnd.randrange(1, 200) for _ in range(short_prompt_len)]
              for _ in range(n_short)]
    longs = [[rnd.randrange(1, 200) for _ in range(long_prompt_len)]
             for _ in range(n_long)]
    schedule = []
    li, stride = 0, max(1, n_short // max(1, n_long))
    for i, p in enumerate(shorts):
        schedule.append(("short", p, max_new, 100 + i))
        if (i + 1) % stride == 0 and li < n_long:
            schedule.append(("long", longs[li], long_max_new, 500 + li))
            li += 1
    gaps = [rnd.expovariate(1000.0 / mean_gap_ms) / 1000.0
            for _ in schedule]

    # The shared nearest-rank helper — one definition with /trace's
    # summary percentiles, so the bench's p50/p99 and the server's agree.
    from tpu_engine.utils.tracing import percentile

    import queue as _q

    class _StampQueue(_q.Queue):
        """Stream queue that timestamps each delivery AT put() — i.e. on
        the scheduler's decode thread. ITL measured here is the server's
        actual emission cadence; a consumer thread per request would add
        GIL-wakeup jitter of the same magnitude as a tick and measure
        the load generator instead of the scheduler."""

        def __init__(self):
            super().__init__()
            self.stamps: list = []

        def put(self, item, **kw):
            if item is not None:
                self.stamps.append((time.perf_counter(), len(item)))
            super().put(item, **kw)

    def run_arm(mixed: bool) -> Tuple[dict, list]:
        gen = ContinuousGenerator(
            spec, params=params, dtype=dtype, n_slots=n_slots,
            step_chunk=step_chunk, max_seq=max_seq,
            kv_block_size=block_size, kv_blocks=kv_blocks,
            prefill_chunk=prefill_chunk, prefix_sharing=False,
            mixed_step=mixed,
            mixed_token_budget=mixed_budget if mixed else 0)
        try:
            # Warm every compiled width outside the timed window (short
            # bucket, long bucket, decode, and the mixed tick widths) —
            # then SNAPSHOT the lifetime dispatch counters so the
            # warm-up's dispatches and tokens stay out of BOTH sides of
            # the dispatches-per-token ratio.
            gen.generate([shorts[0]], max_new_tokens=2)
            gen.generate([longs[0][:long_prompt_len]], max_new_tokens=2)
            warm = gen.stats()

            futs, queues, submit_ts = [], [], []
            t0 = time.perf_counter()
            for i, (kind, prompt, mn, seed) in enumerate(schedule):
                time.sleep(gaps[i])
                q = _StampQueue()
                queues.append(q)
                submit_ts.append(time.perf_counter())
                futs.append(gen.submit(prompt, max_new_tokens=mn,
                                       temperature=0.7, seed=seed,
                                       stream=q))
            outs = [f.result(600) for f in futs]
            wall = time.perf_counter() - t0
            st = gen.stats()
        finally:
            gen.stop()

        itl, ttft = [], []
        for i, (kind, _p, _mn, _s) in enumerate(schedule):
            stamps = queues[i].stamps
            if kind != "short" or not stamps:
                continue
            ttft.append(stamps[0][0] - submit_ts[i])
            prev = stamps[0][0]
            for t, n in stamps[1:]:
                itl.append(t - prev)          # charged to the 1st token
                itl.extend([0.0] * (n - 1))
                prev = t
        itl.sort()
        ttft.sort()
        tokens = sum(len(o) for o in outs)
        if mixed:
            m, m0 = st["mixed"], warm["mixed"]
            dispatches = m["dispatches"] - m0["dispatches"]
            new_tokens = (m["decode_tokens"] + m["prefill_tokens"]
                          - m0["decode_tokens"] - m0["prefill_tokens"])
        else:
            dispatches = (st.get("chunks", 0) - warm.get("chunks", 0)
                          + st.get("admission_dispatches", 0)
                          - warm.get("admission_dispatches", 0))
            new_tokens = tokens + sum(len(p) for _k, p, _m, _s in schedule)
        arm = {
            "itl_p50_ms": round((percentile(itl, 50) or 0) * 1e3, 2),
            "itl_p99_ms": round((percentile(itl, 99) or 0) * 1e3, 2),
            "ttft_p50_ms": round((percentile(ttft, 50) or 0) * 1e3, 2),
            "ttft_p99_ms": round((percentile(ttft, 99) or 0) * 1e3, 2),
            "tokens": tokens,
            "tokens_per_s": round(tokens / wall, 2) if wall else 0.0,
            "wall_s": round(wall, 3),
            "device_dispatches": int(dispatches),
            "dispatches_per_token": round(dispatches / max(1, new_tokens),
                                          4),
        }
        if mixed:
            # Lifetime counters (warm-up included) for the invariant;
            # device_dispatches above is the measured-window count.
            arm["lifetime_ticks"] = m["ticks"]
            arm["lifetime_dispatches"] = m["dispatches"]
            arm["one_dispatch_per_tick"] = (m["dispatches"] == m["ticks"])
            arm["coscheduled_ticks"] = m["coscheduled_ticks"]
            arm["cow_copies"] = st["kv_pool"]["cow_copies"]
        return arm, outs

    results = {"model": model, "model_kwargs": model_kwargs or {},
               "max_seq": max_seq,
               "block_size": block_size, "n_slots": n_slots,
               "step_chunk": step_chunk, "prefill_chunk": prefill_chunk,
               "mixed_token_budget": mixed_budget,
               "workload": {"short": n_short, "long": n_long,
                            "short_prompt_len": short_prompt_len,
                            "long_prompt_len": long_prompt_len,
                            "mean_gap_ms": mean_gap_ms}}
    # Arms alternate and each keeps its lowest-p99 repeat: the two-CPU
    # bench host runs arms sequentially, so a background stall mid-run
    # lands on one arm only — best-of-N per arm is the standard
    # least-external-interference estimate (both arms get the same
    # chance). Stream identity is asserted across EVERY repeat.
    baseline = mixed_arm = None
    base_outs = mixed_outs = None
    streams_stable = True
    for rep in range(max(1, repeats)):
        b_arm, b_o = run_arm(mixed=False)
        m_arm, m_o = run_arm(mixed=True)
        streams_stable &= (b_o == m_o)
        if base_outs is not None:
            streams_stable &= (b_o == base_outs and m_o == mixed_outs)
        base_outs, mixed_outs = b_o, m_o
        if baseline is None or b_arm["itl_p99_ms"] < baseline["itl_p99_ms"]:
            baseline = b_arm
        if (mixed_arm is None
                or m_arm["itl_p99_ms"] < mixed_arm["itl_p99_ms"]):
            mixed_arm = m_arm
        record_partial(f"mixed_ab_rep{rep}",
                       {"baseline_itl_p99_ms": b_arm["itl_p99_ms"],
                        "mixed_itl_p99_ms": m_arm["itl_p99_ms"]})
    results["repeats"] = max(1, repeats)
    results["paged_two_thread"] = baseline
    record_partial("mixed_ab_baseline", baseline)
    results["mixed"] = mixed_arm
    record_partial("mixed_ab_mixed", mixed_arm)

    # Seeded streams must be identical across arms (every repeat) AND vs
    # the dense path.
    results["streams_match_baseline"] = streams_stable
    dense = ContinuousGenerator(spec, params=params, dtype=dtype,
                                n_slots=2, step_chunk=step_chunk,
                                max_seq=max_seq)
    try:
        idx = [0, 1]
        dense_outs = [
            dense.generate([schedule[i][1]],
                           max_new_tokens=schedule[i][2],
                           temperature=0.7, seed=schedule[i][3])[0]
            for i in idx]
        results["streams_match_dense"] = (
            dense_outs == [mixed_outs[i] for i in idx])
    finally:
        dense.stop()
    results["itl_p99_speedup"] = round(
        baseline["itl_p99_ms"] / max(mixed_arm["itl_p99_ms"], 1e-9), 2)
    # p50 of per-token gaps is 0 whenever chunked deliveries dominate
    # (7 of 8 tokens in a chunk arrive at gap 0) — a ratio against it is
    # noise, so it is reported only when both medians are nonzero.
    results["itl_p50_speedup"] = (
        round(baseline["itl_p50_ms"] / mixed_arm["itl_p50_ms"], 2)
        if baseline["itl_p50_ms"] > 0 and mixed_arm["itl_p50_ms"] > 0
        else None)
    results["checks_passed"] = bool(
        mixed_arm.get("one_dispatch_per_tick")
        and results["streams_match_dense"]
        and results["streams_match_baseline"])
    return results


def run_unified_ab(model: str = "gpt2-small-test", n_generate: int = 10,
                   n_score: int = 20, max_new: int = 24,
                   prompt_len: int = 10, score_prompt_len: int = 12,
                   score_completion_len: int = 6,
                   mean_gap_ms: float = 12.0, dtype: str = "float32",
                   n_slots: int = 4, max_seq: int = 256,
                   step_chunk: int = 4,
                   model_kwargs: Optional[dict] = None,
                   repeats: int = 2) -> dict:
    """Unified stateless serving vs the two-lane split (the PR 20
    tentpole A/B). Workload: one Poisson arrival process mixing
    generate streams and score (teacher-forced logprob) requests — the
    mixed-modality traffic ROADMAP item 5 names. Two arms at equal
    resources (same device, same scheduler slot count, same score batch
    cap, same prompts/seeds/arrival gaps):

    - **split**: the continuous scheduler serves generate only; score
      requests ride a dedicated ``BatchProcessor`` lane whose forwards
      run UNCOORDINATED with decode ticks on their own dispatch thread
      (the pre-fold production shape);
    - **unified**: one ``ContinuousGenerator`` with a ``score_provider``
      — scores admit as single-tick rows in the same slot pool and
      dispatch as one grouped forward per tick, interleaved with decode
      by the scheduler itself.

    Reports per arm and class: score latency p50/p99, generate
    completion latency p50/p99 and TTFT p99. Checks: score logprobs and
    generate streams byte-identical across arms AND across every
    repeat; the unified arm's stateless counters hold
    ticks == dispatches (one grouped dispatch per tick with one-shot
    rows in the batch). CPU mesh by default; the on-chip campaign's
    ``unified`` stage reruns it on the device."""
    import random

    import jax

    from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                            create_model)
    from tpu_engine.runtime.batch_processor import BatchProcessor
    from tpu_engine.runtime.generator import Generator
    from tpu_engine.runtime.scheduler import ContinuousGenerator
    from tpu_engine.utils.tracing import percentile

    _ensure_builtin_models_imported()
    # Same sizing rationale as run_mixed_ab: the tiny registry geometry
    # is dispatch-overhead-dominated on CPU; size it up so compute, not
    # scheduler jitter, dominates. model_kwargs={} keeps it tiny
    # (--quick).
    if model_kwargs is None and model == "gpt2-small-test":
        model_kwargs = dict(d_model=256, n_layers=4, n_heads=8,
                            d_ff=1024, vocab=2048)
    spec = create_model(model, max_seq=max_seq, **(model_kwargs or {}))
    params = spec.init(jax.random.PRNGKey(0))
    rnd = random.Random(20)

    # ONE scorer instance serves both arms: shared compiled caches and
    # — by construction — identical bucketed-pad-split numerics, so any
    # cross-arm output difference is a scheduling bug, not jit noise.
    scorer = Generator(spec, params=params, dtype=dtype)

    gens = [[rnd.randrange(1, 200) for _ in range(prompt_len)]
            for _ in range(n_generate)]
    scores = [([rnd.randrange(1, 200) for _ in range(score_prompt_len)],
               [rnd.randrange(1, 200) for _ in range(score_completion_len)])
              for _ in range(n_score)]
    # One interleaved arrival schedule shared by both arms.
    schedule = []
    gi, si = 0, 0
    stride = max(1, n_score // max(1, n_generate))
    while gi < n_generate or si < n_score:
        if gi < n_generate:
            schedule.append(("generate", gi))
            gi += 1
        for _ in range(stride):
            if si < n_score:
                schedule.append(("score", si))
                si += 1
    gaps = [rnd.expovariate(1000.0 / mean_gap_ms) / 1000.0
            for _ in schedule]

    from concurrent.futures import ThreadPoolExecutor
    import queue as _q

    def run_arm(unified: bool) -> Tuple[dict, dict]:
        gen = ContinuousGenerator(
            spec, params=params, dtype=dtype, n_slots=n_slots,
            step_chunk=step_chunk, max_seq=max_seq,
            score_provider=(lambda: scorer) if unified else None)
        proc = None
        if not unified:
            # The retired lane: its own dispatch thread, its own queue,
            # equal batch cap — forwards land whenever they form,
            # uncoordinated with the scheduler's ticks.
            proc = BatchProcessor(
                n_slots, 5.0,
                lambda items: scorer.score([p for p, _c in items],
                                           [c for _p, c in items]),
                name="split-score-lane")
            proc.start()
        try:
            # Warm every compiled path outside the timed window — decode
            # at full slot width, and the scorer at every batch width a
            # grouped dispatch (either arm's) can form. A mid-run jit
            # compile would land on different threads in the two arms
            # (side lane vs decode loop) and measure XLA, not
            # scheduling.
            gen.generate([gens[i % len(gens)] for i in range(n_slots)],
                         max_new_tokens=2)
            for k in range(1, n_slots + 1):
                scorer.score([scores[0][0]] * k, [scores[0][1]] * k)
            if unified:
                gen.submit_score(*scores[0]).result(120)
            warm = gen.stats()

            g_lat = [None] * n_generate
            g_ttft = [None] * n_generate
            g_out = [None] * n_generate
            s_lat = [None] * n_score
            s_out = [None] * n_score

            def score_call(idx, t_sub):
                p, c = scores[idx]
                if unified:
                    lps, _us = gen.submit_score(p, c).result(600)
                else:
                    lps = proc.process((p, c))
                s_lat[idx] = time.perf_counter() - t_sub
                s_out[idx] = list(lps)

            with ThreadPoolExecutor(max_workers=8) as ex:
                futs, sfuts = [], []
                t0 = time.perf_counter()
                for i, (kind, idx) in enumerate(schedule):
                    time.sleep(gaps[i])
                    t_sub = time.perf_counter()
                    if kind == "generate":
                        q = _q.Queue()

                        def first_tok(qq=q, j=idx, ts=t_sub):
                            tok = qq.get(timeout=600)
                            if tok is not None:
                                g_ttft[j] = time.perf_counter() - ts

                        ex.submit(first_tok)
                        futs.append((idx, t_sub,
                                     gen.submit(gens[idx],
                                                max_new_tokens=max_new,
                                                temperature=0.7,
                                                seed=900 + idx,
                                                stream=q)))
                    else:
                        sfuts.append(ex.submit(score_call, idx, t_sub))
                for idx, t_sub, f in futs:
                    g_out[idx] = f.result(600)
                    g_lat[idx] = time.perf_counter() - t_sub
                for f in sfuts:
                    f.result(600)
                wall = time.perf_counter() - t0
            st = gen.stats()
        finally:
            gen.stop()
            if proc is not None:
                proc.stop()

        s_sorted = sorted(s_lat)
        g_sorted = sorted(g_lat)
        ttft_sorted = sorted(t for t in g_ttft if t is not None)
        arm = {
            "score_p50_ms": round((percentile(s_sorted, 50) or 0) * 1e3,
                                  2),
            "score_p99_ms": round((percentile(s_sorted, 99) or 0) * 1e3,
                                  2),
            "generate_p50_ms": round((percentile(g_sorted, 50) or 0)
                                     * 1e3, 2),
            "generate_p99_ms": round((percentile(g_sorted, 99) or 0)
                                     * 1e3, 2),
            "ttft_p99_ms": round((percentile(ttft_sorted, 99) or 0)
                                 * 1e3, 2),
            "wall_s": round(wall, 3),
        }
        if unified:
            su, sw = st["stateless"], warm["stateless"]
            arm["stateless_ticks"] = su["ticks"] - sw["ticks"]
            arm["stateless_dispatches"] = (su["dispatches"]
                                           - sw["dispatches"])
            arm["score_rows"] = su["score_rows"] - sw["score_rows"]
            # One grouped dispatch per tick with one-shot rows in the
            # batch — the ticks==dispatches invariant, counted at two
            # different code sites (lifetime counters).
            arm["ticks_eq_dispatches"] = (su["ticks"] == su["dispatches"])
        return arm, {"gen": g_out, "score": s_out}

    results = {"model": model, "model_kwargs": model_kwargs or {},
               "n_slots": n_slots, "step_chunk": step_chunk,
               "max_seq": max_seq,
               "workload": {"generate": n_generate, "score": n_score,
                            "max_new": max_new,
                            "prompt_len": prompt_len,
                            "score_prompt_len": score_prompt_len,
                            "score_completion_len": score_completion_len,
                            "mean_gap_ms": mean_gap_ms}}
    # Arms alternate; each keeps its lowest-p99 repeat (the same
    # best-of-N least-external-interference estimate every AB scenario
    # here uses). Output identity is asserted across EVERY repeat and
    # across arms.
    split_arm = unified_arm = None
    prev_outs = None
    identical = True
    for rep in range(max(1, repeats)):
        s_arm, s_o = run_arm(unified=False)
        u_arm, u_o = run_arm(unified=True)
        identical &= (s_o == u_o)
        if prev_outs is not None:
            identical &= (s_o == prev_outs)
        prev_outs = s_o
        if (split_arm is None
                or s_arm["score_p99_ms"] < split_arm["score_p99_ms"]):
            split_arm = s_arm
        if (unified_arm is None
                or u_arm["score_p99_ms"] < unified_arm["score_p99_ms"]):
            unified_arm = u_arm
        record_partial(f"unified_ab_rep{rep}",
                       {"split_score_p99_ms": s_arm["score_p99_ms"],
                        "unified_score_p99_ms": u_arm["score_p99_ms"],
                        "split_generate_p99_ms":
                            s_arm["generate_p99_ms"],
                        "unified_generate_p99_ms":
                            u_arm["generate_p99_ms"]})
    results["repeats"] = max(1, repeats)
    results["split"] = split_arm
    results["unified"] = unified_arm
    record_partial("unified_ab_split", split_arm)
    record_partial("unified_ab_unified", unified_arm)
    results["outputs_identical"] = identical
    results["score_p99_speedup"] = round(
        split_arm["score_p99_ms"]
        / max(unified_arm["score_p99_ms"], 1e-9), 2)
    results["generate_p99_speedup"] = round(
        split_arm["generate_p99_ms"]
        / max(unified_arm["generate_p99_ms"], 1e-9), 2)
    results["checks_passed"] = bool(
        identical and unified_arm.get("ticks_eq_dispatches")
        and results["score_p99_speedup"] >= 1.0
        and results["generate_p99_speedup"] >= 1.0)
    return results


def run_spec_continuous_ab(model: str = "gpt2-small-test",
                           max_new: int = 96, k: int = 4,
                           dtype: str = "float32", block_size: int = 16,
                           max_seq: int = 256, n_slots: int = 4,
                           step_chunk: int = 8, prefill_chunk: int = 32,
                           model_kwargs: Optional[dict] = None,
                           prompts: Optional[list] = None) -> dict:
    """Continuous speculative decoding vs the plain paged scheduler
    (the --spec-k tentpole A/B) — COUNTER-based, not wall-clock: the
    speculation win is sequential target passes per token, and the
    scheduler's own counters state it exactly.

    Workload: repetitive greedy streams (prompts whose continuations
    loop — the repeated-text regime prompt-lookup drafting exists for;
    retrieval-stuffed prompts and code behave this way on real models).
    Both arms run the same paged pool, prompts, and seeds; the spec arm
    adds the n-gram drafter with depth ``k``. Reports:

    - tokens_per_row_dispatch (same name as the scheduler stat): emitted
      tokens — accepted draft prefix + the corrected/bonus token — per
      (row, tick) emission pair from the spec arm's counters, i.e. the
      mean per-row stream advance per verify dispatch. NOT the raw
      `accepted_tokens` counter, which counts draft-accepted slots only.
      The plain scheduler advances every row exactly 1 token per
      sequential target pass, so this IS the speedup ratio in sequential
      passes (asserted >= 1.5x here);
    - one-dispatch-per-tick from the spec stats (ticks and dispatches
      are counted at different code sites);
    - byte-identical greedy streams spec vs plain vs a dense rerun;
    - a mid-run deadline-cancelled row returns every pool block.

    Wall-clock tokens/s are reported for color only — on the CPU mesh
    the verify window's extra host work can mask the dispatch saving
    that dominates on a real chip (the on-chip campaign's `spec` stage
    reruns this there)."""
    import jax

    from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                            create_model)
    from tpu_engine.runtime.scheduler import ContinuousGenerator
    from tpu_engine.utils.deadline import Deadline, DeadlineExceeded

    _ensure_builtin_models_imported()
    spec = create_model(model, max_seq=max_seq, **(model_kwargs or {}))
    params = spec.init(jax.random.PRNGKey(0))
    if prompts is None:
        # Probed loopy-continuation prompts for the registry test model
        # (streams with 0.5-0.7 three-gram predictability — the
        # "repetitive workload"); other models get phrase-repeat prompts.
        if model == "gpt2-small-test" and not model_kwargs:
            base = [[153, 128, 149, 117, 18, 24], [128, 175, 137, 110],
                    [135, 127, 88, 187, 115, 74],
                    [122, 179, 171, 17, 16, 188],
                    [10, 23, 112, 108], [120, 150, 117, 93, 77, 64]]
            prompts = base + base[:2]
        else:
            import random as _r
            rnd = _r.Random(42)
            prompts = [([rnd.randrange(1, min(spec.config.vocab, 1000))
                         for _ in range(6)] * 5)[:24] for _ in range(8)]
    width = -(-max_seq // block_size)
    kv_blocks = n_slots * width + 1
    common_kw = dict(params=params, dtype=dtype, n_slots=n_slots,
                     step_chunk=step_chunk, max_seq=max_seq,
                     kv_block_size=block_size, kv_blocks=kv_blocks,
                     prefill_chunk=prefill_chunk)

    def run_arm(spec_k: int) -> Tuple[dict, list]:
        gen = ContinuousGenerator(spec, spec_k=spec_k, **common_kw)
        try:
            gen.generate([prompts[0]], max_new_tokens=4)  # warm compiles
            warm = gen.stats()
            t0 = time.perf_counter()
            outs = gen.generate(prompts, max_new_tokens=max_new)
            wall = time.perf_counter() - t0
            st = gen.stats()
            arm = {"tokens": sum(len(o) for o in outs),
                   "wall_s": round(wall, 3),
                   "tokens_per_s": round(sum(len(o) for o in outs)
                                         / wall, 2) if wall else 0.0}
            if spec_k:
                s, s0 = st["spec"], warm["spec"]
                emitted = s["emitted_tokens"] - s0["emitted_tokens"]
                row_ticks = s["row_ticks"] - s0["row_ticks"]
                arm.update({
                    "spec_dispatches": s["dispatches"] - s0["dispatches"],
                    "proposed_tokens": (s["proposed_tokens"]
                                        - s0["proposed_tokens"]),
                    "accepted_tokens": (s["accepted_tokens"]
                                        - s0["accepted_tokens"]),
                    "emitted_tokens": emitted,
                    "row_dispatches": row_ticks,
                    "tokens_per_row_dispatch": round(
                        emitted / max(1, row_ticks), 3),
                    "accept_ratio": round(
                        (s["accepted_tokens"] - s0["accepted_tokens"])
                        / max(1, s["proposed_tokens"]
                              - s0["proposed_tokens"]), 3),
                    "one_dispatch_per_tick": (s["ticks"]
                                              == s["dispatches"]),
                })
                # Cancelled-row block return, validated on the live
                # scheduler: a doomed long request expires between verify
                # ticks and must hand every block back.
                try:
                    gen.submit(prompts[0] * 3, max_new_tokens=max_new,
                               deadline=Deadline.after_ms(1)).result(60)
                    arm["cancelled_row_expired"] = False
                except DeadlineExceeded:
                    arm["cancelled_row_expired"] = True
                deadline = time.time() + 15
                returned = False
                while time.time() < deadline and not returned:
                    stt = gen.stats()
                    pool = stt["kv_pool"]
                    returned = (stt["active"] == 0
                                and pool["blocks_free"]
                                + pool["radix_nodes"]
                                >= pool["blocks_total"])
                    if not returned:
                        time.sleep(0.05)
                arm["cancelled_row_blocks_returned"] = returned
            return arm, outs
        finally:
            gen.stop()

    results = {"model": model, "max_seq": max_seq, "k": k,
               "block_size": block_size, "n_slots": n_slots,
               "max_new_tokens": max_new, "n_prompts": len(prompts),
               "draft": "ngram"}
    plain_arm, plain_outs = run_arm(0)
    record_partial("spec_cont_plain", plain_arm)
    spec_arm, spec_outs = run_arm(k)
    record_partial("spec_cont_spec", spec_arm)
    results["plain_paged"] = plain_arm
    results["spec"] = spec_arm
    results["streams_match_plain"] = spec_outs == plain_outs

    # Dense cross-check on two prompts: the spec arm's streams are the
    # DENSE scheduler's too (transitively pins all three layouts).
    dense = ContinuousGenerator(spec, params=params, dtype=dtype,
                                n_slots=2, step_chunk=step_chunk,
                                max_seq=max_seq)
    try:
        dense_outs = [dense.generate([prompts[i]],
                                     max_new_tokens=max_new)[0]
                      for i in (0, 1)]
        results["streams_match_dense"] = (
            dense_outs == [spec_outs[i] for i in (0, 1)])
    finally:
        dense.stop()
    ratio = spec_arm["tokens_per_row_dispatch"]
    # The plain scheduler advances 1 token per row per sequential target
    # pass by construction — `ratio` IS the sequential-pass speedup.
    results["tokens_per_dispatch_ratio"] = ratio
    results["checks_passed"] = bool(
        ratio >= 1.5
        and spec_arm["one_dispatch_per_tick"]
        and spec_arm["cancelled_row_expired"]
        and spec_arm["cancelled_row_blocks_returned"]
        and results["streams_match_plain"]
        and results["streams_match_dense"])
    return results


def run_crash_ab(n_streams: int = 12, max_new: int = 48,
                 model: str = "gpt2-small-test") -> dict:
    """Crash-tolerant streaming A/B (DESIGN.md "Crash-tolerant
    streaming"): kill -9 a worker process while its /generate/stream
    load is mid-generation, with the gateway's stream journal + health
    prober ON vs OFF.

    Four standalone worker processes are spawned once; each arm routes
    across three of them through an in-process gateway and kills that
    arm's designated victim the moment a victim-primary stream is
    provably mid-flight. Reported per arm:

    - stream_completion_rate: streams ending in a clean terminal event;
    - identical_rate: streams byte-identical to an unkilled blocking
      control run (greedy AND seeded-sampled — the resume determinism
      rule);
    - availability: short blocking /generate probes fired AFTER the kill
      (ring failover answers these in both arms; the prober just makes
      the dead lane invisible sooner);
    - resumed_streams / prober_ejections (ON arm only).

    The A/B criterion: failover ON completes and matches 100% of
    streams; OFF loses exactly the mid-flight victim streams — the
    measured cost of binding a request to a lane instead of the fleet."""
    import random
    import signal

    from tools.fault_injection import (
        control_oracle,
        drive_streams_with_kill,
        launch_worker_procs,
        rid_for_lane,
        tally_streams,
        victim_lane_for_port,
    )
    from tpu_engine.serving.gateway import Gateway
    from tpu_engine.utils.config import GatewayConfig

    ports, procs = launch_worker_procs(4)
    try:
        def run_arm(indices, victim_idx, failover: bool) -> dict:
            gw = Gateway(
                [f"127.0.0.1:{ports[i]}" for i in indices],
                GatewayConfig(
                    failover_streams=failover,
                    health_probe_interval_s=0.25 if failover else 0.0,
                    health_probe_failures=2))
            try:
                lanes = gw.worker_names()
                victim_lane = victim_lane_for_port(
                    lanes, ports[victim_idx])

                requests = []
                for k in range(n_streams):
                    lane = (victim_lane if k % 3 == 0
                            else lanes[k % len(lanes)])
                    params = ({} if k % 2 == 0
                              else {"temperature": 0.9, "seed": 300 + k})
                    tag = f"{'on' if failover else 'off'}{k}"
                    requests.append({
                        "request_id": rid_for_lane(gw._ring, lane, tag),
                        "prompt_tokens": [(k * 11 + j) % 90 + 1
                                          for j in range(5 + k % 4)],
                        "max_new_tokens": (max_new + 12
                                           if lane == victim_lane
                                           else max_new),
                        **params})
                victim_rids = {r["request_id"] for r in requests
                               if gw._ring.get_node(r["request_id"])
                               == victim_lane}
                control = control_oracle(ports[0], requests)

                def kill_victim():
                    procs[victim_idx].send_signal(signal.SIGKILL)
                    procs[victim_idx].wait(timeout=10)

                results, killed = drive_streams_with_kill(
                    gw, requests, victim_rids, kill_victim,
                    random.Random(1 if failover else 2))
                # Availability AFTER the kill: short blocking probes;
                # ring failover answers them in both arms.
                avail_ok = 0
                for i in range(6):
                    try:
                        gw.route_generate(
                            {"request_id": f"avail_{failover}_{i}",
                             "prompt_tokens": [7, i + 1],
                             "max_new_tokens": 4})
                        avail_ok += 1
                    except Exception:
                        pass
                complete, identical, resumed = tally_streams(
                    results, control)
                fo = gw.get_stats().get("failover", {})
                return {
                    "failover": failover, "streams": len(requests),
                    "victim_primary_streams": len(victim_rids),
                    "victim_killed_mid_stream": killed,
                    "completed": complete,
                    "stream_completion_rate": round(
                        complete / len(requests), 3),
                    "identical": identical,
                    "identical_rate": round(
                        identical / len(requests), 3),
                    "availability_post_kill": round(avail_ok / 6, 3),
                    "resumed_streams": resumed,
                    "resumes_attempted": fo.get("resumes_attempted", 0),
                    "tokens_replayed": fo.get("tokens_replayed", 0),
                    "prober_ejections": fo.get("prober_ejections", 0),
                }
            finally:
                gw.stop()

        on = run_arm([0, 1, 2], 1, True)
        record_partial("crash_on", on)
        off = run_arm([0, 2, 3], 3, False)
        record_partial("crash_off", off)
        results = {"model": model, "n_streams_per_arm": n_streams,
                   "failover_on": on, "failover_off": off}
        results["checks_passed"] = bool(
            on["victim_killed_mid_stream"]
            and off["victim_killed_mid_stream"]
            and on["stream_completion_rate"] == 1.0
            and on["identical_rate"] == 1.0
            and on["resumed_streams"] >= 1
            and on["prober_ejections"] >= 1
            and off["stream_completion_rate"] < 1.0)
        return results
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def run_drain_ab(n_streams: int = 10, max_new: int = 48,
                 model: str = "gpt2-small-test") -> dict:
    """Live stream migration A/B (DESIGN.md "Live stream migration"):
    drain a LOADED lane mid-stream with ``--migrate-streams`` ON (KV
    block handoff: export the row's chain + state, import on another
    lane, zero re-prefilled tokens) vs OFF (today's shed + PR 6 replay:
    full re-prefill of prompt ⧺ emitted on the resume lane).

    Both arms model the rolling-restart reality: the lane is drained
    and its PROCESS IS KILLED shortly after (the maintenance window
    closes — a fleet cannot wait out its longest stream). With
    migration on, remove_worker has already evacuated every journaled
    stream by then (the kill finds nothing to lose); without it, the
    kill truncates the still-running lame-duck streams and PR 6 replays
    them — full re-prefill of prompt ⧺ emitted on the resume lane.

    Four standalone worker processes are spawned once; each arm routes
    across three through an in-process gateway and drains+kills that
    arm's victim the moment a victim-primary stream is provably
    mid-flight. Reported per arm:

    - stream_completion_rate / identical_rate vs an unkilled blocking
      control (greedy AND seeded — the splice determinism rule);
    - reprefill_tokens: tokens_replayed (re-prefixed into resume
      prompts — the replay arm's prefill burden) plus the survivors'
      measured prefilled_tokens delta across the drain window;
    - migrated_rows / imported_rows (ON arm: >= 1, fallbacks 0);
    - post-drain TTFT and ITL p50/p99 over short probe streams fired
      after the drain settles (the fleet is 2/3 its size either way;
      migration must not leave it slower than replay did).

    The A/B criterion: the migrate arm completes 100% byte-identical
    with ZERO replay tokens (migrated rows re-prefill nothing); the
    replay arm completes too (failover is on in both arms) but pays
    tokens_replayed > 0 of re-prefix prefill."""
    import random
    import signal
    import threading

    from tools.fault_injection import (
        _call,
        control_oracle,
        drive_streams_with_kill,
        launch_worker_procs,
        rid_for_lane,
        tally_streams,
        victim_lane_for_port,
    )
    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.utils.config import GatewayConfig
    from tpu_engine.utils.tracing import percentile

    ports, procs = launch_worker_procs(
        4, extra_args=("--kv-blocks", "48"))

    def lane_prefilled(port: int) -> int:
        try:
            _, health = _call(port, "GET", "/health", timeout=30)
            return ((health.get("generator") or {})
                    .get("kv_pool") or {}).get("prefilled_tokens", 0)
        except Exception:
            return 0

    try:
        def run_arm(indices, victim_idx, migrate: bool) -> dict:
            gw = Gateway(
                [f"127.0.0.1:{ports[i]}" for i in indices],
                GatewayConfig(
                    failover_streams=True,
                    migrate_streams=migrate,
                    migrate_timeout_s=60.0,
                    health_probe_interval_s=0.25,
                    health_probe_failures=2))
            try:
                lanes = gw.worker_names()
                victim_lane = victim_lane_for_port(lanes,
                                                   ports[victim_idx])
                survivor_ports = [ports[i] for i in indices
                                  if ports[i] != ports[victim_idx]]
                requests = []
                for k in range(n_streams):
                    lane = (victim_lane if k % 3 == 0
                            else lanes[k % len(lanes)])
                    params = ({} if k % 2 == 0
                              else {"temperature": 0.9, "seed": 700 + k})
                    tag = f"{'mig' if migrate else 'rep'}{k}"
                    # Victim streams run LONG (4x) so every one is
                    # still mid-flight when the drain+kill sequence
                    # lands — the case migration exists for
                    # (kill_when="all" below waits for that).
                    requests.append({
                        "request_id": rid_for_lane(gw._ring, lane, tag),
                        "prompt_tokens": [(k * 11 + j) % 90 + 1
                                          for j in range(5 + k % 4)],
                        "max_new_tokens": (max_new * 4
                                           if lane == victim_lane
                                           else max_new),
                        **params})
                victim_rids = {r["request_id"] for r in requests
                               if gw._ring.get_node(r["request_id"])
                               == victim_lane}
                control = control_oracle(ports[indices[0]], requests)

                def survivors_imported() -> int:
                    total = 0
                    for p in survivor_ports:
                        try:
                            _, health = _call(p, "GET", "/health",
                                              timeout=30)
                        except Exception:
                            continue
                        gmig = ((health.get("generator") or {})
                                .get("migration") or {})
                        total += gmig.get("imported_rows", 0)
                    return total

                pre_prefill = {"v": None}
                imported_before = survivors_imported()

                def drain_and_kill():
                    # Snapshot the survivors' prefill counters at the
                    # drain instant: everything they prefill AFTER this
                    # is resume/migration burden (admissions were all
                    # dispatched before the drain window closes).
                    pre_prefill["v"] = sum(lane_prefilled(p)
                                           for p in survivor_ports)
                    gw.remove_worker(victim_lane, drain=True)
                    # The maintenance window closes: the process goes
                    # away either way, IMMEDIATELY after the drain call
                    # returns. Migrate mode has evacuated every
                    # journaled stream by then (remove_worker blocks on
                    # the transfers and handoff pickup); without it the
                    # kill truncates the still-running lame-duck
                    # streams and the journal replays them.
                    procs[victim_idx].send_signal(signal.SIGKILL)
                    procs[victim_idx].wait(timeout=10)

                results, drained = drive_streams_with_kill(
                    gw, requests, victim_rids, drain_and_kill,
                    random.Random(3 if migrate else 4),
                    arrival_rate=30.0, kill_when="all")
                post_prefill = sum(lane_prefilled(p)
                                   for p in survivor_ports)
                complete, identical, resumed = tally_streams(
                    results, control)
                stats = gw.get_stats()
                fo = stats.get("failover", {})
                mig = stats.get("migration", {})
                imported_rows = survivors_imported() - imported_before

                # Post-drain latency probes: short streams on the
                # shrunken fleet; TTFT + inter-token gaps client-side.
                ttfts, gaps = [], []
                for i in range(8):
                    t0 = time.perf_counter()
                    last = None
                    for frame in gw.route_generate_stream(
                            {"request_id": f"probe_{migrate}_{i}",
                             "prompt_tokens": [7, i + 1, 3],
                             "max_new_tokens": 12}):
                        evt = _parse_sse(frame)
                        if not evt or "tokens" not in evt \
                                or evt.get("done"):
                            continue
                        now = time.perf_counter()
                        if last is None:
                            ttfts.append(now - t0)
                        else:
                            gaps.append(now - last)
                        last = now
                return {
                    "migrate": migrate, "streams": len(requests),
                    "victim_primary_streams": len(victim_rids),
                    "drained_mid_stream": drained,
                    "completed": complete,
                    "stream_completion_rate": round(
                        complete / len(requests), 3),
                    "identical": identical,
                    "identical_rate": round(
                        identical / len(requests), 3),
                    "resumed_streams": resumed,
                    "migrated_streams": mig.get("streams_migrated", 0),
                    "migration_fallbacks": mig.get(
                        "migration_fallbacks", 0),
                    "imported_rows": imported_rows,
                    "reprefill_tokens_replayed": fo.get(
                        "tokens_replayed", 0),
                    "reprefill_tokens_measured": (
                        post_prefill - pre_prefill["v"]
                        if pre_prefill["v"] is not None else None),
                    "post_drain_ttft_ms": {
                        "p50": round(1e3 * (percentile(ttfts, 50) or 0),
                                     1),
                        "p99": round(1e3 * (percentile(ttfts, 99) or 0),
                                     1)},
                    "post_drain_itl_ms": {
                        "p50": round(1e3 * (percentile(gaps, 50) or 0),
                                     1),
                        "p99": round(1e3 * (percentile(gaps, 99) or 0),
                                     1)},
                }
            finally:
                gw.stop()

        on = run_arm([0, 1, 2], 1, True)
        record_partial("drain_migrate", on)
        off = run_arm([0, 2, 3], 3, False)
        record_partial("drain_replay", off)
        results = {"model": model, "n_streams_per_arm": n_streams,
                   "migrate_on": on, "replay_off": off}
        results["checks_passed"] = bool(
            on["drained_mid_stream"] and off["drained_mid_stream"]
            and on["stream_completion_rate"] == 1.0
            and on["identical_rate"] == 1.0
            and on["migrated_streams"] >= 1
            and on["migration_fallbacks"] == 0
            and on["reprefill_tokens_replayed"] == 0
            and on["imported_rows"] >= 1
            and off["stream_completion_rate"] == 1.0
            and off["identical_rate"] == 1.0
            and off["resumed_streams"] >= 1
            and off["reprefill_tokens_replayed"] > 0)
        return results
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def run_disagg_ab(model: str = "gpt2-small-test", n_streams: int = 24,
                  max_new: int = 24, prompt_len: int = 230,
                  burst: int = 3, mean_burst_gap_ms: float = 350.0,
                  block_size: int = 16, slots_per_lane: int = 6,
                  max_seq: int = 512, prefill_chunk: int = 128,
                  quick: bool = False) -> dict:
    """Disaggregated prefill/decode serving A/B (the PR 14 tentpole):
    a bursty long-prompt Poisson workload over 4 in-process lanes —
    2 dedicated prefill + 2 dedicated decode behind a ``--disagg``
    gateway vs 4 colocated mixed-step lanes behind a default gateway.

    The mechanism under test: colocated mixed stepping co-schedules
    every in-flight row's decode token with admitting rows' prefill
    chunks in ONE ragged dispatch — a burst of long prompts inflates
    every decode row's inter-token latency by the chunk compute, and
    prefill TTFT queues behind the decode ticks. Disaggregation gives
    each phase its own lanes: prefill lanes run prompt chunks only
    (TTFT no longer waits out decode ticks), park the finished row, and
    ship chain + sampling snapshot to a decode lane (PR 11 wire
    format, zero re-prefilled tokens); decode lanes never co-schedule a
    prefill chunk again (ITL stops absorbing 100+-token chunk
    dispatches). The handoff gap itself lands in the disagg arm's ITL
    sample — the win must survive paying it honestly.

    Reported per arm: client-side TTFT p50/p99 and ITL p50/p99 over
    every stream, stream identity across arms (greedy AND seeded — the
    splice is byte-exact), handoff accounting (spliced == streams,
    fallbacks 0), zero KV blocks leaked on every pool. Bars:
    disagg TTFT p99 AND ITL p99 both beat colocated; defaults-off
    /stats //health byte-identical (no handoff/role keys anywhere);
    a quantized (int8) split fleet hands off verbatim with no
    requantization. CPU mesh (tiny registry model — phase-interference
    and handoff-cost shapes, not model-size properties); on-chip rerun
    pending like r06-r13."""
    import queue as _q
    import random
    import threading

    import jax

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported, create_model)
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import GatewayConfig, WorkerConfig
    from tpu_engine.utils.tracing import percentile

    _ensure_builtin_models_imported()
    if quick:
        n_streams, prompt_len, max_seq = 12, 110, 256
        prefill_chunk = 64
    spec = create_model(model, max_seq=max_seq)
    params = spec.init(jax.random.PRNGKey(0))
    rnd = random.Random(29)
    requests = []
    for i in range(n_streams):
        params_i = ({} if i % 2 == 0
                    else {"temperature": 0.8, "seed": 900 + i})
        requests.append({
            "request_id": f"dg-{i}",
            "prompt_tokens": [rnd.randrange(1, 200)
                              for _ in range(prompt_len + (i % 7))],
            "max_new_tokens": max_new, **params_i})
    # Bursty Poisson: arrivals land in bursts of `burst` streams, burst
    # gaps exponential — several long prompts hit the fleet at once,
    # the interference shape disaggregation exists for.
    gaps = []
    for i in range(n_streams):
        gaps.append(0.0 if i % burst else
                    rnd.expovariate(1000.0 / mean_burst_gap_ms) / 1000.0)

    # Equal FLEET resources, role-shaped: the colocated arm spreads
    # rows over 4 lanes; the disagg arm concentrates decode rows on 2,
    # so an operator provisions decode lanes with more slots + pool and
    # prefill lanes (rows exported moments after prefill) with less —
    # both arms get the same total slots and total KV blocks.
    bucket = 16
    while bucket < prompt_len + 8:
        bucket *= 2
    blocks_per_row = bucket // block_size + 3
    colo_blocks = slots_per_lane * blocks_per_row + 36
    prefill_slots = max(2, slots_per_lane - 2)
    prefill_blocks = prefill_slots * blocks_per_row + 20
    decode_slots = 2 * slots_per_lane - prefill_slots
    decode_blocks = (4 * colo_blocks - 2 * prefill_blocks) // 2
    shapes = {"both": (slots_per_lane, colo_blocks),
              "prefill": (prefill_slots, prefill_blocks),
              "decode": (decode_slots, decode_blocks)}

    def make_fleet(roles):
        workers = []
        for i, role in enumerate(roles):
            slots, blocks = shapes[role]
            cfg = WorkerConfig(
                node_id=f"lane_{i+1}", model=model, role=role,
                gen_max_batch_size=slots, gen_step_chunk=4,
                gen_prefix_cache_mb=0, gen_kv_block_size=block_size,
                gen_kv_blocks=blocks, gen_mixed_step=True,
                gen_prefill_chunk=prefill_chunk)
            engine = InferenceEngine(spec, params=params, dtype="float32")
            workers.append(WorkerNode(cfg, engine=engine))
        return workers

    def leak_free(workers):
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            ok = True
            for w in workers:
                st = w.generator.stats()
                kp = st["kv_pool"]
                if (st["active"] != 0
                        or kp["blocks_free"] + kp["radix_nodes"]
                        < kp["blocks_total"]):
                    ok = False
            if ok:
                return True
            time.sleep(0.2)
        return False

    def drive(gw, req, out):
        t0 = time.perf_counter()
        toks, ttft, last, gaps_s = [], None, None, []
        try:
            for frame in gw.route_generate_stream(dict(req)):
                evt = _parse_sse(frame)
                if evt is None or evt.get("done"):
                    continue
                if evt.get("tokens"):
                    now = time.perf_counter()
                    if ttft is None:
                        ttft = now - t0
                    else:
                        gaps_s.append(now - last)
                    last = now
                    toks.extend(evt["tokens"])
        except Exception as exc:
            out.put((req["request_id"], None, [], [f"error: {exc}"]))
            return
        out.put((req["request_id"], ttft, gaps_s, toks))

    def run_arm(disagg: bool) -> tuple:
        roles = (("prefill", "prefill", "decode", "decode") if disagg
                 else ("both",) * 4)
        workers = make_fleet(roles)
        gw = Gateway(workers, GatewayConfig(
            disagg=disagg, handoff_timeout_s=60.0))
        try:
            # Warm every lane's compile set (prefill chunks, decode
            # ticks, export/import paths) outside the measurement.
            warm = []
            for i in range(4):
                warm.append({"request_id": f"warm-{i}",
                             "prompt_tokens": [3 + i] * (prompt_len // 2),
                             "max_new_tokens": 4})
            wq: _q.Queue = _q.Queue()
            wt = [threading.Thread(target=drive, args=(gw, r, wq))
                  for r in warm]
            for t in wt:
                t.start()
            for t in wt:
                t.join(timeout=300)
            while not wq.empty():
                wq.get()
            # Handoff accounting over the MEASURED window only (the
            # warm streams hand off too).
            ho0 = dict(gw.get_stats().get("handoff", {})) if disagg \
                else {}
            out: _q.Queue = _q.Queue()
            threads = []
            for req, gap in zip(requests, gaps):
                time.sleep(gap)
                t = threading.Thread(target=drive, args=(gw, req, out))
                t.start()
                threads.append(t)
            for t in threads:
                t.join(timeout=600)
            got = {}
            ttfts, itl = [], []
            while not out.empty():
                rid, ttft, gaps_s, toks = out.get()
                got[rid] = toks
                if ttft is not None:
                    ttfts.append(ttft)
                itl.extend(gaps_s)
            ttfts.sort()  # percentile() takes a pre-sorted list
            itl.sort()
            stats = gw.get_stats()
            arm = {
                "disagg": disagg, "streams": len(requests),
                "completed": sum(1 for t in got.values() if t),
                "ttft_ms": {
                    "p50": round(1e3 * (percentile(ttfts, 50) or 0), 1),
                    "p99": round(1e3 * (percentile(ttfts, 99) or 0), 1)},
                "itl_ms": {
                    "p50": round(1e3 * (percentile(itl, 50) or 0), 1),
                    "p99": round(1e3 * (percentile(itl, 99) or 0), 1)},
                "pools_leak_free": leak_free(workers),
            }
            if disagg:
                ho = stats.get("handoff", {})
                arm["handoff"] = {k: ho.get(k, 0) - ho0.get(k, 0)
                                  for k in (
                    "prefill_routed", "handoffs_attempted",
                    "handoffs_spliced", "handoff_fallbacks",
                    "export_refusals", "destination_unavailable",
                    "dispatch_failed")}
                arm["decode_imported_rows"] = sum(
                    (w.generator.stats().get("migration") or {})
                    .get("imported_rows", 0) for w in workers)
                arm["prefill_holds"] = sum(
                    (w.generator.stats().get("handoff") or {})
                    .get("holds", 0) for w in workers)
            else:
                arm["stats_has_handoff_key"] = "handoff" in stats
                arm["health_has_role_key"] = any(
                    "role" in w.get_health() for w in workers)
            return arm, got
        finally:
            gw.stop()
            for w in workers:
                w.stop()

    off, off_tokens = run_arm(False)
    record_partial("disagg_colocated", off)
    on, on_tokens = run_arm(True)
    record_partial("disagg_on", on)

    identical = sum(1 for rid in off_tokens
                    if on_tokens.get(rid) == off_tokens[rid]
                    and off_tokens[rid])

    # Quantized split fleet: the int8+scale chain must ride the hop
    # verbatim — the handed-off stream equals the same quantized
    # fleet's colocated stream (determinism contract: quantized-vs-
    # quantized byte-identity, not bf16 equality).
    def quant_phase() -> dict:
        qreq = {"request_id": "qz-1",
                "prompt_tokens": [rnd.randrange(1, 200)
                                  for _ in range(prompt_len)],
                "max_new_tokens": 12, "temperature": 0.7, "seed": 17}

        def one(roles, disagg):
            workers = []
            for i, role in enumerate(roles):
                cfg = WorkerConfig(
                    node_id=f"q_{i+1}", model=model, role=role,
                    gen_max_batch_size=2, gen_step_chunk=4,
                    gen_prefix_cache_mb=0, gen_kv_block_size=block_size,
                    gen_kv_blocks=colo_blocks, gen_kv_quantize="int8")
                engine = InferenceEngine(spec, params=params,
                                         dtype="float32")
                workers.append(WorkerNode(cfg, engine=engine))
            gw = Gateway(workers, GatewayConfig(
                disagg=disagg, handoff_timeout_s=60.0))
            try:
                out: _q.Queue = _q.Queue()
                drive(gw, qreq, out)
                _rid, _ttft, _gaps, toks = out.get()
                imported = sum(
                    (w.generator.stats().get("migration") or {})
                    .get("imported_rows", 0) for w in workers)
                spliced = (gw.get_stats().get("handoff", {})
                           .get("handoffs_spliced", 0))
                clean = leak_free(workers)
                return toks, imported, spliced, clean
            finally:
                gw.stop()
                for w in workers:
                    w.stop()

        ctoks, _imp, _spl, cclean = one(("both", "both"), False)
        htoks, imported, spliced, hclean = one(("prefill", "decode"),
                                               True)
        return {
            "stream_identical": bool(htoks and htoks == ctoks),
            "imported_rows": imported, "handoffs_spliced": spliced,
            "pools_leak_free": bool(cclean and hclean),
        }

    quant = quant_phase()
    record_partial("disagg_quant", quant)

    results = {
        "model": model, "n_streams": n_streams,
        "prompt_len": prompt_len, "max_new": max_new,
        "lanes": "2 prefill + 2 decode vs 4 colocated mixed-step",
        "colocated": off, "disagg": on,
        "streams_identical_across_arms": identical,
        "ttft_p99_speedup": round(
            off["ttft_ms"]["p99"] / max(on["ttft_ms"]["p99"], 1e-3), 3),
        "itl_p99_speedup": round(
            off["itl_ms"]["p99"] / max(on["itl_ms"]["p99"], 1e-3), 3),
        "quantized_handoff": quant,
    }
    results["checks_passed"] = bool(
        identical == n_streams
        and on["completed"] == n_streams
        and off["completed"] == n_streams
        and on["ttft_ms"]["p99"] < off["ttft_ms"]["p99"]
        and on["itl_ms"]["p99"] < off["itl_ms"]["p99"]
        and on["handoff"]["handoffs_spliced"] == n_streams
        and on["handoff"]["handoff_fallbacks"] == 0
        and on["pools_leak_free"] and off["pools_leak_free"]
        and not off["stats_has_handoff_key"]
        and not off["health_has_role_key"]
        and quant["stream_identical"]
        and quant["imported_rows"] >= 1
        and quant["pools_leak_free"])
    return results


def run_affinity_ab(model: str = "gpt2-small-test", n_requests: int = 48,
                    n_tenants: int = 8, prefix_len: int = 96,
                    suffix_len: int = 8, max_new: int = 8,
                    mean_gap_ms: float = 50.0, block_size: int = 16,
                    lanes: int = 3, slots_per_lane: int = 2,
                    kv_blocks_per_lane: int = 36, max_seq: int = 256,
                    quick: bool = False) -> dict:
    """Prefix-affinity routing A/B (the PR 7 tentpole): a
    shared-system-prompt Poisson workload over >= 3 in-process lanes
    behind the gateway, --prefix-affinity ON vs OFF.

    Workload: ``n_tenants`` distinct system prompts (each
    ``prefix_len`` tokens = full radix blocks), each request = one
    tenant's prefix + a unique suffix, Poisson arrivals, unique
    request_ids. Per-lane pools are sized so ONE lane cannot hold every
    tenant's prefix (the fleet-capacity shape): request_id routing
    scatters every tenant across every lane — each lane churns through
    all ``n_tenants`` prefixes and keeps evicting/re-prefilling them —
    while affinity routing partitions tenants across lanes so each
    lane's radix holds its share resident. Reported per arm:

    - fleet prefill-skip ratio (sum prefix_hit / (hit + prefilled)
      across lanes, warmup excluded) — the bar: ON >= 2x OFF;
    - client-side TTFT p50/p99 through /generate/stream — ON p99 must
      beat OFF (skipped prefill is exactly the TTFT term);
    - per-lane radix_lookups/radix_hits/prefix_hit_tokens (the /stats
      blind-spot fix — affinity effectiveness observable per lane).

    A separate OFFLOAD phase exercises the hierarchical host-RAM tier on
    one lane (tiny device pool + --kv-host-blocks): fillers demote the
    tenant prefix, a re-hit must SWAP IN instead of recomputing
    (swap_in_events > 0, prefill tokens skipped) with the stream
    byte-identical to the pre-demotion run.

    Runs on the CPU mesh (tiny registry model — routing convergence,
    radix hit ratios, and swap-in counters are topology/workload
    properties, not model-size properties); on-chip rerun pending like
    r06-r09."""
    import queue as _q
    import random

    import jax

    from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                            create_model)
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.runtime.scheduler import ContinuousGenerator
    from tpu_engine.serving.gateway import Gateway
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import GatewayConfig, WorkerConfig

    _ensure_builtin_models_imported()
    if quick:
        # Smaller run, proportionally tighter pools: 6 tenants x 6 radix
        # blocks must still exceed one lane's capacity or the off arm
        # stops thrashing and the contrast (the thing under test)
        # vanishes into the smaller sample.
        n_requests, n_tenants = 24, 6
        kv_blocks_per_lane = min(kv_blocks_per_lane, 30)
    spec = create_model(model, max_seq=max_seq)
    params = spec.init(jax.random.PRNGKey(0))
    rnd = random.Random(7)
    tenants = [[rnd.randrange(1, 200) for _ in range(prefix_len)]
               for _ in range(n_tenants)]
    requests = []
    for i in range(n_requests):
        prompt = (tenants[i % n_tenants]
                  + [rnd.randrange(1, 200) for _ in range(suffix_len)])
        requests.append({"request_id": f"aff-{i}", "prompt_tokens": prompt,
                         "max_new_tokens": max_new})
    gaps = [rnd.expovariate(1000.0 / mean_gap_ms) / 1000.0
            for _ in range(n_requests)]

    def make_fleet():
        workers = []
        for i in range(lanes):
            cfg = WorkerConfig(
                node_id=f"lane_{i+1}", model=model,
                gen_max_batch_size=slots_per_lane, gen_step_chunk=8,
                gen_prefix_cache_mb=0, gen_kv_block_size=block_size,
                gen_kv_blocks=kv_blocks_per_lane)
            engine = InferenceEngine(spec, params=params, dtype="float32")
            workers.append(WorkerNode(cfg, engine=engine))
        return workers

    def fleet_kv(workers):
        per_lane, agg = {}, {"prefix_hit_tokens": 0, "prefilled_tokens": 0,
                             "radix_lookups": 0, "radix_hits": 0}
        for w in workers:
            pool = w.generator.stats()["kv_pool"]
            per_lane[w.node_id] = {k: pool[k] for k in agg}
            for k in agg:
                agg[k] += pool[k]
        return per_lane, agg

    from tpu_engine.serving.gateway import _parse_sse
    from tpu_engine.utils.tracing import percentile

    def first_token_ttft(gw, req, out):
        t0 = time.perf_counter()
        toks = []
        ttft = None
        for frame in gw.route_generate_stream(dict(req)):
            evt = _parse_sse(frame)
            if evt is None or evt.get("done"):
                continue
            if ttft is None and evt.get("tokens"):
                ttft = time.perf_counter() - t0
            toks.extend(evt.get("tokens", ()))
        out.put((req["request_id"], ttft, toks))

    def run_arm(affinity: bool) -> dict:
        workers = make_fleet()
        gw = Gateway(workers, GatewayConfig(
            prefix_affinity=affinity, affinity_block_size=block_size))
        try:
            # Warm EVERY lane's compile set on both the miss path (full
            # bucket prefill) and the radix-hit resumed-window path, with
            # a warm-only prefix, then snapshot the counters so the
            # measured ratios exclude warmup.
            warm_prefix = [rnd.randrange(200, 255)
                           for _ in range(prefix_len)]
            for w in workers:
                for s in ((1, 2, 3, 4), (9, 8, 7)):
                    w.handle_generate({
                        "request_id": f"warm-{w.node_id}-{len(s)}",
                        "prompt_tokens": warm_prefix + list(s),
                        "max_new_tokens": 2})
            _, base = fleet_kv(workers)

            out: "_q.Queue" = _q.Queue()
            threads = []
            t0 = time.perf_counter()
            for req, gap in zip(requests, gaps):
                time.sleep(gap)
                th = threading.Thread(target=first_token_ttft,
                                      args=(gw, req, out), daemon=True)
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=600)
            wall = time.perf_counter() - t0
            got = {}
            ttfts = []
            while not out.empty():
                rid, ttft, toks = out.get()
                got[rid] = toks
                if ttft is not None:
                    ttfts.append(ttft)
            ttfts.sort()  # percentile() takes a pre-sorted list
            per_lane, agg = fleet_kv(workers)
            hit = agg["prefix_hit_tokens"] - base["prefix_hit_tokens"]
            filled = agg["prefilled_tokens"] - base["prefilled_tokens"]
            arm = {
                "affinity": affinity, "requests": len(requests),
                "completed": sum(1 for t in got.values() if t),
                "wall_s": round(wall, 3),
                "fleet_prefill_skip_frac": round(
                    hit / (hit + filled), 4) if hit + filled else 0.0,
                "prefix_hit_tokens": hit, "prefilled_tokens": filled,
                "ttft_p50_ms": round(1e3 * (percentile(ttfts, 50) or 0), 2),
                "ttft_p99_ms": round(1e3 * (percentile(ttfts, 99) or 0), 2),
                "per_lane_kv": per_lane,
            }
            st = gw.get_stats()
            if affinity:
                arm["affinity_stats"] = st["affinity"]
            else:
                arm["affinity_block_absent"] = "affinity" not in st
            return arm, got
        finally:
            gw.stop()
            for w in workers:
                w.stop()

    results = {"model": model, "lanes": lanes, "n_requests": n_requests,
               "n_tenants": n_tenants, "prefix_len": prefix_len,
               "block_size": block_size,
               "kv_blocks_per_lane": kv_blocks_per_lane}
    off, off_streams = run_arm(False)
    record_partial("affinity_off", off)
    on, on_streams = run_arm(True)
    record_partial("affinity_on", on)
    results["affinity_off"], results["affinity_on"] = off, on
    results["skip_gain"] = round(
        on["fleet_prefill_skip_frac"]
        / max(1e-9, off["fleet_prefill_skip_frac"]), 2)
    results["streams_identical_on_vs_off"] = all(
        on_streams.get(r) == off_streams.get(r) for r in on_streams)

    # -- offload phase: host tier swap-in instead of recompute ---------------
    g = ContinuousGenerator(spec, params=params, dtype="float32",
                            n_slots=slots_per_lane, step_chunk=8,
                            max_seq=max_seq, kv_block_size=block_size,
                            kv_blocks=20, kv_host_blocks=16)
    try:
        tprompt = tenants[0] + [3, 1, 4]
        want = g.generate([tprompt], max_new_tokens=max_new)[0]
        for _ in range(4):  # fillers demote the tenant prefix
            g.generate([[rnd.randrange(1, 200) for _ in range(72)]],
                       max_new_tokens=2)
        mid = g.stats()["kv_pool"]
        got = g.generate([tprompt], max_new_tokens=max_new)[0]
        pool = g.stats()["kv_pool"]
        results["offload"] = {
            "demotions": pool["host"]["demotions"],
            "swap_ins": pool["host"]["swap_ins"],
            "swap_in_events": pool["host"]["swap_in_events"],
            "swapped_in_tokens": pool["host"]["swapped_in_tokens"],
            "prefill_tokens_skipped_on_rehit":
                pool["prefix_hit_tokens"] - mid["prefix_hit_tokens"],
            "stream_identical_after_swap_in": got == want,
        }
    finally:
        g.stop()
    record_partial("affinity_offload", results["offload"])

    results["checks_passed"] = bool(
        on["completed"] == n_requests and off["completed"] == n_requests
        and results["streams_identical_on_vs_off"]
        and on["fleet_prefill_skip_frac"]
        >= 2.0 * off["fleet_prefill_skip_frac"]
        and on["ttft_p99_ms"] < off["ttft_p99_ms"]
        and off["affinity_block_absent"]
        and results["offload"]["swap_in_events"] > 0
        and results["offload"]["prefill_tokens_skipped_on_rehit"] > 0
        and results["offload"]["stream_identical_after_swap_in"])
    return results


def run_fleet_prefix_ab(model: str = "gpt2-small-test",
                        n_tenants: int = 6, rounds: int = 4,
                        prefix_len: int = 96, suffix_len: int = 8,
                        max_new: int = 8, block_size: int = 16,
                        lanes: int = 3, slots_per_lane: int = 2,
                        kv_blocks_per_lane: int = 64, max_seq: int = 256,
                        quick: bool = False) -> dict:
    """Fleet-wide KV prefix tier A/B (the PR 18 tentpole): gateway radix
    directory + peer block fetch vs plain ring routing, on an
    AFFINITY-DEFEATING workload — prefix affinity stays OFF and every
    round's request_ids are chosen so the ring lands each tenant's
    shared prefix on a lane that has never seen it. That is exactly the
    shape affinity routing cannot fix (unique ids scatter by design)
    and the directory+fetch tier is built for.

    Workload: ``n_tenants`` shared prefixes (each ``prefix_len`` tokens
    = full radix blocks), ``rounds`` rounds; round 1 establishes each
    tenant's owner lane, the middle rounds deliberately ring-route to a
    lane that has never held the tenant (the cold repeats the fetch
    tier converts), and the FINAL round revisits a warm lane — the same
    local radix hit in both arms, so the off arm's baseline is the
    honest "local hits only" number rather than a degenerate zero.
    Per-lane pools comfortably hold every tenant (no eviction pressure
    — the contrast under test is re-prefill vs peer fetch, not
    capacity). Reported per arm:

    - fleet prefill-skip ratio: (local prefix_hit_tokens +
      prefill_tokens_skipped_remote) / (those + prefilled_tokens),
      warmup excluded — the bar: FETCH >= 2x OFF;
    - client TTFT p50/p99 through /generate/stream (sequential issue —
      ownership must be established before the next round probes it);
    - fetch-arm: gateway prefix_directory stats + per-lane prefix_fetch
      counters (attempted == spliced: no rung ever fires on a healthy
      fleet); off-arm: /stats carries NO prefix_directory block and no
      lane grew a prefix_fetch family (defaults-off wire compat).

    Streams must be byte-identical across arms. Runs on the CPU mesh
    (directory convergence and splice accounting are topology/workload
    properties, not model-size properties); on-chip rerun pending like
    r06-r09."""
    import random

    import jax

    from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                            create_model)
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import GatewayConfig, WorkerConfig
    from tpu_engine.utils.tracing import percentile

    _ensure_builtin_models_imported()
    if quick:
        n_tenants = 3
    spec = create_model(model, max_seq=max_seq)
    params = spec.init(jax.random.PRNGKey(0))
    rnd = random.Random(18)
    tenants = [[rnd.randrange(1, 200) for _ in range(prefix_len)]
               for _ in range(n_tenants)]
    suffixes = [[rnd.randrange(1, 200) for _ in range(suffix_len)]
                for _ in range(n_tenants * rounds)]
    n_requests = n_tenants * rounds

    def make_fleet(fetch: bool):
        workers = []
        for i in range(lanes):
            cfg = WorkerConfig(
                node_id=f"lane_{i+1}", model=model,
                gen_max_batch_size=slots_per_lane, gen_step_chunk=8,
                gen_prefix_cache_mb=0, gen_kv_block_size=block_size,
                gen_kv_blocks=kv_blocks_per_lane,
                gen_prefix_fetch=fetch)
            engine = InferenceEngine(spec, params=params, dtype="float32")
            workers.append(WorkerNode(cfg, engine=engine))
        if fetch:
            by_name = {w.node_id: w for w in workers}

            def transport(hint, payload):
                return by_name[hint["lane"]].handle_export_prefix(payload)
            for w in workers:
                w.set_prefix_fetch_transport(transport)
        return workers

    def fleet_counters(workers):
        agg = {"prefix_hit_tokens": 0, "prefilled_tokens": 0,
               "remote_skipped_tokens": 0, "fetch_attempted": 0,
               "fetch_spliced": 0, "fetch_blocks": 0}
        per_lane = {}
        for w in workers:
            st = w.generator.stats()
            pool = st["kv_pool"]
            pf = st.get("prefix_fetch") or {}
            row = {"prefix_hit_tokens": pool["prefix_hit_tokens"],
                   "prefilled_tokens": pool["prefilled_tokens"],
                   "remote_skipped_tokens":
                       pf.get("prefill_tokens_skipped_remote", 0),
                   "fetch_attempted": pf.get("attempted", 0),
                   "fetch_spliced": pf.get("spliced", 0),
                   "fetch_blocks": pf.get("blocks_spliced", 0)}
            per_lane[w.node_id] = row
            for k in agg:
                agg[k] += row[k]
        return per_lane, agg

    def stream_one(gw, req):
        t0 = time.perf_counter()
        toks, ttft = [], None
        for frame in gw.route_generate_stream(dict(req)):
            evt = _parse_sse(frame)
            if evt is None or evt.get("done"):
                continue
            if ttft is None and evt.get("tokens"):
                ttft = time.perf_counter() - t0
            toks.extend(evt.get("tokens", ()))
        return toks, ttft

    def pick_rid(gw, holders, tag, warm):
        """A request_id whose ring primary is IN ``holders`` (warm
        revisit) or NOT in it (the affinity-defeating cold step). Same
        ring membership both arms, so the chosen ids — and thus the
        routing — are identical across arms."""
        for i in range(4000):
            rid = f"{tag}-{i}"
            if (gw._ring.get_node(rid) in holders) == warm:
                return rid
        return f"{tag}-0"

    def run_arm(fetch: bool) -> tuple:
        workers = make_fleet(fetch)
        gw = Gateway(workers, GatewayConfig(prefix_directory=fetch))
        try:
            # Warm every lane's compile set on the miss path AND the
            # block-aligned resumed-window path (the same windows a
            # splice resumes into), then snapshot counters so measured
            # ratios exclude warmup.
            warm_prefix = [rnd.randrange(200, 255)
                           for _ in range(prefix_len)]
            for w in workers:
                for s in ((1, 2, 3, 4), (9, 8, 7)):
                    w.handle_generate({
                        "request_id": f"warm-{w.node_id}-{len(s)}",
                        "prompt_tokens": warm_prefix + list(s),
                        "max_new_tokens": 2})
            _, base = fleet_counters(workers)

            streams = {}
            ttfts = []
            served_by = {}  # tenant -> lanes that have its prefix
            wall0 = time.perf_counter()
            for r in range(rounds):
                for t in range(n_tenants):
                    # Middle rounds steer AWAY from every lane that
                    # already holds this tenant's blocks (each repeat a
                    # cold lane, the ring at its least favorable); the
                    # last round revisits a warm one (both arms hit
                    # locally — the honest shared baseline).
                    rid = pick_rid(gw, served_by.get(t, set()),
                                   f"fp-t{t}-r{r}", warm=r == rounds - 1)
                    prompt = tenants[t] + suffixes[r * n_tenants + t]
                    toks, ttft = stream_one(
                        gw, {"request_id": rid, "prompt_tokens": prompt,
                             "max_new_tokens": max_new})
                    streams[(t, r)] = toks
                    if ttft is not None:
                        ttfts.append(ttft)
                    served_by.setdefault(t, set()).add(
                        gw._ring.get_node(rid))
            wall = time.perf_counter() - wall0
            ttfts.sort()
            per_lane, agg = fleet_counters(workers)
            skip = {k: agg[k] - base[k] for k in agg}
            gained = (skip["prefix_hit_tokens"]
                      + skip["remote_skipped_tokens"])
            filled = skip["prefilled_tokens"]
            arm = {
                "prefix_fetch": fetch, "requests": n_requests,
                "completed": sum(1 for s in streams.values() if s),
                "wall_s": round(wall, 3),
                "fleet_prefill_skip_frac": round(
                    gained / (gained + filled), 4) if gained + filled
                    else 0.0,
                "local_hit_tokens": skip["prefix_hit_tokens"],
                "remote_skipped_tokens": skip["remote_skipped_tokens"],
                "prefilled_tokens": filled,
                "fetch_attempted": skip["fetch_attempted"],
                "fetch_spliced": skip["fetch_spliced"],
                "fetch_blocks_spliced": skip["fetch_blocks"],
                "ttft_p50_ms": round(1e3 * (percentile(ttfts, 50) or 0), 2),
                "ttft_p99_ms": round(1e3 * (percentile(ttfts, 99) or 0), 2),
                "per_lane": per_lane,
            }
            st = gw.get_stats()
            if fetch:
                arm["prefix_directory"] = st["prefix_directory"]
            else:
                arm["directory_block_absent"] = (
                    "prefix_directory" not in st)
                arm["fetch_stats_absent"] = all(
                    "prefix_fetch" not in w.generator.stats()
                    for w in workers)
            return arm, streams
        finally:
            gw.stop()
            for w in workers:
                w.stop()

    results = {"model": model, "lanes": lanes, "n_requests": n_requests,
               "n_tenants": n_tenants, "rounds": rounds,
               "prefix_len": prefix_len, "block_size": block_size,
               "kv_blocks_per_lane": kv_blocks_per_lane}
    off, off_streams = run_arm(False)
    record_partial("fleet_prefix_off", off)
    on, on_streams = run_arm(True)
    record_partial("fleet_prefix_on", on)
    results["fetch_off"], results["fetch_on"] = off, on
    results["skip_gain"] = round(
        on["fleet_prefill_skip_frac"]
        / max(1e-4, off["fleet_prefill_skip_frac"]), 2)
    results["streams_identical_on_vs_off"] = all(
        on_streams.get(k) == off_streams.get(k) for k in on_streams)
    results["checks_passed"] = bool(
        on["completed"] == n_requests and off["completed"] == n_requests
        and results["streams_identical_on_vs_off"]
        and on["fleet_prefill_skip_frac"]
        >= 2.0 * max(off["fleet_prefill_skip_frac"], 1e-9)
        and on["fetch_spliced"] > 0
        and on["fetch_attempted"] == on["fetch_spliced"]
        and on["prefix_directory"]["hints_attached"] > 0
        and off["directory_block_absent"]
        and off["fetch_stats_absent"])
    return results


def run_overload_ab(model: str = "gpt2-small-test", n_requests: int = 60,
                    max_new: int = 16, lanes: int = 3,
                    slots_per_lane: int = 2, block_size: int = 16,
                    max_seq: int = 128, quick: bool = False) -> dict:
    """Adaptive overload control A/B (the PR 9 tentpole): mixed-priority
    Poisson load at ~2x saturation over >= 3 in-process paged mixed-step
    lanes behind the gateway — overload control ON (priority-tiered
    gateway+worker admission, staged brownout, load-derived Retry-After)
    vs OFF (PR 1 behavior: everything admits, deadlines alone decide).

    Both arms carry identical per-request deadlines; the headline is
    GOODPUT — tokens of requests that completed within their deadline,
    per second of wall — split by tier. The off arm melts every tier
    equally (queues grow past the deadline for everyone); the on arm
    sheds background/batch early and keeps interactive inside its
    deadline. Bar: on-arm INTERACTIVE goodput >= 1.5x the off arm's,
    and a below-saturation stream is byte-identical across arms (the
    control plane must not touch stream content).

    Runs on the CPU mesh (tiny registry model — admission ordering,
    ladder behavior, and goodput shape are control-plane properties,
    not model-size properties); on-chip rerun pending like r06-r10."""
    import queue as _q
    import random

    import jax

    from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                            create_model)
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import GatewayConfig, WorkerConfig
    from tpu_engine.utils.deadline import ShedError
    from tpu_engine.utils.tracing import percentile

    _ensure_builtin_models_imported()
    if quick:
        n_requests = 42
    spec = create_model(model, max_seq=max_seq)
    params = spec.init(jax.random.PRNGKey(0))
    rnd = random.Random(11)
    tiers = ["interactive", "batch", "background"]
    requests = []
    for i in range(n_requests):
        requests.append({
            "request_id": f"ov-{i}",
            "prompt_tokens": [rnd.randrange(1, 200) for _ in range(12)],
            "max_new_tokens": max_new,
            "priority": tiers[i % 3],
        })

    def make_fleet(overload: bool, slo_ms: float = 0.0):
        # The OFF arm is the PR 1 default: unbounded admission, the
        # deadline machinery alone decides — exactly the uncontrolled
        # baseline the tentpole replaces. The ON arm bounds depth,
        # tiers admission, and runs the brownout ladder. slo_ms > 0
        # additionally declares TTFT/completion objectives derived
        # from the arm's deadline, so the artifact carries the
        # error-budget burn the run actually produced.
        workers = []
        for i in range(lanes):
            cfg = WorkerConfig(
                node_id=f"lane_{i+1}", model=model,
                gen_max_batch_size=slots_per_lane, gen_step_chunk=8,
                gen_prefix_cache_mb=0, gen_kv_block_size=block_size,
                gen_kv_blocks=24, gen_mixed_step=True,
                gen_mixed_token_budget=16,
                # ON arm: admitted == decodable now (depth = decode
                # slots) — a queued-but-doomed admission is exactly the
                # goodput leak the control plane exists to close.
                max_queue_depth=slots_per_lane if overload else 0,
                priority_admission=overload, brownout=overload,
                brownout_interval_s=0.15)
            engine = InferenceEngine(spec, params=params, dtype="float32")
            workers.append(WorkerNode(cfg, engine=engine))
        gw = Gateway(workers, GatewayConfig(
            overload_control=overload,
            overload_max_inflight=(2 * lanes * slots_per_lane
                                   if overload else 0),
            slo_ttft_p99_ms=(slo_ms / 2 if slo_ms else 0.0),
            slo_completion_p99_ms=(slo_ms if slo_ms else 0.0)))
        return workers, gw

    def consume(gw, req, deadline_ms, out):
        t0 = time.perf_counter()
        toks, ttft, ok, shed = [], None, False, False
        try:
            for frame in gw.route_generate_stream(
                    dict(req, deadline_ms=deadline_ms)):
                evt = _parse_sse(frame)
                if evt is None:
                    continue
                if evt.get("done"):
                    ok = "error" not in evt
                    break
                if ttft is None and evt.get("tokens"):
                    ttft = time.perf_counter() - t0
                toks.extend(evt.get("tokens", ()))
        except ShedError:
            shed = True
        except Exception:
            pass
        out.put((req["request_id"], req["priority"], ok, shed, ttft,
                 len(toks), (time.perf_counter() - t0) * 1e3))

    def run_arm(overload: bool, rate_hz: float, deadline_ms: float):
        # SLO accounting rides the measured arms only (the ON arm's
        # objectives track its deadline); calibration and the identity
        # probe stay flag-free.
        workers, gw = make_fleet(overload,
                                 slo_ms=deadline_ms if overload else 0.0)
        try:
            for w in workers:  # warm the compile set off the clock
                w.handle_generate({"request_id": f"warm-{w.node_id}",
                                   "prompt_tokens": [1, 2, 3, 4],
                                   "max_new_tokens": 2})
            out: "_q.Queue" = _q.Queue()
            gaps = [rnd.expovariate(rate_hz) for _ in requests]
            threads = []
            t0 = time.perf_counter()
            for req, gap in zip(requests, gaps):
                time.sleep(gap)
                th = threading.Thread(target=consume,
                                      args=(gw, req, deadline_ms, out),
                                      daemon=True)
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=600)
            wall = time.perf_counter() - t0
            by_tier = {t: {"offered": 0, "good": 0, "shed": 0,
                           "missed": 0, "good_tokens": 0, "ttfts": []}
                       for t in tiers}
            while not out.empty():
                rid, tier, ok, shed, ttft, n_toks, lat_ms = out.get()
                d = by_tier[tier]
                d["offered"] += 1
                if ok and lat_ms <= deadline_ms:
                    d["good"] += 1
                    d["good_tokens"] += n_toks
                    if ttft is not None:
                        d["ttfts"].append(ttft)
                elif shed:
                    d["shed"] += 1
                else:
                    d["missed"] += 1
            arm = {"overload_control": overload, "wall_s": round(wall, 3),
                   "by_tier": {}}
            for t in tiers:
                d = by_tier[t]
                d["ttfts"].sort()
                arm["by_tier"][t] = {
                    "offered": d["offered"], "good": d["good"],
                    "shed": d["shed"], "missed": d["missed"],
                    "goodput_tokens_per_s": round(
                        d["good_tokens"] / wall, 3),
                    "ttft_p99_ms": round(
                        1e3 * (percentile(d["ttfts"], 99) or 0), 2),
                }
            st = gw.get_stats()
            if overload:
                arm["gateway_overload"] = st.get("overload")
                # SLO burn-rate block rides the same armed stats
                # snapshot: budget burn per objective for the arm.
                arm["slo"] = st.get("slo")
                arm["brownout"] = {
                    w.node_id: w.get_health().get("brownout")
                    for w in workers}
            else:
                arm["overload_block_absent"] = "overload" not in st
            return arm
        finally:
            gw.stop()
            for w in workers:
                w.stop()

    # Calibration: a full-concurrency burst on a warm uncontrolled
    # fleet measures what the HOST actually sustains (sequential singles
    # understate concurrent service on a shared-CPU mesh). Capacity =
    # completed/wall; the deadline is twice the burst's mean latency —
    # an at-capacity request makes it comfortably, one queued behind 2x
    # overload does not.
    workers, gw = make_fleet(False)
    try:
        for w in workers:
            w.handle_generate({"request_id": f"cal-warm-{w.node_id}",
                               "prompt_tokens": [1, 2, 3, 4],
                               "max_new_tokens": 2})
        n_cal = 2 * lanes * slots_per_lane
        lats: list = []
        lat_lock = threading.Lock()

        def cal_one(i):
            t1 = time.perf_counter()
            gw.route_generate({"request_id": f"cal-{i}",
                               "prompt_tokens": [5, 9, 3, 7],
                               "max_new_tokens": max_new})
            with lat_lock:
                lats.append(time.perf_counter() - t1)

        t0 = time.perf_counter()
        cal_threads = [threading.Thread(target=cal_one, args=(i,),
                                        daemon=True)
                       for i in range(n_cal)]
        for th in cal_threads:
            th.start()
        for th in cal_threads:
            th.join(timeout=600)
        cal_wall = time.perf_counter() - t0
    finally:
        gw.stop()
        for w in workers:
            w.stop()
    svc_s = sum(lats) / max(1, len(lats))
    capacity_hz = len(lats) / max(cal_wall, 1e-3)
    rate_hz = 2.0 * capacity_hz
    deadline_ms = max(400.0, 2.5 * svc_s * 1e3)

    # Below-saturation identity: one idle-fleet stream per arm must be
    # byte-identical (overload control must never touch stream bytes).
    ident_req = {"request_id": "ident", "prompt_tokens": [5, 9, 3, 7],
                 "max_new_tokens": max_new, "priority": "background"}
    ident = {}
    for overload in (False, True):
        workers, gw = make_fleet(overload)
        try:
            frames = list(gw.route_generate_stream(dict(ident_req)))
            toks = []
            for f in frames:
                evt = _parse_sse(f)
                if evt and not evt.get("done"):
                    toks.extend(evt.get("tokens", ()))
            ident[overload] = toks
        finally:
            gw.stop()
            for w in workers:
                w.stop()

    results = {"model": model, "lanes": lanes,
               "slots_per_lane": slots_per_lane,
               "n_requests": n_requests, "max_new": max_new,
               "calibrated_service_s": round(svc_s, 3),
               "offered_rate_hz": round(rate_hz, 3),
               "estimated_capacity_hz": round(capacity_hz, 3),
               "deadline_ms": round(deadline_ms, 1),
               "streams_identical_below_saturation":
                   bool(ident[False]) and ident[False] == ident[True]}
    off = run_arm(False, rate_hz, deadline_ms)
    record_partial("overload_off", off)
    on = run_arm(True, rate_hz, deadline_ms)
    record_partial("overload_on", on)
    results["overload_off"], results["overload_on"] = off, on
    on_hi = on["by_tier"]["interactive"]["goodput_tokens_per_s"]
    off_hi = off["by_tier"]["interactive"]["goodput_tokens_per_s"]
    results["interactive_goodput_gain"] = round(
        on_hi / max(1e-9, off_hi), 2) if off_hi or on_hi else None
    results["checks_passed"] = bool(
        results["streams_identical_below_saturation"]
        and off["overload_block_absent"]
        and on_hi >= 1.5 * off_hi
        and on_hi > 0)
    return results


def run_elastic_ab(model: str = "gpt2-chaos-test",
                   max_lanes: int = 4, quick: bool = False) -> dict:
    """Elastic fleet A/B (DESIGN.md "Elastic fleet"): the SAME diurnal
    trace — a Poisson burst, then a sparse trough — served by a static
    ``max_lanes`` fleet vs the ``--autoscale`` closed loop starting from
    one lane (in-process lanes; InProcessLaneProvider spawns and retires
    scheduler instances live, retirements drain through the PR 11
    stream-migration ladder).

    The headline is LANE-SECONDS — the integral of live lane count over
    the run, the capacity bill a fleet actually pays — at EQUAL
    completion: both arms must finish every stream, and every stream's
    tokens must be identical across arms (growth, drain, and migration
    may never touch stream content). Bar: the elastic arm completes the
    trace on provably fewer lane-seconds than the static arm; it must
    also have actually ridden the loop (scaled up to >= 3 lanes inside
    the burst, back down to 1 in the trough) rather than winning by
    standing still, with fleet counters == fleet marker spans.

    Uses gpt2-chaos-test (not gpt2-small-test): the loop steers by slot
    occupancy, and the tiny model drains bursts faster than a 4 Hz
    control loop can sample them. Runs on the CPU mesh (control-plane
    property, not a model-size property); on-chip rerun pending like
    r06-r10."""
    import random
    import threading

    import jax

    from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                            create_model)
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.autoscaler import InProcessLaneProvider
    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.serving.resilience import FleetCounters
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import GatewayConfig, WorkerConfig

    _ensure_builtin_models_imported()
    spec = create_model(model, max_seq=128)
    params = spec.init(jax.random.PRNGKey(0))
    n_burst = 12 if quick else 24
    n_trough = 4 if quick else 6
    requests = []
    for k in range(n_burst + n_trough):
        params_k = {}
        if k % 3 == 1:
            params_k = {"temperature": 0.9, "seed": 400 + k}
        requests.append({
            "request_id": f"eb_{k}",
            "prompt_tokens": [(k * 5 + j) % 90 + 1
                              for j in range(5 + k % 3)],
            "max_new_tokens": 48 if k < n_burst else 16,
            **params_k})

    def make_lane(name: str) -> WorkerNode:
        cfg = WorkerConfig(node_id=name, model=model,
                           gen_scheduler="continuous",
                           gen_max_batch_size=8, gen_step_chunk=2,
                           gen_kv_block_size=16, gen_kv_blocks=48,
                           gen_prefill_chunk=16, gen_prefix_cache_mb=0)
        engine = InferenceEngine(spec, params=params, dtype="float32")
        return WorkerNode(cfg, engine=engine)

    def run_arm(elastic: bool) -> dict:
        lanes = ([make_lane("el_seed")] if elastic
                 else [make_lane(f"st_{i}") for i in range(max_lanes)])
        retired: list = []
        if elastic:
            gw = Gateway(lanes, GatewayConfig(
                autoscale=True, autoscale_interval_s=0.25,
                autoscale_min_lanes=1, autoscale_max_lanes=max_lanes,
                autoscale_up_pressure=0.30,
                autoscale_down_pressure=0.20,
                autoscale_cooldown_s=0.5,
                autoscale_spawn_timeout_s=60.0,
                migrate_streams=True, failover_streams=True))
            provider = InProcessLaneProvider(
                lambda idx: make_lane(f"el_{idx}"),
                on_retire=retired.append)
            gw.engage_autoscaler(provider=provider)
        else:
            gw = Gateway(lanes, GatewayConfig())

        results: dict = {}
        lock = threading.Lock()
        samples: list = []
        stop_sampling = threading.Event()

        def sampler():
            while not stop_sampling.wait(0.2):
                samples.append((time.monotonic(),
                                len(gw.worker_names())))

        def consume(req):
            toks, final = [], None
            try:
                for frame in gw.route_generate_stream(dict(req)):
                    evt = _parse_sse(frame)
                    if evt is None:
                        continue
                    if evt.get("done"):
                        final = evt
                        break
                    if "tokens" in evt:
                        toks.extend(evt["tokens"])
            except Exception as exc:
                final = {"harness_exception": str(exc)}
            with lock:
                results[req["request_id"]] = (toks, final)

        t0 = time.monotonic()
        samples.append((t0, len(gw.worker_names())))
        sam = threading.Thread(target=sampler, daemon=True)
        sam.start()
        rng = random.Random(23)
        threads = []
        for i, req in enumerate(requests):
            t = threading.Thread(target=consume, args=(req,),
                                 daemon=True)
            t.start()
            threads.append(t)
            if i == n_burst - 1:
                time.sleep(6.0)         # the trough opens
            elif i < n_burst:
                time.sleep(rng.expovariate(8.0))
            else:
                time.sleep(rng.expovariate(0.3))
        for t in threads:
            t.join(timeout=600)
        if elastic:
            # Let the loop settle back to min-lanes — those lane-seconds
            # stay on the elastic arm's bill (the sampler keeps running).
            settle = time.monotonic() + 20.0
            while (len(gw.worker_names()) > 1
                   and time.monotonic() < settle):
                time.sleep(0.2)
        t1 = time.monotonic()
        stop_sampling.set()
        sam.join(timeout=5)
        samples.append((t1, len(gw.worker_names())))
        lane_seconds = sum((samples[i + 1][0] - samples[i][0])
                           * samples[i][1]
                           for i in range(len(samples) - 1))
        lane_counts = [n for _, n in samples]
        fl = dict(gw.get_stats().get("fleet", {}))
        spans = [s for s in gw.tracer.snapshot() if s["op"] == "fleet"]
        counters_match = (len(spans) == sum(
            fl.get(f, 0) for f in FleetCounters.SPAN_FIELDS))
        completed = sum(1 for toks, final in results.values()
                        if final and final.get("done")
                        and "error" not in final)
        tokens = {rid: final.get("tokens") if final else None
                  for rid, (toks, final) in results.items()}
        gw.stop()
        for w in lanes + retired:
            try:
                w.stop()
            except Exception:
                pass
        return {"wall_s": round(t1 - t0, 2),
                "lane_seconds": round(lane_seconds, 2),
                "completed": completed,
                "peak_lanes": max(lane_counts),
                "final_lanes": lane_counts[-1],
                "fleet": fl, "counters_match_spans": counters_match,
                "tokens": tokens}

    log(f"elastic-ab: static arm ({max_lanes} lanes, "
        f"{len(requests)} streams)")
    static = run_arm(elastic=False)
    record_partial("elastic_ab_static", {
        k: v for k, v in static.items() if k != "tokens"})
    log(f"elastic-ab: elastic arm (1..{max_lanes} lanes, closed loop)")
    elastic = run_arm(elastic=True)
    record_partial("elastic_ab_elastic", {
        k: v for k, v in elastic.items() if k != "tokens"})

    n = len(requests)
    identical = sum(
        1 for rid in static["tokens"]
        if static["tokens"][rid] is not None
        and static["tokens"][rid] == elastic["tokens"].get(rid))
    checks = {
        "static_completed_all": static["completed"] == n,
        "elastic_completed_all": elastic["completed"] == n,
        "tokens_identical_across_arms": identical == n,
        "elastic_fewer_lane_seconds":
            elastic["lane_seconds"] < static["lane_seconds"],
        "elastic_scaled_up": elastic["peak_lanes"] >= 3,
        "elastic_scaled_back_down": elastic["final_lanes"] == 1,
        "fleet_counters_match_spans": elastic["counters_match_spans"],
    }
    out = {
        "model": model, "streams": n,
        "static": {k: v for k, v in static.items() if k != "tokens"},
        "elastic": {k: v for k, v in elastic.items() if k != "tokens"},
        "identical_across_arms": identical,
        "lane_seconds_saved": round(
            static["lane_seconds"] - elastic["lane_seconds"], 2),
        "lane_seconds_ratio": round(
            elastic["lane_seconds"] / max(static["lane_seconds"], 1e-9),
            4),
        "checks": checks,
        "checks_passed": all(checks.values()),
    }
    return out


def probe_device(timeout_s: float = 120.0) -> None:
    """Device-liveness preflight in a SUBPROCESS (one process per chip:
    the child exits, and frees the chip, before any server launches; a
    hang in this process would leave the driver with no artifact at
    all). One bounded probe: it runs a tiny matmul, not just
    `jax.devices()` — a device can enumerate and still hang its first
    executed op — and it decides the platform by the serving CLI's own
    rule (`select_platform`: a TPU backend, unless TPU_ENGINE_PLATFORM
    names another on purpose). Raises on a missing, dead or hung device; bench.py then exits
    non-zero — there is no CPU fallback.

    A hung child can sit in uninterruptible sleep and survive SIGKILL, so
    pipes are abandoned on timeout instead of drained (subprocess.run's
    post-kill communicate() has no timeout and would hang right here)."""
    code = ("from tpu_engine.serving.cli import select_platform\n"
            "select_platform()\n"
            "import jax, jax.numpy as jnp\n"
            "x = jnp.ones((128, 128), jnp.bfloat16)\n"
            "jax.block_until_ready(x @ x)\n"
            "print(jax.default_backend(), jax.devices()[0].device_kind)\n")
    proc = subprocess.Popen([sys.executable, "-c", code], cwd=REPO,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        for pipe in (proc.stdout, proc.stderr):
            if pipe is not None:
                pipe.close()
        raise RuntimeError(f"device probe hung >{timeout_s:.0f}s")
    if proc.returncode != 0:
        raise RuntimeError(f"device probe failed: {err[-300:]}")
    log(f"device probe OK: {out.strip()}")


_SCENARIO = "infer"  # set by _main after arg parsing; read by the handler


def emit(line: dict) -> None:
    """Print the driver's one JSON line."""
    print(json.dumps(line), flush=True)


def main() -> int:
    try:
        rc = _main()
        # The run emitted its final line: the run-stamped partial is
        # redundant now (aborted runs keep theirs for forensics).
        cleanup_partial()
        return rc
    except Exception as exc:  # ALWAYS leave the driver one JSON line
        log(f"bench failed: {exc!r}")
        line = {
            "metric": "bench_error", "value": 0.0, "unit": "error",
            "vs_baseline": 0.0, "scenario": _SCENARIO,
            "error": repr(exc)[:500],
        }
        # A wedge after N completed measurements must not zero them out:
        # attach whatever landed before the failure (also on disk at the
        # run-stamped partial path). Metadata-only partials (scenario/ts)
        # are
        # NOT attached — "partial" present must mean real numbers
        # survived, or the driver would read an empty run as evidence.
        if any(k not in ("scenario", "ts") for k in _PARTIAL):
            line["partial"] = _PARTIAL
        print(json.dumps(line), flush=True)
        return 1


def _main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=10_000)
    ap.add_argument("--threads", type=int, default=50)
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--lanes", type=int, default=0,
                    help="serving lanes (0 = one per device)")
    ap.add_argument("--port", type=int, default=0,
                    help="use an already-running server on this port")
    ap.add_argument("--quick", action="store_true",
                    help="1000 requests / 20 threads smoke run")
    ap.add_argument("--cache-test", action="store_true",
                    help="reference cache-effectiveness A/B instead of load")
    ap.add_argument("--distinct", type=int, default=10,
                    help="distinct input vectors in the load (10 = reference "
                         "parity / ~99.7%% hits; large values force the miss "
                         "path)")
    ap.add_argument("--no-compute", action="store_true",
                    help="skip the device-compute (MFU) addendum after the "
                         "serving load")
    ap.add_argument("--scenario",
                    choices=["infer", "generate", "compute", "decode-ab",
                             "spec-ab", "spec-batch-ab", "mixed",
                             "prefill-mfu", "longctx",
                             "miss-sweep", "paged-ab", "mixed-ab",
                             "crash-ab", "drain-ab", "affinity-ab",
                             "overload-ab", "quant-ab", "disagg-ab",
                             "recurrent-ab", "tp-ab", "elastic-ab",
                             "fleet-prefix-ab", "unified-ab"],
                    default="infer")
    args = ap.parse_args()
    # In-process scenarios (compute / decode-ab) honor the same platform
    # override the serving CLI does.
    platform = os.environ.get("TPU_ENGINE_PLATFORM")
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)
    global _SCENARIO
    _SCENARIO = args.scenario
    _PARTIAL.clear()  # never let a previous run's numbers masquerade
    record_partial("scenario", args.scenario)
    # Preflight the device — except in --port mode, where a live server
    # already holds the (exclusive) chip and a second process could not
    # reach it. A failed probe ends the run non-zero: a number from the
    # CPU is never written into a device metric's field.
    if args.port == 0:
        probe_device()
    if args.quick:
        args.requests, args.threads = 1000, 20
    if (args.scenario in ("generate", "decode-ab", "spec-batch-ab")
            and args.model == "resnet50"):
        args.model = "gpt2"
    if args.scenario == "mixed" and args.model == "resnet50":
        args.model = "yolov8n"
    if (args.scenario in ("paged-ab", "mixed-ab", "spec-ab", "affinity-ab",
                          "overload-ab", "quant-ab", "disagg-ab",
                          "recurrent-ab", "tp-ab", "fleet-prefix-ab",
                          "unified-ab")
            and args.model == "resnet50"):
        args.model = "gpt2-small-test"

    if args.scenario == "compute":
        # In-process, no HTTP: pure device-compute evidence.
        compute = run_compute_bench(model=args.model
                                    if args.model != "gpt2" else "resnet50")
        record_partial("compute", compute)
        decode = run_decode_compute()
        record_partial("decode", decode)
        decode_f = run_decode_compute(fused=True)
        record_partial("decode_fused", decode_f)
        # Named so the honest comparison is self-evident: the int8 arm is
        # fused, so its pair is decode_fused (NOT the chunked "decode" —
        # dividing by that would conflate the fusion win into int8's).
        decode_fq = run_decode_compute(quantize=True, fused=True)
        record_partial("decode_fused_int8", decode_fq)
        log(json.dumps({"compute": compute, "decode": decode,
                        "decode_fused": decode_f,
                        "decode_fused_int8": decode_fq}, indent=2))
        emit({
            "metric": "device_compute", "value": compute["samples_per_s"],
            "unit": "samples/s", "vs_baseline": None,
            "mfu": compute["mfu"], "decode_tokens_per_s": decode["tokens_per_s"],
            "compute": compute, "decode": decode, "decode_fused": decode_f,
            "decode_fused_int8": decode_fq,
        })
        return 0

    if args.scenario == "decode-ab":
        result = run_decode_ab(model=args.model)
        record_partial("decode_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "decode_continuous_speedup",
            "value": result["continuous_speedup"], "unit": "x",
            "vs_baseline": None, "model": args.model, **result,
        })
        return 0

    if args.scenario == "spec-ab":
        # Continuous speculative decoding (--spec-k) vs the plain paged
        # scheduler, counter-based. The batch-lane bracket A/B moved to
        # --scenario spec-batch-ab.
        result = run_spec_continuous_ab(
            model=args.model, max_new=24 if args.quick else 96)
        record_partial("spec_continuous_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "spec_tokens_per_row_dispatch",
            "value": result["tokens_per_dispatch_ratio"], "unit": "x",
            "vs_baseline": 1.0, "model": args.model, **result,
        })
        return 0 if result["checks_passed"] else 1

    if args.scenario == "crash-ab":
        # Crash-tolerant streaming A/B: worker processes serve the tiny
        # registry model on the host backend (the kill is the variable
        # under test, not the chip).
        result = run_crash_ab(n_streams=8 if args.quick else 12)
        record_partial("crash_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "crash_stream_completion_rate",
            "value": result["failover_on"]["stream_completion_rate"],
            "unit": "fraction",
            "vs_baseline": result["failover_off"][
                "stream_completion_rate"],
            **result,
        })
        return 0 if result["checks_passed"] else 1

    if args.scenario == "drain-ab":
        # Live stream migration A/B: worker processes on the host
        # backend (the drain semantics are the variable under test, not
        # the chip).
        result = run_drain_ab(n_streams=8 if args.quick else 10)
        record_partial("drain_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "drain_migrated_reprefill_tokens",
            "value": result["migrate_on"]["reprefill_tokens_replayed"],
            "unit": "tokens",
            "vs_baseline": result["replay_off"][
                "reprefill_tokens_replayed"],
            **result,
        })
        return 0 if result["checks_passed"] else 1

    if args.scenario == "elastic-ab":
        # Elastic fleet A/B: in-process lanes on the host backend (the
        # capacity bill under a diurnal trace is the variable under
        # test, not the chip).
        result = run_elastic_ab(model=(args.model if args.model
                                       != "resnet50"
                                       else "gpt2-chaos-test"),
                                quick=args.quick)
        record_partial("elastic_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "elastic_lane_seconds_ratio",
            "value": result["lane_seconds_ratio"], "unit": "x",
            "vs_baseline": 1.0,
            "lane_seconds_saved": result["lane_seconds_saved"],
            **result,
        })
        return 0 if result["checks_passed"] else 1

    if args.scenario == "overload-ab":
        # Adaptive overload control A/B: in-process lanes on the host
        # backend (admission ordering and goodput under saturation are
        # the variables under test, not the chip).
        result = run_overload_ab(model=args.model, quick=args.quick)
        record_partial("overload_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "overload_interactive_goodput_gain",
            "value": result["interactive_goodput_gain"], "unit": "x",
            "vs_baseline": 1.5,
            **result,
        })
        return 0 if result["checks_passed"] else 1

    if args.scenario == "affinity-ab":
        # Prefix-affinity routing + host-tier offload A/B: in-process
        # lanes on the host backend (routing convergence and radix hit
        # ratios are the variables under test, not the chip).
        result = run_affinity_ab(model=args.model, quick=args.quick)
        record_partial("affinity_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "affinity_prefill_skip_gain",
            "value": result["skip_gain"], "unit": "x",
            "vs_baseline": 2.0,
            "ttft_p99_on_ms": result["affinity_on"]["ttft_p99_ms"],
            "ttft_p99_off_ms": result["affinity_off"]["ttft_p99_ms"],
            **result,
        })
        return 0 if result["checks_passed"] else 1

    if args.scenario == "fleet-prefix-ab":
        # Fleet prefix tier A/B: in-process lanes on the host backend
        # (directory convergence and splice accounting are the
        # variables under test, not the chip).
        result = run_fleet_prefix_ab(model=args.model, quick=args.quick)
        record_partial("fleet_prefix_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "fleet_prefix_skip_gain",
            "value": result["skip_gain"], "unit": "x",
            "vs_baseline": 2.0,
            "remote_skipped_tokens":
                result["fetch_on"]["remote_skipped_tokens"],
            **result,
        })
        return 0 if result["checks_passed"] else 1

    if args.scenario == "unified-ab":
        # Unified stateless serving A/B: in-process arms on the host
        # backend by default (the variable under test is lane
        # coordination, not the chip); the on-chip campaign's `unified`
        # stage reruns it on the device.
        kw = {}
        if args.quick:
            kw = dict(n_generate=4, n_score=8, max_new=8,
                      model_kwargs={}, repeats=1)
        result = run_unified_ab(model=args.model, **kw)
        record_partial("unified_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "unified_score_p99_speedup",
            "value": result["score_p99_speedup"], "unit": "x",
            "vs_baseline": 1.0,
            "generate_p99_speedup": result["generate_p99_speedup"],
            **result,
        })
        return 0 if result["checks_passed"] else 1

    if args.scenario == "spec-batch-ab":
        result = run_spec_ab(model=args.model)
        record_partial("spec_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "speculative_speedup_upper",
            "value": result["self_draft"]["speedup_vs_plain"], "unit": "x",
            "vs_baseline": None, "model": args.model, **result,
        })
        return 0

    if args.scenario == "prefill-mfu":
        model = args.model if args.model != "resnet50" else "gpt2"
        result = run_prefill_mfu(model=model,
                                 batch=2 if args.quick else 8,
                                 seq=64 if args.quick else 1024,
                                 iters=3 if args.quick else 10)
        record_partial("prefill_mfu", result)
        log(json.dumps(result, indent=2))
        # `value` must stay numeric for the driver; mfu is None when cost
        # analysis or the chip's peak table is unavailable (CPU smoke).
        value, unit = result["mfu"], "fraction_of_peak"
        if value is None:
            value, unit = result["prefill_tokens_per_s"], "tokens/s"
        emit({
            "metric": "prefill_mfu", "value": value,
            "unit": unit, "vs_baseline": None, **result,
        })
        return 0

    if args.scenario == "longctx":
        model = args.model if args.model != "resnet50" else "gpt2"
        result = run_longcontext_prefill(
            model=model, seqs=(32, 64) if args.quick else (4096, 8192),
            xla_arm_max_seq=64 if args.quick else 4096)
        record_partial("longcontext_prefill", result)
        log(json.dumps(result, indent=2))
        top = max(int(k.split("_S")[1]) for k in result
                  if k.startswith("flash_S"))
        emit({
            "metric": "longcontext_prefill_tokens_per_s",
            "value": result[f"flash_S{top}"]["prefill_tokens_per_s"],
            "unit": "tokens/s", "vs_baseline": None, **result,
        })
        return 0

    if args.scenario == "miss-sweep":
        result = run_miss_path_sweep(
            model="mlp" if args.quick else args.model,
            depths=(4,) if args.quick else (4, 8, 16),
            n_requests=300 if args.quick else 3000,
            n_threads=8 if args.quick else args.threads)
        record_partial("miss_path_sweep", result)
        log(json.dumps(result, indent=2))
        best = max((v["throughput_req_s"], k) for k, v in result.items()
                   if k.startswith("depth"))
        emit({
            "metric": "miss_path_throughput",
            "value": best[0], "unit": "req/s", "best_depth": best[1],
            "vs_baseline": round(best[0] / BASELINE_REQ_S, 3), **result,
        })
        return 0

    if args.scenario == "paged-ab":
        result = run_paged_ab(
            model=args.model,
            n_requests=8 if args.quick else 16,
            max_new=48 if args.quick else 96)
        record_partial("paged_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "paged_kv_capacity_gain",
            "value": result["capacity_gain"], "unit": "x",
            "vs_baseline": None, "model": args.model,
            "prefill_token_savings_frac":
                result["prefill_token_savings_frac"], **result,
        })
        return 0

    if args.scenario == "quant-ab":
        result = run_quant_ab(
            model=args.model,
            n_requests=12 if args.quick else 24,
            max_new=48 if args.quick else 96)
        record_partial("quant_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "kv_quant_capacity_gain",
            "value": result["capacity_gain"], "unit": "x",
            "vs_baseline": None, "model": args.model, **result,
        })
        return 0 if result["checks_passed"] else 1

    if args.scenario == "recurrent-ab":
        result = run_recurrent_ab(att_model=args.model, quick=args.quick)
        record_partial("recurrent_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "recurrent_state_capacity_gain",
            "value": result["capacity_gain_at_longest"], "unit": "x",
            "vs_baseline": None, "model": args.model, **result,
        })
        return 0 if result["checks_passed"] else 1

    if args.scenario == "tp-ab":
        result = run_tp_ab(model=args.model, quick=args.quick)
        record_partial("tp_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "tp_peak_rows_gain",
            "value": result["peak_rows_gain"], "unit": "x",
            "vs_baseline": None, "model": args.model, **result,
        })
        return 0 if result["checks_passed"] else 1

    if args.scenario == "disagg-ab":
        result = run_disagg_ab(model=args.model, quick=args.quick)
        record_partial("disagg_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "disagg_itl_p99_speedup",
            "value": result["itl_p99_speedup"], "unit": "x",
            "vs_baseline": None, "model": args.model, **result,
        })
        return 0 if result["checks_passed"] else 1

    if args.scenario == "mixed-ab":
        result = run_mixed_ab(
            model=args.model,
            n_short=8 if args.quick else 12,
            n_long=2 if args.quick else 4,
            max_new=24 if args.quick else 40,
            long_prompt_len=120 if args.quick else 440,
            max_seq=128 if args.quick else 512,
            prefill_chunk=64 if args.quick else 256,
            model_kwargs={} if args.quick else None)
        record_partial("mixed_ab", result)
        log(json.dumps(result, indent=2))
        emit({
            "metric": "mixed_step_itl_p99_speedup",
            "value": result["itl_p99_speedup"], "unit": "x",
            "vs_baseline": None, "model": args.model, **result,
        })
        return 0 if result["checks_passed"] else 1

    proc = None
    port = args.port
    try:
        if port == 0:
            port, proc = launch_ready(args.model, args.lanes,
                                      mixed=args.scenario == "mixed")
        log(f"waiting for server on :{port} ...")
        wait_ready(port, proc=proc)

        if args.scenario == "mixed":
            result = run_mixed_shape_bench(port)
            record_partial("mixed", result)
            log(json.dumps(result, indent=2))
            result.update(scrape_stats(port))
            emit({
                "metric": "mixed_shape_throughput",
                "value": result["throughput_req_s"], "unit": "req/s",
                "vs_baseline": None, "model": args.model, **result,
            })
            return 0 if result["failed"] == 0 else 1

        if args.cache_test:
            result = run_cache_test(port)
            record_partial("cache_test", result)
            log(json.dumps(result, indent=2))
            emit({
                "metric": "cache_speedup", "value": result["speedup"],
                "unit": "x", "vs_baseline": None, "model": args.model,
                **result,
            })
            return 0

        if args.scenario == "generate":
            result = run_generate_bench(port)
            record_partial("generate", result)
            log(json.dumps(result, indent=2))
            emit({
                "metric": "decode_throughput", "value": result["tokens_per_s"],
                "unit": "tokens/s", "vs_baseline": None, "model": args.model,
                **result,
            })
            return 0 if result["failed"] == 0 else 1

        log("server ready; warmup pass (misses populate the cache) ...")
        warm = LoadGen(port, 20, 4)
        warm.run()

        log(f"benchmark: {args.requests} requests, {args.threads} threads, "
            f"{args.distinct} distinct inputs")
        gen = LoadGen(port, args.requests, args.threads,
                      distinct_inputs=args.distinct)
        result = gen.run()
        result.update(scrape_stats(port))
        record_partial("serving", result)
        log(json.dumps(result, indent=2))

        # Miss-heavy companion load (a cache-hit workload hides the
        # engine): same wire, every input distinct — no cache, every
        # request batches onto the device.
        miss = None
        if args.distinct == 10 and not args.quick:
            n_miss = max(1000, args.requests // 5)
            log(f"miss-path load: {n_miss} distinct requests ...")
            miss = LoadGen(port, n_miss, args.threads,
                           distinct_inputs=n_miss).run()
            miss = {
                "throughput_req_s": miss["throughput_req_s"],
                "p50_ms": miss["latency_ms"]["p50"],
                "p99_ms": miss["latency_ms"]["p99"],
                "success_rate": round(miss["success_rate"], 4),
            }
            record_partial("miss_path", miss)
            log(json.dumps({"miss_path": miss}, indent=2))

        # Per-stage latency attribution from the tracing layer (queue
        # wait vs device compute etc.) — scraped before the server stops.
        trace_stages = scrape_trace_stages(port)
        if trace_stages is not None:
            record_partial("trace_stages", trace_stages)
            log(json.dumps({"trace_stages": trace_stages}, indent=2))

        # Free the chip before the in-process compute addendum.
        if proc is not None:
            stop_server(proc)
            proc = None

        compute = decode = decode_fused = None
        if not args.no_compute:
            compute = run_compute_bench()
            record_partial("compute", compute)
            log(json.dumps({"compute": compute}, indent=2))
            decode = run_decode_compute()
            record_partial("decode", decode)
            log(json.dumps({"decode": decode}, indent=2))
            decode_fused = run_decode_compute(fused=True)
            record_partial("decode_fused", decode_fused)
            log(json.dumps({"decode_fused": decode_fused}, indent=2))

        line = {
            "metric": "serving_throughput",
            "value": result["throughput_req_s"],
            "unit": "req/s",
            "vs_baseline": round(result["throughput_req_s"] / BASELINE_REQ_S, 3),
            "model": args.model,
            "requests": args.requests,
            "threads": args.threads,
            "distinct_inputs": args.distinct,
            "success_rate": round(result["success_rate"], 4),
            "p50_ms": result["latency_ms"]["p50"],
            "p99_ms": result["latency_ms"]["p99"],
            "cache_hit_rate": result.get("cache_hit_rate"),
            "avg_batch_size": result.get("avg_batch_size"),
        }
        if miss is not None:
            line["miss_path"] = miss
        if trace_stages is not None:
            line["trace_stages"] = trace_stages
        if compute is not None:
            line["compute"] = {k: compute[k] for k in
                               ("samples_per_s", "device_samples_per_s",
                                "device_step_ms", "e2e_step_ms",
                                "host_overhead_ms", "mfu",
                                "achieved_tflops", "device_kind") if k in compute}
        if decode is not None:
            line["decode"] = {k: decode[k] for k in
                              ("tokens_per_s", "decode_mfu") if k in decode}
        if decode_fused is not None:
            line["decode_fused"] = {
                k: decode_fused[k] for k in ("tokens_per_s", "decode_mfu")
                if k in decode_fused}
        emit(line)
        return 0 if result["success_rate"] > 0.99 else 1
    finally:
        stop_server(proc)


if __name__ == "__main__":
    sys.exit(main())
