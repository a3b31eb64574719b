#!/usr/bin/env python3
"""Fault-injection harness — BASELINE config 5's breaker scenario, scripted.

The reference "tests" fault tolerance by hand: kill a worker, eyeball the
gateway stats (/root/reference/README.md:322-349). This harness runs the
scenario end-to-end against a live combined server and asserts the breaker
state machine (5 consecutive failures -> OPEN; after timeout one probe ->
HALF_OPEN; 2 successes -> CLOSED, gateway.cpp:19-23 semantics):

  phase 1  baseline load, all lanes healthy        -> 100% success
  phase 2  inject fault into one lane, keep load   -> failovers, breaker OPEN
  phase 3  heal the lane, wait breaker timeout     -> probe, breaker CLOSED
  phase 4  final load                              -> 100% success again

``--slow-lane`` appends phase 5, the failure mode breakers CANNOT answer
(the lane is slow, not dead — it keeps answering, so the breaker stays
CLOSED): one lane gets per-request latency injected past the hedge
threshold, and deadline-carrying load must stay fast — the resilience
layer's hedged dispatch answers from a healthy lane, p99 stays bounded by
the deadline, no successful response exceeds its deadline, and the
``/stats`` hedge/shed/retry counters must be consistent with the fault.
Phase 5 requires the server started with hedging on, e.g.:
  python -m tpu_engine.serving.cli serve --model mlp --lanes 3 \
      --port 8000 --breaker-timeout 2 --hedge --hedge-min-ms 100

A final trace-coverage pass asserts every resilience decision the
``/stats`` counters report (shed, retry, hedge fire/win) has a matching
span in ``/trace/export`` — the tracing layer provably covers the
failure paths, not just the happy path.

``--mixed`` runs a STANDALONE mixed-stepping fault scenario instead: it
spawns its own combined server (gpt2-small-test decode lane with
``--kv-block-size 16`` and a tiny token budget so prefills
span many ticks), fires /generate requests whose deadlines expire
mid-prefill-chunk, and asserts via ``/stats`` + ``/trace/export`` that
every cancelled row returned its blocks to the pool, none reappears in a
later tick's ragged batch (active drains to 0, the pool refills), the
scheduler stayed one-dispatch-per-tick throughout, and a subsequent
request still decodes correctly.

``--spec`` runs a STANDALONE speculative-decoding fault scenario: it
spawns a combined server with a ``--spec-k 4`` paged decode lane, fires
/generate requests whose deadlines expire mid-verification (between
verify ticks, draft windows in flight), and asserts via ``/stats`` +
``/trace/export`` that every cancelled row returned its blocks, the
scheduler stayed one-verify-dispatch-per-tick, post-cancel streams are
byte-identical to pre-cancel ones, and ``spec_verify`` spans carry the
proposed/accepted attrs.

``--crash`` runs the STANDALONE crash-tolerant-streaming chaos scenario
(DESIGN.md "Crash-tolerant streaming"): it spawns three standalone worker
processes (`cli worker`, paged KV), routes /generate/stream load across
them through an in-process gateway with ``failover_streams`` + the health
prober on, kill -9s one worker while its streams are mid-generation, and
asserts every stream still completes **byte-identical** to an unkilled
control run (greedy AND seeded-sampled, penalties/stops included), the
prober ejects the dead lane, zero KV blocks leak on the survivors, and
every failover decision (resume, eject) has a matching counter AND span.
A final pass repeats the kill with failover DISABLED and asserts today's
behavior is unchanged: the victim stream truncates, and /stats carries no
failover block.

``--quant`` runs the STANDALONE quantized-KV chaos scenario (DESIGN.md
"Quantized KV blocks"): three ``--kv-quantize int8`` host-tiered workers;
it proves the int8 lifecycle live (churn demotes quantized blocks with
their scale slots paired 1:1, a re-hit swaps the verbatim int8+scale
bytes back in, swap_in counters == swap_in spans), then kill -9s the
lane holding quantized AND demoted-quantized blocks mid-stream and
asserts the PR 6 resume splices byte-identically on another quantized
lane with zero device-block, host-block, or scale-slot leaks on the
survivors.

``--disagg`` runs the STANDALONE disaggregated-serving chaos scenario
(DESIGN.md "Disaggregated serving"): four worker processes — two
``--role prefill``, two ``--role decode`` — behind a ``--disagg``
gateway. Steady state first: every /generate/stream routes to a prefill
lane, ships its finished KV chain to a decode lane (spliced, zero
fallbacks, zero replay tokens, counters == kv_handoff spans, zero block
leaks on all four pools, byte-identical to control). Then kill -9 a
prefill lane MID-HANDOFF and the adopted stream's decode lane MID-ADOPT
— both land on the replay fallback byte-identically with zero leaks on
the survivors.

``--elastic`` runs the STANDALONE elastic-fleet chaos scenario
(DESIGN.md "Elastic fleet"): two member + two warm-standby worker
processes behind an ``--autoscale`` gateway, driven through a diurnal
ramp — the closed loop must DOUBLE the fleet under Poisson stream load
(standbys join only after a passing /health probe) and HALVE it back at
low pressure with every retired lane drained through live stream
migration; every stream (greedy AND seeded) completes byte-identical to
an unkilled control with zero block leaks on every pool. Then the wedge
ladder: a scale-up at a dead address latches the NAMED ``spawn-wedged``
state and a member kill -9ed mid-drain latches ``drain-wedged`` — both
degraded-but-SERVING (a control stream completes through each), both
cleared via ``/admin/fleet``. Fleet counters == fleet marker spans
throughout.

``--all`` runs every standalone scenario above in sequence, each in its
own interpreter, and prints one JSON summary; exit is nonzero when any
scenario's check fails.

Usage:
  python3 tools/fault_injection.py [--port 8000] [--victim worker_1]
      [--requests-per-phase 60] [--breaker-timeout 2.0] [--slow-lane]
  python3 tools/fault_injection.py --mixed
  python3 tools/fault_injection.py --spec
  python3 tools/fault_injection.py --crash
  python3 tools/fault_injection.py --quant
  python3 tools/fault_injection.py --disagg
  python3 tools/fault_injection.py --elastic
  python3 tools/fault_injection.py --all
Start the server first, with a short breaker timeout so phase 3 is quick:
  python -m tpu_engine.serving.cli serve --model mlp --lanes 3 \
      --port 8000 --breaker-timeout 2
Prints a JSON report; exit 0 iff every phase met its assertion.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import subprocess
import sys
import time

# --launch imports tpu_engine.utils.net; the harness itself must stay
# runnable from anywhere (its target-a-live-server mode is stdlib-only).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def launch_combined(model: str = "mlp", lanes: int = 3,
                    breaker_timeout: float = 2.0, hedge: bool = False,
                    attempts: int = 3):
    """Spawn the combined server for a self-contained harness run
    (``--launch``), bind-race-proofed: utils.net.launch_with_retry picks
    a fresh port and relaunches when the child loses the probe-close→
    bind race and exits before ready (the same consumer-owns-the-retry
    rule bench.launch_ready applies). Returns (port, Popen)."""
    from tpu_engine.utils.net import launch_with_retry

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.setdefault("TPU_ENGINE_PLATFORM", "cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    def spawn(port: int):
        cmd = [sys.executable, "-m", "tpu_engine.serving.cli", "serve",
               "--model", model, "--lanes", str(lanes),
               "--port", str(port),
               "--breaker-timeout", str(breaker_timeout)]
        if hedge:
            cmd += ["--hedge", "--hedge-min-ms", "100"]
        proc = subprocess.Popen(cmd, cwd=repo, env=env,
                                stdout=sys.stderr, stderr=sys.stderr)
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                # Early exit = most likely the bind race: the distinct
                # error type tells launch_with_retry to repick the port.
                raise ChildProcessError(
                    f"server exited rc={proc.returncode} before ready")
            try:
                status, _ = _call(port, "GET", "/stats", timeout=2.0)
                if status == 200:
                    return proc
            except OSError:
                pass
            time.sleep(0.5)
        proc.terminate()
        raise TimeoutError("server never became ready")

    return launch_with_retry(spawn, attempts=attempts)


def _call(port: int, method: str, path: str, body=None, timeout=30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, body=payload,
                     headers={"Content-Type": "application/json"} if payload else {})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def load(port: int, ids, tag: str):
    ok = fail = 0
    nodes = {}
    for i, rid in enumerate(ids):
        try:
            status, body = _call(port, "POST", "/infer", {
                "request_id": rid,
                "input_data": [float(i % 10), float(i % 10 + 1), float(i % 10 + 2)],
            })
            if status == 200:
                ok += 1
                nodes[body["node_id"]] = nodes.get(body["node_id"], 0) + 1
            else:
                fail += 1
        except OSError:
            fail += 1
    return ok, fail, nodes


def route_map(port: int, n: int):
    """Pre-pass: learn which request ids route to which lane. The ring is
    reference-faithful 32-bit FNV-1a and therefore skewed (the reference's
    own published load split is 46.8/24.7/38.5, README.md:297-300) — fault
    phases must use ids KNOWN to route to the victim, not hash luck."""
    pools = {}
    for i in range(n):
        rid = f"probe_{i}"
        status, body = _call(port, "POST", "/infer", {
            "request_id": rid, "input_data": [float(i % 10)] * 3})
        if status == 200:
            pools.setdefault(body["node_id"], []).append(rid)
    return pools


def breaker_state(port: int, victim: str):
    _, stats = _call(port, "GET", "/stats")
    for br in stats.get("circuit_breakers", []):
        if br["node"] == victim:
            return br["state"], stats.get("failovers", 0)
    return None, stats.get("failovers", 0)


_RESILIENCE_DECISIONS = (
    "deadline_rejected", "deadline_expired", "retries",
    "retry_budget_exhausted", "backoff_waits", "hedges",
    "hedge_wins", "hedge_losses", "shed_overloaded",
)


def trace_coverage(port: int, checks: list) -> dict:
    """Assert the trace layer provably covers the resilience paths: every
    decision class the /stats counters report as exercised must have a
    matching ``resilience`` marker span (and retries/hedges their
    ``attempt`` spans) in /trace/export. The span ring is bounded, so the
    assertion is existence per decision class, not count equality — a
    counter with zero matching spans means a failure path the tracing
    layer cannot explain."""
    _, stats = _call(port, "GET", "/stats")
    res = stats.get("resilience", {})
    _, export = _call(port, "GET", "/trace/export")
    events = [e for e in export.get("traceEvents", [])
              if e.get("ph") == "X"]
    markers, attempts = {}, {}
    for e in events:
        args = e.get("args") or {}
        if e.get("name") == "resilience":
            d = args.get("decision")
            markers[d] = markers.get(d, 0) + 1
        elif e.get("name") == "attempt":
            k = args.get("kind")
            attempts[k] = attempts.get(k, 0) + 1
    report = {"counters": {d: res.get(d, 0) for d in _RESILIENCE_DECISIONS
                           if res.get(d, 0)},
              "marker_spans": markers, "attempt_spans": attempts}
    for d in _RESILIENCE_DECISIONS:
        if res.get(d, 0):
            checks.append((f"trace covers {d} "
                           f"({res[d]} in /stats)",
                           markers.get(d, 0) > 0))
    if res.get("retries", 0):
        checks.append(("retry attempts traced as attempt spans",
                       attempts.get("retry", 0) > 0))
    if res.get("hedges", 0):
        checks.append(("hedge dispatches traced as attempt spans",
                       attempts.get("hedge", 0) > 0))
    return report


def slow_lane_phase(port: int, victim: str, victim_ids, n: int,
                    checks: list, latency_s: float = 1.0,
                    deadline_ms: float = 2000.0) -> dict:
    """Phase 5: the victim lane is SLOW (not dead). Deadline-carrying load
    on victim-routed ids must be answered fast by hedging — and every
    success must land inside its deadline."""
    before = _call(port, "GET", "/stats")[1].get("resilience", {})
    _call(port, "POST", "/admin/fault",
          {"node": victim, "action": "slow", "latency_s": latency_s})
    lats_ms, ok, shed, fail = [], 0, 0, 0
    nodes = {}
    try:
        for i, rid in enumerate(victim_ids[:n]):
            t0 = time.perf_counter()
            try:
                # DISTINCT inputs: phase 0-4 warmed the result caches (and
                # the native C++ front answers hits without touching the
                # slowed Python lane at all) — only misses exercise the
                # slow path hedging must rescue.
                status, body = _call(port, "POST", "/infer", {
                    "request_id": rid,
                    "input_data": [5e6 + i, 5e6 + i + 0.25, 5e6 + i + 0.5],
                    "deadline_ms": deadline_ms,
                }, timeout=deadline_ms / 1000.0 + latency_s + 10)
            except OSError:
                fail += 1
                continue
            lat_ms = (time.perf_counter() - t0) * 1e3
            if status == 200:
                ok += 1
                lats_ms.append(lat_ms)
                nodes[body["node_id"]] = nodes.get(body["node_id"], 0) + 1
            elif status == 503:
                shed += 1  # an honest shed beats a deadline-blown success
            else:
                fail += 1
    finally:
        _call(port, "POST", "/admin/fault",
              {"node": victim, "action": "heal"})
    after = _call(port, "GET", "/stats")[1].get("resilience", {})
    lats_ms.sort()
    p99 = lats_ms[int(0.99 * (len(lats_ms) - 1))] if lats_ms else None
    hedges = after.get("hedges", 0) - before.get("hedges", 0)
    wins = after.get("hedge_wins", 0) - before.get("hedge_wins", 0)
    losses = after.get("hedge_losses", 0) - before.get("hedge_losses", 0)
    report = {"ok": ok, "shed": shed, "fail": fail, "nodes": nodes,
              "p99_ms": p99, "deadline_ms": deadline_ms,
              "injected_latency_ms": latency_s * 1e3,
              "hedges": hedges, "hedge_wins": wins,
              "hedge_losses": losses, "resilience": after}
    checks.append(("slow lane: no hard failures", fail == 0))
    checks.append(("slow lane: requests answered", ok > 0))
    checks.append(("slow lane: no success exceeded its deadline",
                   all(l <= deadline_ms for l in lats_ms)))
    checks.append(("slow lane: p99 bounded by the deadline",
                   p99 is not None and p99 <= deadline_ms))
    checks.append(("slow lane: hedges fired", hedges > 0))
    checks.append(("slow lane: hedge wins recorded", wins > 0))
    checks.append(("slow lane: hedge accounting consistent",
                   wins >= 0 and losses >= 0 and wins + losses <= hedges))
    # The breaker must NOT have opened — the lane answers, just slowly;
    # this is exactly the gap the resilience layer closes.
    state, _ = breaker_state(port, victim)
    checks.append(("slow lane: breaker stayed CLOSED", state == "CLOSED"))
    return report


def launch_mixed_server(attempts: int = 3):
    """Spawn a combined server with a mixed-stepping decode lane sized so
    prefills span MANY ticks (budget 2 tokens/tick): a short deadline
    reliably expires mid-prefill-chunk. Returns (port, Popen)."""
    from tpu_engine.utils.net import launch_with_retry

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_ENGINE_PLATFORM", "cpu")

    def spawn(port: int):
        cmd = [sys.executable, "-m", "tpu_engine.serving.cli", "serve",
               "--model", "gpt2-small-test", "--lanes", "1",
               "--port", str(port), "--kv-block-size", "16",
               "--mixed-token-budget", "2",
               "--gen-prefill-chunk", "16"]
        proc = subprocess.Popen(cmd, cwd=repo, env=env,
                                stdout=sys.stderr, stderr=sys.stderr)
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise ChildProcessError(
                    f"server exited rc={proc.returncode} before ready")
            try:
                status, _ = _call(port, "GET", "/stats", timeout=2.0)
                if status == 200:
                    return proc
            except OSError:
                pass
            time.sleep(0.5)
        proc.terminate()
        raise TimeoutError("server never became ready")

    return launch_with_retry(spawn, attempts=attempts)


def mixed_phase(port: int, checks: list) -> dict:
    """Mixed-stepping cancellation scenario: deadline-expired rows
    mid-prefill-chunk must return their blocks and never appear in a
    later tick's ragged batch."""
    # Warm the decode lane (compiles happen here, not under deadlines).
    status, body = _call(port, "POST", "/generate", {
        "request_id": "mx_warm", "prompt_tokens": [5, 9, 3],
        "max_new_tokens": 4}, timeout=600)
    checks.append(("mixed: warm generate ok",
                   status == 200 and len(body.get("tokens", [])) == 4))
    warm_tokens = body.get("tokens")
    _, stats0 = _call(port, "GET", "/stats")
    mixed0 = next(iter(stats0.get("mixed", {}).values()), {})

    # Long prompts (bucket 64 at gpt2-small-test's max_seq) with tiny
    # deadlines: at 2 tokens/tick the ~60-token prefill spans ~30 ticks,
    # so these deadlines expire mid-prefill-chunk, between ticks.
    expired = survived = 0
    for i in range(6):
        prompt = [(i * 13 + j) % 90 + 1 for j in range(58)]
        try:
            status, body = _call(port, "POST", "/generate", {
                "request_id": f"mx_dead_{i}", "prompt_tokens": prompt,
                "max_new_tokens": 20, "deadline_ms": 40 + 10 * i,
            }, timeout=120)
        except OSError:
            status, body = 0, {}
        if status in (500, 503):
            expired += 1
        elif status == 200:
            survived += 1
    checks.append(("mixed: deadlines expired mid-prefill", expired > 0))

    # Drain: every cancelled row must return its blocks (free + radix-held
    # == total) and leave the batch (active == 0).
    pool = active = None
    deadline = time.time() + 20
    while time.time() < deadline:
        _, stats = _call(port, "GET", "/stats")
        mixed = next(iter(stats.get("mixed", {}).values()), {})
        pool = next(iter(stats.get("kv_pool", {}).values()), {})
        active = mixed.get("active")
        if active == 0 and pool and (
                pool["blocks_free"] + pool["radix_nodes"]
                >= pool["blocks_total"]):
            break
        time.sleep(0.2)
    checks.append(("mixed: cancelled rows left the ragged batch "
                   "(active drained to 0)", active == 0))
    checks.append(("mixed: cancelled rows returned their blocks",
                   bool(pool) and pool["blocks_free"] + pool["radix_nodes"]
                   >= pool["blocks_total"]))

    # One dispatch per tick held through the churn, and ticks advanced.
    _, stats = _call(port, "GET", "/stats")
    mixed = next(iter(stats.get("mixed", {}).values()), {})
    checks.append(("mixed: one dispatch per tick",
                   mixed.get("ticks", 0) == mixed.get("dispatches", -1)))
    checks.append(("mixed: ticks advanced during the scenario",
                   mixed.get("ticks", 0) > mixed0.get("ticks", 0)))

    # The scheduler still serves correctly after the cancellations — and
    # a repeated seeded prompt reproduces the warm stream exactly (no
    # half-written state leaked into the pool or radix tree).
    status, body = _call(port, "POST", "/generate", {
        "request_id": "mx_after", "prompt_tokens": [5, 9, 3],
        "max_new_tokens": 4}, timeout=120)
    checks.append(("mixed: post-cancel request streams identically",
                   status == 200 and body.get("tokens") == warm_tokens))

    # Trace coverage: the mixed_step spans are in /trace/export with the
    # ragged-batch attrs the tentpole promises.
    _, export = _call(port, "GET", "/trace/export")
    spans = [e for e in export.get("traceEvents", [])
             if e.get("ph") == "X" and e.get("name") == "mixed_step"]
    has_attrs = any("prefill_tokens" in (e.get("args") or {})
                    and "decode_rows" in (e.get("args") or {})
                    for e in spans)
    checks.append(("mixed: mixed_step spans exported with "
                   "prefill_tokens/decode_rows attrs",
                   len(spans) > 0 and has_attrs))
    return {"expired": expired, "survived": survived,
            "kv_pool": pool, "mixed": mixed,
            "mixed_step_spans": len(spans)}


def launch_spec_server(attempts: int = 3):
    """Spawn a combined server with a speculative decode lane
    (--spec-k 4 over the paged pool): verify windows advance rows
    multiple tokens per tick, and short deadlines expire between verify
    ticks — mid-verification from the request's point of view. Returns
    (port, Popen)."""
    from tpu_engine.utils.net import launch_with_retry

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_ENGINE_PLATFORM", "cpu")

    def spawn(port: int):
        cmd = [sys.executable, "-m", "tpu_engine.serving.cli", "serve",
               "--model", "gpt2-small-test", "--lanes", "1",
               "--port", str(port), "--kv-block-size", "16",
               "--spec-k", "4", "--gen-prefill-chunk", "16"]
        proc = subprocess.Popen(cmd, cwd=repo, env=env,
                                stdout=sys.stderr, stderr=sys.stderr)
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise ChildProcessError(
                    f"server exited rc={proc.returncode} before ready")
            try:
                status, _ = _call(port, "GET", "/stats", timeout=2.0)
                if status == 200:
                    return proc
            except OSError:
                pass
            time.sleep(0.5)
        proc.terminate()
        raise TimeoutError("server never became ready")

    return launch_with_retry(spawn, attempts=attempts)


def spec_phase(port: int, checks: list) -> dict:
    """Speculative-decoding cancellation scenario: rows deadline-
    cancelled mid-verification (between verify ticks, draft windows in
    flight) must return every pool block, and post-cancel streams must
    be identical — no rejected-tail ghost or half-freed block may leak
    into later requests."""
    # Warm the decode lane + capture the oracle stream. [3, 3, 3]
    # degenerates into a repetitive loop on this init, so the warm run
    # also exercises real draft acceptance.
    status, body = _call(port, "POST", "/generate", {
        "request_id": "sp_warm", "prompt_tokens": [3, 3, 3],
        "max_new_tokens": 12}, timeout=600)
    checks.append(("spec: warm generate ok",
                   status == 200 and len(body.get("tokens", [])) == 12))
    warm_tokens = body.get("tokens")
    _, stats0 = _call(port, "GET", "/stats")
    spec0 = next(iter(stats0.get("spec", {}).values()), {})
    checks.append(("spec: scheduler speculating (drafts proposed)",
                   spec0.get("proposed_tokens", 0) > 0))

    # Long generations with tiny deadlines: they admit, enter verify
    # ticks, and expire mid-stream — the row must free between ticks.
    expired = survived = 0
    for i in range(6):
        prompt = [(i * 13 + j) % 90 + 1 for j in range(40)]
        try:
            status, body = _call(port, "POST", "/generate", {
                "request_id": f"sp_dead_{i}", "prompt_tokens": prompt,
                "max_new_tokens": 40, "deadline_ms": 30 + 10 * i,
            }, timeout=120)
        except OSError:
            status, body = 0, {}
        if status in (500, 503):
            expired += 1
        elif status == 200:
            survived += 1
    checks.append(("spec: deadlines expired mid-verification",
                   expired > 0))

    # Drain: every cancelled row returns its blocks and leaves the batch.
    pool = active = None
    deadline = time.time() + 20
    while time.time() < deadline:
        _, stats = _call(port, "GET", "/stats")
        spec = next(iter(stats.get("spec", {}).values()), {})
        pool = next(iter(stats.get("kv_pool", {}).values()), {})
        active = spec.get("active")
        if active == 0 and pool and (
                pool["blocks_free"] + pool["radix_nodes"]
                >= pool["blocks_total"]):
            break
        time.sleep(0.2)
    checks.append(("spec: cancelled rows left the batch "
                   "(active drained to 0)", active == 0))
    checks.append(("spec: cancelled rows returned their blocks",
                   bool(pool) and pool["blocks_free"] + pool["radix_nodes"]
                   >= pool["blocks_total"]))

    # One verify dispatch per tick held through the churn.
    _, stats = _call(port, "GET", "/stats")
    spec = next(iter(stats.get("spec", {}).values()), {})
    checks.append(("spec: one dispatch per tick",
                   spec.get("ticks", 0) == spec.get("dispatches", -1)))
    checks.append(("spec: ticks advanced during the scenario",
                   spec.get("ticks", 0) > spec0.get("ticks", 0)))

    # Post-cancel stream identity: the seeded warm prompt reproduces its
    # stream exactly (no stale draft KV or leaked block corrupts it).
    status, body = _call(port, "POST", "/generate", {
        "request_id": "sp_after", "prompt_tokens": [3, 3, 3],
        "max_new_tokens": 12}, timeout=120)
    checks.append(("spec: post-cancel request streams identically",
                   status == 200 and body.get("tokens") == warm_tokens))

    # Trace coverage: spec_verify spans with draft/accept attrs.
    _, export = _call(port, "GET", "/trace/export")
    spans = [e for e in export.get("traceEvents", [])
             if e.get("ph") == "X" and e.get("name") == "spec_verify"]
    has_attrs = any("proposed" in (e.get("args") or {})
                    and "accepted" in (e.get("args") or {})
                    for e in spans)
    checks.append(("spec: spec_verify spans exported with "
                   "proposed/accepted attrs",
                   len(spans) > 0 and has_attrs))
    return {"expired": expired, "survived": survived,
            "kv_pool": pool, "spec": spec,
            "spec_verify_spans": len(spans)}


def run_spec_standalone() -> int:
    port, proc = launch_spec_server()
    checks: list = []
    try:
        report = {"mode": "spec-standalone", "port": port,
                  "phases": {"spec": spec_phase(port, checks)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def launch_worker_procs(n: int = 3, attempts: int = 3, extra_args=(),
                        per_worker_args=None,
                        model: str = "gpt2-small-test",
                        base_args=("--kv-block-size", "16",
                                   "--step-chunk", "2",
                                   "--prefill-chunk", "16")):
    """Spawn ``n`` standalone worker processes (``cli worker``, paged KV,
    tiny chunks so streams span many frames) — the killable unit of the
    crash/offload scenarios. ``extra_args`` append to each worker's argv
    (the offload scenario adds a tiny pool + ``--kv-host-blocks``);
    ``per_worker_args[i]`` appends per worker (the disagg scenario's
    ``--role`` split). ``model``/``base_args`` swap the served family
    (the recurrent scenario runs state_slab lanes, which take no
    --kv-block-size). Returns (ports, procs)."""
    from tpu_engine.utils.net import launch_with_retry

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_ENGINE_PLATFORM", "cpu")

    def make_spawn(i):
        def spawn(port: int):
            per = (tuple(per_worker_args[i])
                   if per_worker_args is not None else ())
            cmd = [sys.executable, "-m", "tpu_engine.serving.cli", "worker",
                   str(port), f"w{i}", model,
                   *base_args, *extra_args, *per]
            proc = subprocess.Popen(cmd, cwd=repo, env=env,
                                    stdout=sys.stderr, stderr=sys.stderr)
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline:
                if proc.poll() is not None:
                    raise ChildProcessError(
                        f"worker exited rc={proc.returncode} before ready")
                try:
                    status, _ = _call(port, "GET", "/health", timeout=2.0)
                    if status == 200:
                        return proc
                except OSError:
                    pass
                time.sleep(0.5)
            proc.terminate()
            raise TimeoutError("worker never became ready")
        return spawn

    ports, procs = [], []
    for i in range(n):
        port, proc = launch_with_retry(make_spawn(i), attempts=attempts)
        ports.append(port)
        procs.append(proc)
    return ports, procs


def _worker_pool_clean(port: int, timeout_s: float = 30.0):
    """Poll a worker's /health until its scheduler is idle and every KV
    block is accounted for (free list + radix-held). Returns the final
    kv_pool dict (or None if /health never settled)."""
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        try:
            _, health = _call(port, "GET", "/health", timeout=5.0)
        except OSError:
            time.sleep(0.3)
            continue
        gen = health.get("generator", {})
        last = gen.get("kv_pool")
        if (gen.get("active") == 0 and last and
                last["blocks_free"] + last["radix_nodes"]
                >= last["blocks_total"]):
            return last
        time.sleep(0.3)
    return None


def drive_streams_with_kill(gw, requests, victim_rids, kill, rng,
                            arrival_rate: float = 8.0,
                            kill_window_s: float = 120.0,
                            kill_when: str = "any"):
    """The shared chaos drive of the crash, drain and kill scenarios
    below: fire each request as a /generate/stream
    through ``gw`` at Poisson arrivals, invoke ``kill()`` once, the
    moment victim-primary streams are provably mid-generation (>= 3
    tokens relayed, not yet finished), then join. ``kill_when="any"``
    (default) fires on the FIRST such stream — the crash scenarios'
    shape; ``"all"`` waits until EVERY victim stream is mid-generation
    (or already finished) — the drain scenarios' shape, where the
    interesting case is a lane full of in-flight streams, not one.
    Returns (results, killed) where results[rid] = (streamed_tokens,
    final_event) — final_event is None for a truncated stream and
    {"harness_exception": ...} when the iterator raised."""
    import threading

    from tpu_engine.serving.gateway import _parse_sse

    progress = {r["request_id"]: 0 for r in requests}
    results: dict = {}
    lock = threading.Lock()

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
                    with lock:
                        progress[req["request_id"]] = len(toks)
        except Exception as exc:
            final = {"harness_exception": str(exc)}
        with lock:
            results[req["request_id"]] = (toks, final)

    threads = []
    for req in requests:
        t = threading.Thread(target=consume, args=(req,), daemon=True)
        t.start()
        threads.append(t)
        time.sleep(rng.expovariate(arrival_rate))
    killed = False
    deadline = time.monotonic() + kill_window_s
    while time.monotonic() < deadline:
        with lock:
            live = [r for r in victim_rids
                    if progress[r] >= 3 and r not in results]
            settled = [r for r in victim_rids if r in results]
        fire = (bool(live) if kill_when == "any"
                else live and len(live) + len(settled)
                == len(victim_rids))
        if fire:
            kill()
            killed = True
            break
        time.sleep(0.02)
    for t in threads:
        t.join(timeout=600)
    return results, killed


def stream_completed(final) -> bool:
    """A stream counts as completed only on a clean terminal event."""
    return bool(final and final.get("done") and "error" not in final)


def victim_lane_for_port(lanes, port: int) -> str:
    """The gateway lane name backed by the worker on ``port`` (lane
    names are client URLs; suffix-match so port 80 never matches 8080)."""
    return next(l for l in lanes if l.endswith(f":{port}"))


def control_oracle(port: int, requests) -> dict:
    """Blocking /generate control run against ONE healthy worker — the
    uninterrupted oracle spliced streams must match byte-for-byte.
    Returns {request_id: tokens}; raises on any non-200."""
    control = {}
    for r in requests:
        status, body = _call(port, "POST", "/generate",
                             dict(r, request_id="ctl_" + r["request_id"]),
                             timeout=600)
        if status != 200:
            raise RuntimeError(f"control run failed ({status}): {body}")
        control[r["request_id"]] = body["tokens"]
    return control


def tally_streams(results, control):
    """(complete, identical, resumed) over drive_streams_with_kill
    results vs the control oracle."""
    complete = sum(1 for toks, final in results.values()
                   if stream_completed(final))
    identical = sum(1 for rid, (toks, final) in results.items()
                    if toks == control[rid]
                    and final and final.get("tokens") == control[rid])
    resumed = sum(1 for _, final in results.values()
                  if final and final.get("resumed"))
    return complete, identical, resumed


def rid_for_lane(ring, lane: str, tag: str, cap: int = 4000) -> str:
    """Mine a request id whose ring primary is ``lane`` (shared by the
    chaos harness and diagnostics --failover). The
    reference-faithful FNV-1a ring is SKEWED — its own published split is
    46.8/24.7/38.5 — so similar-prefix candidates can run long streaks on
    one lane; iterate plenty before giving up."""
    for i in range(cap):
        rid = f"{tag}_{i}"
        if ring.get_node(rid) == lane:
            return rid
    raise RuntimeError(f"no rid within {cap} candidates maps to {lane}")


def crash_phase(ports, procs, checks: list) -> dict:
    """Kill -9 one worker while its streams are mid-generation under
    Poisson load; with failover on, every stream must complete
    byte-identical to the unkilled control run."""
    import random
    import signal

    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.utils.config import GatewayConfig

    gw = Gateway([f"127.0.0.1:{p}" for p in ports],
                 GatewayConfig(failover_streams=True,
                               health_probe_interval_s=0.25,
                               health_probe_failures=2))
    lanes = gw.worker_names()
    victim_lane = victim_lane_for_port(lanes, ports[1])
    victim_proc = procs[1]

    # Request mix: greedy, seeded-sampled, and controls (penalty + stop)
    # streams; rids are chosen AGAINST the ring so a known share starts on
    # the victim lane, with long budgets so they are mid-flight at kill.
    requests = []
    for k in range(12):
        lane = victim_lane if k % 3 == 0 else lanes[k % len(lanes)]
        params = {}
        if k % 3 == 1:
            params = {"temperature": 0.9, "seed": 100 + k}
        elif k % 3 == 2:
            params = {"temperature": 0.8, "seed": 200 + k,
                      "repetition_penalty": 1.3, "stop_tokens": [7],
                      "top_p": 0.9}
        requests.append({
            "request_id": rid_for_lane(gw._ring, lane, f"cr{k}"),
            "prompt_tokens": [(k * 7 + j) % 90 + 1 for j in range(6 + k % 5)],
            "max_new_tokens": 60 if lane == victim_lane else 24,
            **params})
    victim_rids = {r["request_id"] for r in requests
                   if gw._ring.get_node(r["request_id"]) == victim_lane}

    # Control: every request, blocking, against ONE healthy worker — the
    # uninterrupted oracle the spliced streams must match byte-for-byte.
    try:
        control = control_oracle(ports[0], requests)
    except RuntimeError as exc:
        checks.append(("crash: control generate", False))
        return {"error": str(exc)}
    # Warm the other lanes' compile caches so the kill lands mid-decode,
    # not mid-compile (the resume path itself re-warms the radix).
    for p in ports[1:]:
        _call(p, "POST", "/generate",
              {"request_id": f"warm_{p}", "prompt_tokens": [1, 2, 3],
               "max_new_tokens": 4}, timeout=600)

    def kill_victim():
        victim_proc.send_signal(signal.SIGKILL)
        victim_proc.wait(timeout=10)

    results, killed = drive_streams_with_kill(
        gw, requests, victim_rids, kill_victim, random.Random(0))
    checks.append(("crash: victim killed mid-stream", killed))

    # Every stream completed, byte-identical to the unkilled control.
    complete, identical, resumed = tally_streams(results, control)
    mismatches = [
        {"rid": rid, "control": control[rid], "streamed": toks,
         "final_tokens": (final or {}).get("tokens"),
         "resumed": (final or {}).get("resumed", 0),
         "victim_primary": rid in victim_rids,
         "final": {k: v for k, v in (final or {}).items()
                   if k not in ("tokens",)},
         "params": next(r for r in requests
                        if r["request_id"] == rid)}
        for rid, (toks, final) in results.items()
        if toks != control[rid]
        or not final or final.get("tokens") != control[rid]]
    checks.append(("crash: all streams completed "
                   f"({complete}/{len(requests)})",
                   complete == len(requests)))
    checks.append(("crash: all streams byte-identical to control "
                   f"({identical}/{len(requests)})",
                   identical == len(requests)))
    checks.append(("crash: at least one stream resumed", resumed >= 1))

    # Failover decisions: counters == spans, prober ejected the corpse.
    # Wait for the ejection FIRST — the prober needs ~2 probe intervals
    # after the kill — then settle the counter/span comparison (the
    # prober bumps the counter before recording its span, so one
    # snapshot can land between the two).
    ejected = False
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if victim_lane in gw.ejected_lanes():
            ejected = True
            break
        time.sleep(0.1)
    checks.append(("crash: prober ejected the dead lane", ejected))
    fo, resume_spans, eject_spans = {}, [], []
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        fo = gw.get_stats().get("failover", {})
        spans = gw.tracer.snapshot()
        resume_spans = [s for s in spans if s["op"] == "resume"]
        eject_spans = [s for s in spans if s["op"] == "prober"
                       and s["attrs"]["action"] == "eject"]
        if (len(resume_spans) == fo.get("resumes_attempted", -1)
                and len(eject_spans) == fo.get("prober_ejections", -1)):
            break
        time.sleep(0.1)
    checks.append(("crash: resumes attempted >= 1",
                   fo.get("resumes_attempted", 0) >= 1))
    checks.append(("crash: failover counters == resume spans",
                   len(resume_spans) == fo.get("resumes_attempted", -1)))
    checks.append(("crash: prober ejections == eject spans",
                   len(eject_spans) == fo.get("prober_ejections", -1)
                   and fo.get("prober_ejections", 0) >= 1))

    # Post-kill availability: a FRESH stream admits and completes.
    fresh = {"request_id": "post_kill", "prompt_tokens": [9, 8, 7],
             "max_new_tokens": 8}
    ctl = _call(ports[0], "POST", "/generate",
                dict(fresh, request_id="ctl_post"), timeout=600)[1]
    toks = []
    for frame in gw.route_generate_stream(dict(fresh)):
        evt = _parse_sse(frame)
        if evt and evt.get("done"):
            checks.append(("crash: post-kill stream completes identically",
                           "error" not in evt
                           and evt["tokens"] == ctl["tokens"]))
            break
        if evt and "tokens" in evt:
            toks.extend(evt["tokens"])

    # Zero KV blocks leaked on the survivors.
    for p in (ports[0], ports[2]):
        pool = _worker_pool_clean(p)
        checks.append((f"crash: no KV blocks leaked on survivor :{p}",
                       pool is not None))
    gw.stop()

    # A/B: failover DISABLED is today's behavior — the victim stream
    # truncates (no terminal event), and /stats carries no failover block.
    gw_off = Gateway([f"127.0.0.1:{ports[0]}", f"127.0.0.1:{ports[2]}"],
                     GatewayConfig())
    off_victim_lane = victim_lane_for_port(gw_off.worker_names(), ports[2])
    off_rid = rid_for_lane(gw_off._ring, off_victim_lane, "off")
    off_req = {"request_id": off_rid, "prompt_tokens": [4, 5, 6],
               "max_new_tokens": 60}
    def kill_off_victim():
        procs[2].send_signal(signal.SIGKILL)
        procs[2].wait(timeout=10)

    off_results, off_killed = drive_streams_with_kill(
        gw_off, [off_req], {off_rid}, kill_off_victim, random.Random(1))
    _, off_final = off_results[off_rid]
    truncated = off_killed and not stream_completed(off_final)
    checks.append(("crash: failover OFF leaves the stream truncated "
                   "(today's behavior)", truncated))
    checks.append(("crash: failover OFF /stats has no failover block",
                   "failover" not in gw_off.get_stats()))
    gw_off.stop()
    return {"streams": len(requests), "complete": complete,
            "identical": identical, "mismatches": mismatches,
            "resumed_streams": resumed,
            "victim_primary_streams": len(victim_rids),
            "failover": fo, "resume_spans": len(resume_spans),
            "failover_off_truncated": truncated}


def _worker_pool_clean_tiered(port: int, timeout_s: float = 30.0):
    """`_worker_pool_clean` for host-tiered workers: demoted radix nodes
    hold HOST slots, not device blocks, so the device accounting is
    free + (radix_nodes - host_used) >= total, and the host tier itself
    must not hold more slots than it has."""
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        try:
            _, health = _call(port, "GET", "/health", timeout=5.0)
        except OSError:
            time.sleep(0.3)
            continue
        gen = health.get("generator", {})
        last = gen.get("kv_pool")
        if gen.get("active") == 0 and last:
            host = last.get("host") or {}
            used = host.get("blocks_used", 0)
            if (last["blocks_free"] + last["radix_nodes"] - used
                    >= last["blocks_total"]
                    and used <= host.get("blocks_total", 0)):
                return last
        time.sleep(0.3)
    return None


def offload_phase(ports, procs, checks: list) -> dict:
    """Hierarchical host-tier chaos (--offload): kill -9 a worker that
    HOLDS DEMOTED BLOCKS while one of its streams is mid-generation.
    The host tier dies with the process — failover must not depend on
    it: the PR 6 resume completes byte-identically on another lane, and
    the survivors leak zero device OR host blocks. Before the kill, the
    phase also proves the tier's point on the victim itself: churn
    demotes the shared prefix, and a re-hit SWAPS IT BACK IN (swap_in
    counters move, prefill tokens are skipped) instead of recomputing."""
    import random
    import signal

    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.utils.config import GatewayConfig

    gw = Gateway([f"127.0.0.1:{p}" for p in ports],
                 GatewayConfig(failover_streams=True,
                               health_probe_interval_s=0.25,
                               health_probe_failures=2,
                               prefix_affinity=True,
                               affinity_block_size=16))
    shared = [(j * 13) % 90 + 1 for j in range(32)]  # two full blocks

    # Affinity makes the victim deterministic: the lane owning the
    # shared prefix's fingerprint serves every shared-prefix request.
    fp = gw._affinity_fingerprint({"prompt_tokens": shared})
    victim_lane = gw._ring.get_node(fp)
    victim_port = next(p for p in ports
                       if victim_lane.endswith(f":{p}"))
    victim_idx = ports.index(victim_port)
    survivor_ports = [p for p in ports if p != victim_port]

    # Warm every lane, then prime the victim's radix with the prefix.
    for p in ports:
        _call(p, "POST", "/generate",
              {"request_id": f"warm_{p}", "prompt_tokens": [1, 2, 3],
               "max_new_tokens": 4}, timeout=600)
    status, prime = _call(
        victim_port, "POST", "/generate",
        {"request_id": "prime", "prompt_tokens": shared + [5, 6],
         "max_new_tokens": 4}, timeout=600)
    _, health = _call(victim_port, "GET", "/health", timeout=10)
    pool = health["generator"]["kv_pool"]
    checks.append(("offload: shared prefix primed on victim",
                   status == 200 and pool["radix_nodes"] >= 2))

    # Churn the victim's tiny pool with distinct prompts until the
    # shared prefix (and the fillers') blocks demote to the host tier.
    rnd = random.Random(3)
    for i in range(6):
        filler = [rnd.randrange(1, 200) for _ in range(72)]
        _call(victim_port, "POST", "/generate",
              {"request_id": f"churn{i}", "prompt_tokens": filler,
               "max_new_tokens": 2}, timeout=600)
    _, health = _call(victim_port, "GET", "/health", timeout=10)
    pool = health["generator"]["kv_pool"]
    host = pool.get("host") or {}
    checks.append(("offload: churn demoted blocks to the host tier "
                   f"(demotions={host.get('demotions', 0)})",
                   host.get("demotions", 0) > 0))

    # Re-hit through the gateway — affinity must route it to the victim
    # (the lane owning the fingerprint), whose demoted prefix must swap
    # back in, not recompute.
    hit0, si0 = pool["prefix_hit_tokens"], host.get("swap_ins", 0)
    rehit = gw.route_generate(
        {"request_id": "rehit", "prompt_tokens": shared + [9, 9],
         "max_new_tokens": 4})
    checks.append(("offload: affinity routed the re-hit to the prefix "
                   "owner", rehit["node_id"]
                   == f"w{victim_idx}"))
    _, health = _call(victim_port, "GET", "/health", timeout=10)
    pool = health["generator"]["kv_pool"]
    host = pool.get("host") or {}
    checks.append(("offload: re-hit swapped in instead of recomputing "
                   f"(swap_ins {si0}->{host.get('swap_ins', 0)})",
                   host.get("swap_ins", 0) > si0
                   and pool["prefix_hit_tokens"] > hit0))

    # Mid-stream kill while the victim holds demoted blocks: long
    # shared-prefix stream (affinity -> victim) + the kill the moment it
    # is provably mid-generation; resume must splice byte-identically.
    req = {"request_id": "offload_stream", "prompt_tokens": shared + [2],
           "max_new_tokens": 48}
    control = control_oracle(survivor_ports[0], [req])

    def kill_victim():
        procs[victim_idx].send_signal(signal.SIGKILL)
        procs[victim_idx].wait(timeout=10)

    results, killed = drive_streams_with_kill(
        gw, [req], {req["request_id"]}, kill_victim, random.Random(5))
    checks.append(("offload: victim (holding demoted blocks) killed "
                   "mid-stream", killed))
    toks, final = results[req["request_id"]]
    identical = (stream_completed(final)
                 and toks == control[req["request_id"]]
                 and final.get("tokens") == control[req["request_id"]])
    checks.append(("offload: stream resumed byte-identically on another "
                   "lane", identical and bool(final.get("resumed"))))

    # Survivors: fresh availability + zero device/host block leaks.
    status, _ = _call(survivor_ports[0], "POST", "/generate",
                      {"request_id": "post", "prompt_tokens": [4, 2],
                       "max_new_tokens": 4}, timeout=600)
    checks.append(("offload: post-kill availability", status == 200))
    leak_free = {}
    for p in survivor_ports:
        pool = _worker_pool_clean_tiered(p)
        leak_free[p] = pool is not None
        checks.append((f"offload: zero device+host blocks leaked on "
                       f"survivor :{p}", pool is not None))
    fo = gw.get_stats().get("failover", {})
    gw.stop()
    return {"victim_port": victim_port, "killed": killed,
            "stream_identical": identical,
            "resumed": (final or {}).get("resumed", 0),
            "victim_demotions_at_churn": host.get("demotions", 0),
            "victim_swap_ins": host.get("swap_ins", 0),
            "failover": fo, "survivors_leak_free": leak_free}


def _migration_counters_match_spans(gw) -> bool:
    from tpu_engine.serving.resilience import MigrationCounters

    mig = gw.get_stats().get("migration", {})
    expect = sum(mig.get(f, 0) for f in MigrationCounters.SPAN_FIELDS)
    spans = [s for s in gw.tracer.snapshot() if s["op"] == "migration"]
    return len(spans) == expect


def migrate_phase(ports, procs, checks: list) -> dict:
    """Live-stream-migration chaos (--migrate). Phase A: drain a lane
    MID-STREAM under Poisson load with migrate mode on — every stream
    (the migrated ones included) must complete byte-identical to an
    unkilled control with ZERO replay traffic and zero device/host
    block leaks on every pool, the DRAINED lane's included (it is
    alive; its exported rows must have released everything). Phase B:
    kill -9 the continuation's DESTINATION before the transfer — the
    fallback ladder must land on the PR 6 replay resume and still
    complete the stream byte-identically. Counters == migration marker
    spans throughout."""
    import random
    import signal

    from tpu_engine.serving.gateway import Gateway, _StreamRecord
    from tpu_engine.utils.config import GatewayConfig
    from tpu_engine.utils.tracing import TraceContext

    # ---- Phase A: migrate-mode drain under load -------------------------
    gw = Gateway([f"127.0.0.1:{p}" for p in ports[:3]],
                 GatewayConfig(failover_streams=True,
                               migrate_streams=True,
                               migrate_timeout_s=60.0,
                               health_probe_interval_s=0.25,
                               health_probe_failures=2))
    lanes = gw.worker_names()
    victim_lane = victim_lane_for_port(lanes, ports[1])

    requests = []
    for k in range(10):
        lane = victim_lane if k % 3 == 0 else lanes[k % len(lanes)]
        params = {}
        if k % 3 == 1:
            params = {"temperature": 0.9, "seed": 500 + k}
        elif k % 3 == 2:
            params = {"temperature": 0.8, "seed": 600 + k,
                      "repetition_penalty": 1.3, "stop_tokens": [7],
                      "top_p": 0.9}
        # Victim streams run LONG so every one is still mid-flight when
        # the drain lands (kill_when="all" below waits for that).
        requests.append({
            "request_id": rid_for_lane(gw._ring, lane, f"mg{k}"),
            "prompt_tokens": [(k * 5 + j) % 90 + 1
                              for j in range(6 + k % 5)],
            "max_new_tokens": 150 if lane == victim_lane else 24,
            **params})
    victim_rids = {r["request_id"] for r in requests
                   if gw._ring.get_node(r["request_id"]) == victim_lane}
    try:
        control = control_oracle(ports[0], requests)
    except RuntimeError as exc:
        checks.append(("migrate: control generate", False))
        return {"error": str(exc)}
    for p in ports[1:3]:
        _call(p, "POST", "/generate",
              {"request_id": f"warm_{p}", "prompt_tokens": [1, 2, 3],
               "max_new_tokens": 4}, timeout=600)

    def drain_victim():
        gw.remove_worker(victim_lane, drain=True)

    results, drained = drive_streams_with_kill(
        gw, requests, victim_rids, drain_victim, random.Random(7),
        arrival_rate=30.0, kill_when="all")
    checks.append(("migrate: victim drained mid-stream", drained))
    complete, identical, _resumed = tally_streams(results, control)
    checks.append(("migrate: all streams completed "
                   f"({complete}/{len(requests)})",
                   complete == len(requests)))
    checks.append(("migrate: all streams byte-identical to control "
                   f"({identical}/{len(requests)})",
                   identical == len(requests)))
    stats = gw.get_stats()
    mig = stats.get("migration", {})
    fo = stats.get("failover", {})
    checks.append(("migrate: streams migrated >= 1 "
                   f"({mig.get('streams_migrated', 0)})",
                   mig.get("streams_migrated", 0) >= 1))
    checks.append(("migrate: zero replay fallbacks in a clean drain",
                   mig.get("migration_fallbacks", 0) == 0))
    checks.append(("migrate: zero tokens replayed (no re-prefill)",
                   fo.get("tokens_replayed", 0) == 0))
    checks.append(("migrate: counters == migration spans",
                   _migration_counters_match_spans(gw)))
    # Zero leaks EVERYWHERE — the drained lane is alive and must have
    # released every exported row's blocks too.
    leak_free = {}
    imported_rows = 0
    for p in ports[:3]:
        pool = _worker_pool_clean_tiered(p)
        leak_free[p] = pool is not None
        checks.append((f"migrate: zero device+host blocks leaked on :{p}",
                       pool is not None))
        _, health = _call(p, "GET", "/health", timeout=10)
        gmig = (health.get("generator") or {}).get("migration") or {}
        imported_rows += gmig.get("imported_rows", 0)
        checks.append((f"migrate: no imports rejected on :{p}",
                       gmig.get("import_rejected", 0) == 0))
    checks.append(("migrate: destinations adopted rows "
                   f"({imported_rows})", imported_rows >= 1))
    gw.stop()
    phase_a = {"streams": len(requests), "complete": complete,
               "identical": identical,
               "victim_primary_streams": len(victim_rids),
               "migration": mig, "failover": fo,
               "leak_free": leak_free,
               "imported_rows": imported_rows}

    # ---- Phase B: destination killed before the transfer ----------------
    gw2 = Gateway([f"127.0.0.1:{p}" for p in (ports[0], ports[2],
                                              ports[3])],
                  GatewayConfig(failover_streams=True,
                                migrate_streams=True,
                                migrate_timeout_s=60.0))
    lanes2 = gw2.worker_names()
    source_lane = victim_lane_for_port(lanes2, ports[3])
    rid = rid_for_lane(gw2._ring, source_lane, "mgb")
    req = {"request_id": rid,
           "prompt_tokens": [9, 4, 1, 8, 3], "max_new_tokens": 48}
    control_b = control_oracle(ports[0], [req])
    # The EXACT destination the orchestrator will pick (same preference
    # order), so the kill provably lands on the continuation's target.
    probe_rec = _StreamRecord(rid, req, None,
                              TraceContext.root(rid), source_lane)
    dest_lane = gw2._pick_migration_dest(probe_rec, source_lane)
    dest_port = next(p for p in ports if dest_lane.endswith(f":{p}"))
    dest_idx = ports.index(dest_port)

    def kill_dest_then_drain():
        procs[dest_idx].send_signal(signal.SIGKILL)
        procs[dest_idx].wait(timeout=10)
        gw2.remove_worker(source_lane, drain=True)

    results_b, fired = drive_streams_with_kill(
        gw2, [req], {rid}, kill_dest_then_drain, random.Random(8))
    toks, final = results_b[rid]
    ok_b = (stream_completed(final) and toks == control_b[rid]
            and final.get("tokens") == control_b[rid])
    checks.append(("migrate: dest killed, drain fired mid-stream",
                   fired))
    checks.append(("migrate: replay fallback completed the stream "
                   "byte-identically", ok_b))
    mig2 = gw2.get_stats().get("migration", {})
    fell_back = (mig2.get("migration_fallbacks", 0)
                 + mig2.get("import_dispatch_failed", 0)
                 + mig2.get("export_refusals", 0)) >= 1
    checks.append(("migrate: dest death attributed to the fallback "
                   "ladder", fell_back))
    checks.append(("migrate: phase-B counters == migration spans",
                   _migration_counters_match_spans(gw2)))
    # Survivors = the phase-B ring minus the KILLED destination (the
    # drained source is alive and must be leak-free too: its exported
    # row released everything even though the transfer died).
    for p in (ports[0], ports[2], ports[3]):
        if p == dest_port:
            continue
        pool = _worker_pool_clean_tiered(p)
        checks.append((f"migrate: zero blocks leaked on survivor :{p}",
                       pool is not None))
    gw2.stop()
    return {"phase_a": phase_a,
            "phase_b": {"source": source_lane, "dest": dest_lane,
                        "completed_identical": ok_b,
                        "migration": mig2,
                        "resumed": (final or {}).get("resumed", 0)}}


def migrate_quant_phase(checks: list) -> dict:
    """Phase C (in-process): a QUANTIZED fleet's drain — int8 payload +
    scale slots cross the wire verbatim, the continuation equals the
    uninterrupted quantized control, and zero device/host block or
    scale-slot leaks on every pool."""
    import threading

    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import GatewayConfig, WorkerConfig

    workers = [WorkerNode(WorkerConfig(
        node_id=f"q{i}", model="gpt2-small-test", dtype="float32",
        gen_scheduler="continuous", gen_step_chunk=2,
        gen_kv_block_size=16, gen_kv_blocks=40, gen_kv_host_blocks=8,
        gen_kv_quantize="int8", gen_prefill_chunk=16,
        gen_max_batch_size=4)) for i in range(3)]
    p0 = workers[0].engine.params
    for w in workers[1:]:
        w.apply_weights(p0)
    gw = Gateway(list(workers),
                 GatewayConfig(failover_streams=True,
                               migrate_streams=True,
                               migrate_timeout_s=60.0))
    try:
        prompt = [5, 9, 3, 17, 4, 22, 8]
        control = workers[2].handle_generate(
            {"request_id": "qctl", "prompt_tokens": prompt,
             "max_new_tokens": 32})["tokens"]
        rid = next(f"qm{i}" for i in range(4000)
                   if gw._ring.get_node(f"qm{i}") == "q0")
        toks, final = [], [None]
        armed = threading.Event()

        def consume():
            for frame in gw.route_generate_stream(
                    {"request_id": rid, "prompt_tokens": prompt,
                     "max_new_tokens": 32}):
                evt = _parse_sse(frame)
                if evt is None:
                    continue
                if evt.get("done"):
                    final[0] = evt
                    break
                if "tokens" in evt:
                    toks.extend(evt["tokens"])
                    if len(toks) >= 3:
                        armed.set()

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        armed.wait(300)
        gw.remove_worker("q0", drain=True)
        t.join(timeout=300)
        ok = (final[0] is not None and "error" not in final[0]
              and toks == control and final[0]["tokens"] == control)
        checks.append(("migrate: quantized drain stream identical to "
                       "quantized control", ok))
        mig = gw.get_stats().get("migration", {})
        checks.append(("migrate: quantized stream migrated (not "
                       "replayed)", mig.get("streams_migrated", 0) >= 1
                       and mig.get("migration_fallbacks", 0) == 0))
        leaks_ok = True
        for w in workers:
            st = None
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                st = w.generator.stats()
                kp = st["kv_pool"]
                host = kp.get("host") or {}
                used = host.get("blocks_used", 0)
                if (st["active"] == 0
                        and kp["blocks_free"] + kp["radix_nodes"] - used
                        >= kp["blocks_total"]
                        and host.get("scale_slots_leaked", 0) == 0):
                    break
                time.sleep(0.3)
            else:
                leaks_ok = False
        checks.append(("migrate: zero device/host/scale-slot leaks on "
                       "every quantized pool", leaks_ok))
        return {"identical": ok, "migration": mig}
    finally:
        gw.stop()
        for w in workers:
            w.stop()


def run_migrate_standalone() -> int:
    ports, procs = launch_worker_procs(
        4, extra_args=("--kv-blocks", "40", "--kv-host-blocks", "8"))
    checks: list = []
    try:
        phases = {"migrate": migrate_phase(ports, procs, checks)}
        phases["quantized"] = migrate_quant_phase(checks)
        report = {"mode": "migrate-standalone", "worker_ports": ports,
                  "phases": phases}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks) and bool(checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def _handoff_counters_match_spans(gw) -> bool:
    from tpu_engine.serving.resilience import HandoffCounters

    ho = gw.get_stats().get("handoff", {})
    expect = sum(ho.get(f, 0) for f in HandoffCounters.SPAN_FIELDS)
    spans = [s for s in gw.tracer.snapshot() if s["op"] == "kv_handoff"]
    return len(spans) == expect


def disagg_phase(ports, procs, checks: list) -> dict:
    """Disaggregated-serving chaos (--disagg) over 2 prefill + 2 decode
    worker processes behind a role-aware gateway. Phase A: steady-state
    Poisson load — every stream routes to a prefill lane, hands its KV
    chain to a decode lane (spliced, zero fallbacks, zero replay
    tokens), and completes byte-identical to an unkilled control; every
    handoff decision has a matching counter AND kv_handoff span; zero
    block leaks on all four pools. Phase B: kill -9 a PREFILL lane
    mid-handoff (row admitted, chain not yet shipped) — the stream
    lands on the replay fallback and still completes byte-identically.
    Phase C: kill -9 the DECODE lane mid-adopt (continuation spliced
    and streaming) — same replay guarantee, zero leaks on survivors."""
    import random
    import signal
    import threading

    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.utils.config import GatewayConfig

    gw = Gateway([f"127.0.0.1:{p}" for p in ports],
                 GatewayConfig(disagg=True, handoff_timeout_s=60.0,
                               failover_streams=True,
                               health_probe_interval_s=0.25,
                               health_probe_failures=2))
    lanes = gw.worker_names()
    roles = gw.worker_roles()
    checks.append(("disagg: gateway discovered the role split",
                   sorted(roles.values())
                   == ["decode", "decode", "prefill", "prefill"]))

    # ---- Phase A: steady-state handoff under Poisson load ---------------
    requests = []
    for k in range(8):
        params = {}
        if k % 3 == 1:
            params = {"temperature": 0.9, "seed": 300 + k}
        elif k % 3 == 2:
            params = {"temperature": 0.8, "seed": 400 + k,
                      "repetition_penalty": 1.3, "stop_tokens": [7],
                      "top_p": 0.9}
        requests.append({
            "request_id": f"dg{k}",
            "prompt_tokens": [(k * 7 + j) % 90 + 1
                              for j in range(18 + k % 5)],
            "max_new_tokens": 20, **params})
    try:
        control = control_oracle(ports[0], requests)
    except RuntimeError as exc:
        checks.append(("disagg: control generate", False))
        return {"error": str(exc)}

    rng = random.Random(11)
    results: dict = {}
    lock = threading.Lock()

    def consume(req, progress=None):
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
                    if progress is not None:
                        progress(req["request_id"], len(toks))
        except Exception as exc:
            final = {"harness_exception": str(exc)}
        with lock:
            results[req["request_id"]] = (toks, final)

    threads = []
    for req in requests:
        t = threading.Thread(target=consume, args=(req,), daemon=True)
        t.start()
        threads.append(t)
        time.sleep(rng.expovariate(20.0))
    for t in threads:
        t.join(timeout=600)
    complete, identical, _ = tally_streams(results, control)
    checks.append(("disagg: all steady-state streams completed "
                   f"({complete}/{len(requests)})",
                   complete == len(requests)))
    checks.append(("disagg: all streams byte-identical to control "
                   f"({identical}/{len(requests)})",
                   identical == len(requests)))
    ho = gw.get_stats().get("handoff", {})
    fo = gw.get_stats().get("failover", {})
    checks.append(("disagg: every stream routed to a prefill lane "
                   f"({ho.get('prefill_routed', 0)})",
                   ho.get("prefill_routed", 0) == len(requests)))
    checks.append(("disagg: every handoff spliced onto a decode lane "
                   f"({ho.get('handoffs_spliced', 0)})",
                   ho.get("handoffs_spliced", 0) == len(requests)))
    checks.append(("disagg: zero handoff fallbacks in steady state",
                   ho.get("handoff_fallbacks", 0) == 0
                   and ho.get("export_refusals", 0) == 0
                   and ho.get("dispatch_failed", 0) == 0))
    checks.append(("disagg: zero tokens replayed (zero re-prefill)",
                   fo.get("tokens_replayed", 0) == 0))
    checks.append(("disagg: counters == kv_handoff spans",
                   _handoff_counters_match_spans(gw)))
    imported = exported = holds = 0
    for p in ports:
        pool = _worker_pool_clean(p)
        checks.append((f"disagg: zero blocks leaked on :{p}",
                       pool is not None))
        _, health = _call(p, "GET", "/health", timeout=10)
        g = health.get("generator") or {}
        imported += (g.get("migration") or {}).get("imported_rows", 0)
        exported += (g.get("migration") or {}).get("exported_rows", 0)
        holds += (g.get("handoff") or {}).get("holds", 0)
    checks.append(("disagg: prefill lanes exported every row "
                   f"({exported})", exported >= len(requests)))
    checks.append(("disagg: decode lanes adopted every row "
                   f"({imported})", imported >= len(requests)))
    checks.append((f"disagg: rows parked for handoff ({holds})",
                   holds >= len(requests)))
    phase_a = {"streams": len(requests), "complete": complete,
               "identical": identical, "handoff": ho,
               "exported_rows": exported, "imported_rows": imported}

    # ---- Phase B: kill -9 the PREFILL lane mid-handoff ------------------
    victim_lane = next(l for l in lanes if roles[l] == "prefill")
    victim_port = next(p for p in ports
                       if victim_lane.endswith(f":{p}"))
    victim_idx = ports.index(victim_port)
    rid_b = rid_for_lane(gw._prefill_ring, victim_lane, "dgb")
    req_b = {"request_id": rid_b,
             "prompt_tokens": [9, 4, 1, 8, 3, 6, 2, 11, 5, 7],
             "max_new_tokens": 24, "temperature": 0.7, "seed": 77}
    control_b = control_oracle(ports[1], [req_b])[rid_b]
    tb = threading.Thread(target=consume, args=(req_b,), daemon=True)

    def victim_admitted() -> bool:
        try:
            _, health = _call(victim_port, "GET", "/health", timeout=2)
        except OSError:
            return False
        return (health.get("generator") or {}).get("active", 0) >= 1

    tb.start()
    deadline = time.monotonic() + 60
    fired = False
    while time.monotonic() < deadline:
        if victim_admitted():
            # The row is on the prefill lane (prefilling or parked,
            # chain not yet adopted elsewhere): kill mid-handoff.
            procs[victim_idx].send_signal(signal.SIGKILL)
            procs[victim_idx].wait(timeout=10)
            fired = True
            break
        time.sleep(0.01)
    tb.join(timeout=600)
    toks_b, final_b = results.get(rid_b, ([], None))
    checks.append(("disagg: prefill lane killed mid-handoff", fired))
    checks.append(("disagg: prefill-death stream completed "
                   "byte-identically via the replay fallback",
                   stream_completed(final_b) and toks_b == control_b
                   and final_b.get("tokens") == control_b))
    checks.append(("disagg: phase-B counters == kv_handoff spans",
                   _handoff_counters_match_spans(gw)))
    survivors_b = [p for p in ports if p != victim_port]
    for p in survivors_b:
        pool = _worker_pool_clean(p)
        checks.append((f"disagg: zero blocks leaked on survivor :{p}",
                       pool is not None))
    phase_b = {"victim": victim_lane, "completed_identical":
               stream_completed(final_b) and toks_b == control_b}

    # ---- Phase C: kill -9 the DECODE lane mid-adopt ---------------------
    live_prefill = next(l for l in lanes
                        if roles[l] == "prefill" and l != victim_lane)
    rid_c = rid_for_lane(gw._prefill_ring, live_prefill, "dgc")
    req_c = {"request_id": rid_c,
             "prompt_tokens": [3, 14, 8, 2, 9, 5, 1, 12],
             "max_new_tokens": 60}
    alive_port = next(p for p in ports
                      if procs[ports.index(p)].poll() is None)
    control_c = control_oracle(alive_port, [req_c])[rid_c]
    progress = {"n": 0}

    def track(_rid, n):
        progress["n"] = n

    tc = threading.Thread(target=consume, args=(req_c, track),
                          daemon=True)
    tc.start()
    deadline = time.monotonic() + 120
    fired_c = False
    while time.monotonic() < deadline:
        serving = gw.active_streams().get(rid_c)
        if (progress["n"] >= 3 and serving is not None
                and roles.get(serving) == "decode"):
            # The decode lane ADOPTED the chain and is streaming: kill
            # it mid-adopt(ed decode).
            dport = next(p for p in ports if serving.endswith(f":{p}"))
            didx = ports.index(dport)
            procs[didx].send_signal(signal.SIGKILL)
            procs[didx].wait(timeout=10)
            fired_c = True
            break
        time.sleep(0.01)
    tc.join(timeout=600)
    toks_c, final_c = results.get(rid_c, ([], None))
    checks.append(("disagg: decode lane killed mid-adopt", fired_c))
    checks.append(("disagg: decode-death stream completed "
                   "byte-identically via the replay fallback",
                   stream_completed(final_c) and toks_c == control_c
                   and final_c.get("tokens") == control_c))
    checks.append(("disagg: phase-C counters == kv_handoff spans",
                   _handoff_counters_match_spans(gw)))
    survivors_c = [p for p in ports
                   if procs[ports.index(p)].poll() is None]
    for p in survivors_c:
        pool = _worker_pool_clean(p)
        checks.append((f"disagg: zero blocks leaked on survivor :{p} "
                       "after the decode kill", pool is not None))
    gw.stop()
    return {"phase_a": phase_a, "phase_b": phase_b,
            "phase_c": {"completed_identical":
                        stream_completed(final_c)
                        and toks_c == control_c}}


def run_disagg_standalone() -> int:
    ports, procs = launch_worker_procs(
        4, extra_args=("--kv-blocks", "60"),
        per_worker_args=(("--role", "prefill"), ("--role", "prefill"),
                         ("--role", "decode"), ("--role", "decode")))
    checks: list = []
    try:
        report = {"mode": "disagg-standalone", "worker_ports": ports,
                  "phases": {"disagg": disagg_phase(ports, procs,
                                                    checks)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks) and bool(checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def run_offload_standalone() -> int:
    ports, procs = launch_worker_procs(
        3, extra_args=("--kv-blocks", "20", "--kv-host-blocks", "16"))
    checks: list = []
    try:
        report = {"mode": "offload-standalone", "worker_ports": ports,
                  "phases": {"offload": offload_phase(ports, procs,
                                                      checks)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks) and bool(checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def quant_phase(ports, procs, checks: list) -> dict:
    """Quantized-pool chaos (--quant): every lane serves a --kv-quantize
    int8 paged pool with the host tier on. Prove the quantized lifecycle
    live, then kill -9 the lane HOLDING QUANTIZED (and demoted-quantized)
    blocks mid-stream: the PR 6 resume must still splice byte-identically
    on another quantized lane, survivors must leak zero device blocks,
    zero host blocks AND zero scale slots, and the victim's swap-in
    counters must match its swap_in spans before it dies
    (counters == spans on the quantized path too)."""
    import random
    import signal

    from tpu_engine.serving.gateway import Gateway
    from tpu_engine.utils.config import GatewayConfig

    gw = Gateway([f"127.0.0.1:{p}" for p in ports],
                 GatewayConfig(failover_streams=True,
                               health_probe_interval_s=0.25,
                               health_probe_failures=2,
                               prefix_affinity=True,
                               affinity_block_size=16))
    shared = [(j * 13) % 90 + 1 for j in range(32)]  # two full blocks

    # Every lane must actually serve the int8 pool (the loud-misconfig
    # guard means a silently-bf16 lane would be a wiring bug).
    quantized = {}
    for p in ports:
        _, health = _call(p, "GET", "/health", timeout=10)
        quantized[p] = (health.get("generator", {}).get("kv_pool", {})
                        .get("quantized"))
    checks.append(("quant: every lane serves an int8 pool",
                   all(v == "int8" for v in quantized.values())))

    # Affinity makes the victim deterministic: the lane owning the
    # shared prefix's fingerprint serves every shared-prefix request.
    fp = gw._affinity_fingerprint({"prompt_tokens": shared})
    victim_lane = gw._ring.get_node(fp)
    victim_port = next(p for p in ports if victim_lane.endswith(f":{p}"))
    victim_idx = ports.index(victim_port)
    survivor_ports = [p for p in ports if p != victim_port]

    for p in ports:
        _call(p, "POST", "/generate",
              {"request_id": f"warm_{p}", "prompt_tokens": [1, 2, 3],
               "max_new_tokens": 4}, timeout=600)
    status, _ = _call(
        victim_port, "POST", "/generate",
        {"request_id": "prime", "prompt_tokens": shared + [5, 6],
         "max_new_tokens": 4}, timeout=600)
    checks.append(("quant: shared prefix primed on victim", status == 200))

    # Churn the tiny pool until quantized blocks demote to the host tier
    # — int8 payload + scale vectors must travel (and account) together.
    rnd = random.Random(3)
    for i in range(6):
        filler = [rnd.randrange(1, 200) for _ in range(72)]
        _call(victim_port, "POST", "/generate",
              {"request_id": f"churn{i}", "prompt_tokens": filler,
               "max_new_tokens": 2}, timeout=600)
    _, health = _call(victim_port, "GET", "/health", timeout=10)
    pool = health["generator"]["kv_pool"]
    host = pool.get("host") or {}
    checks.append(("quant: churn demoted quantized blocks "
                   f"(demotions={host.get('demotions', 0)})",
                   host.get("demotions", 0) > 0))
    # scale_slots_leaked is the REAL pairing invariant (host slots used
    # minus an actual radix walk of demoted nodes, computed pool-side
    # under the lock) — it must exist on a quantized tier and stay 0.
    checks.append(("quant: demoted scale slots pair with radix nodes "
                   f"(used={host.get('scale_slots_used')}, "
                   f"leaked={host.get('scale_slots_leaked')})",
                   host.get("scale_slots_used") is not None
                   and host.get("scale_slots_leaked") == 0))

    # Re-hit: the demoted QUANTIZED prefix must swap back in (verbatim
    # int8+scale — the resumed stream must match the pre-demotion one).
    si0 = host.get("swap_ins", 0)
    rehit = gw.route_generate(
        {"request_id": "rehit", "prompt_tokens": shared + [9, 9],
         "max_new_tokens": 4})
    _, health = _call(victim_port, "GET", "/health", timeout=10)
    pool = health["generator"]["kv_pool"]
    host = pool.get("host") or {}
    checks.append(("quant: re-hit swapped the int8 prefix back in "
                   f"(swap_ins {si0}->{host.get('swap_ins', 0)})",
                   host.get("swap_ins", 0) > si0
                   and rehit["node_id"] == f"w{victim_idx}"))

    # counters == spans on the quantized swap-in path: every swap_in
    # event the victim's pool counted has a matching `swap_in` stage
    # span in its trace ring.
    _, export = _call(victim_port, "GET", "/trace/export", timeout=10)
    swap_spans = sum(1 for e in export.get("traceEvents", [])
                     if e.get("ph") == "X" and e.get("name") == "swap_in")
    checks.append(("quant: swap_in counters == swap_in spans "
                   f"({host.get('swap_in_events', 0)} vs {swap_spans})",
                   host.get("swap_in_events", 0) == swap_spans))

    # Mid-stream kill while the victim holds quantized + demoted-
    # quantized blocks: the resume must splice byte-identically on a
    # surviving quantized lane (quantized streams are deterministic, so
    # the PR 6 replay contract holds exactly as in bf16 mode). A burst
    # of shared-prefix streams — all affinity-routed to the victim —
    # SATURATES the lane (admission queueing + full decode batches), so
    # some stream is provably mid-generation long enough for the kill
    # to land even on a fast host where one short stream would finish
    # between monitor polls (the tiny test model caps streams at ~30
    # tokens; wall time, not token count, is what widens the window).
    reqs = [{"request_id": f"quant_stream_{i}",
             "prompt_tokens": shared + [2 + i],
             "max_new_tokens": 30} for i in range(14)]
    rids = {r["request_id"] for r in reqs}
    control = control_oracle(survivor_ports[0], reqs)

    def kill_victim():
        procs[victim_idx].send_signal(signal.SIGKILL)
        procs[victim_idx].wait(timeout=10)

    results, killed = drive_streams_with_kill(
        gw, reqs, rids, kill_victim, random.Random(5))
    checks.append(("quant: victim (holding quantized blocks) killed "
                   "mid-stream", killed))
    identical = all(
        stream_completed(results[rid][1])
        and results[rid][0] == control[rid]
        and results[rid][1].get("tokens") == control[rid]
        for rid in rids)
    resumes = sum(int((results[rid][1] or {}).get("resumed", 0))
                  for rid in rids)
    final = results[reqs[0]["request_id"]][1]
    checks.append(("quant: every stream completed byte-identically "
                   f"(resumes={resumes})", identical and resumes > 0))

    # Survivors: fresh availability + zero device/host/scale-slot leaks.
    status, _ = _call(survivor_ports[0], "POST", "/generate",
                      {"request_id": "post", "prompt_tokens": [4, 2],
                       "max_new_tokens": 4}, timeout=600)
    checks.append(("quant: post-kill availability", status == 200))
    leak_free = {}
    for p in survivor_ports:
        pool = _worker_pool_clean_tiered(p)
        scale_ok = (pool is not None
                    and (pool.get("host") or {}).get(
                        "scale_slots_leaked", 0) == 0)
        leak_free[p] = bool(pool is not None and scale_ok)
        checks.append((f"quant: zero device+host block and scale-slot "
                       f"leaks on survivor :{p}", leak_free[p]))
    fo = gw.get_stats().get("failover", {})
    gw.stop()
    return {"victim_port": victim_port, "killed": killed,
            "stream_identical": identical,
            "resumed": (final or {}).get("resumed", 0),
            "victim_demotions": host.get("demotions", 0),
            "victim_swap_ins": host.get("swap_ins", 0),
            "swap_in_spans": swap_spans,
            "failover": fo, "survivors_leak_free": leak_free}


def run_quant_standalone() -> int:
    ports, procs = launch_worker_procs(
        3, extra_args=("--kv-blocks", "20", "--kv-host-blocks", "16",
                       "--kv-quantize", "int8"))
    checks: list = []
    try:
        report = {"mode": "quant-standalone", "worker_ports": ports,
                  "phases": {"quant": quant_phase(ports, procs, checks)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks) and bool(checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def run_crash_standalone() -> int:
    ports, procs = launch_worker_procs(3)
    checks: list = []
    try:
        report = {"mode": "crash-standalone", "worker_ports": ports,
                  "phases": {"crash": crash_phase(ports, procs, checks)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks) and bool(checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def _worker_state_pool_clean(port: int, timeout_s: float = 30.0):
    """Poll a state_slab worker's /health until its scheduler is idle
    and every state row is accounted for (rows_free == rows_total and
    the admitted/released counters agree) — the zero-slab-leak check.
    Returns the final state_pool dict (or None if it never settled)."""
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        try:
            _, health = _call(port, "GET", "/health", timeout=5.0)
        except OSError:
            time.sleep(0.3)
            continue
        gen = health.get("generator", {})
        last = gen.get("state_pool")
        if (gen.get("active") == 0 and last
                and last["rows_free"] == last["rows_total"]
                and last["rows_admitted"] == last["rows_released"]):
            return last
        time.sleep(0.3)
    return None


def recurrent_phase(ports, procs, checks: list) -> dict:
    """The state_slab family under the crash harness: kill -9 one
    SSD-model worker while its streams are mid-generation under Poisson
    load; with failover on, every stream must complete byte-identical
    to the unkilled control (the replay resume re-prefills prompt ⧺
    emitted through the SAME recurrence the decode steps run, so the
    resumed state is exact) and every surviving pool must account for
    every state row — zero slab leaks."""
    import random
    import signal

    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.utils.config import GatewayConfig

    gw = Gateway([f"127.0.0.1:{p}" for p in ports],
                 GatewayConfig(failover_streams=True,
                               health_probe_interval_s=0.25,
                               health_probe_failures=2))
    lanes = gw.worker_names()
    victim_lane = victim_lane_for_port(lanes, ports[1])
    victim_proc = procs[1]

    # The served family is live and declared: state_pool present,
    # kv_pool absent, on every lane's /health.
    family_ok = True
    for p in ports:
        _, health = _call(p, "GET", "/health", timeout=10.0)
        g = health.get("generator", {})
        family_ok &= ("state_pool" in g and "kv_pool" not in g
                      and "block-addressable"
                      in g["state_pool"]["prefix_sharing"])
    checks.append(("recurrent: lanes serve the state_slab family "
                   "(state_pool in /health, no kv_pool)", family_ok))

    # Request mix: greedy and seeded-sampled streams, victim-primary
    # rows with long budgets so they are provably mid-flight at kill.
    requests = []
    for k in range(12):
        lane = victim_lane if k % 3 == 0 else lanes[k % len(lanes)]
        params = {}
        if k % 3 == 1:
            params = {"temperature": 0.9, "seed": 300 + k}
        requests.append({
            "request_id": rid_for_lane(gw._ring, lane, f"rc{k}"),
            "prompt_tokens": [(k * 5 + j) % 90 + 1
                              for j in range(5 + k % 4)],
            "max_new_tokens": 56 if lane == victim_lane else 20,
            **params})
    victim_rids = {r["request_id"] for r in requests
                   if gw._ring.get_node(r["request_id"]) == victim_lane}

    try:
        control = control_oracle(ports[0], requests)
    except RuntimeError as exc:
        checks.append(("recurrent: control generate", False))
        return {"error": str(exc)}
    for p in ports[1:]:
        _call(p, "POST", "/generate",
              {"request_id": f"warm_{p}", "prompt_tokens": [1, 2, 3],
               "max_new_tokens": 4}, timeout=600)

    def kill_victim():
        victim_proc.send_signal(signal.SIGKILL)
        victim_proc.wait(timeout=10)

    # Tight arrivals: an O(1)-state lane streams a 56-token request in
    # ~100 ms on the CPU mesh — the default 8/s Poisson stagger would
    # let every victim stream FINISH before the kill loop even starts.
    results, killed = drive_streams_with_kill(
        gw, requests, victim_rids, kill_victim, random.Random(2),
        arrival_rate=60.0)
    checks.append(("recurrent: victim killed mid-stream", killed))

    complete, identical, resumed = tally_streams(results, control)
    checks.append(("recurrent: all streams completed "
                   f"({complete}/{len(requests)})",
                   complete == len(requests)))
    checks.append(("recurrent: all streams byte-identical to control "
                   f"({identical}/{len(requests)})",
                   identical == len(requests)))
    checks.append(("recurrent: at least one stream resumed",
                   resumed >= 1))

    # Failover decisions: counters == spans (the family rides the SAME
    # journal/resume machinery — no recurrent-specific counters to
    # drift), and the prober ejects the corpse.
    ejected = False
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if victim_lane in gw.ejected_lanes():
            ejected = True
            break
        time.sleep(0.1)
    checks.append(("recurrent: prober ejected the dead lane", ejected))
    fo, resume_spans = {}, []
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        fo = gw.get_stats().get("failover", {})
        resume_spans = [s for s in gw.tracer.snapshot()
                        if s["op"] == "resume"]
        if len(resume_spans) == fo.get("resumes_attempted", -1):
            break
        time.sleep(0.1)
    checks.append(("recurrent: failover counters == resume spans",
                   len(resume_spans) == fo.get("resumes_attempted", -1)
                   and fo.get("resumes_attempted", 0) >= 1))

    # Post-kill availability: a FRESH stream admits and completes.
    fresh = {"request_id": "post_kill_rc", "prompt_tokens": [9, 8, 7],
             "max_new_tokens": 8}
    ctl = _call(ports[0], "POST", "/generate",
                dict(fresh, request_id="ctl_post_rc"), timeout=600)[1]
    for frame in gw.route_generate_stream(dict(fresh)):
        evt = _parse_sse(frame)
        if evt and evt.get("done"):
            checks.append(("recurrent: post-kill stream completes "
                           "identically",
                           "error" not in evt
                           and evt["tokens"] == ctl["tokens"]))
            break

    # Zero state-slab rows leaked on the survivors.
    pools = {}
    for p in (ports[0], ports[2]):
        pool = _worker_state_pool_clean(p)
        pools[p] = pool
        checks.append((f"recurrent: zero slab rows leaked on "
                       f"survivor :{p}", pool is not None))
    gw.stop()
    return {"streams": len(requests), "complete": complete,
            "identical": identical, "resumed_streams": resumed,
            "victim_primary_streams": len(victim_rids),
            "failover": fo, "survivor_state_pools": pools}


def run_recurrent_standalone() -> int:
    # step-chunk 1: one token per dispatch, so streams span many SSE
    # frames and the kill provably lands mid-generation.
    ports, procs = launch_worker_procs(
        3, model="ssd-small-test",
        base_args=("--step-chunk", "1", "--prefill-chunk", "16",
                   "--state-rows", "12"))
    checks: list = []
    try:
        report = {"mode": "recurrent-standalone", "worker_ports": ports,
                  "phases": {"recurrent": recurrent_phase(ports, procs,
                                                          checks)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks) and bool(checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def tp_phase(ports, procs, checks: list) -> dict:
    """Kill -9 the TENSOR-PARALLEL lane (tp=2) mid-stream under Poisson
    load: the PR 6 replay resume must complete every stream
    byte-identical to an unkilled control on the DIFFERENTLY-SHARDED
    tp=1 survivor — the cross-geometry identity the TP tentpole
    promises (same fold_in(seed, position) sampling, logits equal to
    the argmax on this backend). Also pins: the /health topology label,
    the gateway ring picking the label up via prober sweeps (vnode
    weight 2), failover counters == resume spans, and zero KV blocks
    leaked on the survivor. ports[0] = the tp=2 victim, ports[1] = the
    tp=1 survivor."""
    import random
    import signal

    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.utils.config import GatewayConfig

    gw = Gateway([f"127.0.0.1:{p}" for p in ports],
                 GatewayConfig(failover_streams=True,
                               health_probe_interval_s=0.25,
                               health_probe_failures=2))
    lanes = gw.worker_names()
    victim_lane = victim_lane_for_port(lanes, ports[0])
    victim_proc = procs[0]

    # The TP lane advertises its mesh shape on /health...
    _, health = _call(ports[0], "GET", "/health", timeout=30.0)
    topo = health.get("topology") or {}
    checks.append(("tp: victim /health carries the topology label "
                   f"(tp={topo.get('tp')})", topo.get("tp") == 2))
    _, h1 = _call(ports[1], "GET", "/health", timeout=30.0)
    checks.append(("tp: tp=1 survivor /health has no topology key",
                   "topology" not in h1))
    # ...and the prober folds it into the ring: vnode weight 2 beside
    # the survivor's 1 (the topology-aware ring, discovered not
    # configured).
    weighted = False
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if gw._ring.node_weight(victim_lane) == 2:
            weighted = True
            break
        time.sleep(0.1)
    topo_stats = gw.get_stats().get("topology", {})
    checks.append(("tp: prober re-weighted the TP lane's vnodes",
                   weighted
                   and topo_stats.get("ring_weights", {}).get(
                       victim_lane) == 2))

    # Request mix (greedy + seeded), a known share primary on the TP
    # victim with long budgets so the kill lands mid-generation.
    requests = []
    for k in range(10):
        lane = victim_lane if k % 2 == 0 else lanes[k % len(lanes)]
        params = ({} if k % 3 == 0
                  else {"temperature": 0.9, "seed": 300 + k})
        requests.append({
            "request_id": rid_for_lane(gw._ring, lane, f"tp{k}"),
            "prompt_tokens": [(k * 5 + j) % 90 + 1
                              for j in range(5 + k % 4)],
            "max_new_tokens": 48 if lane == victim_lane else 16,
            **params})
    victim_rids = {r["request_id"] for r in requests
                   if gw._ring.get_node(r["request_id"]) == victim_lane}

    # Control oracle: the tp=1 SURVIVOR — spliced streams off the dead
    # tp=2 lane must match single-device serving byte-for-byte.
    try:
        control = control_oracle(ports[1], requests)
    except RuntimeError as exc:
        checks.append(("tp: control generate", False))
        gw.stop()
        return {"error": str(exc)}

    def kill_victim():
        victim_proc.send_signal(signal.SIGKILL)
        victim_proc.wait(timeout=10)

    results, killed = drive_streams_with_kill(
        gw, requests, victim_rids, kill_victim, random.Random(3),
        arrival_rate=12.0)
    checks.append(("tp: tp=2 victim killed mid-stream", killed))

    complete, identical, resumed = tally_streams(results, control)
    mismatches = [
        {"rid": rid, "control": control[rid], "streamed": toks,
         "final_tokens": (final or {}).get("tokens"),
         "victim_primary": rid in victim_rids}
        for rid, (toks, final) in results.items()
        if toks != control[rid]
        or not final or final.get("tokens") != control[rid]]
    checks.append((f"tp: all streams completed "
                   f"({complete}/{len(requests)})",
                   complete == len(requests)))
    checks.append((f"tp: all streams byte-identical to the tp=1 "
                   f"control ({identical}/{len(requests)})",
                   identical == len(requests)))
    checks.append(("tp: at least one stream resumed on the "
                   "differently-sharded survivor", resumed >= 1))

    # Counters == spans (the established failover discipline).
    fo, resume_spans = {}, []
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        fo = gw.get_stats().get("failover", {})
        resume_spans = [s for s in gw.tracer.snapshot()
                        if s["op"] == "resume"]
        if len(resume_spans) == fo.get("resumes_attempted", -1):
            break
        time.sleep(0.1)
    checks.append(("tp: failover counters == resume spans",
                   len(resume_spans) == fo.get("resumes_attempted", -1)
                   and fo.get("resumes_attempted", 0) >= 1))

    # Zero KV blocks leaked on the tp=1 survivor.
    pool = _worker_pool_clean(ports[1])
    checks.append((f"tp: no KV blocks leaked on survivor :{ports[1]}",
                   pool is not None))
    gw.stop()
    return {"streams": len(requests), "complete": complete,
            "identical": identical, "resumed_streams": resumed,
            "mismatches": mismatches,
            "victim_primary_streams": len(victim_rids),
            "victim_topology": topo, "topology_stats": topo_stats,
            "failover": fo, "survivor_pool": pool}


def run_tp_standalone() -> int:
    # The worker processes need >= 2 visible devices for the tp=2 lane:
    # provision the virtual CPU mesh in the inherited env (a TPU host's
    # real chips override; the flag is a CPU-backend no-op elsewhere).
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    ports, procs = launch_worker_procs(
        2, per_worker_args=(("--tp", "2"), ()))
    checks: list = []
    try:
        report = {"mode": "tp-standalone", "worker_ports": ports,
                  "phases": {"tp": tp_phase(ports, procs, checks)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks) and bool(checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def run_mixed_standalone() -> int:
    port, proc = launch_mixed_server()
    checks: list = []
    try:
        report = {"mode": "mixed-standalone", "port": port,
                  "phases": {"mixed": mixed_phase(port, checks)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def launch_overload_server(attempts: int = 3):
    """Spawn the combined server the --overload scenario drives: 3
    continuous paged mixed-step lanes with EVERY overload knob on —
    gateway tier admission + tenant buckets + load-derived Retry-After,
    worker priority admission, and the staged brownout controller with
    a tight control interval so the ladder moves within the run."""
    from tpu_engine.utils.net import launch_with_retry

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_ENGINE_PLATFORM", "cpu")

    def spawn(port: int):
        cmd = [sys.executable, "-m", "tpu_engine.serving.cli", "serve",
               "--model", "gpt2-small-test", "--lanes", "3",
               "--port", str(port),
               "--kv-block-size", "16", "--kv-blocks", "24",
               "--mixed-token-budget", "16",
               "--spec-k", "2",
               "--max-queue-depth", "4",
               "--default-deadline-ms", "30000",
               "--overload-control", "--overload-max-inflight", "12",
               "--tenant-rate", "1", "--tenant-burst", "3",
               "--priority-admission",
               "--brownout", "--brownout-clamp-tokens", "4",
               "--native-front", "off"]
        proc = subprocess.Popen(cmd, cwd=repo, env=env,
                                stdout=sys.stderr, stderr=sys.stderr)
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise ChildProcessError(
                    f"server exited rc={proc.returncode} before ready")
            try:
                status, _ = _call(port, "GET", "/stats", timeout=2.0)
                if status == 200:
                    return proc
            except OSError:
                pass
            time.sleep(0.5)
        proc.terminate()
        raise TimeoutError("server never became ready")

    return launch_with_retry(spawn, attempts=attempts)


def _combined_pools_clean(port: int, timeout_s: float = 60.0):
    """Poll combined /stats until every lane's scheduler is idle and all
    KV blocks are accounted for (free list + radix-held) — the
    zero-leak check after an overload storm."""
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        try:
            _, stats = _call(port, "GET", "/stats", timeout=5.0)
        except OSError:
            time.sleep(0.3)
            continue
        pools = stats.get("kv_pool") or {}
        mixed = stats.get("mixed") or {}
        last = pools
        if pools and all(
                p["blocks_free"] + p["radix_nodes"] >= p["blocks_total"]
                for p in pools.values()) and all(
                (m.get("active") or 0) == 0 for m in mixed.values()):
            return last
        time.sleep(0.3)
    return None


def overload_phase(port: int, checks: list) -> dict:
    """Mixed-priority Poisson load past saturation against a 3-lane
    fleet with full overload control on. Asserts: low-tier requests shed
    first (shed rate strictly ordered background > interactive), every
    completed interactive request lands inside its deadline (p99), the
    brownout ladder engages during the storm and clears after it
    (escalations == restores > 0, every transition span-matched),
    gateway overload counters == overload marker spans, and zero KV
    blocks leak."""
    import random
    import threading

    rng = random.Random(7)
    deadline_ms = 25_000.0
    tiers = ["interactive", "batch", "background"]
    reqs = []
    for i in range(42):
        tier = tiers[i % 3]
        reqs.append({
            "request_id": f"ov_{tier}_{i}",
            "prompt_tokens": [5, 9, 3, (i % 7) + 2],
            "max_new_tokens": 8,
            "priority": tier,
            # One flooding tenant shares a 1 req/s bucket; the rest are
            # distinct tenants — the bucket must punish only the flood.
            # The flood rides the BACKGROUND slice (i % 3 == 2), so its
            # rate-limit 503s can never inflate interactive's shed rate
            # and muddy the lowest-tier-first assertion.
            "tenant": "flood" if i % 3 == 2 else f"t{i}",
            "deadline_ms": deadline_ms,
        })

    results = {}
    res_lock = threading.Lock()

    def fire(req):
        t0 = time.perf_counter()
        try:
            status, body = _call(port, "POST", "/generate", req,
                                 timeout=120.0)
        except OSError as exc:
            status, body = -1, {"error": str(exc)}
        with res_lock:
            results[req["request_id"]] = (
                status, (time.perf_counter() - t0) * 1e3, body)

    # Brownout stage observer: sample every lane's ladder while the
    # storm runs — the engage/clear evidence.
    stages = {}
    stop_obs = threading.Event()

    def observe():
        while not stop_obs.is_set():
            try:
                _, h = _call(port, "GET", "/health", timeout=5.0)
                for node, lane in (h.get("lanes") or {}).items():
                    bo = lane.get("brownout") or {}
                    stages.setdefault(node, []).append(bo.get("stage", 0))
            except OSError:
                pass
            stop_obs.wait(0.15)

    obs = threading.Thread(target=observe, daemon=True)
    obs.start()
    threads = []
    for req in reqs:
        t = threading.Thread(target=fire, args=(req,), daemon=True)
        t.start()
        threads.append(t)
        time.sleep(rng.expovariate(12.0))  # ~12 arrivals/s >> capacity
    for t in threads:
        t.join(timeout=300)
    # Let the ladder walk back down before sampling the final state.
    drain_deadline = time.monotonic() + 30
    while time.monotonic() < drain_deadline:
        _, h = _call(port, "GET", "/health", timeout=5.0)
        lanes = h.get("lanes") or {}
        if all((l.get("brownout") or {}).get("stage", 0) == 0
               for l in lanes.values()):
            break
        time.sleep(0.3)
    stop_obs.set()
    obs.join(timeout=5)

    by_tier = {t: {"ok": 0, "shed": 0, "other": 0, "lat_ms": []}
               for t in tiers}
    for rid, (status, lat_ms, body) in results.items():
        tier = rid.split("_")[1]
        if status == 200:
            by_tier[tier]["ok"] += 1
            by_tier[tier]["lat_ms"].append(lat_ms)
        elif status == 503:
            by_tier[tier]["shed"] += 1
        else:
            by_tier[tier]["other"] += 1

    def shed_rate(t):
        d = by_tier[t]
        n = d["ok"] + d["shed"] + d["other"]
        return d["shed"] / max(1, n)

    inter = by_tier["interactive"]
    lat = sorted(inter["lat_ms"])
    p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))] if lat else None

    _, stats = _call(port, "GET", "/stats")
    _, health = _call(port, "GET", "/health")
    ov = stats.get("overload") or {}
    lanes = health.get("lanes") or {}
    bo = {node: lane.get("brownout") or {} for node, lane in lanes.items()}
    max_stage = {node: max(s) if s else 0 for node, s in stages.items()}

    # counters == spans: every gateway overload decision and every
    # brownout transition has its marker span in /trace/export.
    _, export = _call(port, "GET", "/trace/export")
    events = [e for e in export.get("traceEvents", [])
              if e.get("ph") == "X" and e.get("name") == "overload"]
    gw_spans = sum(1 for e in events
                   if "decision" in (e.get("args") or {}))
    bo_spans = sum(1 for e in events
                   if "action" in (e.get("args") or {}))
    gw_count = (ov.get("rate_limited", 0) + ov.get("shed_tier", 0)
                + ov.get("shed_depth", 0))
    bo_count = sum(b.get("escalations", 0) + b.get("restores", 0)
                   for b in bo.values())

    checks.append(("every request resolved (no hangs/errors)",
                   len(results) == len(reqs)
                   and all(d["other"] == 0 for d in by_tier.values())))
    checks.append(("overload sheds observed (fleet was saturated)",
                   sum(d["shed"] for d in by_tier.values()) > 0))
    checks.append(("low tier sheds first (background > interactive)",
                   shed_rate("background") > shed_rate("interactive")))
    checks.append(("interactive goodput survives (completions > 0)",
                   inter["ok"] > 0))
    checks.append(("interactive p99 under its deadline",
                   p99 is not None and p99 < deadline_ms))
    checks.append(("flooding tenant rate-limited",
                   ov.get("rate_limited", 0) > 0))
    checks.append(("brownout engaged during the storm (some lane)",
                   any(m >= 1 for m in max_stage.values())))
    checks.append(("brownout cleared after the storm (all lanes stage 0)",
                   all(b.get("stage", 1) == 0 for b in bo.values())
                   and bool(bo)))
    checks.append(("brownout escalations == restores (ladder walked "
                   "back down in order)",
                   bo_count > 0 and all(
                       b.get("escalations", 0) == b.get("restores", -1)
                       for b in bo.values())))
    checks.append(("gateway overload counters == overload marker spans",
                   gw_count == gw_spans))
    checks.append(("brownout transitions == overload spans on lanes",
                   bo_count == bo_spans))
    pools = _combined_pools_clean(port)
    checks.append(("zero KV blocks leaked after the storm",
                   pools is not None))
    return {
        "by_tier": {t: {"ok": d["ok"], "shed": d["shed"],
                        "other": d["other"],
                        "shed_rate": round(shed_rate(t), 3)}
                    for t, d in by_tier.items()},
        "interactive_p99_ms": round(p99, 1) if p99 is not None else None,
        "deadline_ms": deadline_ms,
        "gateway_overload": ov,
        "brownout": bo,
        "brownout_max_stage_observed": max_stage,
        "spans": {"gateway": gw_spans, "brownout": bo_spans},
        "kv_pools_after": pools,
    }


def run_overload_standalone() -> int:
    port, proc = launch_overload_server()
    checks: list = []
    try:
        report = {"mode": "overload-standalone", "port": port,
                  "phases": {"overload": overload_phase(port, checks)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks) and bool(checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def _fleet_counters_match_spans(gw) -> bool:
    from tpu_engine.serving.resilience import FleetCounters

    fl = gw.get_stats().get("fleet", {})
    expect = sum(fl.get(f, 0) for f in FleetCounters.SPAN_FIELDS)
    spans = [s for s in gw.tracer.snapshot() if s["op"] == "fleet"]
    return len(spans) == expect


def elastic_phase(ports, procs, checks: list) -> dict:
    """Elastic-fleet chaos (--elastic). A diurnal ramp against the live
    closed loop: 2 member lanes + 2 warm standbys behind an --autoscale
    gateway. The high phase drives Poisson stream load past the up
    threshold and the fleet must DOUBLE (probe-gated standby
    registration); the low phase runs a trickle feeder that keeps ONE
    pinned long stream per live lane so pressure settles below the down
    threshold while every lane still holds a journaled stream — the
    fleet must HALVE back to min-lanes with retirements drained through
    live stream migration. Every stream
    (greedy AND seeded) must complete byte-identical to an unkilled
    control, zero blocks leaked anywhere. Then the wedge ladder: a
    scale-up aimed at a dead address latches ``spawn-wedged``; a member
    kill -9ed mid-drain latches ``drain-wedged`` — both NAMED
    degraded-but-serving states the fleet keeps serving through, both
    clearable via /admin/fleet. Fleet counters == fleet marker spans
    throughout."""
    import random
    import signal
    import threading

    from tpu_engine.serving.autoscaler import StandbyLaneProvider
    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.utils.config import GatewayConfig

    member_ports, standby_ports = ports[:2], ports[2:4]
    gw = Gateway([f"127.0.0.1:{p}" for p in member_ports],
                 GatewayConfig(autoscale=True,
                               autoscale_interval_s=0.25,
                               autoscale_min_lanes=2,
                               autoscale_max_lanes=4,
                               autoscale_up_pressure=0.30,
                               autoscale_down_pressure=0.20,
                               autoscale_cooldown_s=0.5,
                               autoscale_spawn_timeout_s=5.0,
                               failover_streams=True,
                               migrate_streams=True,
                               migrate_timeout_s=60.0))

    # ---- compile warmup: every lane (members AND standbys) serves one
    # tiny stream first. A cold worker's first generate blocks /health
    # behind the compile, which the controller correctly treats as a
    # BLIND lane and holds — this scenario tests the loop's steering,
    # not cold-start compile latency. ------------------------------------
    def _warm(port):
        try:
            _call(port, "POST", "/generate",
                  {"request_id": f"warm_{port}",
                   "prompt_tokens": [3, 1, 4], "max_new_tokens": 4},
                  timeout=600)
        except Exception:
            pass
    warmers = [threading.Thread(target=_warm, args=(p,), daemon=True)
               for p in ports[:4]]
    for t in warmers:
        t.start()
    for t in warmers:
        t.join(timeout=600)

    # ---- the diurnal waves (built before the loop starts) ---------------
    # Request ids are mined per member lane (the FNV-1a ring is skewed;
    # an unmined burst can land almost entirely on one lane and read as
    # half the fleet pressure it should).
    member_lanes = sorted(gw.worker_names())
    high = []
    for k in range(16):
        params = {}
        if k % 3 == 1:
            params = {"temperature": 0.9, "seed": 700 + k}
        elif k % 3 == 2:
            params = {"temperature": 0.8, "seed": 800 + k,
                      "top_p": 0.9, "repetition_penalty": 1.2}
        high.append({"request_id": rid_for_lane(
                         gw._ring, member_lanes[k % 2], f"hi{k}"),
                     "prompt_tokens": [(k * 7 + j) % 90 + 1
                                       for j in range(5 + k % 4)],
                     "max_new_tokens": 32, **params})
    try:
        control = control_oracle(ports[0], high)
    except RuntimeError as exc:
        checks.append(("elastic: control generate", False))
        gw.stop()
        return {"error": str(exc)}

    results: dict = {}
    lock = threading.Lock()
    threads: list = []

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

    def fire(reqs, rate, rng):
        for req in reqs:
            t = threading.Thread(target=consume, args=(req,), daemon=True)
            t.start()
            threads.append(t)
            time.sleep(rng.expovariate(rate))

    def wait_lane_count(target, timeout):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if len(gw.worker_names()) == target:
                return True
            time.sleep(0.1)
        return False

    provider = StandbyLaneProvider(
        [f"127.0.0.1:{p}" for p in standby_ports])
    ctl = gw.engage_autoscaler(provider=provider)
    checks.append(("elastic: controller loop running", ctl.running))

    # ---- high phase: the ramp up ----------------------------------------
    # The burst must saturate both member lanes long enough for pressure
    # to outlive the actuation cooldown and force BOTH probe-gated
    # registrations (hence gpt2-chaos-test: multi-second stream lives).
    rng = random.Random(17)
    fire(high, rate=12.0, rng=rng)
    doubled = wait_lane_count(4, timeout=120.0)
    checks.append(("elastic: fleet doubled under load (2 -> 4 lanes, "
                   "probe-gated standby registration)", doubled))

    # ---- low phase: a trickle feeder keeps one pinned long stream per
    # live lane, so once the burst drains, pressure sits below the down
    # threshold while every lane still holds a journaled stream — each
    # retirement must ride live migration, never an idle-lane removal. --
    low: list = []
    feed_stop = threading.Event()

    def feeder():
        for rnd in range(60):
            if feed_stop.is_set():
                return
            round_reqs = []
            for j, lane in enumerate(sorted(gw.worker_names())):
                try:
                    rid = rid_for_lane(gw._ring, lane, f"lo{rnd}_{j}")
                except RuntimeError:
                    continue  # lane left the ring mid-build
                params = {} if (rnd + j) % 2 == 0 else \
                    {"temperature": 0.9, "seed": 900 + rnd * 8 + j}
                round_reqs.append(
                    {"request_id": rid,
                     "prompt_tokens": [(rnd * 11 + j * 3 + m) % 90 + 1
                                       for m in range(6)],
                     "max_new_tokens": 96, **params})
            with lock:
                low.extend(round_reqs)
            round_threads = []
            for req in round_reqs:
                t = threading.Thread(target=consume, args=(req,),
                                     daemon=True)
                t.start()
                round_threads.append(t)
            for t in round_threads:
                t.join(timeout=600)

    feed_thread = None
    if doubled:
        feed_thread = threading.Thread(target=feeder, daemon=True)
        feed_thread.start()
    halved = wait_lane_count(2, timeout=180.0)
    checks.append(("elastic: fleet halved at low pressure (4 -> 2 lanes "
                   "through the drain+migrate ladder)", halved))
    feed_stop.set()
    if feed_thread is not None:
        feed_thread.join(timeout=600)
    ctl.stop()

    for t in threads:
        t.join(timeout=600)
    # The feeder's control runs AFTER the wave (the oracle is
    # deterministic, so when it runs does not matter) — computing it
    # inline would open pressure gaps mid-descent. The oracle worker
    # may have been drained by a ramp-down retirement, so undrain it
    # first (idempotent).
    try:
        _call(ports[0], "POST", "/admin/drain", {"action": "undrain"},
              timeout=30)
    except Exception:
        pass
    try:
        control.update(control_oracle(ports[0], low))
    except RuntimeError:
        checks.append(("elastic: low-phase control generate", False))
        low = [r for r in low if r["request_id"] in control]
    wave = high + low
    complete, identical, _resumed = tally_streams(
        {r["request_id"]: results[r["request_id"]] for r in wave}, control)
    checks.append(("elastic: all ramp streams completed "
                   f"({complete}/{len(wave)})", complete == len(wave)))
    checks.append(("elastic: all ramp streams byte-identical to control, "
                   f"greedy and seeded ({identical}/{len(wave)})",
                   identical == len(wave)))
    fl = gw.get_stats().get("fleet", {})
    mig = gw.get_stats().get("migration", {})
    checks.append(("elastic: >= 2 probe-gated registrations "
                   f"({fl.get('scale_up_completed', 0)})",
                   fl.get("scale_up_completed", 0) >= 2))
    checks.append(("elastic: >= 2 graceful retirements "
                   f"({fl.get('scale_down_completed', 0)})",
                   fl.get("scale_down_completed", 0) >= 2))
    checks.append(("elastic: scale-down rode live stream migration "
                   f"({mig.get('streams_migrated', 0)} migrated)",
                   mig.get("streams_migrated", 0) >= 1))
    checks.append(("elastic: suppressed decisions counted as held "
                   f"({fl.get('decisions_held', 0)})",
                   fl.get("decisions_held", 0) >= 1))
    ramp = {"streams": len(wave), "complete": complete,
            "identical": identical, "fleet": dict(fl),
            "migration": dict(mig),
            "lanes_after_ramp": sorted(gw.worker_names())}

    # ---- wedge ladder: named degraded-but-serving states ----------------
    # (manual actuations on the STOPPED controller — same ladder.)
    res = gw.fleet_admin({"action": "add", "worker": "127.0.0.1:1"})
    checks.append(("elastic: dead-address spawn lands spawn-wedged "
                   f"({res.get('status')})",
                   res.get("status") == "spawn-wedged"))
    st = gw.fleet_status()
    checks.append(("elastic: fleet state names the wedge "
                   f"({st['state']})", "spawn-wedged" in st["state"]))

    def still_serving(tag, port_hint):
        req = {"request_id": tag,
               "prompt_tokens": [3, 1, 4, 1, 5], "max_new_tokens": 8}
        try:
            ctl_toks = control_oracle(port_hint, [req])[tag]
            toks, final = [], None
            for frame in gw.route_generate_stream(dict(req)):
                evt = _parse_sse(frame)
                if evt is None:
                    continue
                if evt.get("done"):
                    final = evt
                    break
                if "tokens" in evt:
                    toks.extend(evt["tokens"])
            return stream_completed(final) and toks == ctl_toks
        except Exception:
            return False

    live_ports = [p for i, p in enumerate(ports[:4])
                  if procs[i].poll() is None]
    serving_port = next(p for p in live_ports
                        if any(l.endswith(f":{p}")
                               for l in gw.worker_names()))
    checks.append(("elastic: fleet serves through spawn-wedged",
                   still_serving("wz_spawn", serving_port)))
    res = gw.fleet_admin({"action": "clear", "worker": "127.0.0.1:1"})
    checks.append(("elastic: spawn wedge clears via /admin/fleet",
                   res.get("status") == "cleared"))

    # kill -9 a member mid-drain: the drain call dies, membership still
    # shrinks, drain-wedged latches as a durable operator signal.
    victim = sorted(gw.worker_names())[0]
    victim_port = next(p for p in ports if victim.endswith(f":{p}"))
    procs[ports.index(victim_port)].send_signal(signal.SIGKILL)
    procs[ports.index(victim_port)].wait(timeout=10)
    res = gw.fleet_admin({"action": "remove", "worker": victim})
    checks.append(("elastic: kill -9 mid-drain lands removed-degraded "
                   f"({res.get('status')})",
                   res.get("status") == "removed-degraded"))
    st = gw.fleet_status()
    checks.append(("elastic: drain wedge latched and named "
                   f"({st['state']})", "drain-wedged" in st["state"]
                   and victim not in st["lanes"]))
    survivor_port = next(p for p in ports
                         if gw.worker_names()[0].endswith(f":{p}"))
    checks.append(("elastic: fleet serves through drain-wedged",
                   still_serving("wz_drain", survivor_port)))
    res = gw.fleet_admin({"action": "clear", "worker": victim})
    checks.append(("elastic: drain wedge clears only via /admin/fleet",
                   res.get("status") == "cleared"
                   and gw.fleet_status()["state"] == "steady"))
    # Idempotency of the manual surface: named no-ops, never errors.
    checks.append(("elastic: re-add of a member answers already-member",
                   gw.fleet_admin({"action": "add",
                                   "worker": gw.worker_names()[0]}
                                  ).get("status") == "already-member"))
    checks.append(("elastic: re-remove answers unknown-lane",
                   gw.fleet_admin({"action": "remove", "worker": victim}
                                  ).get("status") == "unknown-lane"))
    checks.append(("elastic: double clear answers not-degraded",
                   gw.fleet_admin({"action": "clear", "worker": victim}
                                  ).get("status") == "not-degraded"))

    checks.append(("elastic: fleet counters == fleet marker spans",
                   _fleet_counters_match_spans(gw)))
    leak_free = {}
    for p in ports[:4]:
        if procs[ports.index(p)].poll() is not None:
            continue  # the kill -9 victim
        pool = _worker_pool_clean(p)
        leak_free[p] = pool is not None
        checks.append((f"elastic: zero KV blocks leaked on :{p}",
                       pool is not None))
    fleet_final = dict(gw.get_stats().get("fleet", {}))
    gw.stop()
    return {"ramp": ramp, "fleet_final": fleet_final,
            "leak_free": leak_free, "killed": victim}


def run_elastic_standalone() -> int:
    # gpt2-chaos-test, not gpt2-small-test: the autoscaler steers by lane
    # pressure, and the tiny model drains a burst faster than the 4 Hz
    # control loop can observe it (slots never stay occupied).
    ports, procs = launch_worker_procs(4, model="gpt2-chaos-test",
                                       extra_args=("--kv-blocks", "80"))
    checks: list = []
    try:
        report = {"mode": "elastic-standalone", "worker_ports": ports,
                  "phases": {"elastic": elastic_phase(ports, procs,
                                                      checks)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks) and bool(checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def stitch_phase(ports, procs, checks: list,
                 dump_dir: str) -> dict:
    """Cross-lane trace stitching chaos (--stitch): ONE stream driven
    through every mobility mechanism the engine has — disagg prefill →
    decode handoff, then a migrate-mode drain of its decode lane, then
    kill -9 of the migration destination forcing the replay resume —
    must come out byte-identical to an unmoved control AND export ONE
    merged trace via the stitcher whose spans cover every reachable
    lane that served it, with zero orphaned spans and mobility
    counters == hop markers. The kill must also leave a flight-recorder
    postmortem on the resume lane naming the anomaly. ports[0] is the
    prefill lane, ports[1:4] decode lanes (all with --trace-stitch and
    the flight recorder armed), ports[4] a plain defaults-off worker
    (the control oracle and the wire-identity probe)."""
    import random
    import signal

    from tpu_engine.serving.gateway import Gateway
    from tpu_engine.utils.config import GatewayConfig

    gw = Gateway([f"127.0.0.1:{p}" for p in ports[:4]],
                 GatewayConfig(disagg=True, handoff_timeout_s=60.0,
                               failover_streams=True,
                               migrate_streams=True,
                               migrate_timeout_s=60.0,
                               trace_stitch=True))
    rid = "st_0"
    # Long enough that the stream is provably mid-generation through
    # BOTH moves and the kill (tiny CPU models decode fast).
    req = {"request_id": rid, "prompt_tokens": [5, 9, 3, 17, 11],
           "max_new_tokens": 360}
    try:
        control = control_oracle(ports[4], [req])
    except RuntimeError as exc:
        checks.append(("stitch: control generate", False))
        return {"error": str(exc)}
    # Warm every lane's compile cache so the drain and the kill land
    # mid-decode, not mid-compile.
    for p in ports[:4]:
        _call(p, "POST", "/generate",
              {"request_id": f"warm_{p}", "prompt_tokens": [1, 2, 3],
               "max_new_tokens": 4}, timeout=600)

    moved = {"drained": None, "killed": None, "kill_port": None}

    def drain_then_kill():
        # Stage 1: the handoff has landed (>=3 tokens relayed implies
        # the decode lane owns the stream) — drain that decode lane
        # with migrate semantics.
        rec = gw._streams.get(rid)
        if rec is None:
            return
        lane0 = rec.lane
        moved["drained"] = lane0
        gw.remove_worker(lane0, drain=True)
        # Stage 2: wait for the migration splice to land on a new lane.
        deadline = time.monotonic() + 90
        lane1 = None
        while time.monotonic() < deadline:
            mig = gw.get_stats().get("migration", {})
            rec = gw._streams.get(rid)
            if rec is None:
                return  # stream already finished — too short to kill
            if (mig.get("streams_migrated", 0) >= 1
                    and rec.lane and rec.lane != lane0):
                lane1 = rec.lane
                break
            time.sleep(0.05)
        if lane1 is None:
            return
        time.sleep(0.15)  # a few post-migration tokens on the new lane
        # Stage 3: kill -9 the migration destination mid-stream — the
        # replay resume is the stream's THIRD serving lane.
        moved["killed"] = lane1
        port1 = next(p for p in ports[:4] if lane1.endswith(f":{p}"))
        moved["kill_port"] = port1
        procs[ports.index(port1)].send_signal(signal.SIGKILL)

    results, fired = drive_streams_with_kill(
        gw, [req], {rid}, drain_then_kill, random.Random(11),
        kill_window_s=300.0)
    checks.append(("stitch: drain+kill fired mid-stream",
                   fired and moved["killed"] is not None))
    toks, final = results[rid]
    checks.append(("stitch: thrice-moved stream byte-identical to "
                   "unmoved control",
                   stream_completed(final) and toks == control[rid]
                   and final.get("tokens") == control[rid]))
    stats = gw.get_stats()
    ho = stats.get("handoff", {})
    mig = stats.get("migration", {})
    fo = stats.get("failover", {})
    checks.append(("stitch: prefill→decode handoff spliced "
                   f"({ho.get('handoffs_spliced', 0)})",
                   ho.get("handoffs_spliced", 0) >= 1))
    checks.append(("stitch: stream migrated off the drained lane "
                   f"({mig.get('streams_migrated', 0)})",
                   mig.get("streams_migrated", 0) >= 1))
    checks.append(("stitch: kill -9 landed on the replay resume "
                   f"({fo.get('resumes_succeeded', 0)})",
                   fo.get("resumes_succeeded", 0) >= 1))

    # THE tentpole assertion: one merged tree from /admin/trace/<rid>.
    stitched = gw.stitched_trace(rid)
    lanes = set(stitched.get("lanes") or [])
    hops = stitched.get("hops") or []
    # Every lane the ledger says served the stream must contribute
    # spans — except the killed one, whose ring died with its process.
    served = {h["lane"] for h in hops}
    reachable = {l for l in served if l != moved["killed"]}
    checks.append(("stitch: merged trace covers every reachable lane "
                   f"({sorted(lanes)} ⊇ {sorted(reachable)} + gateway)",
                   "gateway" in lanes and reachable <= lanes
                   and len(reachable) >= 2))
    checks.append(("stitch: zero orphaned spans "
                   f"({stitched.get('orphans')})",
                   stitched.get("orphans") == 0))
    # Mobility counters == hop markers, both in the ledger and in the
    # span stream (the existing per-mechanism invariants must still
    # hold on the composed path).
    kinds: dict = {}
    for h in hops:
        kinds[h["kind"]] = kinds.get(h["kind"], 0) + 1
    checks.append(("stitch: ledger hops == mobility counters "
                   f"({kinds})",
                   kinds.get("handoff", 0) == ho.get("handoffs_spliced",
                                                     -1)
                   and kinds.get("migrate", 0) == mig.get(
                       "streams_migrated", -1)
                   and kinds.get("resume", 0) == fo.get(
                       "resumes_succeeded", -1)
                   and kinds.get("admit", 0) == 1))
    checks.append(("stitch: handoff counters == kv_handoff spans",
                   _handoff_counters_match_spans(gw)))
    checks.append(("stitch: migration counters == migration spans",
                   _migration_counters_match_spans(gw)))
    resume_spans = [s for s in gw.tracer.snapshot()
                    if s["op"] == "resume"]
    checks.append(("stitch: failover counters == resume spans",
                   len(resume_spans) == fo.get("resumes_attempted", -1)))

    # The kill must have left a black box: the gateway's resume path
    # asks the resume lane's flight recorder for a postmortem named
    # for the event.
    dump_seen = None
    for p in ports[:4]:
        if p == moved["kill_port"]:
            continue
        try:
            _, tl = _call(p, "GET", "/admin/timeline", timeout=5.0)
        except OSError:
            continue
        last = (tl.get("flight") or tl).get("last_dump")
        if last and str(last.get("anomaly", "")).startswith(
                "failover_resume:"):
            dump_seen = dict(last, port=p)
            break
    checks.append(("stitch: flight-recorder dump fired on the kill "
                   f"and names the anomaly ({dump_seen})",
                   dump_seen is not None))

    # Defaults-off wire identity: the plain worker (no new flags) must
    # expose NO flight block and the armed worker must expose one (the
    # probe is sensitive); the data plane must be byte-identical
    # between the two (same model, same request ⇒ same tokens, no new
    # response keys).
    # An armed worker that is NEITHER the killed lane (dead) NOR the
    # drained lane (refusing admissions) serves the probe.
    dead_or_draining = {moved["kill_port"]}
    if moved["drained"]:
        dead_or_draining.add(next(
            p for p in ports[:4] if moved["drained"].endswith(f":{p}")))
    armed_port = next(p for p in ports[:4] if p not in dead_or_draining)
    _, h_plain = _call(ports[4], "GET", "/health", timeout=10)
    _, h_armed = _call(armed_port, "GET", "/health", timeout=10)
    plain_flight = (h_plain.get("generator") or {}).get("flight")
    armed_flight = (h_armed.get("generator") or {}).get("flight")
    checks.append(("stitch: defaults-off worker has no flight block, "
                   "armed worker does",
                   plain_flight is None and armed_flight is not None))
    probe = {"request_id": "wire_probe", "prompt_tokens": [2, 4, 6],
             "max_new_tokens": 6}
    _, r_plain = _call(ports[4], "POST", "/generate", dict(probe),
                       timeout=600)
    _, r_armed = _call(armed_port, "POST", "/generate", dict(probe),
                       timeout=600)
    checks.append(("stitch: /generate wire schema identical with "
                   "flags on vs off",
                   sorted(r_plain) == sorted(r_armed)
                   and r_plain.get("tokens") == r_armed.get("tokens")))
    gw.stop()
    return {"stream": {"tokens": len(toks),
                       "identical": toks == control[rid]},
            "moved": moved, "hops": hops,
            "trace": {"lanes": sorted(lanes),
                      "spans": len(stitched.get("spans") or []),
                      "orphans": stitched.get("orphans")},
            "handoff": ho, "migration": mig, "failover": fo,
            "flight_dump": dump_seen}


def run_stitch_standalone() -> int:
    import shutil
    import tempfile

    dump_dir = tempfile.mkdtemp(prefix="flight_stitch_")
    obs = ("--trace-stitch", "--flight-recorder", "256",
           "--flight-dump-dir", dump_dir)
    ports, procs = launch_worker_procs(
        5, per_worker_args=(("--role", "prefill") + obs,
                            ("--role", "decode") + obs,
                            ("--role", "decode") + obs,
                            ("--role", "decode") + obs,
                            ("--role", "decode")))
    checks: list = []
    try:
        report = {"mode": "stitch-standalone", "worker_ports": ports,
                  "phases": {"stitch": stitch_phase(ports, procs,
                                                    checks, dump_dir)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks) and bool(checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        shutil.rmtree(dump_dir, ignore_errors=True)


def _prefix_dir_counters_match_spans(gw) -> bool:
    """Gateway prefix-directory counters == ``prefix_dir`` marker spans
    (``evictions`` is a span-free value counter and excluded by
    SPAN_FIELDS) — same discipline as `_fleet_counters_match_spans`."""
    from tpu_engine.serving.resilience import PrefixDirCounters

    pd = gw.get_stats().get("prefix_directory", {})
    expect = sum(pd.get(f, 0) for f in PrefixDirCounters.SPAN_FIELDS)
    spans = [s for s in gw.tracer.snapshot() if s["op"] == "prefix_dir"]
    return len(spans) == expect


def fleet_prefix_phase(ports, procs, checks: list) -> dict:
    """Fleet prefix tier under real fleet faults (--fleet-prefix):
    3 --prefix-fetch workers behind a --prefix-directory gateway over
    HTTP. A shared 48-token prefix is established on one lane, then a
    second lane's hinted request must SPLICE it over the wire (remote
    prefill skipped, stream byte-identical to an uninterrupted oracle).
    Then the fallback ladder under faults: a DRAINED owner refuses the
    export BY NAME and the hinted stream recomputes locally
    (peer_refused); a kill -9ed owner leaves the fetch dialing a corpse
    and the stream recomputes locally (peer_unreachable) — every
    fallback byte-identical, zero KV blocks leaked on the survivors,
    the prober eject invalidates the dead lane's directory entries, and
    directory counters == prefix_dir marker spans throughout."""
    import signal

    from tpu_engine.serving.gateway import Gateway, _parse_sse
    from tpu_engine.utils.config import GatewayConfig

    gw = Gateway([f"127.0.0.1:{p}" for p in ports],
                 GatewayConfig(prefix_directory=True,
                               health_probe_interval_s=0.5,
                               health_probe_failures=2))
    lanes = gw.worker_names()
    lane = {i: victim_lane_for_port(lanes, p) for i, p in enumerate(ports)}

    def fetch_stats(port):
        _, health = _call(port, "GET", "/health", timeout=10.0)
        return (health.get("generator") or {}).get("prefix_fetch") or {}

    # Two disjoint shared prefixes (3 full 16-token blocks each) with
    # per-request suffix tails — the directory keys on the block-aligned
    # prefix fingerprint, so every request below shares a chain without
    # sharing a prompt. Sized to the test model: 48 prefix + 6 suffix +
    # 8 new tokens stays under gpt2-small-test's 64-position window, so
    # nothing silently truncates.
    p1 = [(17 * j + 5) % 97 + 1 for j in range(48)]
    p2 = [(13 * j + 11) % 89 + 1 for j in range(48)]

    def req(rid, prefix, salt):
        return {"request_id": rid,
                "prompt_tokens": prefix + [(salt * 9 + j) % 90 + 1
                                           for j in range(6)],
                "max_new_tokens": 8}

    # Warm every lane's compile cache on an UNRELATED prompt so fetch
    # timings measure the tier, not XLA.
    for p in ports:
        _call(p, "POST", "/generate",
              {"request_id": f"warm_{p}", "prompt_tokens": [1, 2, 3],
               "max_new_tokens": 4}, timeout=600)

    outputs: dict = {}
    requests: list = []

    def run_blocking(rid, prefix, salt):
        r = req(rid, prefix, salt)
        requests.append(r)
        outputs[rid] = gw.route_generate(dict(r))["tokens"]

    # 1) Establish lane 0 as the P1 owner (post-completion record).
    r_own1 = rid_for_lane(gw._ring, lane[0], "fpown1")
    run_blocking(r_own1, p1, 1)
    checks.append(("fleet-prefix: owner recorded in the directory",
                   gw.get_stats().get("prefix_directory", {})
                   .get("entries", 0) >= 1))

    # 2) Hinted STREAM on lane 1: the gateway stamps the peer hint, the
    # lane pulls the chain over real HTTP and splices — remote prefill
    # skipped, one attempt, one splice.
    i_fetch = 1
    r_fetch = rid_for_lane(gw._ring, lane[i_fetch], "fpfetch")
    rf = req(r_fetch, p1, 2)
    requests.append(rf)
    toks, final = [], None
    for frame in gw.route_generate_stream(dict(rf)):
        evt = _parse_sse(frame)
        if evt and evt.get("done"):
            final = evt
            break
        if evt and "tokens" in evt:
            toks.extend(evt["tokens"])
    outputs[r_fetch] = (final or {}).get("tokens")
    checks.append(("fleet-prefix: hinted stream completed",
                   stream_completed(final) and toks == outputs[r_fetch]))
    fs = fetch_stats(ports[i_fetch])
    checks.append(("fleet-prefix: peer fetch spliced over HTTP "
                   f"(attempted={fs.get('attempted')} "
                   f"spliced={fs.get('spliced')})",
                   fs.get("attempted") == 1 and fs.get("spliced") == 1
                   and fs.get("blocks_spliced", 0) >= 3
                   and fs.get("prefill_tokens_skipped_remote", 0) >= 48))

    # 3) Drained owner refuses BY NAME. The P1 chain now lives on both
    # lane 0 and lane 1 (and the directory may point at either after a
    # prober sweep) — drain BOTH so the hint, wherever it lands, meets a
    # refusal; the hinted request on lane 2 must fall back to local
    # prefill and still match the oracle.
    for i in (0, 1):
        _call(ports[i], "POST", "/admin/drain", {"action": "drain"},
              timeout=30)
    _, refused = _call(ports[i_fetch], "POST", "/admin/export_prefix",
                       {"tokens": p1[:32]}, timeout=30)
    checks.append(("fleet-prefix: drained owner refuses export by name",
                   refused.get("ok") is False
                   and "is draining" in refused.get("reason", "")
                   and f"w{i_fetch}" in refused.get("reason", "")))
    r_drain = rid_for_lane(gw._ring, lane[2], "fpdrain")
    run_blocking(r_drain, p1, 3)
    for i in (0, 1):
        _call(ports[i], "POST", "/admin/drain", {"action": "undrain"},
              timeout=30)
    fs2 = fetch_stats(ports[2])
    checks.append(("fleet-prefix: refused fetch fell back to local "
                   f"prefill (peer_refused={fs2.get('peer_refused')})",
                   fs2.get("attempted") == 1
                   and fs2.get("peer_refused") == 1
                   and fs2.get("spliced", 0) == 0))

    # 4) Kill -9 the owner of a SECOND prefix, then fetch: the hint
    # dials a corpse, the lane recomputes locally, the stream is still
    # byte-identical. Lane 2 is the only P2 holder, lane 1 the fetcher.
    r_own2 = rid_for_lane(gw._ring, lane[2], "fpown2")
    run_blocking(r_own2, p2, 4)
    procs[2].send_signal(signal.SIGKILL)
    procs[2].wait(timeout=10)
    r_kill = rid_for_lane(gw._ring, lane[i_fetch], "fpkill")
    run_blocking(r_kill, p2, 5)
    fs3 = fetch_stats(ports[i_fetch])
    checks.append(("fleet-prefix: dead-owner fetch fell back to local "
                   f"prefill (peer_unreachable={fs3.get('peer_unreachable')})",
                   fs3.get("attempted") == 2
                   and fs3.get("peer_unreachable") == 1
                   and fs3.get("spliced") == 1))

    # 5) The prober ejects the corpse and the eject invalidates its
    # directory entries (a dead lane can't serve a peer fetch).
    ejected = False
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if lane[2] in gw.ejected_lanes():
            ejected = True
            break
        time.sleep(0.1)
    pd = gw.get_stats().get("prefix_directory", {})
    checks.append(("fleet-prefix: prober ejected the dead owner",
                   ejected))
    checks.append(("fleet-prefix: eject invalidated the dead lane's "
                   f"entries (invalidations={pd.get('invalidations')})",
                   pd.get("invalidations", 0) >= 1))
    checks.append(("fleet-prefix: prober sweeps seeded the directory "
                   f"(seeded={pd.get('seeded')})",
                   pd.get("seeded", 0) >= 1))
    checks.append(("fleet-prefix: hints attached "
                   f"({pd.get('hints_attached')})",
                   pd.get("hints_attached", 0) >= 3))

    # 6) Oracle: every gateway stream vs a blocking control on ONE
    # surviving worker (identical weights fleet-wide; run LAST so the
    # control's own radix inserts can't pre-warm the fetch targets).
    try:
        control = control_oracle(ports[0], requests)
    except RuntimeError as exc:
        checks.append(("fleet-prefix: control generate", False))
        gw.stop()
        return {"error": str(exc)}
    identical = sum(1 for rid, toks in outputs.items()
                    if toks == control[rid])
    checks.append(("fleet-prefix: every stream byte-identical to "
                   f"control ({identical}/{len(outputs)})",
                   identical == len(outputs) and len(outputs) == 5))

    # 7) Export sanity on a live lane: a real chain for the shared
    # prefix, a refusal (not an error) for an empty one.
    _, chain = _call(ports[0], "POST", "/admin/export_prefix",
                     {"tokens": p1[:32]}, timeout=30)
    checks.append(("fleet-prefix: live export returns a verifiable chain",
                   chain.get("ok") is True
                   and chain.get("blocks", 0) >= 2
                   and (chain.get("chain") or {}).get("block_size") == 16
                   and "checksum" in (chain.get("chain") or {})))
    _, empty = _call(ports[0], "POST", "/admin/export_prefix",
                     {"tokens": []}, timeout=30)
    checks.append(("fleet-prefix: empty export refused, not raised",
                   empty.get("ok") is False
                   and "no token prefix" in empty.get("reason", "")))

    # 8) Directory counters == prefix_dir marker spans (settle briefly:
    # the prober bumps the counter before recording its span).
    agree = False
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if _prefix_dir_counters_match_spans(gw):
            agree = True
            break
        time.sleep(0.1)
    checks.append(("fleet-prefix: directory counters == prefix_dir "
                   "spans", agree))

    # 9) Zero KV blocks leaked on the survivors.
    for p in (ports[0], ports[1]):
        pool = _worker_pool_clean(p)
        checks.append((f"fleet-prefix: no KV blocks leaked on :{p}",
                       pool is not None))
    gw.stop()
    return {"prefix_directory": pd,
            "fetch_lane": {"splice": fs, "after_kill": fs3},
            "refused_lane": fs2, "drain_refusal": refused,
            "streams": len(outputs), "identical": identical}


def run_fleet_prefix_standalone() -> int:
    ports, procs = launch_worker_procs(3, extra_args=("--prefix-fetch",))
    checks: list = []
    try:
        report = {"mode": "fleet-prefix-standalone", "worker_ports": ports,
                  "phases": {"fleet_prefix":
                             fleet_prefix_phase(ports, procs, checks)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks) and bool(checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def unified_phase(ports, procs, checks: list) -> dict:
    """Kill -9 one lane serving MIXED generate+score traffic under
    Poisson load (PR 20's unified stateless pool: scores ride the same
    continuous scheduler as decode rows). The generative streams must
    resume byte-identical through the PR 6 replay ladder; an in-flight
    score against the dead lane FAILS RETRYABLE (blocking op → gateway
    ring-order failover) and completes byte-identical on a surviving
    lane; zero KV blocks leak and every stateless row is accounted for
    (admitted == completed + failed on the survivors); gateway failover
    counters == resume spans and one score route span per request."""
    import random
    import signal
    import threading

    from tpu_engine.serving.gateway import Gateway
    from tpu_engine.utils.config import GatewayConfig

    gw = Gateway([f"127.0.0.1:{p}" for p in ports],
                 GatewayConfig(failover_streams=True,
                               health_probe_interval_s=0.25,
                               health_probe_failures=2))
    lanes = gw.worker_names()
    victim_lane = victim_lane_for_port(lanes, ports[1])
    victim_proc = procs[1]

    # Generate mix: greedy and seeded-sampled streams, victim-weighted
    # (long budgets there so the kill lands mid-stream).
    gen_requests = []
    for k in range(8):
        lane = victim_lane if k % 2 == 0 else lanes[k % len(lanes)]
        params = ({"temperature": 0.9, "seed": 100 + k}
                  if k % 2 else {})
        gen_requests.append({
            "request_id": rid_for_lane(gw._ring, lane, f"ug{k}"),
            "prompt_tokens": [(k * 5 + j) % 90 + 1
                              for j in range(6 + k % 4)],
            # Long victim budgets: a warm stream finishes in ~0.1s on
            # the CPU backend, and drive_streams_with_kill only starts
            # its kill loop AFTER every arrival has launched — the
            # victim streams must outlive the arrival phase.
            "max_new_tokens": 160 if lane == victim_lane else 24,
            **params})
    victim_rids = {r["request_id"] for r in gen_requests
                   if gw._ring.get_node(r["request_id"]) == victim_lane}

    # Score mix: single-tick rows in the same pool, victim-weighted the
    # same way so some are provably in flight against the dead lane.
    score_requests = []
    for k in range(16):
        lane = victim_lane if k % 2 == 0 else lanes[k % len(lanes)]
        score_requests.append({
            "request_id": rid_for_lane(gw._ring, lane, f"us{k}"),
            "prompt_tokens": [(k * 3 + j) % 90 + 1
                              for j in range(4 + k % 3)],
            "completion_tokens": [(k + j) % 90 + 1
                                  for j in range(3 + k % 2)]})

    # Controls: blocking runs against ONE healthy worker — the oracles
    # both classes must match byte-for-byte.
    try:
        gen_control = control_oracle(ports[0], gen_requests)
    except RuntimeError as exc:
        checks.append(("unified: control generate", False))
        return {"error": str(exc)}
    score_control = {}
    for r in score_requests:
        status, body = _call(ports[0], "POST", "/score",
                             dict(r, request_id="ctl_" + r["request_id"]),
                             timeout=600)
        if status != 200:
            checks.append(("unified: control score", False))
            return {"error": f"control score failed ({status}): {body}"}
        score_control[r["request_id"]] = body["logprobs"]
    # Warm the other lanes' compile caches (generate AND score buckets)
    # so the kill lands mid-decode, not mid-compile.
    for p in ports[1:]:
        _call(p, "POST", "/generate",
              {"request_id": f"warm_{p}", "prompt_tokens": [1, 2, 3],
               "max_new_tokens": 4}, timeout=600)
        _call(p, "POST", "/score",
              {"request_id": f"warm_s_{p}", "prompt_tokens": [1, 2, 3],
               "completion_tokens": [4, 5]}, timeout=600)

    # Score driver: Poisson-fire the score mix through the gateway for
    # the whole drive window (before, during, and after the kill). A
    # dead-lane dispatch is a blocking op, so the gateway's ring-order
    # failover retries it on a survivor transparently — the check is
    # that EVERY score completes identical to control anyway.
    score_results: dict = {}

    def drive_scores():
        rng = random.Random(7)
        for r in score_requests:
            time.sleep(rng.expovariate(12.0))
            rid = r["request_id"]
            try:
                out = gw.route_score(dict(r))
                score_results[rid] = {"ok": True,
                                      "logprobs": out["logprobs"],
                                      "node": out.get("node_id")}
            except Exception as exc:  # recorded, asserted below
                score_results[rid] = {"ok": False, "error": str(exc)}

    def kill_victim():
        victim_proc.send_signal(signal.SIGKILL)
        victim_proc.wait(timeout=10)

    score_thread = threading.Thread(target=drive_scores, daemon=True)
    score_thread.start()
    results, killed = drive_streams_with_kill(
        gw, gen_requests, victim_rids, kill_victim, random.Random(0),
        arrival_rate=24.0)
    score_thread.join(timeout=600)
    checks.append(("unified: victim killed mid-stream", killed))

    # Generative class: every stream completed byte-identical to the
    # unkilled control via the PR 6 resume ladder.
    complete, identical, resumed = tally_streams(results, gen_control)
    checks.append(("unified: all generative streams completed "
                   f"({complete}/{len(gen_requests)})",
                   complete == len(gen_requests)))
    checks.append(("unified: generative streams byte-identical "
                   f"({identical}/{len(gen_requests)})",
                   identical == len(gen_requests)))
    checks.append(("unified: at least one stream resumed", resumed >= 1))

    # Score class: every request completed with logprobs identical to
    # control — including the ones whose ring primary was the corpse.
    score_ok = sum(1 for rid, r in score_results.items()
                   if r.get("ok")
                   and r["logprobs"] == score_control[rid])
    checks.append(("unified: all scores completed byte-identical "
                   f"({score_ok}/{len(score_requests)})",
                   score_ok == len(score_requests)))

    # The retryable contract, demonstrated end-to-end: a DIRECT call to
    # the dead lane fails with a connection error (what an in-flight
    # request experiences), and the SAME request through the gateway
    # completes on a survivor, identical to control.
    retry_req = {"request_id": "us_retry", "prompt_tokens": [2, 4, 6],
                 "completion_tokens": [8, 10]}
    status, ctl = _call(ports[0], "POST", "/score",
                        dict(retry_req, request_id="ctl_us_retry"),
                        timeout=600)
    direct_failed = False
    try:
        _call(ports[1], "POST", "/score", dict(retry_req), timeout=5)
    except OSError:
        direct_failed = True
    checks.append(("unified: direct score to dead lane fails retryable",
                   direct_failed))
    try:
        rerouted = gw.route_score(dict(retry_req))
        checks.append(("unified: retried score completes on a survivor",
                       rerouted["logprobs"] == ctl["logprobs"]
                       and rerouted.get("node_id") != "w1"))
    except Exception:
        checks.append(("unified: retried score completes on a survivor",
                       False))

    # Counters == spans: failover counters match resume spans (settle —
    # the counter bumps before its span lands), and the gateway holds
    # exactly one route span per score request (+ the retry demo).
    fo, resume_spans = {}, []
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        fo = gw.get_stats().get("failover", {})
        spans = gw.tracer.snapshot()
        resume_spans = [s for s in spans if s["op"] == "resume"]
        if len(resume_spans) == fo.get("resumes_attempted", -1):
            break
        time.sleep(0.1)
    checks.append(("unified: failover counters == resume spans",
                   len(resume_spans) == fo.get("resumes_attempted", -1)
                   and fo.get("resumes_attempted", 0) >= 1))
    score_route_spans = [s for s in gw.tracer.snapshot()
                         if s["op"] == "route"
                         and s["request_id"].startswith("us")]
    checks.append(("unified: one route span per score request",
                   len(score_route_spans) == len(score_requests) + 1))

    # Zero leaks on the survivors: every KV block accounted for AND
    # every stateless row retired (admitted == completed + failed; a
    # leaked row would hold a slot and strand the admitted counter).
    for p in (ports[0], ports[2]):
        pool = _worker_pool_clean(p)
        checks.append((f"unified: no KV blocks leaked on survivor :{p}",
                       pool is not None))
        _, health = _call(p, "GET", "/health", timeout=5.0)
        st = (health.get("generator") or {}).get("stateless") or {}
        checks.append(
            (f"unified: stateless rows accounted for on :{p}",
             st.get("admitted", -1)
             == st.get("completed", 0) + st.get("failed", 0)
             and st.get("admitted", 0) > 0))
    gw.stop()
    return {"victim": victim_lane,
            "generate": {"complete": complete, "identical": identical,
                         "resumed": resumed},
            "score": {"ok_identical": score_ok,
                      "total": len(score_requests)},
            "failover": fo}


def run_unified_standalone() -> int:
    ports, procs = launch_worker_procs(3)
    checks: list = []
    try:
        report = {"mode": "unified-standalone", "worker_ports": ports,
                  "phases": {"unified": unified_phase(ports, procs,
                                                      checks)}}
        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks) and bool(checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def run_all_standalone() -> int:
    """--all: every standalone chaos scenario in sequence, each in its
    own interpreter (a wedged scenario cannot poison the next), one JSON
    summary on stdout, nonzero exit when ANY scenario's check fails."""
    flags = ("--mixed", "--spec", "--crash", "--offload", "--quant",
             "--migrate", "--disagg", "--recurrent", "--tp",
             "--overload", "--elastic", "--stitch", "--fleet-prefix",
             "--unified")
    here = os.path.abspath(__file__)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    summary: dict = {"mode": "all-standalone", "scenarios": {}}
    rc_all = 0
    for flag in flags:
        t0 = time.monotonic()
        verdict: dict = {}
        try:
            proc = subprocess.run([sys.executable, here, flag],
                                  capture_output=True, text=True,
                                  env=env, timeout=3600)
            verdict["rc"] = proc.returncode
            try:
                # The scenario's stdout is its JSON report; keep the
                # verdict + the failing check names, not the transcript.
                rep = json.loads(proc.stdout[proc.stdout.index("{"):])
                verdict["passed"] = bool(rep.get("passed"))
                verdict["failed_checks"] = [
                    k for k, v in (rep.get("checks") or {}).items()
                    if not v]
            except (ValueError, KeyError):
                verdict["passed"] = proc.returncode == 0
                verdict["stdout_tail"] = proc.stdout[-400:]
        except subprocess.TimeoutExpired:
            verdict = {"rc": None, "passed": False, "error": "timeout"}
        verdict["seconds"] = round(time.monotonic() - t0, 1)
        if not verdict["passed"]:
            rc_all = 1
        summary["scenarios"][flag.lstrip("-")] = verdict
        print(f"[all] {flag.lstrip('-')}: "
              f"{'ok' if verdict['passed'] else 'FAIL'} "
              f"({verdict['seconds']}s)", file=sys.stderr)
    summary["passed"] = rc_all == 0
    print(json.dumps(summary, indent=2))
    return rc_all


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--victim", default="worker_1")
    ap.add_argument("--requests-per-phase", type=int, default=60)
    ap.add_argument("--breaker-timeout", type=float, default=30.0,
                    help="server's breaker_timeout_s (phase 3 waits this long)")
    ap.add_argument("--slow-lane", action="store_true",
                    help="append phase 5: slow (not dead) lane; requires "
                         "the server started with --hedge")
    ap.add_argument("--slow-latency", type=float, default=1.0,
                    help="phase 5 injected per-request latency (seconds)")
    ap.add_argument("--deadline-ms", type=float, default=2000.0,
                    help="phase 5 per-request deadline budget")
    ap.add_argument("--launch", metavar="MODEL", default=None,
                    help="spawn the combined server myself (3 lanes, "
                         "breaker timeout from --breaker-timeout, hedging "
                         "on with --slow-lane) instead of targeting an "
                         "already-running one; the launch retries on the "
                         "free-port bind race")
    ap.add_argument("--mixed", action="store_true",
                    help="standalone mixed-stepping scenario: spawns its "
                         "own paged server and asserts cancelled "
                         "mid-prefill rows return their blocks (see "
                         "module docstring); ignores the other flags")
    ap.add_argument("--spec", action="store_true",
                    help="standalone speculative-decoding scenario: "
                         "spawns its own --spec-k server, deadline-"
                         "cancels rows mid-verification, and asserts "
                         "every pool block returns and post-cancel "
                         "streams are identical; ignores the other flags")
    ap.add_argument("--crash", action="store_true",
                    help="standalone crash-tolerant-streaming scenario: "
                         "spawns three worker processes, kill -9s one "
                         "mid-stream under Poisson load, and asserts "
                         "every stream completes byte-identical to an "
                         "unkilled control run with zero KV-block leaks "
                         "(see module docstring); ignores the other flags")
    ap.add_argument("--offload", action="store_true",
                    help="standalone host-tier offload scenario: spawns "
                         "three host-tiered worker processes, demotes a "
                         "shared prefix on the affinity lane, asserts a "
                         "re-hit SWAPS IN instead of recomputing, then "
                         "kill -9s that worker (holding demoted blocks) "
                         "mid-stream and asserts the failover resume "
                         "completes byte-identically with zero device or "
                         "host blocks leaked on the survivors; ignores "
                         "the other flags")
    ap.add_argument("--quant", action="store_true",
                    help="standalone quantized-KV scenario: spawns three "
                         "--kv-quantize int8 host-tiered workers, proves "
                         "the int8 demote/swap-in lifecycle live "
                         "(scale slots pair with host slots, swap_in "
                         "counters == spans), then kill -9s the lane "
                         "holding quantized and demoted-quantized blocks "
                         "mid-stream and asserts the PR 6 resume "
                         "completes byte-identically with zero device, "
                         "host, or scale-slot leaks on the survivors; "
                         "ignores the other flags")
    ap.add_argument("--migrate", action="store_true",
                    help="standalone live-stream-migration scenario: "
                         "spawns four host-tiered worker processes, "
                         "drains a lane MID-STREAM under Poisson load "
                         "with --migrate-streams semantics (every "
                         "stream completes byte-identical with zero "
                         "replay traffic and zero block leaks — the "
                         "drained lane's pool included), then kill -9s "
                         "the continuation's DESTINATION and asserts "
                         "the replay fallback still completes the "
                         "stream, plus an in-process QUANTIZED drain "
                         "(int8+scale chains verbatim, zero scale-slot "
                         "leaks); counters == migration spans "
                         "throughout; ignores the other flags")
    ap.add_argument("--disagg", action="store_true",
                    help="standalone disaggregated-serving scenario: "
                         "spawns 2 prefill + 2 decode worker processes "
                         "behind a role-aware gateway, proves the "
                         "steady-state KV chain handoff live (spliced, "
                         "zero fallbacks, byte-identical, zero leaks, "
                         "counters == kv_handoff spans), then kill -9s "
                         "a prefill lane mid-handoff and a decode lane "
                         "mid-adopt — both land on the replay fallback "
                         "byte-identically; ignores the other flags")
    ap.add_argument("--recurrent", action="store_true",
                    help="standalone recurrent-family (state_slab) "
                         "scenario: spawns three SSD-model worker "
                         "processes (fixed-size state rows, no KV "
                         "blocks), kill -9s one mid-stream under "
                         "Poisson load, and asserts every stream "
                         "completes byte-identical to an unkilled "
                         "control via the replay resume (the "
                         "recurrence makes prompt ⧺ emitted re-prefill "
                         "exact) with zero state-slab rows leaked on "
                         "the survivors and failover counters == "
                         "resume spans; ignores the other flags")
    ap.add_argument("--tp", action="store_true",
                    help="standalone tensor-parallel scenario: spawns a "
                         "tp=2 worker (sharded model + H_kv-sharded KV "
                         "pool over a 2-device mesh) beside a tp=1 "
                         "worker, kill -9s the TP lane mid-stream under "
                         "Poisson load, and asserts every stream "
                         "completes byte-identical to an unkilled tp=1 "
                         "control via the replay resume (cross-shard-"
                         "geometry identity), the /health topology "
                         "label re-weights the gateway ring, failover "
                         "counters == resume spans, and zero KV blocks "
                         "leak on the survivor; ignores the other flags")
    ap.add_argument("--overload", action="store_true",
                    help="standalone overload-control scenario: spawns a "
                         "3-lane combined server with every overload "
                         "knob on, drives mixed-priority Poisson load "
                         "past saturation, and asserts low-tier "
                         "requests shed first, interactive p99 stays "
                         "under its deadline, the brownout ladder "
                         "engages and clears in order, counters == "
                         "marker spans, and zero KV blocks leak; "
                         "ignores the other flags")
    ap.add_argument("--elastic", action="store_true",
                    help="standalone elastic-fleet scenario: spawns 2 "
                         "member + 2 standby worker processes behind an "
                         "--autoscale gateway and runs a diurnal ramp — "
                         "the fleet must double under load (probe-gated "
                         "standby registration) and halve back at low "
                         "pressure through the drain+migrate ladder with "
                         "every stream (greedy AND seeded) completing "
                         "byte-identical to control and zero block "
                         "leaks; then a dead-address spawn and a kill -9 "
                         "mid-drain must land in the NAMED spawn-wedged "
                         "/ drain-wedged degraded states with the fleet "
                         "still serving; fleet counters == fleet spans "
                         "throughout; ignores the other flags")
    ap.add_argument("--stitch", action="store_true",
                    help="standalone cross-lane trace-stitching "
                         "scenario: spawns 1 prefill + 3 decode workers "
                         "with --trace-stitch and the flight recorder "
                         "armed (plus one defaults-off control worker), "
                         "drives ONE stream through handoff + "
                         "drain-migration + kill -9 resume, and asserts "
                         "the stream lands byte-identical to the "
                         "unmoved control, /admin/trace/<rid> returns "
                         "ONE merged tree covering every reachable "
                         "lane with zero orphaned spans, mobility "
                         "counters == hop markers, the kill leaves a "
                         "flight-recorder postmortem naming the "
                         "anomaly, and the defaults-off worker's wire "
                         "surfaces carry no new keys; ignores the "
                         "other flags")
    ap.add_argument("--fleet-prefix", action="store_true",
                    help="standalone fleet-prefix-tier scenario: spawns "
                         "3 --prefix-fetch workers behind a "
                         "--prefix-directory gateway, proves a hinted "
                         "stream splices a shared prefix from its owner "
                         "over HTTP (remote prefill skipped, "
                         "byte-identical), then walks the fallback "
                         "ladder under faults — a DRAINED owner refuses "
                         "the export by name and a kill -9ed owner "
                         "leaves the fetch dialing a corpse, with every "
                         "fallback stream recomputed locally and "
                         "byte-identical to control, the prober eject "
                         "invalidating the dead lane's directory "
                         "entries, directory counters == prefix_dir "
                         "spans, and zero KV blocks leaked on the "
                         "survivors; ignores the other flags")
    ap.add_argument("--unified", action="store_true",
                    help="standalone unified-stateless chaos scenario "
                         "(PR 20): spawns 3 paged workers serving MIXED "
                         "generate+score traffic from ONE continuous "
                         "pool, kill -9s a lane under Poisson load, and "
                         "asserts the generative streams resume "
                         "byte-identical (PR 6 ladder), in-flight score "
                         "requests fail retryable and complete "
                         "byte-identical on a surviving lane, zero KV "
                         "blocks leak, every stateless row is accounted "
                         "for, and failover counters == resume spans; "
                         "ignores the other flags")
    ap.add_argument("--all", action="store_true",
                    help="run EVERY standalone chaos scenario in "
                         "sequence, each in its own interpreter, and "
                         "print one JSON summary; exit nonzero when any "
                         "scenario's check fails; ignores the other "
                         "flags")
    args = ap.parse_args()
    if args.all:
        return run_all_standalone()
    if args.unified:
        return run_unified_standalone()
    if args.elastic:
        return run_elastic_standalone()
    if args.stitch:
        return run_stitch_standalone()
    if args.fleet_prefix:
        return run_fleet_prefix_standalone()
    if args.tp:
        return run_tp_standalone()
    if args.disagg:
        return run_disagg_standalone()
    if args.migrate:
        return run_migrate_standalone()
    if args.quant:
        return run_quant_standalone()
    if args.overload:
        return run_overload_standalone()
    if args.mixed:
        return run_mixed_standalone()
    if args.spec:
        return run_spec_standalone()
    if args.crash:
        return run_crash_standalone()
    if args.recurrent:
        return run_recurrent_standalone()
    if args.offload:
        return run_offload_standalone()
    proc = None
    if args.launch:
        args.breaker_timeout = min(args.breaker_timeout, 2.0)
        port, proc = launch_combined(model=args.launch,
                                     breaker_timeout=args.breaker_timeout,
                                     hedge=args.slow_lane)
        args.port = port
    try:
        port, n = args.port, args.requests_per_phase
        checks = []

        # Phase 0: routing pre-pass — collect ids per lane, pick the victim.
        pools = route_map(port, max(4 * n, 100))
        victim = (args.victim
                  if len(pools.get(args.victim, [])) >= 5
                  else max(pools, key=lambda k: len(pools[k])))
        victim_ids = pools[victim]
        all_ids = [rid for p in pools.values() for rid in p]
        report = {"victim": victim,
                  "routing": {k: len(v) for k, v in pools.items()},
                  "phases": {}}
        checks.append(("victim owns enough keys to trip the breaker",
                       len(victim_ids) >= 5))

        # Phase 1: healthy baseline over every lane's keys. The pre-pass
        # populated the LRU caches; reuse of the same ids exercises hits too.
        ok, fail, nodes = load(port, all_ids[:n], "base")
        state, _ = breaker_state(port, victim)
        report["phases"]["baseline"] = {"ok": ok, "fail": fail, "nodes": nodes,
                                        "breaker": state}
        checks.append(("baseline 100% success", fail == 0))

        # Phase 2: inject fault; drive ids that route PRIMARY to the victim so
        # its breaker sees consecutive failures while failover answers them.
        _call(port, "POST", "/admin/fault", {"node": victim, "action": "fail"})
        ok, fail, nodes = load(port, victim_ids[:n], "fault")
        state, failovers = breaker_state(port, victim)
        report["phases"]["faulted"] = {"ok": ok, "fail": fail, "nodes": nodes,
                                       "breaker": state, "failovers": failovers}
        checks.append(("failover keeps success at 100%", fail == 0))
        checks.append(("victim took no faulted traffic", victim not in nodes))
        checks.append(("breaker OPEN after consecutive failures", state == "OPEN"))
        checks.append(("failovers counted", failovers > 0))

        # Phase 3: heal, wait out the breaker timeout, probe traffic re-closes it.
        _call(port, "POST", "/admin/fault", {"node": victim, "action": "heal"})
        time.sleep(args.breaker_timeout + 0.5)
        ok, fail, nodes = load(port, victim_ids[:n], "heal")
        state, _ = breaker_state(port, victim)
        report["phases"]["healed"] = {"ok": ok, "fail": fail, "nodes": nodes,
                                      "breaker": state}
        checks.append(("breaker CLOSED after recovery", state == "CLOSED"))
        checks.append(("victim serving again", nodes.get(victim, 0) > 0))

        # Phase 4: steady state across all lanes.
        ok, fail, nodes = load(port, all_ids[:n], "final")
        report["phases"]["final"] = {"ok": ok, "fail": fail, "nodes": nodes}
        checks.append(("final 100% success", fail == 0))

        # Phase 5 (--slow-lane): slow-not-dead lane under deadline load.
        if args.slow_lane:
            report["phases"]["slow_lane"] = slow_lane_phase(
                port, victim, victim_ids, n, checks,
                latency_s=args.slow_latency, deadline_ms=args.deadline_ms)

        # Final: the tracing layer must explain every resilience decision the
        # counters recorded (shed / retry / hedge fire & win — PR 1's failure
        # paths, now provably span-covered).
        report["trace_coverage"] = trace_coverage(port, checks)

        report["checks"] = {name: passed for name, passed in checks}
        report["passed"] = all(p for _, p in checks)
        print(json.dumps(report, indent=2))
        return 0 if report["passed"] else 1
    finally:
        if proc is not None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


if __name__ == "__main__":
    sys.exit(main())
