#!/usr/bin/env python3
"""6-step live-system smoke test — ops parity with the reference's
diagnostics.sh (/root/reference/diagnostics.sh): process check (:9-24),
port check (:27-36), worker /health (:39-56), gateway /stats (:59-68),
direct worker /infer (:71-89), end-to-end gateway /infer (:92-109) — each
with a ✓/✗ verdict and a non-zero exit code when any step fails.

Usage:
  python3 diagnostics.py [--gateway http://localhost:8000]
                         [--workers localhost:8001 localhost:8002 ...]
In combined single-process mode (`serve`), pass only --gateway: worker
health is proxied at /health and there are no separate worker ports.
"""

from __future__ import annotations

import argparse
import http.client
import json
import socket
import subprocess
import sys
import time

OK, FAIL = "✓", "✗"
_results = []
_TOTAL = 6  # --kernel-parity appends step 7, --mixed-parity step 8,
#             --spec-parity step 9, --quant-parity step 10,
#             --ssd-parity step 11, --tp-parity step 12, --failover
#             step 13, --migrate step 14, --disagg step 15,
#             --overload step 16, --elastic step 17, --stitch step 18,
#             --lint step 19


def step(n: int, title: str, ok: bool, detail: str = "") -> None:
    mark = OK if ok else FAIL
    print(f"[{n}/{_TOTAL}] {title}: {mark} {detail}".rstrip())
    _results.append(ok)


def _get(hostport: str, path: str, timeout=5.0):
    host, port = hostport.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def _post(hostport: str, path: str, body: dict, timeout=30.0):
    host, port = hostport.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("POST", path, body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def _strip(url: str, default_port: int = 8000) -> str:
    u = url.split("://", 1)[-1].split("/", 1)[0]
    return u if ":" in u else f"{u}:{default_port}"


def main() -> int:
    global _TOTAL
    ap = argparse.ArgumentParser()
    ap.add_argument("--gateway", default="http://localhost:8000")
    ap.add_argument("--workers", nargs="*", default=[])
    ap.add_argument("--kernel-parity", action="store_true",
                    help="step 7: paged-attention kernel vs XLA reference "
                         "parity on this host's backend (in-process, no "
                         "server; compiles a small kernel — seconds on "
                         "CPU, validates Mosaic on a TPU host)")
    ap.add_argument("--mixed-parity", action="store_true",
                    help="step 8: RAGGED paged-attention kernel (the "
                         "ragged tick's read path) vs the XLA gather "
                         "reference at mixed q_lens {1, 7, 16, 17} — "
                         "decode rows and prefill chunks in one batch")
    ap.add_argument("--spec-parity", action="store_true",
                    help="step 9: ragged kernel at the SPECULATIVE "
                         "verify-window shapes (--spec-k serving): "
                         "undrafted decode rows, k+1 verify windows, "
                         "and block-boundary prefill chunks in one "
                         "batch vs the XLA gather reference")
    ap.add_argument("--quant-parity", action="store_true",
                    help="step 10: QUANTIZED (int8 block pool) paged-"
                         "attention kernels vs their dequantizing XLA "
                         "gather references — the fused-dequant decode "
                         "and ragged read paths behind --kv-quantize "
                         "(the on-chip gate before serving int8 KV)")
    ap.add_argument("--ssd-parity", action="store_true",
                    help="step 11: State Space Duality parity — the "
                         "SSD/Mamba chunked matmul-form prefill scan vs "
                         "the O(1) decode recurrence (ops.ssd, the "
                         "state_slab model family behind e.g. mamba2): "
                         "max|Δ| over outputs AND final state must stay "
                         "bounded, the gate before serving the "
                         "matmul-form prefill on a device")
    ap.add_argument("--tp-parity", action="store_true",
                    help="step 12: tensor-parallel serving parity — a "
                         "tp=2 continuous scheduler (sharded params + "
                         "H_kv-sharded KV pool on this host's mesh) vs "
                         "the single-device arm: greedy streams must "
                         "be byte-identical and every mixed tick one "
                         "dispatch (in-process, no server; the gate "
                         "before serving --tp on a device)")
    ap.add_argument("--failover", action="store_true",
                    help="step 13: one scripted kill/resume against a "
                         "local worker pair (spawned here): kill -9 the "
                         "stream's lane mid-generation and print the "
                         "spliced-vs-control diff — the crash-tolerant "
                         "streaming smoke without the full "
                         "fault_injection --crash chaos run")
    ap.add_argument("--migrate", action="store_true",
                    help="step 14: one scripted migrate-mode drain "
                         "against a local worker pair (spawned here): "
                         "drain the stream's lane mid-generation with "
                         "--migrate-streams semantics and print the "
                         "spliced-vs-control diff plus the migration "
                         "counters — the KV-handoff smoke without the "
                         "full fault_injection --migrate chaos run")
    ap.add_argument("--disagg", action="store_true",
                    help="step 15: one scripted prefill→decode handoff "
                         "against a local 1-prefill + 1-decode worker "
                         "pair (spawned here) behind a --disagg "
                         "gateway: stream routes to the prefill lane, "
                         "ships its KV chain, splices on the decode "
                         "lane — prints the spliced-vs-control diff "
                         "plus the handoff counters, the disagg smoke "
                         "without the full fault_injection --disagg "
                         "chaos run")
    ap.add_argument("--overload", action="store_true",
                    help="step 16: overload-control state of the live "
                         "system — the gateway's /stats overload block "
                         "(in-flight gauge, tier/rate-limit sheds, "
                         "pressure) and every lane's current brownout "
                         "ladder stage from /health")
    ap.add_argument("--elastic", action="store_true",
                    help="step 17: elastic-fleet state of the live "
                         "system — the gateway's /admin/fleet status "
                         "(membership, named degraded states like "
                         "spawn-wedged/drain-wedged, controller "
                         "engagement, last observed fleet pressure) "
                         "and the decision counters")
    ap.add_argument("--stitch", action="store_true",
                    help="step 18: one scripted cross-lane stitched "
                         "trace against a local worker pair (spawned "
                         "here) with --trace-stitch armed: drain-migrate "
                         "a live stream to the other lane, then render "
                         "the merged /admin/trace/<request_id> tree — "
                         "lanes touched, span count, hop markers, and "
                         "the orphan count (must be zero)")
    ap.add_argument("--fleet-prefix", action="store_true",
                    help="step 19: one scripted fleet-prefix fetch "
                         "against a local worker pair (spawned here) "
                         "with --prefix-fetch armed: establish one lane "
                         "as the owner of a shared 48-token prefix, "
                         "then a hinted request on the OTHER lane must "
                         "pull the owner's KV chain over HTTP and "
                         "splice it — blocks spliced, remote prefill "
                         "tokens skipped, hint bookkeeping, and "
                         "byte-identity to an unhinted control")
    ap.add_argument("--unified", action="store_true",
                    help="step 20: one scripted unified-pool mixed tick "
                         "(in-process, no server): a decode stream and "
                         "concurrent /score requests share ONE "
                         "continuous scheduler — renders the mixed-row "
                         "tick live (decode rows beside single-tick "
                         "score rows in the same scheduler) and checks "
                         "the scores answer byte-identical to a solo "
                         "control with ticks == dispatches on the "
                         "stateless counter block")
    ap.add_argument("--lint", action="store_true",
                    help="step 21: engine-lint static-analysis suite "
                         "over tpu_engine/ (in-process, no server): lock "
                         "discipline, hot-path trace leaks, "
                         "counters==spans pairing, flag discipline — "
                         "prints the per-rule finding summary")
    args = ap.parse_args()
    _TOTAL = (6 + int(args.kernel_parity) + int(args.mixed_parity)
              + int(args.spec_parity) + int(args.quant_parity)
              + int(args.ssd_parity) + int(args.tp_parity)
              + int(args.failover) + int(args.migrate)
              + int(args.disagg) + int(args.overload)
              + int(args.elastic) + int(args.stitch)
              + int(args.fleet_prefix) + int(args.unified)
              + int(args.lint))
    gw = _strip(args.gateway)
    # Accept both bare host:port (reference diagnostics.sh style) and full
    # http:// URLs — same normalization as the gateway address.
    workers = [_strip(w, default_port=8080) for w in args.workers]
    combined = not workers

    # 1. process check (reference :9-24)
    try:
        out = subprocess.run(
            ["pgrep", "-af", "serving.cli|worker_node|gateway"],
            capture_output=True, text=True).stdout.strip()
        n_proc = len([ln for ln in out.splitlines() if "pgrep" not in ln])
        step(1, "serving processes", n_proc > 0, f"({n_proc} found)")
    except FileNotFoundError:
        step(1, "serving processes", True, "(pgrep unavailable, skipped)")

    # 2. port check (reference :27-36)
    ports_ok = True
    for hp in [gw] + workers:
        host, port = hp.rsplit(":", 1)
        s = socket.socket()
        s.settimeout(2)
        try:
            s.connect((host, int(port)))
        except OSError:
            ports_ok = False
        finally:
            s.close()
    step(2, "ports listening", ports_ok, f"({gw}{' + ' + str(len(workers)) + ' workers' if workers else ''})")

    # 3. worker /health (reference :39-56)
    ok, details = True, []
    targets = workers or [gw]
    for hp in targets:
        try:
            status, body = _get(hp, "/health")
            healthy = status == 200 and body.get("healthy") is True
            ok = ok and healthy
            details.append(f"{body.get('node_id', hp)}:{'up' if healthy else 'DOWN'}")
        except OSError as exc:
            ok = False
            details.append(f"{hp}:{exc}")
    step(3, "worker health", ok, "(" + ", ".join(details) + ")")

    # 4. gateway /stats (reference :59-68)
    try:
        status, body = _get(gw, "/stats")
        n = body.get("total_workers", 0)
        step(4, "gateway stats", status == 200 and n > 0, f"({n} workers)")
    except OSError as exc:
        step(4, "gateway stats", False, f"({exc})")

    # 5. direct worker inference, bypassing the gateway (reference :71-89)
    payload = {"request_id": "diag_direct", "input_data": [1.0, 2.0, 3.0]}
    if combined:
        step(5, "direct worker infer", True, "(combined mode: no direct port, skipped)")
    else:
        try:
            status, body = _post(workers[0], "/infer", payload)
            step(5, "direct worker infer", status == 200 and "output_data" in body,
                 f"({len(body.get('output_data', []))} outputs from {body.get('node_id')})")
        except OSError as exc:
            step(5, "direct worker infer", False, f"({exc})")

    # 6. end-to-end through the gateway (reference :92-109)
    try:
        status, body = _post(gw, "/infer",
                             {"request_id": "diag_e2e", "input_data": [4.0, 5.0, 6.0]})
        step(6, "gateway end-to-end infer", status == 200 and "output_data" in body,
             f"(node {body.get('node_id')}, {body.get('inference_time_us')} us)")
    except OSError as exc:
        step(6, "gateway end-to-end infer", False, f"({exc})")

    # 7 (--kernel-parity): paged-attention Pallas kernel vs XLA reference
    # — a decode-only tick's read (every row one token, heads packed)
    # behind --kv-block-size serving; run on a TPU
    # host this validates the Mosaic compile, elsewhere the interpreter.
    if args.kernel_parity:
        try:
            import jax.numpy as jnp

            from tpu_engine.ops.paged_attention import ragged_parity_check

            diff = max(ragged_parity_check(q_lens=(1, 1)),
                       ragged_parity_check(q_lens=(1, 1), n_heads=8,
                                           n_kv_heads=2, d_head=16))
            bf16 = ragged_parity_check(q_lens=(1, 1), dtype=jnp.bfloat16)
            step(7, "paged-attention kernel parity",
                 diff < 2e-5 and bf16 < 2e-2,
                 f"(max|Δ| f32 {diff:.2e}, bf16 {bf16:.2e})")
        except Exception as exc:
            step(7, "paged-attention kernel parity", False, f"({exc})")

    # 8 (--mixed-parity): the ragged kernel behind --kv-block-size serving —
    # one batch mixing decode rows (q_len 1) and prefill chunks (q_len up
    # to block_size+1, crossing a block boundary) against the XLA gather
    # reference. On a TPU host this validates the Mosaic compile.
    if args.mixed_parity:
        n = 6 + int(args.kernel_parity) + 1
        try:
            import jax.numpy as jnp

            from tpu_engine.ops.paged_attention import ragged_parity_check

            diff = max(ragged_parity_check(q_lens=(1, 7, 16, 17)),
                       ragged_parity_check(q_lens=(1, 3, 8, 9),
                                           n_heads=8, n_kv_heads=2,
                                           d_head=16, block_size=8,
                                           table_len=8))
            bf16 = ragged_parity_check(q_lens=(1, 7, 16, 17),
                                       dtype=jnp.bfloat16)
            step(n, "ragged mixed-step kernel parity",
                 diff < 2e-5 and bf16 < 2e-2,
                 f"(max|Δ| f32 {diff:.2e}, bf16 {bf16:.2e})")
        except Exception as exc:
            step(n, "ragged mixed-step kernel parity", False, f"({exc})")

    # 9 (--spec-parity): the ragged kernel at the verify-window shapes
    # the --spec-k scheduler dispatches — greedy identity depends on the
    # verify window's logits matching the plain path's bit-for-bit, so
    # kernel-vs-reference parity here is the on-chip gate before
    # enabling continuous speculation on a device.
    if args.spec_parity:
        n = 6 + int(args.kernel_parity) + int(args.mixed_parity) + 1
        try:
            import jax.numpy as jnp

            from tpu_engine.ops.paged_attention import (
                spec_verify_parity_check,
            )

            diff = max(spec_verify_parity_check(k=4),
                       spec_verify_parity_check(k=3, n_heads=8,
                                                n_kv_heads=2, d_head=16,
                                                block_size=8,
                                                table_len=8))
            bf16 = spec_verify_parity_check(k=4, dtype=jnp.bfloat16)
            step(n, "speculative verify-window kernel parity",
                 diff < 2e-5 and bf16 < 2e-2,
                 f"(max|Δ| f32 {diff:.2e}, bf16 {bf16:.2e})")
        except Exception as exc:
            step(n, "speculative verify-window kernel parity", False,
                 f"({exc})")

    # 10 (--quant-parity): the QUANTIZED read paths behind --kv-quantize
    # int8 — the fused-dequant Pallas kernels (decode + ragged) against
    # the dequantizing XLA gather references. The one-time-write
    # exactness story holds only if the kernel's in-VMEM dequant matches
    # the reference's gathered dequant, so this is the on-chip gate
    # before enabling int8 KV on a device.
    if args.quant_parity:
        n = (6 + int(args.kernel_parity) + int(args.mixed_parity)
             + int(args.spec_parity) + 1)
        try:
            from tpu_engine.ops.paged_attention import (
                quant_ragged_parity_check,
            )

            decode = max(quant_ragged_parity_check(q_lens=(1, 1)),
                         quant_ragged_parity_check(
                             q_lens=(1, 1), n_heads=8, n_kv_heads=2,
                             d_head=64, table_len=8))
            ragged = quant_ragged_parity_check(q_lens=(1, 7, 16, 17))
            step(n, "quantized (int8) kernel parity",
                 decode < 2e-4 and ragged < 2e-4,
                 f"(max|Δ| decode {decode:.2e}, ragged {ragged:.2e})")
        except Exception as exc:
            step(n, "quantized (int8) kernel parity", False, f"({exc})")

    # 11 (--ssd-parity): State Space Duality — the SSD/Mamba family's
    # chunked matmul-form prefill scan against the O(1) decode
    # recurrence (the two dual forms of the same selective-SSM layer;
    # ops.ssd). The serving path keeps the recurrence for byte-identity,
    # so this parity is the gate before the matmul form serves prefill
    # on a device.
    if args.ssd_parity:
        n = (6 + int(args.kernel_parity) + int(args.mixed_parity)
             + int(args.spec_parity) + int(args.quant_parity) + 1)
        try:
            from tpu_engine.ops.ssd import ssd_parity_check

            small = ssd_parity_check()
            wide = ssd_parity_check(batch=1, seq=65, heads=8, head_dim=16,
                                    d_state=16, chunk=16, seed=3)
            worst_y = max(small["max_abs_diff_y"], wide["max_abs_diff_y"])
            worst_s = max(small["max_abs_diff_state"],
                          wide["max_abs_diff_state"])
            step(n, "SSD duality parity (matmul form vs recurrence)",
                 small["ok"] and wide["ok"],
                 f"(max|Δ| y {worst_y:.2e}, state {worst_s:.2e})")
        except Exception as exc:
            step(n, "SSD duality parity (matmul form vs recurrence)",
                 False, f"({exc})")

    # 12 (--tp-parity): tensor-parallel serving — a tp=2 continuous
    # scheduler (registry-declared param placement, H_kv-sharded pool)
    # against the single-device arm, in-process. Greedy streams must be
    # byte-identical and mixed ticks == dispatches; on a multi-chip
    # host this validates the SPMD compile a lane needs before
    # serving --tp.
    if args.tp_parity:
        n = (6 + int(args.kernel_parity) + int(args.mixed_parity)
             + int(args.spec_parity) + int(args.quant_parity)
             + int(args.ssd_parity) + 1)
        try:
            import os as _os

            if "jax" not in sys.modules and not _os.environ.get(
                    "XLA_FLAGS", ""):
                # CPU hosts: provision a 2-device virtual mesh while we
                # still can (before jax initializes). TPU hosts ignore
                # the flag; a live multi-chip backend uses real chips.
                _os.environ["XLA_FLAGS"] = (
                    "--xla_force_host_platform_device_count=2")
            import jax as _jax

            from tpu_engine.models.registry import (
                _ensure_builtin_models_imported,
                create_model,
            )
            from tpu_engine.runtime.scheduler import ContinuousGenerator

            _ensure_builtin_models_imported()
            if len(_jax.devices()) < 2:
                step(n, "tensor-parallel serving parity (tp=2 vs 1)",
                     True, "(single visible device: skipped — set "
                           "XLA_FLAGS=--xla_force_host_platform_device_"
                           "count=2 on CPU hosts)")
            else:
                tp_spec = create_model("gpt2-small-test", max_seq=64)
                tp_params = tp_spec.init(_jax.random.PRNGKey(0))
                tp_prompts = [[5, 9, 3, 17], [2, 4, 6, 8, 10, 12],
                              [1] * 20]

                def _tp_run(tp):
                    gen = ContinuousGenerator(
                        tp_spec, params=tp_params, dtype="float32",
                        n_slots=4, kv_block_size=16, prefill_chunk=16,
                        mixed_token_budget=32, tp=tp)
                    try:
                        out = gen.generate(tp_prompts, max_new_tokens=10)
                        return out, gen.stats()
                    finally:
                        gen.stop()

                ref, _ = _tp_run(1)
                sharded, st = _tp_run(2)
                m = st["mixed"]
                ok = (sharded == ref and m["ticks"] == m["dispatches"]
                      and st.get("tp", {}).get("tp") == 2)
                step(n, "tensor-parallel serving parity (tp=2 vs 1)",
                     ok,
                     f"(streams "
                     f"{'identical' if sharded == ref else 'DIVERGED'}"
                     f", ticks={m['ticks']} "
                     f"dispatches={m['dispatches']})")
        except Exception as exc:
            step(n, "tensor-parallel serving parity (tp=2 vs 1)", False,
                 f"({exc})")

    # 13 (--failover): one scripted kill/resume against a local worker
    # pair — the journal splice, live, in one line: spawn two standalone
    # workers, stream through a failover-enabled gateway, kill -9 the
    # serving lane mid-stream, and diff the spliced stream against an
    # unkilled blocking control.
    if args.failover:
        n = (6 + int(args.kernel_parity) + int(args.mixed_parity)
             + int(args.spec_parity) + int(args.quant_parity)
             + int(args.ssd_parity) + int(args.tp_parity) + 1)
        procs = []
        try:
            import signal
            import threading

            from tools.fault_injection import (
                _call,
                launch_worker_procs,
                rid_for_lane,
            )
            from tpu_engine.serving.gateway import Gateway, _parse_sse
            from tpu_engine.utils.config import GatewayConfig

            ports, procs = launch_worker_procs(2)
            gw = Gateway([f"127.0.0.1:{p}" for p in ports],
                         GatewayConfig(failover_streams=True))
            victim_lane = next(l for l in gw.worker_names()
                               if str(ports[0]) in l)
            rid = rid_for_lane(gw._ring, victim_lane, "fo")
            req = {"request_id": rid, "prompt_tokens": [5, 9, 3, 17],
                   "max_new_tokens": 24, "temperature": 0.9, "seed": 7}
            _, ctl = _call(ports[1], "POST", "/generate",
                           dict(req, request_id="ctl"), timeout=600)
            control = ctl["tokens"]
            toks, final = [], {}

            def consume():
                for frame in gw.route_generate_stream(dict(req)):
                    evt = _parse_sse(frame)
                    if evt and evt.get("done"):
                        final.update(evt)
                        break
                    if evt and "tokens" in evt:
                        toks.extend(evt["tokens"])

            t = threading.Thread(target=consume, daemon=True)
            t.start()
            import time as _time

            deadline = _time.monotonic() + 120
            while _time.monotonic() < deadline and len(toks) < 2:
                _time.sleep(0.02)
            procs[0].send_signal(signal.SIGKILL)
            procs[0].wait(timeout=10)
            t.join(timeout=300)
            gw.stop()
            spliced = final.get("tokens")
            if spliced == control and toks == control:
                detail = (f"(identical: {len(control)} tokens, "
                          f"resumed={final.get('resumed', 0)}, "
                          f"replayed="
                          f"{gw.failover.get('tokens_replayed')})")
                ok = True
            else:
                div = next((i for i, (a, b) in enumerate(
                    zip(spliced or [], control))
                    if a != b), min(len(spliced or []), len(control)))
                detail = (f"(DIVERGED at token {div}: "
                          f"spliced={spliced} control={control})")
                ok = False
            step(n, "stream kill/resume splice vs control", ok, detail)
        except Exception as exc:
            step(n, "stream kill/resume splice vs control", False,
                 f"({exc})")
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()

    # (--migrate): one scripted migrate-mode drain against a local
    # worker pair — the KV block handoff, live, in one line: stream
    # through a migrate-enabled gateway, remove the serving lane with
    # drain=True, and diff the spliced stream against an unkilled
    # blocking control (zero re-prefilled tokens expected).
    if args.migrate:
        n = (6 + int(args.kernel_parity) + int(args.mixed_parity)
             + int(args.spec_parity) + int(args.quant_parity)
             + int(args.ssd_parity) + int(args.tp_parity)
             + int(args.failover) + 1)
        procs = []
        try:
            import threading

            from tools.fault_injection import (
                _call,
                launch_worker_procs,
                rid_for_lane,
            )
            from tpu_engine.serving.gateway import Gateway, _parse_sse
            from tpu_engine.utils.config import GatewayConfig

            ports, procs = launch_worker_procs(2)
            gw = Gateway([f"127.0.0.1:{p}" for p in ports],
                         GatewayConfig(failover_streams=True,
                                       migrate_streams=True,
                                       migrate_timeout_s=60.0))
            victim_lane = next(l for l in gw.worker_names()
                               if str(ports[0]) in l)
            rid = rid_for_lane(gw._ring, victim_lane, "mg")
            req = {"request_id": rid, "prompt_tokens": [5, 9, 3, 17],
                   "max_new_tokens": 24, "temperature": 0.9, "seed": 7}
            _, ctl = _call(ports[1], "POST", "/generate",
                           dict(req, request_id="ctl"), timeout=600)
            control = ctl["tokens"]
            toks, final = [], {}

            def consume():
                for frame in gw.route_generate_stream(dict(req)):
                    evt = _parse_sse(frame)
                    if evt and evt.get("done"):
                        final.update(evt)
                        break
                    if evt and "tokens" in evt:
                        toks.extend(evt["tokens"])

            t = threading.Thread(target=consume, daemon=True)
            t.start()
            import time as _time

            deadline = _time.monotonic() + 120
            while _time.monotonic() < deadline and len(toks) < 2:
                _time.sleep(0.02)
            gw.remove_worker(victim_lane, drain=True)
            t.join(timeout=300)
            mig = gw.get_stats().get("migration", {})
            gw.stop()
            spliced = final.get("tokens")
            if spliced == control and toks == control:
                detail = (f"(identical: {len(control)} tokens, "
                          f"migrated={mig.get('streams_migrated')}, "
                          f"fallbacks={mig.get('migration_fallbacks')}, "
                          f"tokens_migrated="
                          f"{mig.get('tokens_migrated')})")
                ok = mig.get("streams_migrated", 0) >= 1
            else:
                div = next((i for i, (a, b) in enumerate(
                    zip(spliced or [], control))
                    if a != b), min(len(spliced or []), len(control)))
                detail = (f"(DIVERGED at token {div}: "
                          f"spliced={spliced} control={control})")
                ok = False
            step(n, "migrate-mode drain splice vs control", ok, detail)
        except Exception as exc:
            step(n, "migrate-mode drain splice vs control", False,
                 f"({exc})")
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()

    # (--disagg): one scripted prefill→decode handoff against a local
    # worker pair — the steady-state disaggregated path, live, in one
    # line: stream through a --disagg gateway (1 prefill + 1 decode
    # lane), let the KV chain hand off, and diff the spliced stream
    # against an unkilled blocking control (zero re-prefilled tokens).
    if args.disagg:
        n = (6 + int(args.kernel_parity) + int(args.mixed_parity)
             + int(args.spec_parity) + int(args.quant_parity)
             + int(args.ssd_parity) + int(args.tp_parity)
             + int(args.failover) + int(args.migrate) + 1)
        procs = []
        try:
            import threading

            from tools.fault_injection import _call, launch_worker_procs
            from tpu_engine.serving.gateway import Gateway, _parse_sse
            from tpu_engine.utils.config import GatewayConfig

            ports, procs = launch_worker_procs(
                2, per_worker_args=(("--role", "prefill"),
                                    ("--role", "decode")))
            dgw = Gateway([f"127.0.0.1:{p}" for p in ports],
                          GatewayConfig(disagg=True,
                                        handoff_timeout_s=60.0,
                                        failover_streams=True))
            req = {"request_id": "dg", "prompt_tokens": [5, 9, 3, 17],
                   "max_new_tokens": 24, "temperature": 0.9, "seed": 7}
            _, ctl = _call(ports[1], "POST", "/generate",
                           dict(req, request_id="ctl"), timeout=600)
            control = ctl["tokens"]
            toks, final = [], {}

            def consume_dg():
                for frame in dgw.route_generate_stream(dict(req)):
                    evt = _parse_sse(frame)
                    if evt and evt.get("done"):
                        final.update(evt)
                        break
                    if evt and "tokens" in evt:
                        toks.extend(evt["tokens"])

            t = threading.Thread(target=consume_dg, daemon=True)
            t.start()
            t.join(timeout=300)
            ho = dgw.get_stats().get("handoff", {})
            dgw.stop()
            spliced = final.get("tokens")
            if spliced == control and toks == control:
                detail = (f"(identical: {len(control)} tokens, "
                          f"routed={ho.get('prefill_routed')}, "
                          f"spliced={ho.get('handoffs_spliced')}, "
                          f"fallbacks={ho.get('handoff_fallbacks')})")
                ok = ho.get("handoffs_spliced", 0) >= 1
            else:
                div = next((i for i, (a, b) in enumerate(
                    zip(spliced or [], control))
                    if a != b), min(len(spliced or []), len(control)))
                detail = (f"(DIVERGED at token {div}: "
                          f"spliced={spliced} control={control})")
                ok = False
            step(n, "disagg prefill→decode handoff vs control", ok,
                 detail)
        except Exception as exc:
            step(n, "disagg prefill→decode handoff vs control", False,
                 f"({exc})")
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()

    # (--overload): overload-control state, live — the gateway's
    # /stats overload block and each lane's brownout ladder stage. Works
    # whether or not the flags are on: a defaults-off deployment reports
    # "overload control off" (the additive blocks are absent), which is
    # itself the wire-compat check in one line.
    if args.overload:
        n = (6 + int(args.kernel_parity) + int(args.mixed_parity)
             + int(args.spec_parity) + int(args.quant_parity)
             + int(args.ssd_parity) + int(args.tp_parity)
             + int(args.failover) + int(args.migrate)
             + int(args.disagg) + 1)
        try:
            status, stats = _get(gw, "/stats")
            ov = stats.get("overload")
            parts = []
            if ov is None:
                parts.append("gateway overload control off")
            else:
                parts.append(
                    f"inflight {ov.get('inflight')}"
                    + (f"/{ov['max_inflight']}" if "max_inflight" in ov
                       else "")
                    + f", pressure {ov.get('pressure')}, "
                    f"sheds tier={ov.get('shed_tier')} "
                    f"depth={ov.get('shed_depth')} "
                    f"rate={ov.get('rate_limited')}")
            # Brownout stage per lane: direct worker /health, or the
            # combined front's per-lane breakdown.
            lanes = {}
            if workers:
                for w in workers:
                    try:
                        _, h = _get(w, "/health")
                        lanes[h.get("node_id", w)] = h.get("brownout")
                    except Exception:
                        lanes[w] = None
            else:
                _, h = _get(gw, "/health")
                for node, lane_h in (h.get("lanes") or {}).items():
                    lanes[node] = lane_h.get("brownout")
            if any(b for b in lanes.values()):
                parts.append("brownout " + ", ".join(
                    f"{node}:{(b or {}).get('stage_name', 'off')}"
                    f"[{(b or {}).get('stage', '-')}]"
                    for node, b in sorted(lanes.items())))
            else:
                parts.append("brownout off on all lanes")
            step(n, "overload control state", status == 200,
                 "(" + "; ".join(parts) + ")")
        except Exception as exc:
            step(n, "overload control state", False, f"({exc})")

    # 17 (--elastic): elastic-fleet state of the live system — the
    # /admin/fleet status surface: membership, NAMED degraded states
    # (spawn-wedged / drain-wedged), whether the closed loop is
    # engaged, the last observed fleet pressure, and the decision
    # counters. A static fleet answers too (controller unstarted,
    # counters zero) — that is the defaults-off wire-compat check.
    if args.elastic:
        n = (6 + int(args.kernel_parity) + int(args.mixed_parity)
             + int(args.spec_parity) + int(args.quant_parity)
             + int(args.ssd_parity) + int(args.tp_parity)
             + int(args.failover) + int(args.migrate)
             + int(args.disagg) + int(args.overload) + 1)
        try:
            status, fleet = _post(gw, "/admin/fleet",
                                  {"action": "status"})
            parts = [f"state {fleet.get('state')}",
                     f"{len(fleet.get('lanes') or [])} lanes",
                     "autoscale "
                     + ("on" if fleet.get("autoscale") else "off")]
            if fleet.get("pressure") is not None:
                parts.append(f"pressure {fleet['pressure']}")
            ctr = fleet.get("counters") or {}
            acted = {k: v for k, v in ctr.items() if v}
            parts.append("decisions " + (", ".join(
                f"{k}={v}" for k, v in sorted(acted.items()))
                or "none yet"))
            for lane, reason in sorted(
                    (fleet.get("degraded") or {}).items()):
                parts.append(f"DEGRADED {lane}:{reason}")
            step(n, "elastic fleet state",
                 status == 200 and bool(fleet.get("ok")),
                 "(" + "; ".join(parts) + ")")
        except Exception as exc:
            step(n, "elastic fleet state", False, f"({exc})")

    # (--stitch): one scripted cross-lane stitched trace — the
    # observability-plane smoke, live, in one line: drive a stream
    # through a --trace-stitch gateway over a spawned worker pair,
    # drain-migrate it to the other lane mid-generation, then render
    # the merged /admin/trace/<request_id> tree. The stream must land
    # byte-identical to an unmoved control AND the stitched tree must
    # cover both lanes with zero orphaned spans.
    if args.stitch:
        n = (6 + int(args.kernel_parity) + int(args.mixed_parity)
             + int(args.spec_parity) + int(args.quant_parity)
             + int(args.ssd_parity) + int(args.tp_parity)
             + int(args.failover) + int(args.migrate)
             + int(args.disagg) + int(args.overload)
             + int(args.elastic) + 1)
        procs = []
        try:
            import threading

            from tools.fault_injection import (
                _call,
                launch_worker_procs,
                rid_for_lane,
            )
            from tpu_engine.serving.gateway import Gateway, _parse_sse
            from tpu_engine.utils.config import GatewayConfig

            ports, procs = launch_worker_procs(
                2, per_worker_args=(("--trace-stitch",),
                                    ("--trace-stitch",)))
            sgw = Gateway([f"127.0.0.1:{p}" for p in ports],
                          GatewayConfig(failover_streams=True,
                                        migrate_streams=True,
                                        migrate_timeout_s=60.0,
                                        trace_stitch=True))
            victim_lane = next(l for l in sgw.worker_names()
                               if str(ports[0]) in l)
            rid = rid_for_lane(sgw._ring, victim_lane, "st")
            req = {"request_id": rid, "prompt_tokens": [5, 9, 3, 17],
                   "max_new_tokens": 24, "temperature": 0.9, "seed": 7}
            _, ctl = _call(ports[1], "POST", "/generate",
                           dict(req, request_id="ctl"), timeout=600)
            control = ctl["tokens"]
            toks, final = [], {}

            def consume_st():
                for frame in sgw.route_generate_stream(dict(req)):
                    evt = _parse_sse(frame)
                    if evt and evt.get("done"):
                        final.update(evt)
                        break
                    if evt and "tokens" in evt:
                        toks.extend(evt["tokens"])

            t = threading.Thread(target=consume_st, daemon=True)
            t.start()
            import time as _time

            deadline = _time.monotonic() + 120
            while _time.monotonic() < deadline and len(toks) < 2:
                _time.sleep(0.02)
            sgw.remove_worker(victim_lane, drain=True)
            t.join(timeout=300)
            stitched = sgw.stitched_trace(rid)
            sgw.stop()
            spliced = final.get("tokens")
            lanes = stitched.get("lanes") or []
            spans = stitched.get("spans") or []
            orphans = stitched.get("orphans", -1)
            hops = stitched.get("hops") or []
            hop_kinds = ",".join(h.get("kind", "?") for h in hops)
            if spliced == control and toks == control:
                detail = (f"({len(control)} tokens identical; "
                          f"{len(lanes)} lanes {lanes}, "
                          f"{len(spans)} spans, orphans={orphans}, "
                          f"hops=[{hop_kinds}])")
                ok = len(lanes) >= 2 and orphans == 0 and len(hops) >= 2
            else:
                div = next((i for i, (a, b) in enumerate(
                    zip(spliced or [], control))
                    if a != b), min(len(spliced or []), len(control)))
                detail = (f"(DIVERGED at token {div}: "
                          f"spliced={spliced} control={control})")
                ok = False
            step(n, "cross-lane stitched trace", ok, detail)
        except Exception as exc:
            step(n, "cross-lane stitched trace", False, f"({exc})")
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()

    # (--fleet-prefix): one scripted owner→peer KV prefix fetch — the
    # fleet prefix tier's smoke, live, in one line: lane 0 serves a
    # shared 48-token prefix (becoming its directory owner), then a
    # request landing on lane 1 carries the gateway's peer hint and
    # must SPLICE the owner's chain over HTTP instead of re-prefilling
    # it, byte-identical to an unhinted control.
    if args.fleet_prefix:
        n = (6 + int(args.kernel_parity) + int(args.mixed_parity)
             + int(args.spec_parity) + int(args.quant_parity)
             + int(args.ssd_parity) + int(args.tp_parity)
             + int(args.failover) + int(args.migrate)
             + int(args.disagg) + int(args.overload)
             + int(args.elastic) + int(args.stitch) + 1)
        procs = []
        try:
            from tools.fault_injection import (
                _call,
                launch_worker_procs,
                rid_for_lane,
                victim_lane_for_port,
            )
            from tpu_engine.serving.gateway import Gateway
            from tpu_engine.utils.config import GatewayConfig

            ports, procs = launch_worker_procs(
                2, extra_args=("--prefix-fetch",))
            pgw = Gateway([f"127.0.0.1:{p}" for p in ports],
                          GatewayConfig(prefix_directory=True))
            lanes = pgw.worker_names()
            shared = [(17 * j + 5) % 97 + 1 for j in range(48)]
            own_rid = rid_for_lane(
                pgw._ring, victim_lane_for_port(lanes, ports[0]), "fpd_o")
            fetch_rid = rid_for_lane(
                pgw._ring, victim_lane_for_port(lanes, ports[1]), "fpd_f")
            own = pgw.route_generate(
                {"request_id": own_rid, "prompt_tokens": shared + [3, 1],
                 "max_new_tokens": 8})
            fetch_req = {"request_id": fetch_rid,
                         "prompt_tokens": shared + [5, 2],
                         "max_new_tokens": 8}
            _, ctl = _call(ports[0], "POST", "/generate",
                           dict(fetch_req, request_id="fpd_ctl"),
                           timeout=600)
            fetched = pgw.route_generate(dict(fetch_req))
            _, health = _call(ports[1], "GET", "/health", timeout=10)
            fs = (health.get("generator") or {}).get("prefix_fetch") or {}
            pd = pgw.get_stats().get("prefix_directory", {})
            pgw.stop()
            identical = fetched["tokens"] == ctl["tokens"]
            ok = (identical and bool(own.get("tokens"))
                  and fs.get("attempted") == 1 and fs.get("spliced") == 1
                  and fs.get("blocks_spliced", 0) >= 3
                  and pd.get("hints_attached", 0) >= 1)
            step(n, "fleet prefix fetch", ok,
                 f"({fs.get('blocks_spliced', 0)} blocks spliced, "
                 f"{fs.get('prefill_tokens_skipped_remote', 0)} remote "
                 f"prefill tokens skipped, "
                 f"{pd.get('hints_attached', 0)} hints attached, "
                 f"{pd.get('entries', 0)} directory entries; "
                 f"{'byte-identical' if identical else 'DIVERGED'})")
        except Exception as exc:
            step(n, "fleet prefix fetch", False, f"({exc})")
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()

    if args.unified:
        n = (6 + int(args.kernel_parity) + int(args.mixed_parity)
             + int(args.spec_parity) + int(args.quant_parity)
             + int(args.ssd_parity) + int(args.tp_parity)
             + int(args.failover) + int(args.migrate)
             + int(args.disagg) + int(args.overload)
             + int(args.elastic) + int(args.stitch)
             + int(args.fleet_prefix) + 1)
        try:
            import threading as _threading

            from tpu_engine.serving.worker import WorkerNode
            from tpu_engine.utils.config import WorkerConfig

            uw = WorkerNode(WorkerConfig(
                node_id="diag_u", model="gpt2-small-test",
                dtype="float32", max_batch_size=4))
            try:
                score_req = {"prompt_tokens": [1, 2, 3],
                             "completion_tokens": [4, 5]}
                control = uw.handle_score(
                    dict(score_req, request_id="du_ctl"))
                base = uw.generator.stats()["stateless"]["dispatches"]
                # Live mixed-tick watcher: sample the scheduler while
                # the workload runs and keep the first snapshot where
                # decode rows are resident AND a one-shot dispatch has
                # landed since the watch began — the mixed-row tick,
                # caught in the act.
                live: dict = {}
                stop_w = _threading.Event()

                def watch():
                    while not stop_w.is_set():
                        st = uw.generator.stats()
                        sl = st.get("stateless", {})
                        if (st.get("active", 0) > 0
                                and sl.get("dispatches", 0) > base
                                and not live):
                            live.update(
                                decode_rows=st["active"],
                                oneshot_dispatches=(sl["dispatches"]
                                                    - base),
                                score_rows=sl.get("score_rows", 0))
                        time.sleep(0.002)

                results: dict = {}

                def drive_gen():
                    results["g"] = uw.handle_generate(
                        {"request_id": "du_g",
                         "prompt_tokens": [1, 2, 3, 4],
                         "max_new_tokens": 24})

                def drive_score(i):
                    results[f"s{i}"] = uw.handle_score(
                        dict(score_req, request_id=f"du_s{i}"))

                wt = _threading.Thread(target=watch, daemon=True)
                wt.start()
                gt = _threading.Thread(target=drive_gen)
                gt.start()
                time.sleep(0.05)  # let the stream take residency
                sts = [_threading.Thread(target=drive_score, args=(i,))
                       for i in range(3)]
                for t in sts:
                    t.start()
                for t in [gt] + sts:
                    t.join()
                stop_w.set()
                wt.join(timeout=5)
                sl = uw.generator.stats()["stateless"]
                identical = all(
                    results[f"s{i}"]["logprobs"] == control["logprobs"]
                    for i in range(3))
                ticks_ok = sl["ticks"] == sl["dispatches"]
                ok = (bool(live) and identical and ticks_ok
                      and sl["failed"] == 0)
                step(n, "unified mixed-row tick", ok,
                     f"({live.get('decode_rows', 0)} decode rows beside "
                     f"{live.get('oneshot_dispatches', 0)} one-shot "
                     f"dispatch(es), {sl.get('score_rows', 0)} score "
                     f"rows total; ticks==dispatches "
                     f"{'holds' if ticks_ok else 'VIOLATED'}; scores "
                     f"{'byte-identical' if identical else 'DIVERGED'})")
            finally:
                uw.stop()
        except Exception as exc:
            step(n, "unified mixed-row tick", False, f"({exc})")

    # 12 (--lint): the engine-lint suite, in-process — the same gate
    # tier-1 runs (tests/test_engine_lint.py), surfaced here so an
    # operator can check a working tree before pushing.
    if args.lint:
        n = _TOTAL  # always the last step
        try:
            from tools.analyze import baseline as lint_baseline
            from tools.analyze import run_suite

            report = run_suite()
            new, old = lint_baseline.split(report.findings)
            counts = {}
            for f in new:
                counts[f.rule] = counts.get(f.rule, 0) + 1
            summary = (", ".join(f"{r}={c}" for r, c in sorted(
                counts.items())) or "no findings")
            step(n, "engine-lint static analysis", not new,
                 f"({summary}; {len(old)} baselined, "
                 f"{len(report.waived)} waived)")
            for f in new:
                print(f"      {f.format()}")
        except Exception as exc:
            step(n, "engine-lint static analysis", False, f"({exc})")

    n_ok = sum(_results)
    print(f"\n{n_ok}/{len(_results)} checks passed")
    return 0 if n_ok == len(_results) else 1


if __name__ == "__main__":
    sys.exit(main())
