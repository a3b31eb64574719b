"""CLI entry points, argv-compatible with the reference binaries.

Reference launch lines work verbatim with ``python -m tpu_engine.serving.cli``
(or the ``bin/worker_node`` / ``bin/gateway`` wrappers):

  worker_node <port> <node_id> [model_path]     (worker_node.cpp:145-168;
                                                 $MODEL_PATH honored)
  gateway <worker1:port> [worker2:port] ...     (gateway.cpp:161-171)

Plus the TPU-native combined mode the reference doesn't have:

  serve [--model resnet50] [--lanes N] [--port 8000]
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def _run_forever(stoppables=()):
    """Block until SIGTERM/SIGINT, then DRAIN instead of dying mid-request:
    the HTTP front stops accepting first, then each lane's batcher/decode
    scheduler joins (in-flight work resolves its futures). The reference's
    only shutdown is an abrupt kill (README.md:322 tests fault tolerance
    by exactly that)."""
    import signal
    import threading

    ev = threading.Event()

    def _handle(_signum, _frame):
        ev.set()

    try:
        signal.signal(signal.SIGTERM, _handle)
        signal.signal(signal.SIGINT, _handle)
    except ValueError:
        pass  # non-main thread (embedding); fall back to sleep loop
    try:
        while not ev.is_set():
            ev.wait(3600)
    except KeyboardInterrupt:
        pass
    # Second signal = force quit: restore default handlers so an operator
    # isn't locked out of Ctrl+C while a drain (or a hung lane) runs.
    try:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        signal.signal(signal.SIGINT, signal.SIG_DFL)
    except ValueError:
        pass
    for s in stoppables:
        try:
            s.stop()
        except Exception:
            pass


def select_platform() -> None:
    """The entry point decides the platform ONCE, before the first
    compile. TPU_ENGINE_PLATFORM=cpu runs on the host backend on purpose
    (tests, chaos tools, several worker processes on one machine — a chip
    belongs to one process). Otherwise the backend must be a TPU: when
    libtpu finds no chip JAX drops to the CPU without a word, and that
    must fail the start by name, never produce a serving process. Then
    the compile cache is placed (utils.checkpoint: from the environment,
    else <checkout>/.jax_cache) for this process and its children."""
    import jax

    platform = os.environ.get("TPU_ENGINE_PLATFORM")
    if platform:
        jax.config.update("jax_platforms", platform)
    elif jax.default_backend() != "tpu":
        raise SystemExit(
            f"tpu_engine: no TPU found — JAX's default backend is "
            f"{jax.default_backend()!r}. This program serves on a TPU; "
            f"set TPU_ENGINE_PLATFORM=cpu to run on the host CPU on "
            f"purpose (tests, several workers on one machine).")
    from tpu_engine.utils.checkpoint import enable_compilation_cache

    enable_compilation_cache()


def _add_autoscale_flags(parser) -> None:
    """Elastic-fleet flags shared by ``gateway`` and ``serve`` (DESIGN.md
    "Elastic fleet"). All default to None so only explicitly-set flags
    reach GatewayConfig — defaults stay wire-byte-identical."""
    parser.add_argument("--autoscale", action="store_true",
                        help="closed-loop elastic fleet: a controller "
                             "thread reads per-lane overload pressure "
                             "and spawns/retires lanes against it — "
                             "scale-down drains via live stream "
                             "migration (zero tokens lost), scale-up "
                             "registers only after a passing /health "
                             "probe (implies --migrate-streams)")
    parser.add_argument("--autoscale-interval", type=float, default=None,
                        help="control-loop tick interval seconds "
                             "(default 1)")
    parser.add_argument("--autoscale-min-lanes", type=int, default=None,
                        help="never drain the fleet below this many "
                             "lanes (default 1)")
    parser.add_argument("--autoscale-max-lanes", type=int, default=None,
                        help="never spawn above this many lanes "
                             "(default 0 = provider capacity rules)")
    parser.add_argument("--autoscale-up-pressure", type=float,
                        default=None,
                        help="mean fleet pressure above which a lane "
                             "is spawned (default 0.75)")
    parser.add_argument("--autoscale-down-pressure", type=float,
                        default=None,
                        help="mean fleet pressure below which a lane "
                             "is retired (default 0.25)")
    parser.add_argument("--autoscale-cooldown", type=float, default=None,
                        help="minimum seconds between actuated "
                             "decisions (default 5)")
    parser.add_argument("--autoscale-spawn-timeout", type=float,
                        default=None,
                        help="a spawned lane that has not probed "
                             "healthy within this window is destroyed "
                             "and the fleet enters the named "
                             "spawn-wedged degraded state (default 30)")
    parser.add_argument("--autoscale-rebalance-band", type=float,
                        default=None,
                        help="role-rebalance arm (needs --disagg): flip "
                             "a lane prefill<->decode when the "
                             "prefill:decode pressure ratio leaves this "
                             "band, re-arming inside band/2 "
                             "(default 0 = off; must be > 1)")


def _apply_autoscale_flags(args, gw_kw: dict) -> None:
    if args.autoscale:
        gw_kw["autoscale"] = True
        # Scale-down must ride the live-migration ladder — without it,
        # retiring a lane sheds its streams onto the replay resume as
        # the PLAN rather than the last rung.
        gw_kw["migrate_streams"] = True
    if args.autoscale_interval is not None:
        gw_kw["autoscale_interval_s"] = args.autoscale_interval
    if args.autoscale_min_lanes is not None:
        gw_kw["autoscale_min_lanes"] = args.autoscale_min_lanes
    if args.autoscale_max_lanes is not None:
        gw_kw["autoscale_max_lanes"] = args.autoscale_max_lanes
    if args.autoscale_up_pressure is not None:
        gw_kw["autoscale_up_pressure"] = args.autoscale_up_pressure
    if args.autoscale_down_pressure is not None:
        gw_kw["autoscale_down_pressure"] = args.autoscale_down_pressure
    if args.autoscale_cooldown is not None:
        gw_kw["autoscale_cooldown_s"] = args.autoscale_cooldown
    if args.autoscale_spawn_timeout is not None:
        gw_kw["autoscale_spawn_timeout_s"] = args.autoscale_spawn_timeout
    if args.autoscale_rebalance_band is not None:
        gw_kw["autoscale_rebalance_band"] = args.autoscale_rebalance_band


def _add_slo_flags(parser) -> None:
    """Observability-plane gateway flags shared by ``gateway`` and
    ``serve`` (DESIGN.md "Observability plane"). All default to None /
    off so defaults stay wire-byte-identical."""
    parser.add_argument("--trace-stitch", action="store_true",
                        help="cross-lane trace stitching: propagate each "
                             "stream's trace context through every "
                             "mobility hop (handoff, migration, crash "
                             "resume) and keep a stream ledger so "
                             "GET /admin/trace/<request_id> returns ONE "
                             "merged Perfetto tree covering every lane "
                             "the stream touched")
    parser.add_argument("--trace-ledger-capacity", type=int, default=None,
                        help="streams the stitch ledger remembers "
                             "(FIFO eviction; default 512)")
    parser.add_argument("--slo-ttft-p99-ms", type=float, default=None,
                        help="TTFT latency objective in ms: --slo-target "
                             "of first tokens must land under this; "
                             "burn rate surfaces at /admin/slo, /stats "
                             "and tpu_engine_slo_* (0/unset = off)")
    parser.add_argument("--slo-itl-p99-ms", type=float, default=None,
                        help="inter-token latency objective in ms "
                             "(0/unset = off)")
    parser.add_argument("--slo-completion-p99-ms", type=float,
                        default=None,
                        help="full request-completion latency objective "
                             "in ms, measured at gateway scope — "
                             "failover/handoff/migration time included "
                             "(0/unset = off)")
    parser.add_argument("--slo-target", type=float, default=None,
                        help="good-sample fraction the objectives "
                             "demand (default 0.99; error budget = "
                             "1 - target)")
    parser.add_argument("--slo-window-s", type=float, default=None,
                        help="sliding burn-rate window seconds "
                             "(default 300)")
    parser.add_argument("--autoscale-slo-feed", action="store_true",
                        help="feed SLO burn into the elastic-fleet "
                             "controller: fleet pressure becomes "
                             "max(lane pressure, worst burn / 2) — the "
                             "feed only ever ADDS pressure (needs "
                             "--autoscale and an --slo-* objective)")


def _apply_slo_flags(args, gw_kw: dict) -> None:
    if args.trace_stitch:
        gw_kw["trace_stitch"] = True
    if args.trace_ledger_capacity is not None:
        gw_kw["trace_ledger_capacity"] = args.trace_ledger_capacity
    if args.slo_ttft_p99_ms is not None:
        gw_kw["slo_ttft_p99_ms"] = args.slo_ttft_p99_ms
    if args.slo_itl_p99_ms is not None:
        gw_kw["slo_itl_p99_ms"] = args.slo_itl_p99_ms
    if args.slo_completion_p99_ms is not None:
        gw_kw["slo_completion_p99_ms"] = args.slo_completion_p99_ms
    if args.slo_target is not None:
        gw_kw["slo_target"] = args.slo_target
    if args.slo_window_s is not None:
        gw_kw["slo_window_s"] = args.slo_window_s
    if args.autoscale_slo_feed:
        gw_kw["autoscale_slo_feed"] = True


def _add_flight_flags(parser) -> None:
    """Observability-plane worker flags shared by ``worker_node`` and
    ``serve``: the per-tick flight recorder and the jax.profiler
    capture directory."""
    parser.add_argument("--profile-dir", type=str, default=None,
                        help="jax.profiler capture directory: arms "
                             "POST /admin/profile {\"ticks\": N} to "
                             "trace exactly N scheduler ticks into "
                             "this dir (TensorBoard/Perfetto; "
                             "unset = profiling refused)")
    parser.add_argument("--flight-recorder", type=int, default=None,
                        help="per-tick flight recorder: keep a ring of "
                             "this many per-tick scheduler records "
                             "(GET /admin/timeline), auto-dumped to a "
                             "postmortem JSON on anomaly — recover, "
                             "deadline-miss burst, degraded fleet "
                             "state (0/unset = off)")
    parser.add_argument("--flight-dump-dir", type=str, default=None,
                        help="directory for flight-recorder postmortem "
                             "dumps (unset = dumps stay in-memory, "
                             "visible via /admin/timeline last_dump)")


def _apply_flight_flags(args, gen_kw: dict) -> None:
    if args.profile_dir is not None:
        gen_kw["profile_dir"] = args.profile_dir
    if args.flight_recorder is not None:
        gen_kw["flight_recorder"] = args.flight_recorder
    if args.flight_dump_dir is not None:
        gen_kw["flight_dump_dir"] = args.flight_dump_dir


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv:
        print(__doc__)
        return 2
    cmd, rest = argv[0], argv[1:]

    if cmd != "gateway":
        # The gateway routes over HTTP and must never claim a chip; every
        # other command computes.
        select_platform()

    if cmd in ("worker", "worker_node"):
        from tpu_engine.serving.app import model_from_path, serve_worker
        from tpu_engine.utils.config import WorkerConfig

        if not rest:
            print("Usage: worker_node <port> <node_id> [model_path] "
                  "[--kv-block-size N] [--kv-blocks N] "
                  "[--kv-host-blocks N] [--kv-quantize int8] "
                  "[--step-chunk N] "
                  "[--prefill-chunk N] [--scheduler-stall-s S]")
            return 1
        parser = argparse.ArgumentParser(prog="worker_node")
        parser.add_argument("port", type=int)
        parser.add_argument("node_id", nargs="?", default=None)
        parser.add_argument("model_arg", nargs="?", default=None)
        # Optional generation knobs so a STANDALONE worker (the unit the
        # `gateway` command routes across, and the unit the chaos harness
        # kill -9s) can serve the same paged/continuous configuration as
        # combined mode — the positional reference argv stays verbatim.
        parser.add_argument("--kv-block-size", type=int, default=None,
                            help="paged KV block size (0/unset = dense)")
        parser.add_argument("--kv-blocks", type=int, default=None,
                            help="paged KV pool size in blocks (0 = auto)")
        parser.add_argument("--kv-host-blocks", type=int, default=None,
                            help="hierarchical host-RAM KV tier: demote "
                                 "cold radix prefixes to this many pinned "
                                 "host blocks and swap them back in on a "
                                 "radix hit instead of recomputing "
                                 "(0/unset = off)")
        parser.add_argument("--kv-quantize", default=None,
                            choices=("int8",),
                            help="store paged KV block payloads int8 with "
                                 "per-(slot, kv-head) f32 scales — ~2x "
                                 "blocks on the same HBM; requires "
                                 "--kv-block-size (unset = bf16 pool)")
        parser.add_argument("--state-rows", type=int, default=None,
                            help="recurrent state slab pool capacity in "
                                 "rows (state_slab-family models, e.g. "
                                 "mamba2: one fixed-size row per live "
                                 "stream, constant in sequence length; "
                                 "0/unset = auto)")
        parser.add_argument("--tp", type=int, default=None,
                            help="tensor-parallel serving: shard the "
                                 "model (registry-declared partition "
                                 "rule) and the paged KV pool's H_kv "
                                 "axis over this many local devices — "
                                 "one SPMD ragged dispatch per tick; "
                                 "needs --kv-block-size; unshardable "
                                 "families (mamba2) refuse at startup "
                                 "(unset/1 = single-device)")
        parser.add_argument("--step-chunk", type=int, default=None,
                            help="decode chunk length per dispatch")
        parser.add_argument("--prefill-chunk", type=int, default=None,
                            help="prefill chunk width")
        parser.add_argument("--scheduler-stall-s", type=float, default=None,
                            help="decode-loop liveness threshold: /health "
                                 "reads unhealthy when the loop has not "
                                 "ticked for this long (0/unset = report "
                                 "age only)")
        parser.add_argument("--priority-admission", action="store_true",
                            help="shed lowest-priority-tier first under "
                                 "depth pressure (requests carry "
                                 "priority: interactive|batch|background)")
        parser.add_argument("--adaptive-depth", action="store_true",
                            help="AIMD adaptive concurrency limit driven "
                                 "by observed latency vs the "
                                 "sliding-window baseline")
        parser.add_argument("--brownout", action="store_true",
                            help="staged brownout controller: degrade "
                                 "gracefully (budget shrink, spec off, "
                                 "swap-in deferral, low-tier clamp) "
                                 "before shedding")
        parser.add_argument("--role", default=None,
                            choices=("prefill", "decode", "both"),
                            help="disaggregated serving role (needs "
                                 "--kv-block-size for dedicated roles): "
                                 "a role-aware gateway (--disagg) lands "
                                 "fresh generate work on prefill lanes "
                                 "and ships finished KV chains to "
                                 "decode lanes; flippable at runtime "
                                 "via /admin/role (default: both = "
                                 "today's colocated behavior)")
        parser.add_argument("--trace-stitch", action="store_true",
                            help="cross-lane trace stitching (worker "
                                 "side): exported row snapshots and KV "
                                 "chains carry the stream's trace "
                                 "context so the importing lane's spans "
                                 "join the same tree")
        parser.add_argument("--prefix-fetch", action="store_true",
                            help="fleet prefix tier (worker side; needs "
                                 "--kv-block-size + prefix sharing): "
                                 "serve /admin/export_prefix to peers, "
                                 "publish bounded radix summaries in "
                                 "/health, and pull a gateway-hinted "
                                 "peer's KV chain before prefilling a "
                                 "local radix miss — every failure "
                                 "falls back to local prefill")
        parser.add_argument("--prefix-fetch-timeout", type=float,
                            default=None,
                            help="per-fetch peer budget in seconds "
                                 "(default 5)")
        parser.add_argument("--prefix-fetch-inflight", type=int,
                            default=None,
                            help="concurrent outbound peer fetches per "
                                 "lane; excess misses prefill locally "
                                 "(default 2)")
        parser.add_argument("--no-unified-stateless", action="store_true",
                            help="retire the unified stateless lane: "
                                 "route /predict misses and /score "
                                 "through the legacy dedicated batch "
                                 "processor instead of single-tick rows "
                                 "in the continuous scheduler (default: "
                                 "unified — one slot pool, one set of "
                                 "deadlines/brownout/counters for every "
                                 "request class)")
        _add_flight_flags(parser)
        args = parser.parse_args(rest)
        port = args.port
        node_id = args.node_id or f"worker_{port}"
        model_arg = args.model_arg or os.environ.get("MODEL_PATH", "resnet50")
        # A real path loads real weights (HF/torch/orbax via the worker's
        # _load_model_path); a bare registry name serves random init. HF
        # checkpoint dirs resolve their registry model from config.json
        # (e.g. model_type "resnet" → resnet50-v1, the importable family).
        model_path = model_arg if os.path.exists(model_arg) else None
        model = None
        if model_path and model_path.endswith(".onnx"):
            model = "onnx"  # architecture comes from the file (onnx_graph)
        elif model_path:
            sidecar = os.path.join(model_path, "tpu_engine_model.json")
            if os.path.isdir(model_path) and os.path.exists(sidecar):
                # Self-describing orbax checkpoint (train CLI writes it).
                import json

                with open(sidecar) as f:
                    model = json.load(f)["model"]
            else:
                from tpu_engine.models.import_weights import (
                    model_name_from_hf,
                )

                model = model_name_from_hf(model_path)
        gen_kw = {}
        if args.kv_block_size is not None:
            gen_kw["gen_kv_block_size"] = args.kv_block_size
        if args.kv_blocks is not None:
            gen_kw["gen_kv_blocks"] = args.kv_blocks
        if args.kv_host_blocks is not None:
            gen_kw["gen_kv_host_blocks"] = args.kv_host_blocks
        if args.kv_quantize is not None:
            gen_kw["gen_kv_quantize"] = args.kv_quantize
        if args.state_rows is not None:
            gen_kw["gen_state_rows"] = args.state_rows
        if args.tp is not None:
            gen_kw["tp"] = args.tp
        if args.step_chunk is not None:
            gen_kw["gen_step_chunk"] = args.step_chunk
        if args.prefill_chunk is not None:
            gen_kw["gen_prefill_chunk"] = args.prefill_chunk
        if args.scheduler_stall_s is not None:
            gen_kw["scheduler_stall_s"] = args.scheduler_stall_s
        if args.priority_admission:
            gen_kw["priority_admission"] = True
        if args.adaptive_depth:
            gen_kw["adaptive_depth"] = True
        if args.brownout:
            gen_kw["brownout"] = True
        if args.role is not None:
            gen_kw["role"] = args.role
        if args.trace_stitch:
            gen_kw["trace_stitch"] = True
        if args.prefix_fetch:
            gen_kw["gen_prefix_fetch"] = True
        if args.prefix_fetch_timeout is not None:
            gen_kw["gen_prefix_fetch_timeout_s"] = args.prefix_fetch_timeout
        if args.prefix_fetch_inflight is not None:
            gen_kw["gen_prefix_fetch_inflight"] = args.prefix_fetch_inflight
        if args.no_unified_stateless:
            gen_kw["unified_stateless"] = False
        _apply_flight_flags(args, gen_kw)
        cfg = WorkerConfig(port=port, node_id=node_id,
                           model=model or model_from_path(model_arg),
                           model_path=model_path, **gen_kw)
        worker, server = serve_worker(cfg, background=True)
        _run_forever([server, worker])
        return 0

    if cmd == "gateway":
        from tpu_engine.serving.app import serve_gateway
        from tpu_engine.utils.config import GatewayConfig

        if not rest:
            print("Usage: gateway <worker1_host:port> [worker2_host:port] ...")
            return 1
        parser = argparse.ArgumentParser(prog="gateway")
        parser.add_argument("workers", nargs="+")
        parser.add_argument("--port", type=int, default=8000)
        parser.add_argument("--breaker-timeout", type=float, default=30.0,
                            help="circuit-breaker OPEN->HALF_OPEN timeout "
                                 "seconds (reference gateway.cpp:22)")
        parser.add_argument("--failover-streams", action="store_true",
                            help="crash-tolerant streaming: journal "
                                 "/generate/stream token events and resume "
                                 "a mid-stream worker failure on another "
                                 "ring lane, splicing one seamless "
                                 "byte-identical stream (default: the "
                                 "stream terminates with an error event)")
        parser.add_argument("--health-probe-interval", type=float,
                            default=0.0,
                            help="proactive lane health prober: GET each "
                                 "worker's /health at this interval and "
                                 "eject lanes from routing after 3 "
                                 "consecutive failures, restoring them on "
                                 "recovery (seconds; 0 = off)")
        parser.add_argument("--migrate-streams", action="store_true",
                            help="live stream migration: graceful removal "
                                 "(remove_worker drain) EXPORTS each "
                                 "in-flight /generate/stream's KV block "
                                 "chain + state off the draining lane and "
                                 "resumes it mid-stream on another lane "
                                 "with zero re-prefilled tokens (any "
                                 "failure falls back to the replay "
                                 "resume; implies the stream journal)")
        parser.add_argument("--migrate-timeout", type=float, default=None,
                            help="per-stream migration transfer budget in "
                                 "seconds, clamped to the stream's "
                                 "original deadline (default 30)")
        parser.add_argument("--drain-timeout", type=float, default=None,
                            help="graceful-drain acknowledgment bound in "
                                 "seconds: a wedged lane's drain call is "
                                 "abandoned (counted) and removal "
                                 "proceeds (default 10)")
        parser.add_argument("--retry-budget", type=float, default=None,
                            help="global retry budget: failover retries "
                                 "(stream resumes included) capped at this "
                                 "fraction of recent requests "
                                 "(default: unlimited)")
        parser.add_argument("--prefix-affinity", action="store_true",
                            help="route /generate(+/stream) on a "
                                 "block-aligned prompt-prefix fingerprint "
                                 "instead of request_id: shared prefixes "
                                 "converge on the lane whose radix tree "
                                 "already holds the KV blocks (ring-order "
                                 "fallback under ejection/imbalance)")
        parser.add_argument("--affinity-block-size", type=int, default=None,
                            help="fingerprint block granularity — MUST "
                                 "match the workers' --kv-block-size "
                                 "(default 16)")
        parser.add_argument("--affinity-prefix-blocks", type=int,
                            default=None,
                            help="leading blocks the fingerprint covers "
                                 "(default 4)")
        parser.add_argument("--affinity-max-imbalance", type=int,
                            default=None,
                            help="skip the affinity lane (ring order) once "
                                 "it is this many recent dispatches hotter "
                                 "than its least-loaded peer (0 = always "
                                 "honor affinity)")
        parser.add_argument("--prefix-directory", action="store_true",
                            help="fleet prefix tier (gateway side): keep "
                                 "a bounded fingerprint->owner-lane "
                                 "directory (prober /health summaries + "
                                 "post-completion updates) and stamp "
                                 "generate-class dispatches with a "
                                 "prefix_hint so --prefix-fetch lanes "
                                 "can pull the owner's KV chain instead "
                                 "of re-prefilling it (works with "
                                 "affinity off)")
        parser.add_argument("--prefix-dir-capacity", type=int,
                            default=None,
                            help="directory LRU bound in entries "
                                 "(default 512)")
        parser.add_argument("--overload-control", action="store_true",
                            help="priority-tiered gateway admission "
                                 "(lowest tier sheds first as "
                                 "--overload-max-inflight fills) + "
                                 "load-derived Retry-After on sheds")
        parser.add_argument("--overload-max-inflight", type=int,
                            default=None,
                            help="gateway in-flight gauge for tier "
                                 "admission (0 = no gauge)")
        parser.add_argument("--tenant-rate", type=float, default=None,
                            help="per-tenant token-bucket rate limit "
                                 "(requests/s; 0 = off)")
        parser.add_argument("--disagg", action="store_true",
                            help="disaggregated prefill/decode serving: "
                                 "while the fleet has dedicated "
                                 "--role prefill lanes, /generate(+/"
                                 "stream) lands on a prefill lane and "
                                 "the finished KV chain ships to a "
                                 "decode lane picked by load (zero "
                                 "re-prefilled tokens; every failure "
                                 "falls back to local decode or the "
                                 "replay resume)")
        parser.add_argument("--handoff-timeout", type=float, default=None,
                            help="per-stream prefill→decode handoff "
                                 "budget in seconds, clamped to the "
                                 "stream's deadline (default 30)")
        _add_autoscale_flags(parser)
        _add_slo_flags(parser)
        parser.add_argument("--standby-worker", action="append",
                            default=None, metavar="HOST:PORT",
                            help="pre-launched worker ADDRESS for the "
                                 "elastic fleet's warm standby pool "
                                 "(repeatable); joins the ring only "
                                 "when the autoscaler scales up and its "
                                 "/health probe passes")
        args = parser.parse_args(rest)
        gw_kw = {}
        if args.overload_control:
            gw_kw["overload_control"] = True
        if args.overload_max_inflight is not None:
            gw_kw["overload_max_inflight"] = args.overload_max_inflight
        if args.tenant_rate is not None:
            gw_kw["tenant_rate"] = args.tenant_rate
        if args.retry_budget is not None:
            gw_kw["retry_budget_ratio"] = args.retry_budget
        if args.migrate_streams:
            gw_kw["migrate_streams"] = True
        _apply_autoscale_flags(args, gw_kw)
        _apply_slo_flags(args, gw_kw)
        if args.migrate_timeout is not None:
            gw_kw["migrate_timeout_s"] = args.migrate_timeout
        if args.drain_timeout is not None:
            gw_kw["drain_timeout_s"] = args.drain_timeout
        if args.prefix_affinity:
            gw_kw["prefix_affinity"] = True
        if args.affinity_block_size is not None:
            gw_kw["affinity_block_size"] = args.affinity_block_size
        if args.affinity_prefix_blocks is not None:
            gw_kw["affinity_prefix_blocks"] = args.affinity_prefix_blocks
        if args.affinity_max_imbalance is not None:
            gw_kw["affinity_max_imbalance"] = args.affinity_max_imbalance
        if args.prefix_directory:
            gw_kw["prefix_directory"] = True
        if args.prefix_dir_capacity is not None:
            gw_kw["prefix_directory_capacity"] = args.prefix_dir_capacity
        if args.disagg:
            gw_kw["disagg"] = True
        if args.handoff_timeout is not None:
            gw_kw["handoff_timeout_s"] = args.handoff_timeout
        gw, server = serve_gateway(
            args.workers,
            GatewayConfig(port=args.port,
                          breaker_timeout_s=args.breaker_timeout,
                          failover_streams=args.failover_streams,
                          health_probe_interval_s=args.health_probe_interval,
                          **gw_kw),
            background=True,
            standby_workers=args.standby_worker)
        _run_forever([server, gw])
        return 0

    if cmd == "serve":
        from tpu_engine.serving.app import serve_combined

        parser = argparse.ArgumentParser(prog="serve")
        parser.add_argument("--model", default="resnet50")
        parser.add_argument("--model-path", default=None,
                            help="HF/torch/orbax checkpoint with real weights "
                                 "(default: random init)")
        parser.add_argument("--lanes", type=int, default=0)
        parser.add_argument("--mesh", default=None,
                            help="mesh-sharded serving: one engine spanning "
                                 "all chips, e.g. data=8 or model=2,data=4 "
                                 "(batch scatter / TP weights over ICI)")
        parser.add_argument("--port", type=int, default=8000)
        parser.add_argument("--warmup", action="store_true",
                            help="pre-compile all batch buckets before listening")
        parser.add_argument("--shape-buckets", default=None,
                            help="mixed-shape serving: comma-separated HxWxC "
                                 "list, e.g. 320x320x3,640x640x3")
        parser.add_argument("--batch-buckets", default=None,
                            help="comma-separated batch sizes to compile, "
                                 "e.g. 1,8,32,128 (default 1..32; larger "
                                 "buckets raise MFU on throughput-bound "
                                 "fleets — batch 32 is the reference "
                                 "batcher's cap, not the chip's)")
        parser.add_argument("--pipeline-depth", type=int, default=None,
                            help="submitted batches kept in flight on the "
                                 "miss path (default 4); raise when the "
                                 "dispatch round-trip dwarfs the device "
                                 "step (high-latency links)")
        parser.add_argument("--cache-capacity", type=int, default=None,
                            help="result-cache entries per lane (default "
                                 "1000, reference worker_node.cpp:33)")
        parser.add_argument("--batch-timeout-ms", type=float, default=None,
                            help="dynamic batcher flush timeout (default "
                                 "20, reference worker_node.cpp:36)")
        parser.add_argument("--breaker-timeout", type=float, default=None,
                            help="circuit-breaker OPEN->HALF_OPEN timeout "
                                 "seconds (default 30, reference gateway.cpp:22)")
        # -- resilience layer (DESIGN.md "Request resilience"; every knob
        # defaults off/permissive = reference-faithful behavior) ---------
        parser.add_argument("--default-deadline-ms", type=float, default=None,
                            help="deadline applied to requests without a "
                                 "deadline_ms field; expired requests shed "
                                 "503 + Retry-After instead of queueing "
                                 "(default: no deadline)")
        parser.add_argument("--retry-budget", type=float, default=None,
                            help="global retry budget: failover retries "
                                 "capped at this fraction of recent "
                                 "requests, e.g. 0.1 (default: unlimited)")
        parser.add_argument("--retry-backoff-ms", type=float, default=None,
                            help="base exponential backoff between failover "
                                 "attempts, with +/-50%% jitter (default 0 "
                                 "= immediate ring-order march)")
        parser.add_argument("--hedge", action="store_true",
                            help="hedged dispatch for idempotent ops: when "
                                 "the primary lane exceeds the hedge "
                                 "latency quantile, fire the next lane and "
                                 "take the first response")
        parser.add_argument("--hedge-quantile", type=float, default=None,
                            help="latency quantile that arms a hedge "
                                 "(default 0.95)")
        parser.add_argument("--hedge-min-ms", type=float, default=None,
                            help="floor under the hedge threshold; also "
                                 "the threshold until enough samples "
                                 "(default 50)")
        parser.add_argument("--max-queue-depth", type=int, default=None,
                            help="per-lane admission cap: concurrent "
                                 "requests beyond this shed 503 "
                                 "(default 0 = unbounded)")
        # -- adaptive overload control (DESIGN.md "Overload control";
        # every knob defaults off = behavior above unchanged) ------------
        parser.add_argument("--overload-control", action="store_true",
                            help="gateway overload control: "
                                 "priority-tiered admission (requests "
                                 "carry priority: interactive | batch | "
                                 "background; lowest tier sheds first "
                                 "as --overload-max-inflight fills) and "
                                 "load-derived Retry-After on sheds")
        parser.add_argument("--overload-max-inflight", type=int,
                            default=None,
                            help="gateway in-flight gauge the tier "
                                 "fractions admit against (background "
                                 "sheds at 70%%, batch at 85%%, "
                                 "interactive at 100%%; 0 = no gauge)")
        parser.add_argument("--tenant-rate", type=float, default=None,
                            help="per-tenant token bucket: each tenant "
                                 "(request \"tenant\" key) sustains this "
                                 "many requests/s; excess sheds 503 with "
                                 "the bucket's refill time as "
                                 "Retry-After (0 = off)")
        parser.add_argument("--tenant-burst", type=float, default=None,
                            help="token-bucket depth per tenant "
                                 "(default 0 = auto: 2x rate)")
        parser.add_argument("--priority-admission", action="store_true",
                            help="worker lanes shed lowest-priority-tier "
                                 "first under depth pressure (tier "
                                 "fractions of the lane's concurrency "
                                 "limit)")
        parser.add_argument("--adaptive-depth", action="store_true",
                            help="AIMD adaptive concurrency limit per "
                                 "lane: replaces the static "
                                 "--max-queue-depth cap with a limit "
                                 "driven by observed latency vs the "
                                 "sliding-window baseline")
        parser.add_argument("--brownout", action="store_true",
                            help="staged brownout: a per-lane control "
                                 "loop reads saturation signals (tick "
                                 "age, queue depth, pool starvation, "
                                 "deadline misses) and degrades "
                                 "gracefully — shrink the mixed token "
                                 "budget, suspend speculation, defer "
                                 "host-tier swap-ins, clamp low-tier "
                                 "token budgets — before any shed, "
                                 "restoring in reverse as pressure "
                                 "clears")
        parser.add_argument("--brownout-clamp-tokens", type=int,
                            default=None,
                            help="stage-4 max_new_tokens ceiling for "
                                 "below-top-tier generate requests "
                                 "(default 32)")
        parser.add_argument("--failover-streams", action="store_true",
                            help="crash-tolerant streaming: journal "
                                 "/generate/stream token events and resume "
                                 "a mid-stream lane failure on another "
                                 "ring lane (prompt + emitted tokens, "
                                 "budget offset), splicing one seamless "
                                 "byte-identical stream")
        parser.add_argument("--migrate-streams", action="store_true",
                            help="live stream migration: graceful lane "
                                 "removal exports each in-flight stream's "
                                 "KV block chain + state and resumes it "
                                 "mid-stream on another lane with zero "
                                 "re-prefilled tokens (failures fall back "
                                 "to the replay resume; implies the "
                                 "stream journal)")
        parser.add_argument("--migrate-timeout", type=float, default=None,
                            help="per-stream migration transfer budget in "
                                 "seconds, clamped to the stream's "
                                 "original deadline (default 30)")
        parser.add_argument("--drain-timeout", type=float, default=None,
                            help="graceful-drain acknowledgment bound in "
                                 "seconds: a wedged lane's drain call is "
                                 "abandoned (counted) and removal "
                                 "proceeds (default 10)")
        parser.add_argument("--health-probe-interval", type=float,
                            default=None,
                            help="proactive lane health prober: probe each "
                                 "lane's health at this interval, ejecting "
                                 "lanes after 3 consecutive failures and "
                                 "restoring them on recovery (seconds; "
                                 "default off)")
        parser.add_argument("--scheduler-stall-s", type=float, default=None,
                            help="decode-loop liveness threshold: a "
                                 "continuous scheduler whose loop has not "
                                 "ticked for this long reads unhealthy in "
                                 "/health (wedged-device detection; set "
                                 "above the worst first-request compile; "
                                 "default off — age is reported either "
                                 "way)")
        parser.add_argument("--native-front", choices=["auto", "on", "off"],
                            default="auto",
                            help="serving edge: the C++ HttpFront when "
                                 "available (auto), required (on), or the "
                                 "Python front (off — required for "
                                 "incremental SSE streaming granularity; "
                                 "the C++ front ships a stream as one "
                                 "buffered body)")
        parser.add_argument("--gen-scheduler",
                            choices=["batch", "continuous", "speculative"],
                            default="continuous",
                            help="decode scheduling: continuous "
                                 "(iteration-level admission), "
                                 "batch-to-completion, or speculative "
                                 "(draft-model proposals verified by the "
                                 "target in one windowed pass; temperature "
                                 "sampling only)")
        parser.add_argument("--gen-draft-model", default=None,
                            help="draft model for --gen-scheduler "
                                 "speculative (default: auto, e.g. "
                                 "gpt2 -> distilgpt2)")
        parser.add_argument("--gen-draft-path", default=None,
                            help="draft model weights checkpoint")
        parser.add_argument("--gen-spec-k", type=int, default=4,
                            help="speculation depth: draft tokens proposed "
                                 "per verify round")
        parser.add_argument("--gen-decode-fused", action="store_true",
                            help="batch scheduler: whole decode loop as "
                                 "one dispatch (zero per-chunk host "
                                 "syncs; identical streams)")
        parser.add_argument("--no-unified-stateless", action="store_true",
                            help="retire the unified stateless lane: "
                                 "route /predict misses and /score "
                                 "through the legacy dedicated batch "
                                 "processor instead of single-tick rows "
                                 "in the continuous scheduler (default: "
                                 "unified — one slot pool, one set of "
                                 "deadlines/brownout/counters for every "
                                 "request class)")
        parser.add_argument("--gen-prefill-chunk", type=int, default=256,
                            help="chunked prefill window (continuous "
                                 "scheduler): longer prompts admit in "
                                 "window dispatches so decode interleaves "
                                 "(0 disables)")
        parser.add_argument("--gen-prefix-cache-mb", type=int, default=64,
                            help="continuous-scheduler prefix cache budget "
                                 "(device KV MB; repeated prompts skip "
                                 "prefill; 0 disables)")
        parser.add_argument("--kv-block-size", type=int, default=0,
                            help="paged KV cache (continuous scheduler): "
                                 "columns per block, e.g. 16 or 32. Rows "
                                 "reserve blocks for the tokens they hold "
                                 "instead of max_seq each — several times "
                                 "more concurrent rows at the same HBM. "
                                 "0 (default) keeps the dense cache")
        parser.add_argument("--kv-blocks", type=int, default=0,
                            help="paged pool size in blocks (0 = auto: "
                                 "the dense layout's capacity)")
        parser.add_argument("--kv-host-blocks", type=int, default=0,
                            help="hierarchical host-RAM KV tier (needs "
                                 "--kv-block-size + prefix sharing): LRU "
                                 "eviction demotes cold radix prefixes to "
                                 "this many pinned host-RAM blocks, and a "
                                 "radix hit on a demoted prefix swaps the "
                                 "blocks back in asynchronously instead "
                                 "of recomputing its prefill — host RAM "
                                 "becomes prefix-cache capacity "
                                 "(tests/test_kv_offload.py). "
                                 "0 = off")
        parser.add_argument("--kv-quantize", default="",
                            choices=("", "int8"),
                            help="quantized KV blocks (needs "
                                 "--kv-block-size): store block payloads "
                                 "int8 with per-(slot, kv-head) f32 "
                                 "scales, quantized once at block write "
                                 "and dequantized inside the paged "
                                 "attention read — ~2x blocks on the same "
                                 "HBM (tests/test_kv_quant.py). "
                                 "Greedy streams stay deterministic but "
                                 "are not byte-identical to the bf16 "
                                 "pool. Default off = today's pool")
        parser.add_argument("--state-rows", type=int, default=0,
                            help="recurrent state slab pool capacity in "
                                 "rows (state_slab-family models, e.g. "
                                 "mamba2/ssd-small-test: each live "
                                 "stream owns ONE fixed-size "
                                 "(n_layers, state_dim) f32 row for its "
                                 "whole life — peak concurrent rows are "
                                 "independent of sequence length, "
                                 "tests/test_ssd.py. "
                                 "0 = auto: decode slots + 1)")
        parser.add_argument("--tp", type=int, default=None,
                            help="tensor-parallel serving (needs "
                                 "--kv-block-size): every lane serves "
                                 "the model sharded over this many "
                                 "local devices on a `model`-axis mesh "
                                 "— registry-declared param placement, "
                                 "H_kv-sharded KV pool, one SPMD "
                                 "ragged dispatch per tick (tests/"
                                 "test_tp_serving.py); default lane "
                                 "count becomes devices//tp; "
                                 "unshardable families (mamba2) "
                                 "refuse at startup (unset/1 = "
                                 "single-device lanes)")
        parser.add_argument("--prefix-affinity", action="store_true",
                            help="gateway: route /generate(+/stream) on a "
                                 "block-aligned prompt-prefix fingerprint "
                                 "instead of request_id so shared prefixes "
                                 "converge on the lane whose radix tree "
                                 "already holds the blocks; falls back to "
                                 "ring order when the affinity lane is "
                                 "ejected, broken, or imbalanced")
        parser.add_argument("--affinity-block-size", type=int, default=None,
                            help="fingerprint block granularity (defaults "
                                 "to --kv-block-size when paged, else 16)")
        parser.add_argument("--affinity-prefix-blocks", type=int,
                            default=None,
                            help="leading blocks the fingerprint covers "
                                 "(default 4)")
        parser.add_argument("--affinity-max-imbalance", type=int,
                            default=None,
                            help="skip the affinity lane (ring order) once "
                                 "it is this many recent dispatches hotter "
                                 "than its least-loaded ring peer "
                                 "(default 0 = always honor affinity)")
        parser.add_argument("--prefix-sharing", choices=["on", "off"],
                            default="on",
                            help="block-level radix prefix sharing (paged "
                                 "mode): shared prompt prefixes reuse "
                                 "already-filled KV blocks and skip their "
                                 "prefill compute")
        parser.add_argument("--prefix-fetch", action="store_true",
                            help="fleet-wide prefix tier (needs "
                                 "--kv-block-size + prefix sharing): the "
                                 "gateway keeps a fingerprint->owner-lane "
                                 "directory and stamps generate dispatches "
                                 "with a prefix_hint; a lane admitting a "
                                 "local radix miss pulls the owner's KV "
                                 "chain peer-to-peer (checksum-verified) "
                                 "instead of re-prefilling — every "
                                 "failure falls back to local prefill "
                                 "(tests/test_fleet_prefix.py)")
        parser.add_argument("--prefix-fetch-timeout", type=float,
                            default=None,
                            help="per-fetch peer budget in seconds "
                                 "(default 5)")
        parser.add_argument("--mixed-token-budget", type=int, default=0,
                            help="new tokens per mixed tick (decode rows "
                                 "count 1 each; the rest splits over "
                                 "admitting rows' chunks and caps the "
                                 "compiled chunk width). 0 = auto "
                                 "(--gen-prefill-chunk)")
        parser.add_argument("--spec-k", type=int, default=0,
                            help="continuous speculative decoding (needs "
                                 "--kv-block-size): a drafter proposes up "
                                 "to "
                                 "this many tokens per decode row per tick "
                                 "and the tick's ONE ragged dispatch "
                                 "verifies every window — rows advance "
                                 "1..k+1 tokens per dispatch, greedy "
                                 "streams byte-identical to plain decode "
                                 "(tests/test_spec_decoding.py). 0 = off")
        parser.add_argument("--spec-draft", choices=["ngram", "model"],
                            default="ngram",
                            help="drafter for --spec-k: ngram = host-side "
                                 "prompt-lookup (no second model, no extra "
                                 "dispatches; default), model = greedy "
                                 "proposals from --gen-draft-model (one "
                                 "draft dispatch per drafted row per tick)")
        parser.add_argument("--quantize", choices=["int8"], default=None,
                            help="weight-only quantization: dense/conv "
                                 "kernels stored int8 with per-channel "
                                 "scales (halves weight HBM traffic)")
        parser.add_argument("--role", default="both",
                            choices=("prefill", "decode", "both"),
                            help="serving role for EVERY lane (see "
                                 "--lane-roles for a split in-process "
                                 "fleet; dedicated roles need "
                                 "--kv-block-size)")
        parser.add_argument("--lane-roles", default=None,
                            help="disaggregated in-process fleet: "
                                 "comma-separated per-lane roles, e.g. "
                                 "prefill,prefill,decode,decode "
                                 "(assigned round-robin; overrides "
                                 "--role; pair with --disagg)")
        parser.add_argument("--disagg", action="store_true",
                            help="role-aware gateway: land fresh "
                                 "/generate(+/stream) work on prefill "
                                 "lanes and ship each finished KV chain "
                                 "to a decode lane picked by load — "
                                 "zero re-prefilled tokens, every "
                                 "failure falls back to local decode "
                                 "or the replay resume (tests/"
                                 "test_disagg.py)")
        parser.add_argument("--handoff-timeout", type=float, default=None,
                            help="per-stream prefill→decode handoff "
                                 "budget in seconds, clamped to the "
                                 "stream's deadline (default 30)")
        _add_autoscale_flags(parser)
        _add_slo_flags(parser)
        _add_flight_flags(parser)
        args = parser.parse_args(rest)
        gw_kw = {}
        if args.breaker_timeout is not None:
            gw_kw["breaker_timeout_s"] = args.breaker_timeout
        if args.default_deadline_ms is not None:
            gw_kw["default_deadline_ms"] = args.default_deadline_ms
        if args.retry_budget is not None:
            gw_kw["retry_budget_ratio"] = args.retry_budget
        if args.retry_backoff_ms is not None:
            gw_kw["retry_backoff_base_ms"] = args.retry_backoff_ms
        if args.hedge:
            gw_kw["hedge_enabled"] = True
        if args.hedge_quantile is not None:
            gw_kw["hedge_quantile"] = args.hedge_quantile
        if args.hedge_min_ms is not None:
            gw_kw["hedge_min_ms"] = args.hedge_min_ms
        if args.failover_streams:
            gw_kw["failover_streams"] = True
        if args.migrate_streams:
            gw_kw["migrate_streams"] = True
        if args.migrate_timeout is not None:
            gw_kw["migrate_timeout_s"] = args.migrate_timeout
        if args.drain_timeout is not None:
            gw_kw["drain_timeout_s"] = args.drain_timeout
        if args.health_probe_interval is not None:
            gw_kw["health_probe_interval_s"] = args.health_probe_interval
        if args.overload_control:
            gw_kw["overload_control"] = True
        if args.overload_max_inflight is not None:
            gw_kw["overload_max_inflight"] = args.overload_max_inflight
        if args.tenant_rate is not None:
            gw_kw["tenant_rate"] = args.tenant_rate
        if args.tenant_burst is not None:
            gw_kw["tenant_burst"] = args.tenant_burst
        if args.prefix_affinity:
            gw_kw["prefix_affinity"] = True
            # Fingerprint granularity defaults to the lanes' actual block
            # size — a mismatched pair would converge requests that share
            # no reusable blocks (or scatter ones that do).
            if args.affinity_block_size is not None:
                gw_kw["affinity_block_size"] = args.affinity_block_size
            elif args.kv_block_size > 0:
                gw_kw["affinity_block_size"] = args.kv_block_size
            if args.affinity_prefix_blocks is not None:
                gw_kw["affinity_prefix_blocks"] = args.affinity_prefix_blocks
            if args.affinity_max_imbalance is not None:
                gw_kw["affinity_max_imbalance"] = args.affinity_max_imbalance
        if args.prefix_fetch:
            # One flag arms BOTH halves in combined mode: the gateway's
            # directory + hint stamping and the lanes' peer fetch path.
            gw_kw["prefix_directory"] = True
            # The directory fingerprints at the lanes' REAL block size
            # even with affinity routing off — a mismatched granularity
            # would promise chains the radix trees don't share at.
            if "affinity_block_size" not in gw_kw and args.kv_block_size > 0:
                gw_kw["affinity_block_size"] = args.kv_block_size
        if args.disagg:
            gw_kw["disagg"] = True
        if args.handoff_timeout is not None:
            gw_kw["handoff_timeout_s"] = args.handoff_timeout
        _apply_autoscale_flags(args, gw_kw)
        _apply_slo_flags(args, gw_kw)
        gateway_config = None
        if gw_kw:
            from tpu_engine.utils.config import GatewayConfig

            gateway_config = GatewayConfig(port=args.port, **gw_kw)
        from tpu_engine.utils.config import WorkerConfig

        buckets = None
        if args.shape_buckets:
            buckets = tuple(
                tuple(int(d) for d in s.split("x"))
                for s in args.shape_buckets.split(","))
        bb_kw = {}
        if args.batch_buckets:
            bb_kw["batch_buckets"] = tuple(
                int(b) for b in args.batch_buckets.split(","))
            # The batcher flushes at the largest bucket — otherwise a
            # bigger compiled bucket could never fill.
            bb_kw["max_batch_size"] = max(bb_kw["batch_buckets"])
        if args.pipeline_depth is not None:
            bb_kw["pipeline_depth"] = args.pipeline_depth
        if args.cache_capacity is not None:
            bb_kw["cache_capacity"] = args.cache_capacity
        if args.batch_timeout_ms is not None:
            bb_kw["batch_timeout_ms"] = args.batch_timeout_ms
        if args.max_queue_depth is not None:
            bb_kw["max_queue_depth"] = args.max_queue_depth
        if args.tp is not None:
            bb_kw["tp"] = args.tp
        if args.scheduler_stall_s is not None:
            bb_kw["scheduler_stall_s"] = args.scheduler_stall_s
        if args.priority_admission:
            bb_kw["priority_admission"] = True
        if args.adaptive_depth:
            bb_kw["adaptive_depth"] = True
        if args.brownout:
            bb_kw["brownout"] = True
        if args.brownout_clamp_tokens is not None:
            bb_kw["brownout_clamp_tokens"] = args.brownout_clamp_tokens
        # One --trace-stitch flag arms BOTH halves in combined mode: the
        # gateway's ledger + payload injection and the lanes' snapshot /
        # chain trace headers.
        if args.trace_stitch:
            bb_kw["trace_stitch"] = True
        if args.prefix_fetch:
            bb_kw["gen_prefix_fetch"] = True
        if args.prefix_fetch_timeout is not None:
            bb_kw["gen_prefix_fetch_timeout_s"] = args.prefix_fetch_timeout
        if args.no_unified_stateless:
            bb_kw["unified_stateless"] = False
        _apply_flight_flags(args, bb_kw)
        worker_config = WorkerConfig(shape_buckets=buckets, **bb_kw,
                                     gen_scheduler=args.gen_scheduler,
                                     gen_draft_model=args.gen_draft_model,
                                     gen_draft_path=args.gen_draft_path,
                                     gen_spec_k=args.gen_spec_k,
                                     gen_prefix_cache_mb=args.gen_prefix_cache_mb,
                                     gen_prefill_chunk=args.gen_prefill_chunk,
                                     gen_kv_block_size=args.kv_block_size,
                                     gen_kv_blocks=args.kv_blocks,
                                     gen_kv_host_blocks=args.kv_host_blocks,
                                     gen_kv_quantize=args.kv_quantize,
                                     gen_prefix_sharing=(
                                         args.prefix_sharing == "on"),
                                     gen_mixed_token_budget=(
                                         args.mixed_token_budget),
                                     gen_continuous_spec_k=args.spec_k,
                                     gen_state_rows=args.state_rows,
                                     gen_spec_draft=args.spec_draft,
                                     gen_decode_fused=args.gen_decode_fused,
                                     quantize=args.quantize,
                                     role=args.role,
                                     model_path=args.model_path)
        native_front = {"auto": None, "on": True, "off": False}[
            args.native_front]
        lane_roles = None
        if args.lane_roles:
            lane_roles = [r.strip() for r in args.lane_roles.split(",")
                          if r.strip()]
        gw, workers, server = serve_combined(
            model=args.model, lanes=args.lanes, port=args.port,
            warmup=args.warmup, worker_config=worker_config,
            gateway_config=gateway_config, mesh=args.mesh,
            native_front=native_front, lane_roles=lane_roles)
        _run_forever([server, *workers, gw])
        return 0

    if cmd == "import-weights":
        # HF/torch checkpoint → orbax checkpoint serving artifact:
        #   import-weights --model gpt2 --src /path/to/hf_ckpt --out ckpt/
        # The orbax output then serves via `worker_node <port> <id> ckpt/`.
        parser = argparse.ArgumentParser(prog="import-weights")
        parser.add_argument("--model", required=True,
                            help="registry model name (gpt2, bert, resnet50-v1)")
        parser.add_argument("--src", required=True,
                            help="HF checkpoint dir, .safetensors, or torch .bin")
        parser.add_argument("--out", required=True)
        args = parser.parse_args(rest)
        from tpu_engine.models.import_weights import load_pretrained
        from tpu_engine.utils.checkpoint import save_params

        params = load_pretrained(args.model, args.src)
        path = save_params(args.out, params)
        print(f"imported {args.src} as {args.model} -> {path}")
        return 0

    if cmd == "train":
        # Causal-LM fine-tune loop (the reference is inference-only; the
        # TPU-native framework's sharded apply drives training too):
        #   train --model gpt2-small-test --steps 50 --out ckpt/
        #   train --mesh data=2,model=4 --remat ...       (sharded + remat)
        #   train --resume ckpt/state --out ckpt/         (exact resume)
        # Writes orbax train state to <out>/state and bare params to
        # <out>/params — the latter serves directly:
        #   worker_node 8001 w1 <out>/params
        parser = argparse.ArgumentParser(prog="train")
        parser.add_argument("--model", default="gpt2-small-test",
                            help="registry decoder LM (needs a "
                                 "TransformerConfig)")
        parser.add_argument("--steps", type=int, default=50)
        parser.add_argument("--batch", type=int, default=8)
        parser.add_argument("--seq", type=int, default=None,
                            help="train sequence length (default: the "
                                 "model's max_seq)")
        parser.add_argument("--lr", type=float, default=1e-3)
        parser.add_argument("--mesh", default=None,
                            help="e.g. data=2,model=4 — params TP-shard "
                                 "over model, batch over data; axis sizes "
                                 "must multiply to the local device count "
                                 "(pure DP on 8 chips: data=8)")
        parser.add_argument("--remat", action="store_true",
                            help="jax.checkpoint each block (activation "
                                 "HBM ~ one layer instead of all L)")
        parser.add_argument("--data", default=None,
                            help=".npy int32 token array (N, seq+1); "
                                 "default: a fixed synthetic batch "
                                 "(memorization smoke)")
        parser.add_argument("--out", default=None,
                            help="checkpoint dir (state + params)")
        parser.add_argument("--resume", default=None,
                            help="train-state dir to resume from")
        parser.add_argument("--log-every", type=int, default=10)
        parser.add_argument("--seed", type=int, default=0)
        args = parser.parse_args(rest)

        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax

        from tpu_engine.models.registry import (
            _ensure_builtin_models_imported,
            create_model,
        )
        from tpu_engine.models.transformer import (
            TransformerConfig,
            transformer_apply,
        )
        from tpu_engine.training.train import (
            cross_entropy_loss,
            make_train_step,
            shard_params_tp,
        )
        from tpu_engine.utils.checkpoint import (
            load_train_state,
            save_params,
            save_train_state,
        )

        _ensure_builtin_models_imported()
        spec = create_model(args.model)
        cfg = spec.config
        if not isinstance(cfg, TransformerConfig) or not cfg.causal:
            print(f"'{args.model}' is not a causal-LM transformer")
            return 2
        seq = min(args.seq or cfg.max_seq, cfg.max_seq)

        def apply_fn(params, x, dtype=jnp.bfloat16):
            return transformer_apply(params, x.astype(jnp.int32), cfg,
                                     dtype=dtype, remat=args.remat)

        init_state, train_step = make_train_step(
            apply_fn, loss_fn=cross_entropy_loss,
            optimizer=optax.adamw(args.lr), dtype=jnp.float32)
        params = spec.init(jax.random.PRNGKey(args.seed))

        mesh = None
        if args.mesh:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from tpu_engine.serving.app import parse_mesh_spec

            mesh = parse_mesh_spec(args.mesh)

        def place(tree):
            """TP-shard 2-D kernels over `model` when the mesh has that
            axis (pure-DP meshes replicate params; the batch still shards
            over `data`)."""
            if mesh is None:
                return tree
            if "model" in mesh.shape:
                return jax.device_put(
                    tree, shard_params_tp(tree, mesh, "model"))
            return jax.device_put(
                tree, jax.tree.map(lambda _l: NamedSharding(mesh, P()),
                                   tree))

        params = place(params)
        state = jax.jit(init_state)(params)
        if args.resume:
            # load_train_state restores host arrays; re-place the WHOLE
            # state (opt_state mirrors the param tree) or a sharded mesh
            # run would silently train on full replicated copies.
            state = place(load_train_state(args.resume, like=state))
            print(f"resumed at step {int(state.step)}")

        if args.data:
            tokens = np.load(args.data).astype(np.int32)
            assert tokens.ndim == 2 and tokens.shape[1] >= seq + 1, \
                f"need (N, >= {seq + 1}) tokens, got {tokens.shape}"
        else:  # fixed synthetic batch: loss falling = the loop works
            tokens = np.random.default_rng(args.seed).integers(
                1, cfg.vocab, (args.batch, seq + 1)).astype(np.int32)

        jitted = jax.jit(train_step, donate_argnums=(0,))
        rng = np.random.default_rng(args.seed + 1)
        max_off = tokens.shape[1] - (seq + 1)
        for k in range(args.steps):
            rows = (rng.integers(0, tokens.shape[0], args.batch)
                    if args.data else np.arange(args.batch))
            # Random column offset: long --data documents train on every
            # window, not just their first seq+1 tokens.
            off = int(rng.integers(0, max_off + 1)) if max_off > 0 else 0
            window = tokens[rows, off:off + seq + 1]
            x = jnp.asarray(window[:, :-1], jnp.float32)
            y = jnp.asarray(window[:, 1:], jnp.int32)
            if mesh is not None:
                x = jax.device_put(x, NamedSharding(mesh, P("data", None)))
                y = jax.device_put(y, NamedSharding(mesh, P("data", None)))
            state, loss = jitted(state, x, y)
            if k % args.log_every == 0 or k == args.steps - 1:
                print(f"step {int(state.step)}: loss {float(loss):.4f}",
                      flush=True)
        if args.out:
            import json

            spath = save_train_state(os.path.join(args.out, "state"), state,
                                     overwrite=True)
            ppath = save_params(os.path.join(args.out, "params"),
                                state.params, overwrite=True)
            # Self-describing checkpoint: worker_node resolves the
            # architecture from this sidecar, so the reference launch line
            # `worker_node <port> <id> <ckpt>/params` needs no model flag.
            with open(os.path.join(ppath, "tpu_engine_model.json"),
                      "w") as f:
                json.dump({"model": args.model}, f)
            print(f"saved train state -> {spath}")
            print(f"saved servable params -> {ppath}")
        return 0

    if cmd == "save-checkpoint":
        # Initialize a model's params and persist them — gives model_path
        # launch lines (reference worker_node.cpp:154-168) a real artifact.
        parser = argparse.ArgumentParser(prog="save-checkpoint")
        parser.add_argument("--model", required=True)
        parser.add_argument("--out", required=True)
        parser.add_argument("--seed", type=int, default=0)
        args = parser.parse_args(rest)
        import jax

        from tpu_engine.models.registry import create_model, _ensure_builtin_models_imported
        from tpu_engine.utils.checkpoint import save_params

        _ensure_builtin_models_imported()
        spec = create_model(args.model)
        params = spec.init(jax.random.PRNGKey(args.seed))
        path = save_params(args.out, params)
        print(f"saved {args.model} params -> {path}")
        return 0

    print(f"unknown command '{cmd}' "
          "(expected worker_node | gateway | serve | train | "
          "save-checkpoint | import-weights)")
    return 2


if __name__ == "__main__":
    sys.exit(main())
