"""Process composition: wire workers/gateway to HTTP servers.

Three launchable shapes:

- ``serve_worker`` — one worker lane behind HTTP (reference
  ``worker_node <port> <node_id> [model]``, ``worker_node.cpp:145-204``);
- ``serve_gateway`` — routing gateway over remote HTTP workers (reference
  ``gateway <worker:port> ...``, ``gateway.cpp:161-200``);
- ``serve_combined`` — the TPU-native shape: one process, one HTTP front
  door, N in-process lanes pinned round-robin onto the local chips
  (SURVEY.md §7 design stance). No per-request HTTP between gateway and
  lanes; the hash ring selects a lane directly.
"""

from __future__ import annotations

import os
from typing import List, Optional, Tuple

from tpu_engine.serving.autoscaler import (InProcessLaneProvider,
                                           StandbyLaneProvider)
from tpu_engine.serving.gateway import Gateway
from tpu_engine.serving.http import JsonHttpServer
from tpu_engine.serving.worker import WorkerNode
from tpu_engine.utils.config import GatewayConfig, WorkerConfig
from tpu_engine.utils.deadline import ShedError
from tpu_engine.utils.metrics import render_prometheus
from tpu_engine.utils.tracing import export_chrome, stitch_trace


def model_from_path(path_or_name: str) -> str:
    """Map a reference-style model path (e.g. models/resnet50-v2-7.onnx) to a
    registry name so reference launch lines work unchanged."""
    from tpu_engine.models.registry import available_models, _ensure_builtin_models_imported

    _ensure_builtin_models_imported()
    names = available_models()
    if path_or_name in names:
        return path_or_name
    base = path_or_name.rsplit("/", 1)[-1].lower()
    for name in names:
        if name in base.replace("-", "").replace("_", ""):
            return name
    for name in names:  # resnet50-v2-7.onnx → resnet50
        if base.startswith(name[: max(4, len(name) - 2)]):
            return name
    raise ValueError(f"cannot map '{path_or_name}' to a registered model {names}")


def serve_worker(config: WorkerConfig, background: bool = True) -> Tuple[WorkerNode, JsonHttpServer]:
    worker = WorkerNode(config)
    server = JsonHttpServer(config.port)
    server.route("POST", "/infer", lambda body: (200, worker.handle_infer_raw(body)))
    server.route("POST", "/generate", lambda body: (200, worker.handle_generate(body)))
    server.route("POST", "/generate/stream",
                 lambda body: (200, worker.handle_generate_stream(body)))
    server.route("GET", "/health", lambda _body: (200, worker.get_health()))
    server.route("GET", "/metrics", lambda _body: (
        200, render_prometheus([worker.get_health()],
                               recorders={worker.node_id: worker.tracer},
                               named_hists=worker.latency_histograms()),
        "text/plain; version=0.0.4"))
    server.route("GET", "/trace", lambda _body: (200, {
        "summary": {worker.node_id: worker.tracer.summary()},
        "recent": worker.tracer.recent(20),
        "stages": {worker.node_id: worker.tracer.stage_summary()},
    }))
    server.route("GET", "/trace/export", lambda _body: (
        200, export_chrome({worker.node_id: worker.tracer})))
    server.route("POST", "/admin/reload", lambda body: (
        200, worker.reload_weights(body["model_path"])))
    server.route("POST", "/score", lambda body: (
        200, worker.handle_score(body)))

    # Drain (lame-duck): refuse new admissions with 503 + Retry-After while
    # in-flight work completes — the graceful half of removing a worker
    # from a gateway's ring (the reference's only removal is SIGKILL).
    def _admin_drain(body):
        action = (body or {}).get("action", "drain")
        if action == "drain":
            status = worker.drain()
        elif action == "undrain":
            status = worker.undrain()
        else:
            return 400, {"error": "action must be drain|undrain"}
        # "status" names the idempotent outcome (draining /
        # already-draining / undrained / not-draining) — double-drain
        # and undrain-of-idle answer it instead of re-running effects.
        return 200, {"ok": True, "node_id": worker.node_id,
                     "draining": worker.draining, "status": status}

    server.route("POST", "/admin/drain", _admin_drain)
    # Live stream migration (DESIGN.md): export one live stream's row —
    # the gateway's migrate-mode drain drives this per stream; the
    # continuation rides /generate/stream with a `migrate_import` body.
    server.route("POST", "/admin/migrate",
                 lambda body: (200, worker.handle_migrate_export(body or {})))
    # Fleet prefix tier (DESIGN.md "Fleet-wide prefix tier"): serve this
    # lane's longest radix chain matching a peer's token prefix — the
    # peer verifies checksum + geometry before trusting a byte, so the
    # export itself never refuses on trust grounds (only on drain /
    # non-paged / no-match, as named non-raising statuses).
    server.route("POST", "/admin/export_prefix",
                 lambda body: (200, worker.handle_export_prefix(body or {})))
    # Disaggregated serving: flip the lane's role at runtime (the
    # gateway's set_worker_role rides drain + migrate around this).
    server.route("POST", "/admin/role",
                 lambda body: (200, worker.set_role((body or {}).get(
                     "role", ""))))
    # Observability plane (DESIGN.md): the per-tick flight recorder
    # (GET = ring contents, POST {"dump": reason} = forced postmortem)
    # and the tick-bounded jax.profiler capture (needs --profile-dir;
    # POST {"ticks": N} | {"action": "stop"|"status"}).
    server.route("GET", "/admin/timeline",
                 lambda body: (200, worker.handle_timeline(body)))
    server.route("POST", "/admin/timeline",
                 lambda body: (200, worker.handle_timeline(body or {})))
    server.route("POST", "/admin/profile",
                 lambda body: (200, worker.handle_profile(body or {})))
    server.route("GET", "/admin/profile",
                 lambda body: (200, worker.handle_profile(
                     {"action": "status"})))
    # Cross-lane stitching, single-lane flavor: only this lane's
    # fragments (the gateway's /admin/trace merges the whole fleet).
    server.route_prefix(
        "GET", "/admin/trace/",
        lambda _body, rid: (200, stitch_trace(
            {worker.node_id: worker.tracer.snapshot()}, rid)))
    _print_worker_banner(worker, config)
    server.start(background=background)
    return worker, server


def serve_gateway(worker_urls: List[str], config: Optional[GatewayConfig] = None,
                  background: bool = True,
                  standby_workers: Optional[List[str]] = None,
                  ) -> Tuple[Gateway, JsonHttpServer]:
    """``standby_workers``: pre-launched worker ADDRESSES the elastic
    fleet controller may bring into (and out of) rotation — the warm
    pool behind ``--autoscale`` in gateway mode. They are NOT registered
    at startup; the probe gate admits them on scale-up."""
    config = config or GatewayConfig()
    gateway = Gateway(worker_urls, config)
    server = JsonHttpServer(config.port)
    server.route("POST", "/infer", lambda body: (200, gateway.route_request_raw(body)))
    server.route("POST", "/generate", lambda body: (200, gateway.route_generate(body)))
    server.route("POST", "/generate/stream",
                 lambda body: (200, gateway.route_generate_stream(body)))
    server.route("GET", "/stats", lambda _body: (200, gateway.get_stats()))
    server.route("POST", "/score", lambda body: (200, gateway.route_score(body)))
    server.route("GET", "/metrics", lambda _body: (
        200, render_prometheus([], gateway.get_stats(),
                               recorders={"gateway": gateway.tracer}),
        "text/plain; version=0.0.4"))
    server.route("GET", "/trace", lambda _body: (200, {
        "summary": {"gateway": gateway.tracer.summary()},
        "recent": gateway.tracer.recent(20),
        "stages": {"gateway": gateway.tracer.stage_summary()},
    }))
    server.route("GET", "/trace/export", lambda _body: (
        200, export_chrome({"gateway": gateway.tracer})))
    # Disaggregated serving: flip a lane's role fleet-side — the
    # gateway drains + migrates streams off the lane around the flip.
    server.route("POST", "/admin/role", lambda body: (
        200, gateway.set_worker_role((body or {}).get("node", ""),
                                     (body or {}).get("role", ""))))
    # Elastic fleet (DESIGN.md "Elastic fleet"): the operator surface —
    # status / add (probe-then-register) / remove (drain+migrate
    # retire) / rebalance (role flip) / clear (degraded state). Works
    # with or without --autoscale; every failure is a named,
    # non-raising status.
    server.route("POST", "/admin/fleet", lambda body: (
        200, gateway.fleet_admin(body or {})))
    # Observability plane: the merged cross-lane stitch (fragments
    # pulled from each lane's /trace/export — best-effort on dead
    # lanes) and the SLO burn status. Both answer with their flags
    # off: the stitch falls back to request_id correlation; /admin/slo
    # names the missing objectives instead of 404ing.
    server.route_prefix(
        "GET", "/admin/trace/",
        lambda _body, rid: (200, gateway.stitched_trace(rid)))
    server.route("GET", "/admin/slo", lambda _body: (
        200, gateway.slo_status()
        or {"error": "no objectives configured "
                     "(set --slo-ttft-p99-ms / --slo-itl-p99-ms / "
                     "--slo-completion-p99-ms)"}))
    if config.autoscale or standby_workers:
        gateway.engage_autoscaler(
            provider=StandbyLaneProvider(list(standby_workers or [])))
    print(f"Gateway listening on port {config.port}")
    print(f"Workers: {len(worker_urls)}")
    print("Circuit breakers enabled")
    print("Ready!")
    server.start(background=background)
    return gateway, server


def parse_mesh_spec(spec: str):
    """'data=8' / 'model=2,data=4' → Mesh over the local devices. A missing
    ``data`` axis is added with size 1 so the engine's batch-scatter axis
    always exists."""
    from tpu_engine.parallel.mesh import create_mesh

    axes = []
    for part in spec.split(","):
        name, _, size = part.partition("=")
        axes.append((name.strip(), int(size)))
    if "data" not in (n for n, _ in axes):
        axes.append(("data", 1))
    return create_mesh(shape=tuple(s for _, s in axes),
                       axis_names=tuple(n for n, _ in axes))


def _mesh_engine(model: str, lane_cfg: WorkerConfig, mesh, params=None):
    """One engine spanning the whole mesh: batches scatter over ``data``
    (ICI, XLA collectives — the north-star's in-process replacement for the
    reference's HTTP worker fan-out), weights shard over ``model`` when that
    axis is >1 (answering the reference's dead ``shard_id`` stub,
    worker_node.cpp:32)."""
    from tpu_engine.models.registry import create_model, _ensure_builtin_models_imported
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.training.train import shard_params_tp

    _ensure_builtin_models_imported()
    import jax

    spec = create_model(model)
    if params is None:
        params = spec.init(jax.random.PRNGKey(0))
    shardings = None
    if mesh.shape.get("model", 1) > 1:
        shardings = shard_params_tp(params, mesh, axis="model")
    return InferenceEngine(
        spec,
        params=params,
        dtype=lane_cfg.dtype,
        batch_buckets=lane_cfg.batch_buckets,
        shape_buckets=lane_cfg.shape_buckets,
        mesh=mesh,
        param_shardings=shardings,
    )


def serve_combined(
    model: str = "resnet50",
    lanes: int = 0,
    port: int = 8000,
    worker_config: Optional[WorkerConfig] = None,
    gateway_config: Optional[GatewayConfig] = None,
    background: bool = True,
    warmup: bool = False,
    native_front: Optional[bool] = None,
    mesh=None,
    lane_roles: Optional[List[str]] = None,
):
    """One process: HTTP front door + in-process lanes over local devices.

    ``lanes=0`` means one lane per local device. Lanes share nothing but the
    host process: each has its own cache, batcher and engine pinned to a chip
    (round-robin when lanes > devices).

    ``mesh`` (spec string like 'data=8' / 'model=2,data=4', or a
    jax.sharding.Mesh) switches to mesh-sharded serving: ONE lane whose
    engine spans all mesh devices — the dynamic batcher aggregates requests
    and each batch is scattered over the ``data`` axis / computed against
    ``model``-sharded weights in a single XLA dispatch.

    ``lane_roles`` (disaggregated serving): per-lane serving roles
    assigned round-robin, e.g. ["prefill", "prefill", "decode",
    "decode"] — pair with a ``--disagg`` gateway config so fresh
    generate work lands on prefill lanes and finished KV chains ship to
    decode lanes. None (default) uses ``worker_config.role`` uniformly.
    """
    import jax

    devices = jax.devices()
    gateway_config = gateway_config or GatewayConfig(port=port)
    # Multi-model serving: "a,b" assigns models to lanes round-robin;
    # requests carry {"model": "..."} and the gateway routes on per-model
    # sub-rings (Triton-style — the reference is one model per worker).
    models = [m.strip() for m in str(model).split(",") if m.strip()]
    if len(models) > 1 and worker_config is not None \
            and worker_config.model_path:
        raise ValueError("model_path is ambiguous with multiple models; "
                         "serve them from separate processes or extend "
                         "the config per model")
    # Real weights (HF/torch/orbax) are loaded once and shared by every lane
    # (each engine device_puts its own copy onto its chip).
    params = None
    if worker_config is not None and worker_config.model_path:
        from tpu_engine.serving.worker import _load_model_path

        params = _load_model_path(models[0], worker_config.model_path)
    workers = []
    if mesh is not None:
        if isinstance(mesh, str):
            mesh = parse_mesh_spec(mesh)
        if len(models) > 1:
            raise ValueError("mesh-sharded serving is single-model")
        cfg = worker_config or WorkerConfig()
        lane_cfg = WorkerConfig(**{**cfg.__dict__, "node_id": "worker_1",
                                   "model": models[0]})
        engine = _mesh_engine(models[0], lane_cfg, mesh, params=params)
        workers.append(WorkerNode(lane_cfg, engine=engine))
        n_lanes = 1
    else:
        if lanes and lanes < len(models):
            raise ValueError(
                f"lanes={lanes} cannot serve {len(models)} models — "
                f"later-listed models would silently get no lane")
        tp = int(getattr(worker_config, "tp", 1) or 1) \
            if worker_config is not None else 1
        if tp > 1:
            # Tensor-parallel lanes each span a tp-device mesh slice:
            # the default fleet is devices // tp lanes, not one per
            # chip (the "lanes are chips" rule becomes "virtual nodes
            # are chips" — the gateway ring weights them that way).
            n_lanes = lanes or max(1, len(devices) // tp, len(models))
        else:
            n_lanes = lanes or max(len(devices), len(models))
        if lane_roles and lanes and lanes < len(lane_roles):
            raise ValueError(
                f"lanes={lanes} cannot honor {len(lane_roles)} lane "
                f"roles — later-listed roles would silently get no lane")
        if lane_roles:
            n_lanes = max(n_lanes, len(lane_roles))
        for i in range(n_lanes):
            cfg = worker_config or WorkerConfig()
            over = {"node_id": f"worker_{i+1}",
                    "model": models[i % len(models)]}
            if lane_roles:
                over["role"] = lane_roles[i % len(lane_roles)]
            if tp > 1:
                # Disjoint mesh slices per lane (round-robin when an
                # explicit --lanes oversubscribes): lane i owns devices
                # [i*tp, (i+1)*tp) — without this every lane would
                # stack its mesh on devices [0, tp).
                n_slices = max(1, len(devices) // tp)
                over["tp_device_offset"] = (i % n_slices) * tp
            lane_cfg = WorkerConfig(**{**cfg.__dict__, **over})
            from tpu_engine.runtime.engine import InferenceEngine

            engine = InferenceEngine(
                lane_cfg.model,
                params=params,
                dtype=lane_cfg.dtype,
                batch_buckets=lane_cfg.batch_buckets,
                shape_buckets=lane_cfg.shape_buckets,
                quantize=lane_cfg.quantize,
                device=devices[i % len(devices)],
            )
            workers.append(WorkerNode(lane_cfg, engine=engine))
    # A fleet that moves live streams between lanes needs every
    # generative lane's family to ride the chain wire format: refused
    # here, by capability, not at the first drain.
    for flag, cap in (("migrate_streams", "migration"),
                      ("disagg", "handoff")):
        if not getattr(gateway_config, flag, False):
            continue
        for w in workers:
            spec = getattr(w.engine, "spec", None)
            if (spec is not None and "generate" in spec.capabilities
                    and not spec.supports(cap)):
                for lane in workers:
                    lane.stop()
                raise RuntimeError(
                    f"--{flag.replace('_', '-')} needs the '{cap}' "
                    f"capability, which model '{spec.name}' "
                    f"({spec.state_family} family) does not declare "
                    f"(the chain wire format carries a K and a V a head "
                    f"for every block of the row)")
    if warmup:
        # Pre-compile every batch bucket before accepting traffic — the
        # reference pays its graph compile at session load the same way
        # (inference_engine.cpp:31). Lanes pinned to the same device share
        # XLA's compile cache, so this is ~one compile per bucket. A
        # warm-up that raises is a FAILED START: a kernel the compiler
        # rejects here would reject every request after it, and a lane
        # that came up "ready" anyway would look healthy and idle.
        try:
            for w in workers:
                w.engine.warmup()
                if getattr(w.generator, "_stateless", False):
                    # Stateless-family scheduler: no generation lane to
                    # warm — engine.warmup() above already compiled every
                    # one-shot bucket the single-tick rows dispatch into.
                    continue
                if w.generator is not None:
                    # Also compile the generation lane (smallest prompt
                    # bucket + one decode chunk) — a cold /generate
                    # otherwise pays tens of seconds of XLA compiles on
                    # its first request. Straight to the generator: the
                    # worker's request path would pollute the
                    # reference-exact /health counters and the trace with
                    # a phantom request.
                    w.generator.generate([[1, 2, 3]], max_new_tokens=2)
        except BaseException:
            for w in workers:
                w.stop()
            raise
    gateway = Gateway(workers, gateway_config)
    # Fleet prefix tier, combined-mode transport: in-process lanes have
    # no URL to dial, so a peer fetch is a direct handle_export_prefix
    # call on the owning lane object. Any lookup/shape surprise raises
    # and the caller classifies it as peer_unreachable (local prefill).
    prefix_fetch_on = bool(worker_config is not None
                           and getattr(worker_config,
                                       "gen_prefix_fetch", False))

    def _peer_export(hint, payload):
        lane = hint.get("lane")
        for w in list(workers):
            if w.node_id == lane:
                return w.handle_export_prefix(payload)
        raise KeyError(f"no in-process lane named {lane!r}")

    if prefix_fetch_on:
        for w in workers:
            w.set_prefix_fetch_transport(_peer_export)
    if gateway_config.autoscale and mesh is None:
        # Elastic fleet in combined mode: the provider mints fresh
        # in-process lanes with the same config/device round-robin the
        # startup loop used (indices continue past the static fleet so
        # names never collide), and retired lanes are stopped and
        # dropped from the per-lane surfaces.
        from tpu_engine.runtime.engine import InferenceEngine

        base_lanes = n_lanes

        def _spawn_lane(idx):
            i = base_lanes + idx
            cfg = worker_config or WorkerConfig()
            over = {"node_id": f"worker_{i+1}",
                    "model": models[i % len(models)]}
            if lane_roles:
                over["role"] = lane_roles[i % len(lane_roles)]
            if tp > 1:
                n_slices = max(1, len(devices) // tp)
                over["tp_device_offset"] = (i % n_slices) * tp
            lane_cfg = WorkerConfig(**{**cfg.__dict__, **over})
            engine = InferenceEngine(
                lane_cfg.model,
                params=params,
                dtype=lane_cfg.dtype,
                batch_buckets=lane_cfg.batch_buckets,
                shape_buckets=lane_cfg.shape_buckets,
                quantize=lane_cfg.quantize,
                device=devices[i % len(devices)],
            )
            w = WorkerNode(lane_cfg, engine=engine)
            if prefix_fetch_on:
                w.set_prefix_fetch_transport(_peer_export)
            workers.append(w)
            return w

        def _drop_lane(w):
            try:
                workers.remove(w)
            except ValueError:
                pass

        gateway.engage_autoscaler(provider=InProcessLaneProvider(
            _spawn_lane,
            max_lanes=gateway_config.autoscale_max_lanes,
            on_retire=_drop_lane))
    routes = {}
    routes[("POST", "/infer")] = lambda body: (200, gateway.route_request_raw(body))
    routes[("POST", "/generate")] = lambda body: (200, gateway.route_generate(body))
    routes[("POST", "/generate/stream")] = (
        lambda body: (200, gateway.route_generate_stream(body)))

    def _stats(_body):
        """Gateway /stats, plus per-lane paged-KV pool, mixed-step, and
        speculative-decoding health when a decode lane runs them
        (additive keys; the reference-exact schema is untouched for
        dense deployments)."""
        out = gateway.get_stats()
        kv, mixed, spec, state, pfetch = {}, {}, {}, {}, {}
        stateless, weights = {}, {}
        for w in workers:
            gen = getattr(w, "generator", None)
            if gen is None or not hasattr(gen, "stats"):
                continue
            try:
                st = gen.stats()
            except Exception:
                continue
            if st.get("stateless", {}).get("dispatches"):
                # Unified stateless serving: one-shot row counters per
                # lane, present only once a lane actually dispatched a
                # single-tick row (defaults-off /stats is untouched).
                stateless[w.node_id] = st["stateless"]
            if st.get("kv_pool"):
                kv[w.node_id] = st["kv_pool"]
            if st.get("weights", {}).get("step_bytes"):
                # Lanes that keep a copy of the step's kernels in the
                # step's dtype beside the master tree (absent where the
                # steps read the master itself).
                weights[w.node_id] = st["weights"]
            if st.get("prefix_fetch"):
                # Fleet prefix tier, lane half: peer-fetch attempts and
                # fallback rungs per lane (present only once a hint was
                # acted on — defaults-off /stats is untouched).
                pfetch[w.node_id] = st["prefix_fetch"]
            if st.get("state_pool"):
                # state_slab-family lanes (models.ssd): the kv_pool
                # analog — gated the same way, absent on kv_paged
                # fleets.
                state[w.node_id] = st["state_pool"]
            if st.get("mixed"):
                mixed[w.node_id] = dict(st["mixed"],
                                        active=st.get("active"))
            if st.get("spec"):
                spec[w.node_id] = dict(st["spec"],
                                       active=st.get("active"))
        if kv:
            out["kv_pool"] = kv
        if state:
            out["state_pool"] = state
        if mixed:
            out["mixed"] = mixed
        if spec:
            out["spec"] = spec
        if pfetch:
            out["prefix_fetch"] = pfetch
        if stateless:
            out["stateless"] = stateless
        if weights:
            out["weights"] = weights
        return 200, out

    routes[("GET", "/stats")] = _stats
    # Lane health is addressable through the gateway process in combined mode.
    for w in workers:
        routes[("GET", f"/health/{w.node_id}")] = lambda _b, w=w: (200, w.get_health())

    def _aggregate_health(_b):
        """Whole-process /health: counters summed over lanes (so reference
        tooling scraping one worker URL per process reports truthfully),
        plus a per-lane breakdown. Field names stay reference-exact."""
        lanes_h = [w.get_health() for w in workers]
        total = sum(h["total_requests"] for h in lanes_h)
        hits = sum(h["cache_hits"] for h in lanes_h)
        bp_keys = ("total_batches", "timeout_batches", "full_batches")
        bp = {k: sum(h["batch_processor"][k] for h in lanes_h) for k in bp_keys}
        n_batches = bp["total_batches"]
        bp["avg_batch_size"] = round(
            sum(h["batch_processor"]["avg_batch_size"]
                * h["batch_processor"]["total_batches"]
                for h in lanes_h) / n_batches, 4) if n_batches else 0.0
        agg_hit_rate = (sum(h["cache_hit_rate"] * h["total_requests"]
                            for h in lanes_h) / total) if total else 0.0
        return 200, {
            "healthy": all(h["healthy"] for h in lanes_h),
            "node_id": lanes_h[0]["node_id"] if len(lanes_h) == 1 else "combined",
            "total_requests": total,
            "cache_hits": hits,
            "cache_size": sum(h["cache_size"] for h in lanes_h),
            "cache_hit_rate": round(agg_hit_rate, 6),
            "batch_processor": bp,
            "lanes": {h["node_id"]: h for h in lanes_h},
        }

    routes[("GET", "/health")] = _aggregate_health

    # Fault injection (BASELINE config 5). The reference injects faults by
    # killing worker processes (README.md:322-349); in-process lanes expose
    # an explicit admin hook instead: {"node": "worker_1", "action":
    # "fail"|"heal"|"slow"}. "slow" adds {"latency_s": X} of delay per
    # request WITHOUT failing — the slow-lane fault breakers cannot see,
    # which the resilience layer (deadlines/hedging) exists to answer.
    def _admin_fault(body):
        node = body.get("node")
        action = body.get("action", "fail")
        targets = [w for w in workers if w.node_id == node or node in (None, "*")]
        if not targets:
            return 404, {"error": f"unknown node '{node}'"}
        for w in targets:
            if action == "fail":
                w.inject_fault()
            elif action == "slow":
                w.inject_latency(float(body.get("latency_s", 1.0)))
            else:
                w.heal()
        return 200, {"ok": True, "nodes": [w.node_id for w in targets],
                     "action": action}

    routes[("POST", "/admin/fault")] = _admin_fault

    # Drain (lame-duck) mode: {"node": "worker_1"|"*", "action":
    # "drain"|"undrain", "remove": false}. "remove": true additionally
    # takes the drained lane off the hash ring (graceful removal — the
    # resilience-layer answer to the reference's kill-the-process).
    def _admin_drain(body):
        node = body.get("node")
        action = body.get("action", "drain")
        if action not in ("drain", "undrain"):
            return 400, {"error": "action must be drain|undrain"}
        targets = [w for w in workers
                   if w.node_id == node or node in (None, "*")]
        if not targets:
            # Named, non-raising: draining a lane that is not a member
            # is an idempotent no-op (it may have been retired between
            # the operator's read and this call), not a 404 surprise.
            return 200, {"ok": False, "status": "unknown-lane",
                         "node": node}
        for w in targets:
            if action == "drain":
                if body.get("remove") and gateway.config.migrate_streams:
                    # Migrate-mode graceful removal: remove_worker owns
                    # the whole ladder — bounded drain, per-stream KV
                    # handoff, then ring removal (DESIGN.md "Live
                    # stream migration").
                    gateway.remove_worker(w.node_id, drain=True)
                    continue
                w.drain()
                if body.get("remove"):
                    # Already drained above — plain ring removal (the
                    # drain=True flavor would drain the same lane twice).
                    gateway.remove_worker(w.node_id)
            else:
                w.undrain()
        return 200, {"ok": True, "action": action,
                     "nodes": [w.node_id for w in targets],
                     "removed": bool(body.get("remove"))
                     and action == "drain"}

    routes[("POST", "/admin/drain")] = _admin_drain

    # Role flips (disaggregated serving): {"node": "worker_1", "role":
    # "prefill"|"decode"|"both"} — the gateway rides /admin/drain +
    # stream migration around the flip so live streams move, not break.
    def _admin_role(body):
        node = (body or {}).get("node")
        role = (body or {}).get("role", "")
        if not any(w.node_id == node for w in workers):
            return 404, {"error": f"unknown node '{node}'"}
        return 200, gateway.set_worker_role(node, role)

    routes[("POST", "/admin/role")] = _admin_role

    # Elastic fleet operator surface (DESIGN.md "Elastic fleet") —
    # status / add / remove / rebalance / clear; named, non-raising
    # statuses. Active with or without --autoscale.
    routes[("POST", "/admin/fleet")] = lambda body: (
        200, gateway.fleet_admin(body or {}))

    # Tracing (SURVEY.md §5: the reference has only per-request wall
    # clocks). "summary"/"recent" keep the original schema; "gateway" and
    # "stages" (per-stage queue_wait / batch_form / device_compute
    # breakdown, scraped by bench.py) are additive.
    def _trace(_body):
        return 200, {
            "summary": {w.node_id: w.tracer.summary() for w in workers},
            "recent": [s for w in workers for s in w.tracer.recent(20)],
            "gateway": gateway.tracer.summary(),
            "stages": {w.node_id: w.tracer.stage_summary()
                       for w in workers},
        }

    def _trace_export(_body):
        recs = {w.node_id: w.tracer for w in workers}
        recs["gateway"] = gateway.tracer
        return 200, export_chrome(recs)

    def _admin_profile(body):
        from tpu_engine.utils import tracing

        body = body or {}
        if body.get("action") == "start":
            return 200, tracing.profiler_start(body.get("log_dir", "/tmp/tpu_engine_profile"))
        if body.get("action") == "stop":
            return 200, tracing.profiler_stop()
        # Tick-bounded capture (observability plane): {"ticks": N
        # [, "node": id]} arms ONE lane's scheduler to stop the trace
        # after exactly N ticks (needs the lane's --profile-dir).
        # {"action": "status"} reports ticks left + the last capture.
        node = body.get("node")
        targets = [w for w in workers
                   if node in (None, "*") or w.node_id == node]
        if not targets:
            return 404, {"error": f"unknown node '{node}'"}
        if body.get("action") == "status" or body.get("ticks"):
            return 200, targets[0].handle_profile(body)
        return 400, {"error": "action must be start|stop|status, "
                              "or pass ticks"}

    # Flight recorder (observability plane): GET = every lane's tick
    # ring; POST {"dump": reason[, "node": id]} = forced postmortem.
    def _admin_timeline(body):
        body = body or {}
        node = body.get("node")
        targets = [w for w in workers
                   if node in (None, "*") or w.node_id == node]
        if not targets:
            return 404, {"error": f"unknown node '{node}'"}
        return 200, {"lanes": {w.node_id: w.handle_timeline(body)
                               for w in targets}}

    routes[("GET", "/trace")] = _trace
    routes[("GET", "/trace/export")] = _trace_export
    routes[("POST", "/admin/profile")] = _admin_profile
    routes[("GET", "/admin/timeline")] = _admin_timeline
    routes[("POST", "/admin/timeline")] = _admin_timeline
    def _named_hists():
        named = {}
        for w in workers:
            for name, by_node in w.latency_histograms().items():
                named.setdefault(name, {}).update(by_node)
        return named

    routes[("GET", "/metrics")] = lambda _b: (
        200, render_prometheus([w.get_health() for w in workers],
                               gateway.get_stats(),
                               recorders={**{w.node_id: w.tracer
                                             for w in workers},
                                          "gateway": gateway.tracer},
                               named_hists=_named_hists()),
        "text/plain; version=0.0.4")

    # Hot weight reload (no serving pause; the reference restarts worker
    # processes to change weights). {"model_path": ..., "node": optional,
    # "model": optional} — all lanes by default. The checkpoint loads from
    # disk ONCE; each lane then swaps independently, and per-node outcomes
    # are reported even on partial failure (an error mid-fleet must not
    # hide which lanes already serve the new weights). In a multi-model
    # deployment a bare reload is ambiguous — the checkpoint is loaded
    # against ONE architecture, and two models that happen to share tree
    # structure/shapes would silently accept each other's weights (swap
    # validates only treedef/shape/dtype) — so the caller must name the
    # target with "model" or "node" when more than one model is served.
    def _admin_reload(body):
        from tpu_engine.serving.worker import _load_model_path

        node = body.get("node")
        targets = [w for w in workers
                   if node in (None, "*") or w.node_id == node]
        if not targets:
            return 404, {"error": f"unknown node '{node}'"}
        model = body.get("model")
        if model is not None:
            targets = [w for w in targets
                       if getattr(w.engine.spec, "name", None) == model]
            if not targets:
                return 404, {"error": f"no lane serves model '{model}'"}
        else:
            served = {getattr(w.engine.spec, "name", None) for w in targets}
            if len(served) > 1:
                return 400, {"error":
                             "multiple models served "
                             f"({sorted(str(s) for s in served)}): "
                             "pass 'model' or 'node' to pick the target"}
        path = body["model_path"]
        params = _load_model_path(targets[0].engine.spec, path)
        if params is None:
            return 400, {"error": f"no loadable weights at '{path}'"}
        outcomes, ok = [], True
        for w in targets:
            try:
                outcomes.append(w.apply_weights(params, source=path))
            except Exception as exc:
                ok = False
                outcomes.append({"ok": False, "node_id": w.node_id,
                                 "error": str(exc)[:300]})
        return (200 if ok else 500), {"ok": ok, "reloaded": outcomes}

    routes[("POST", "/admin/reload")] = _admin_reload
    routes[("POST", "/score")] = (
        lambda body: (200, gateway.route_score(body)))
    # Observability plane: SLO burn over the merged lane histograms
    # (combined mode sees every lane's live TTFT/ITL windows) and the
    # merged cross-lane stitch (in-process fragments, no HTTP hop).
    routes[("GET", "/admin/slo")] = lambda _b: (
        200, gateway.slo_status(_named_hists())
        or {"error": "no objectives configured "
                     "(set --slo-ttft-p99-ms / --slo-itl-p99-ms / "
                     "--slo-completion-p99-ms)"})
    prefix_routes = {("GET", "/admin/trace/"): (
        lambda _b, rid: (200, gateway.stitched_trace(rid)))}

    server = _make_front_server(port, routes, workers, gateway, native_front,
                                prefix_routes=prefix_routes)
    kind = "native C++ front" if not isinstance(server, JsonHttpServer) else "python front"
    topo = (f"mesh {dict(mesh.shape)}" if mesh is not None
            else f"{n_lanes} lanes over {len(devices)} device(s)")
    print(f"tpu_engine combined serving: {topo}, port {port} ({kind})")
    _print_runtime_banner(workers, kind)
    # Listen only now: a client that sees the port answer may signal the
    # process, and the banner above must already be in the log.
    if isinstance(server, JsonHttpServer):
        server.start(background=background)
        return gateway, workers, server
    server.start()
    if not background:
        import time as _time

        try:
            while True:
                _time.sleep(3600)
        except KeyboardInterrupt:
            pass
    return gateway, workers, server


def _make_front_server(port: int, routes: dict, workers, gateway,
                       native_front: Optional[bool],
                       prefix_routes: Optional[dict] = None):
    """Choose the serving edge: the C++ HttpFront (cache hits answered
    without the GIL; misses + misc routes fall back to Python) when the
    native lib and raw-mode lane caches are available, else the Python
    ThreadingHTTPServer. native_front: None=auto, True=require, False=off.

    Multi-model deployments always use the Python front: the C++ hit path
    rings request_ids over ALL lanes with input-bytes cache keys — no
    model awareness — so it could answer a {"model": "gpt2"} request with
    an mlp lane's cached fragment. Silent wrong-model output beats any
    hit-path speedup; extend the C++ key schema before re-enabling."""
    models = {getattr(w.engine.spec, "name", None) for w in workers}
    if len(models) > 1:
        if native_front is True:
            raise RuntimeError(
                "native front is single-model (its ring and cache keys "
                "carry no model); serve multi-model with the python front")
        native_front = False
    use_native = False
    if native_front is not False:
        try:
            from tpu_engine.core import native

            use_native = native.available() and all(
                isinstance(w.cache, native.NativeLRUCache)
                and getattr(w.cache, "_raw", False) for w in workers)
        except Exception:
            use_native = False
        if native_front is True and not use_native:
            raise RuntimeError("native front requested but libtpucore.so or "
                               "raw-mode lane caches are unavailable")
    if not use_native:
        server = JsonHttpServer(port)
        for (method, path), handler in routes.items():
            server.route(method, path, handler)
        for (method, prefix), handler in (prefix_routes or {}).items():
            server.route_prefix(method, prefix, handler)
        return server

    import json as _json

    from tpu_engine.core.native import NativeHttpFront

    def fallback(method: str, path: str, body: bytes):
        handler = routes.get((method, path))
        if handler is None and prefix_routes:
            # Prefix routes (e.g. /admin/trace/<request_id>): same
            # longest-prefix-wins contract as JsonHttpServer.
            for (m, prefix), ph in sorted(prefix_routes.items(),
                                          key=lambda kv: -len(kv[0][1])):
                if m == method and path.startswith(prefix) \
                        and len(path) > len(prefix):
                    suffix = path[len(prefix):]
                    handler = (lambda body, _h=ph, _s=suffix:
                               _h(body, _s))
                    break
        if handler is None:
            return 404, _json.dumps({"error": f"no route {method} {path}"}).encode()
        try:
            parsed = _json.loads(body) if method == "POST" else None
            result = handler(parsed)
            # (status, payload) or (status, payload, content_type); the
            # content type rides through tpu_front_reply2 so /metrics is
            # text/plain even behind the C++ front (Prometheus 3.x rejects
            # scrapes served as application/json).
            ctype = result[2] if len(result) == 3 else None
            status, payload = result[0], result[1]
            if not isinstance(payload, (bytes, bytearray)):
                if (hasattr(payload, "__iter__")
                        and not isinstance(payload, (dict, list, str))):
                    # SSE iterator (/generate/stream): the C++ front
                    # replies with one complete buffer, so the events ship
                    # as a single SSE-formatted body — same wire contract,
                    # no incremental flush (use the python front or a
                    # worker port for true streaming granularity). Drained
                    # INSIDE this try: an iterator error must become a
                    # 500 response, never escape into the C++ callback.
                    payload = b"".join(payload)
                else:
                    payload = _json.dumps(payload).encode()
        except ShedError as exc:
            # Resilience refusal (deadline/overload/drain): 503 with the
            # machine-readable kind. (The C++ reply path carries no extra
            # headers, so Retry-After rides only the Python front.)
            return 503, _json.dumps({"error": str(exc),
                                     "kind": exc.kind}).encode()
        except (KeyError, ValueError, TypeError) as exc:
            return 400, _json.dumps({"error": str(exc)}).encode()
        except Exception as exc:
            return 500, _json.dumps({"error": str(exc)}).encode()
        if ctype is not None:
            return status, payload, ctype
        return status, payload

    front = NativeHttpFront(port, fallback)
    for w in workers:
        front.add_lane(w.node_id, w.cache, gateway.breaker_for(w.node_id))
        w.external_counters = (lambda name=w.node_id: front.lane_counters(name))
        w.on_fault_change(lambda healthy, name=w.node_id:
                          front.set_lane_enabled(name, healthy))
    return front


def _print_runtime_banner(workers, front: str) -> None:
    """What this process will actually run on, as log lines (not wire
    fields — /health and /stats schemas do not change): the backend and
    device kind, each lane's device(s), the attention implementation
    every path selected with its interpret flag, the front, and the
    compile-cache directory. A lane that fell to the CPU, to an XLA
    reference, or to the Pallas interpreter is visible here at start-up;
    chip_smoke.py asserts on these lines."""
    import jax

    from tpu_engine.models.transformer import default_attention
    from tpu_engine.ops.flash import flash_attention
    from tpu_engine.ops.paged_attention import selected_implementations

    devices = jax.devices()
    backend = jax.default_backend()
    print(f"  backend: {backend}, device_kind: {devices[0].device_kind}, "
          f"devices: {len(devices)}")
    for w in workers:
        mesh = getattr(w.engine, "_mesh", None)
        lane_devices = (list(mesh.devices.flat) if mesh is not None
                        else w._tp_devices()
                        or [getattr(w.engine, "_device", None)])
        print(f"  lane {w.node_id} -> device "
              + ",".join("default" if d is None else str(d.id)
                         for d in lane_devices))
    interpret = backend != "tpu"
    impls = {"flash": ("pallas" if default_attention() is flash_attention
                       else "xla"), **selected_implementations()}
    for path, impl in impls.items():
        print(f"  attention {path}: {impl}"
              + (f" interpret={interpret}" if impl == "pallas" else ""))
    for w in workers:
        # The tiles each grouped expert product was traced with so far
        # (warm-up's shapes): "xla" where the rule stated none.
        gen = getattr(w, "generator", None)
        stats = gen.stats() if gen is not None else {}
        for shape, tiles in stats.get("moe", {}).get("tilings", {}).items():
            print(f"  lane {w.node_id} expert tiles {shape}: {tiles}")
        mixed = stats.get("mixed", {})
        if mixed.get("kv_planes"):
            # A lane whose model applies its layers several times a token
            # over a pool a plane a (pass, layer) deep.
            passes, planes = mixed["ut_steps"], mixed["kv_planes"]
            print(f"  lane {w.node_id} {passes} passes x "
                  f"{planes // passes} layers, {planes} planes, "
                  f"{mixed['kv_bytes_per_token'] / 1e6:.2f} MB a token")
        block = mixed.get("block_decode")
        if block:
            # A lane whose rows denoise blocks: its reveal rule, and
            # whether its ticks run one ahead of their results.
            print(f"  lane {w.node_id} decodes by blocks of "
                  f"{block['block_length']} ({block['reveal']}, "
                  f"{block['tokens_per_pass']} a pass): "
                  + ("ticks run one ahead" if block["runs_ahead"] else
                     "ticks in the drained order (the rule ends a block "
                     "at a pass the host cannot foresee)"))
    print(f"  front: {front}")
    # The entry point exports the directory it placed (utils.checkpoint).
    print(f"  compile cache: "
          f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or 'off'}",
          flush=True)


def _print_worker_banner(worker: WorkerNode, config: WorkerConfig) -> None:
    # Startup banner parity (reference worker_node.cpp:192-201).
    bar = "━" * 44
    print(bar)
    print(f"Worker Node: {config.node_id}")
    print(bar)
    print(f"   Port:              {config.port}")
    print(f"   Model:             {worker.engine.spec.name}")
    print(f"   Cache Capacity:    {config.cache_capacity} entries")
    print(f"   Batch Size:        {config.max_batch_size} requests")
    print(f"   Batch Timeout:     {int(config.batch_timeout_ms)}ms")
    print(bar)
    _print_runtime_banner([worker], "python front")
    print("Ready to accept requests!")
