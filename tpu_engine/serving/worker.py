"""WorkerNode: a serving lane — LRU result cache + dynamic batcher + engine.

Capability parity with the reference worker
(``/root/reference/src/worker_node.cpp``): ``handle_infer`` is cache-first
(``:50-83``), misses go through the dynamic batcher into batched execution,
and ``get_health`` exposes the exact JSON schema the reference documents
(``README.md:157-202``) and its tooling parses (``benchmark.py:148-178``,
``diagnostics.sh:39-56``).

TPU-native differences:
- the engine executes on a TPU chip (or mesh slice) through the
  shape-bucketed XLA executable cache instead of ONNX Runtime;
- per-request inference time is batch_duration / batch_size like the
  reference (``worker_node.cpp:123``), measured around the XLA dispatch;
- the result cache can be the native C++ LRU (byte-blob keys) when
  libtpucore.so is available.

A worker lane is addressable either over HTTP (reference deployment shape)
or in-process by the gateway (TPU-native shape: one process, lanes = chips).
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import queue
import socket
import threading
import time
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from tpu_engine.core.lru_cache import LRUCache
from tpu_engine.runtime.batch_processor import BatchProcessor
from tpu_engine.serving.http import sse_event
from tpu_engine.serving.overload import (
    AIMDLimit,
    BROWNOUT_BUDGET_FRAC,
    BROWNOUT_STAGES,
    BrownoutController,
    TIER_ADMIT_FRAC,
    TOP_TIER,
    parse_priority,
)
from tpu_engine.serving.resilience import AdmissionController
from tpu_engine.utils.config import WorkerConfig
from tpu_engine.utils.deadline import (
    Deadline,
    DeadlineExceeded,
    ShedError,
    clamp_timeout,
)
from tpu_engine.utils.sampling import clamp_top_k as _clamp_top_k
from tpu_engine.utils.sampling import validate_min_p as _validate_min_p
from tpu_engine.utils.sampling import expand_stopping_params
from tpu_engine.utils.streams import (
    STREAM_STALL_S,
    UNREGISTERED,
    EventStream,
    StreamOutbox,
)
from tpu_engine.utils.tracing import (
    SpanRecorder,
    StreamClock,
    TraceContext,
    TraceSink,
)


@dataclass
class _BatchItem:
    request_id: str
    input_data: Sequence[float]
    shape: Optional[tuple] = None  # mixed-shape serving (BASELINE config 4)
    # The request's worker-root span context: queue_wait / batch_form /
    # device_compute stage spans parent here (utils.tracing).
    trace: Optional[TraceContext] = None


@dataclass
class _BatchResult:
    output_data: np.ndarray
    inference_time_us: int


class _RootSpan:
    """Mutable state of one worker-root span while its request runs: the
    span's context (stage children parent here), plus the cached flag and
    attrs the request path fills in before the scope records."""

    __slots__ = ("ctx", "request_id", "attrs", "cached")

    def __init__(self, ctx: TraceContext, request_id: str):
        self.ctx = ctx
        self.request_id = request_id
        self.attrs = {"outcome": "error"}
        self.cached = False


class _Inflight:
    """One in-flight computation shared by concurrent identical requests."""

    __slots__ = ("event", "frag", "time_us", "error")

    def __init__(self):
        self.event = threading.Event()
        self.frag: Optional[bytes] = None
        self.time_us = 0
        self.error: Optional[BaseException] = None


@dataclass
class _GenItem:
    request_id: str
    prompt: list
    max_new_tokens: int
    eos_id: int
    temperature: float
    seed: int
    top_p: float = 1.0
    top_k: int = 0
    repetition_penalty: float = 1.0
    stop_tokens: tuple = ()
    beam_width: int = 1
    length_penalty: float = 1.0
    min_p: float = 0.0
    trace: Optional[TraceContext] = None  # worker-root ctx (stage spans)


@dataclass
class _GenResult:
    tokens: list
    generate_time_us: int


@dataclass
class _ScoreItem:
    request_id: str
    prompt: list
    completion: list


def _load_model_path(model, model_path: Optional[str]):
    """Resolve the worker's model_path into a parameter pytree (or None for
    random init). HF checkpoint layouts (config.json / *.safetensors /
    pytorch_model.bin, or those files directly) go through the pretrained
    importers; other directories are treated as orbax checkpoints.
    `model` may be a registry name or an already-built ModelSpec (the
    HF-config-driven path) — a spec is passed through so the importer's
    architecture assertions run against it."""
    name = model if isinstance(model, str) else model.name
    spec = None if isinstance(model, str) else model
    if not model_path:
        return None
    if os.path.isfile(model_path):
        if model_path.endswith((".safetensors", ".bin", ".pt", ".pth")):
            from tpu_engine.models.import_weights import load_pretrained

            return load_pretrained(name, model_path, spec=spec)
        return None  # e.g. a reference-style .onnx path used only for naming
    if os.path.isdir(model_path):
        if any(os.path.exists(os.path.join(model_path, f))
               for f in ("config.json", "model.safetensors",
                         "pytorch_model.bin",
                         "model.safetensors.index.json")):
            from tpu_engine.models.import_weights import load_pretrained

            return load_pretrained(name, model_path, spec=spec)
        from tpu_engine.utils.checkpoint import load_params

        return load_params(model_path)
    return None


def _encode_output(arr) -> bytes:
    """Pre-encoded ``output_data`` fragment for the response/cache.

    Native %.6g writer when libtpucore is available (~3x json.dumps and
    GIL-free — the miss path pays this per request, and at b32 the Python
    encode alone was ~20 ms of GIL time per batch). Six significant
    digits is the serving noise floor: engines compute in bf16 (~3
    digits), and even float32 outputs keep ~1e-6 relative error. The
    fallback is the plain full-precision json.dumps — slower but never
    less accurate (decimal-place rounding would zero small magnitudes)."""
    from tpu_engine.core import native

    frag = native.json_encode_f32(arr)
    if frag is not None:
        return frag
    return json.dumps(np.asarray(arr, np.float64).tolist()).encode()


def _make_cache(capacity: int):
    # Values are the pre-encoded output_data JSON fragments (bytes) — raw
    # mode lets the native HTTP front read entries without unpickling.
    try:
        from tpu_engine.core import native

        if native.available():
            return native.NativeLRUCache(capacity, raw=True)
    except Exception:
        pass
    return LRUCache(capacity)


class WorkerNode:
    def __init__(self, config: Optional[WorkerConfig] = None, engine=None, **overrides):
        self.config = config or WorkerConfig.from_env(**overrides)
        self.node_id = self.config.node_id
        # Pre-escaped for the raw-splice response path: an operator-supplied
        # node_id containing quotes/backslashes must not corrupt the JSON.
        self._node_id_json = json.dumps(self.node_id).encode()
        if engine is None:
            from tpu_engine.runtime.engine import InferenceEngine

            if (self.config.model_path or "").endswith(".onnx"):
                # Arbitrary-ONNX serving (reference inference_engine.cpp:31-87):
                # the graph itself is staged to XLA — architecture AND weights
                # come from the file, no registry entry needed.
                from tpu_engine.models.onnx_graph import build_onnx_model

                if self.config.quantize is not None:
                    # ONNX initializers are flat named arrays, not the
                    # kernel dicts ops.quant rewrites — forwarding the flag
                    # would silently quantize nothing. Fail loudly instead.
                    raise RuntimeError(
                        "quantize is not supported for raw .onnx graphs "
                        "(import the checkpoint into a registry "
                        "architecture to serve quantized)")
                spec, params = build_onnx_model(self.config.model_path)
                engine = InferenceEngine(
                    spec,
                    params=params,
                    dtype=self.config.dtype,
                    batch_buckets=self.config.batch_buckets,
                    shape_buckets=self.config.shape_buckets,
                )
            else:
                # model_path (reference positional arg / $MODEL_PATH,
                # worker_node.cpp:154-168): real weights instead of random
                # init. Accepts an HF checkpoint dir / .safetensors / torch
                # .bin (via models.import_weights) or an orbax checkpoint
                # dir. An HF dir's config.json drives the architecture
                # (geometry AND shape-invariant fields like rope_theta) so
                # the engine spec matches the imported weights exactly.
                model = self.config.model
                if self.config.model_path and os.path.isdir(
                        self.config.model_path):
                    from tpu_engine.models.import_weights import hf_spec_kwargs
                    from tpu_engine.models.registry import (
                        create_model, _ensure_builtin_models_imported)

                    kwargs = hf_spec_kwargs(self.config.model_path)
                    if kwargs:
                        _ensure_builtin_models_imported()
                        model = create_model(self.config.model, **kwargs)
                params = _load_model_path(model, self.config.model_path)
                engine = InferenceEngine(
                    model,
                    params=params,
                    dtype=self.config.dtype,
                    batch_buckets=self.config.batch_buckets,
                    shape_buckets=self.config.shape_buckets,
                    quantize=self.config.quantize,
                )
        self.engine = engine
        # Tracing: one span ring per lane (request roots + stage children
        # + per-stage histograms). Created before the batchers so their
        # observer hook has a live recorder from the first batch on; the
        # engine reports its XLA compile events into the same ring so
        # first-request compile stalls are attributable in /trace/export.
        self.tracer = SpanRecorder(self.config.trace_capacity)
        try:
            self.engine.tracer = self.tracer
            self.engine.trace_node = self.node_id
        except AttributeError:
            pass  # test fakes with __slots__: engine tracing is optional
        self.cache = _make_cache(self.config.cache_capacity)
        self.batch_processor: BatchProcessor[_BatchItem, _BatchResult] = BatchProcessor(
            self.config.max_batch_size,
            self.config.batch_timeout_ms,
            self._process_batch,
            linger_ms=self.config.batch_linger_ms,
            name=f"{self.node_id}-batcher",
            # Split-phase pipelining needs engine.batch_submit/collect;
            # plain engines (tests inject batch_predict-only fakes) run the
            # reference-style lockstep loop.
            submit_callback=(self._submit_batch
                             if hasattr(self.engine, "batch_submit") else None),
            collect_callback=(self._collect_batch
                              if hasattr(self.engine, "batch_submit") else None),
            ready_callback=((lambda s: self.engine.handle_ready(s[0]))
                            if hasattr(self.engine, "handle_ready") else None),
            pipeline_depth=self.config.pipeline_depth,
            observer=self._batch_observer,
        )
        self.batch_processor.start()
        # Autoregressive generation lane (transformer models only): its own
        # batcher so decode loops never block one-shot /infer traffic.
        self.generator = None
        self._gen_processor: Optional[BatchProcessor[_GenItem, _GenResult]] = None
        self._continuous = self.config.gen_scheduler == "continuous"
        self._speculative = self.config.gen_scheduler == "speculative"
        # Unified stateless serving (DESIGN.md; the fold that retired
        # the dedicated batch lane): one-shot /infer and /score admit as
        # single-tick rows in the continuous scheduler — one slot pool,
        # one admission queue, one set of counters with decode streams.
        # Continuous-only: any other gen_scheduler keeps the batch lane.
        self._unified = (bool(getattr(self.config, "unified_stateless",
                                      True))
                         and self._continuous)
        if self.config.gen_continuous_spec_k > 0 and not self._continuous:
            # --spec-k is the continuous scheduler's knob; under any other
            # gen_scheduler the flag would build that lane's generator and
            # silently serve without speculation — same loud contract as
            # every other spec misconfiguration.
            raise RuntimeError(
                f"--spec-k requires gen_scheduler=continuous, got "
                f"{self.config.gen_scheduler!r} (batch-lane speculation "
                f"is gen_scheduler=speculative)")
        if self.config.gen_kv_host_blocks > 0 and (
                not self._continuous
                or self.config.gen_kv_block_size <= 0
                or not self.config.gen_prefix_sharing):
            # Loud, not the silent "this model can't generate" fallback:
            # an operator who asked for the host KV tier must never get a
            # lane that quietly recomputes every evicted prefix instead.
            raise RuntimeError(
                "--kv-host-blocks requires the continuous scheduler with "
                "the paged KV cache and prefix sharing on "
                "(--kv-block-size > 0, --prefix-sharing on)")
        if self.config.gen_kv_quantize and (
                not self._continuous
                or self.config.gen_kv_block_size <= 0):
            # Same loud contract: an operator who asked for the 2x KV
            # capacity multiplier must never get a lane that quietly
            # serves the full-precision (half-capacity) pool instead.
            raise RuntimeError(
                "--kv-quantize requires the continuous scheduler with "
                "the paged KV cache (--kv-block-size > 0)")
        if self.config.gen_kv_quantize not in ("", "int8"):
            raise RuntimeError(
                f"--kv-quantize must be 'int8', got "
                f"{self.config.gen_kv_quantize!r}")
        if self.config.gen_prefix_fetch and (
                not self._continuous
                or self.config.gen_kv_block_size <= 0
                or not self.config.gen_prefix_sharing):
            # Same loud contract: an operator who asked for the fleet
            # prefix tier must never get a lane that quietly ignores
            # every gateway hint and recomputes each shared prefix.
            raise RuntimeError(
                "--prefix-fetch requires the continuous scheduler with "
                "the paged KV cache and prefix sharing on "
                "(--kv-block-size > 0, --prefix-sharing on)")
        # Serving-state family fences (models.registry declares the
        # family; the worker refuses mismatched machinery LOUDLY — an
        # operator who asked for a kv_paged knob on a recurrent model
        # must never get a lane that quietly ignores it).
        model_family = getattr(self.engine.spec, "state_family", None)
        if model_family == "state_slab":
            if not self._continuous:
                raise RuntimeError(
                    f"model "
                    f"'{getattr(self.engine.spec, 'name', self.config.model)}'"
                    f" serves the state_slab family, which requires "
                    f"gen_scheduler=continuous (got "
                    f"{self.config.gen_scheduler!r}: the batch and "
                    f"speculative lanes serve only kv_paged models)")
            if (self.config.gen_kv_block_size > 0
                    or self.config.gen_kv_blocks > 0
                    or self.config.gen_kv_host_blocks > 0
                    or self.config.gen_kv_quantize):
                raise RuntimeError(
                    "state_slab-family models have no paged KV cache: "
                    "--kv-block-size/--kv-blocks/--kv-host-blocks/"
                    "--kv-quantize apply to the kv_paged family "
                    "(state capacity is --state-rows)")
            if self.config.gen_continuous_spec_k > 0:
                raise RuntimeError(
                    "--spec-k requires a kv_paged-family model: the "
                    "state_slab recurrence has no KV verify window")
        elif self.config.gen_state_rows > 0:
            raise RuntimeError(
                "--state-rows applies to state_slab-family models; "
                f"model "
                f"'{getattr(self.engine.spec, 'name', self.config.model)}'"
                f" serves the {model_family or 'kv_paged'} family")
        if model_family == "stateless":
            # Stateless-family fences: one-shot rows hold no
            # autoregressive state, so every generative-state knob is a
            # LOUD refusal — previously these were silently inert
            # (the generator was simply never built for config-less
            # models), which violated the misconfiguration contract.
            if self.config.gen_continuous_spec_k > 0:
                # Checked BEFORE the KV fence: an operator who asked for
                # speculation gets the speculative-lane diagnosis even
                # when KV knobs are also set (tests pin this wording).
                raise RuntimeError(
                    f"speculative lane misconfigured: --spec-k requires "
                    f"a generation-capable family; model "
                    f"'{getattr(self.engine.spec, 'name', self.config.model)}'"
                    f" serves the stateless family (one-shot rows have "
                    f"no decode loop to speculate)")
            if (self.config.gen_kv_block_size > 0
                    or self.config.gen_kv_blocks > 0
                    or self.config.gen_kv_host_blocks > 0
                    or self.config.gen_kv_quantize):
                raise RuntimeError(
                    "stateless-family models have no KV cache: "
                    "--kv-block-size/--kv-blocks/--kv-host-blocks/"
                    "--kv-quantize apply to the kv_paged family")
            if self.config.gen_mixed_step:
                raise RuntimeError(
                    "mixed stepping merges prefill and decode "
                    "dispatches; stateless-family models have neither "
                    "(one-shot rows already ride one grouped dispatch "
                    "per tick)")
        # Tensor-parallel serving fences (the registry declares the
        # partition rule; the worker refuses misconfigurations LOUDLY —
        # an operator who asked for a sharded lane must never get a
        # silently single-device one, and an unshardable family must
        # never be heuristically mis-sharded).
        if int(self.config.tp) < 1:
            raise RuntimeError(f"--tp must be >= 1, got {self.config.tp}")
        if int(self.config.tp) > 1:
            # Unshardable family first: the pinned per-model refusal
            # (e.g. mamba2's conv tail/state slab) outranks the generic
            # knob-combination message.
            from tpu_engine.models.registry import tp_unshardable_reason

            reason = tp_unshardable_reason(self.engine.spec)
            if reason is not None:
                raise RuntimeError(
                    f"model "
                    f"'{getattr(self.engine.spec, 'name', self.config.model)}'"
                    f" cannot serve tensor-parallel (--tp "
                    f"{self.config.tp}): {reason}")
            if not self._continuous or self.config.gen_kv_block_size <= 0:
                raise RuntimeError(
                    "--tp requires the continuous scheduler with the "
                    "paged KV cache (--kv-block-size > 0): the sharded "
                    "pool layout is the paged pool")
        if self.config.role not in ("prefill", "decode", "both"):
            raise RuntimeError(
                f"--role must be prefill|decode|both, got "
                f"{self.config.role!r}")
        if self.config.role != "both" and (
                not self._continuous
                or (self.config.gen_kv_block_size <= 0
                    and model_family != "state_slab")):
            # A dedicated role without an exportable state family could
            # never export or adopt a chain — the lane would silently
            # serve colocated. Same loud contract as every other
            # misconfiguration. (state_slab rows export as
            # one-pseudo-block chains, so slab lanes qualify.)
            raise RuntimeError(
                "--role prefill|decode requires the continuous "
                "scheduler with the paged KV cache "
                "(--kv-block-size > 0)")
        if (self.config.role != "both" and model_family
                and "generate" in self.engine.spec.capabilities
                and not self.engine.spec.supports("handoff")):
            # A dedicated role exports or adopts a chain after prefill:
            # a family whose pool cannot ride the chain wire format would
            # start, and then refuse every handoff.
            raise RuntimeError(
                f"--role {self.config.role} needs the 'handoff' "
                f"capability, which model "
                f"'{getattr(self.engine.spec, 'name', self.config.model)}'"
                f" ({model_family} family) does not declare (the chain "
                f"wire format carries a K and a V a head for every block "
                f"of the row)")
        if getattr(self.engine.spec, "config", None) is not None:
            try:
                if self._speculative:
                    # Draft-model speculation: batch-mode lane; the target
                    # verifies gen_spec_k draft tokens per windowed pass
                    # (runtime.speculative). Wire contract narrows to
                    # temperature sampling (handle_generate validates).
                    self.generator = self._build_speculative()
                    self._gen_processor = BatchProcessor(
                        self.config.gen_max_batch_size,
                        self.config.batch_timeout_ms,
                        self._process_gen_batch,
                        name=f"{self.node_id}-gen-batcher",
                        observer=self._batch_observer,
                    )
                    self._gen_processor.start()
                elif self._continuous:
                    # Iteration-level scheduling: the scheduler IS the
                    # batcher — HTTP handler threads submit directly and
                    # requests join the running decode batch between chunks.
                    from tpu_engine.runtime.scheduler import ContinuousGenerator

                    self.generator = ContinuousGenerator(
                        self.engine.spec, params=self.engine.params,
                        dtype=self.config.dtype,
                        n_slots=self.config.gen_max_batch_size,
                        step_chunk=self.config.gen_step_chunk,
                        prefix_cache_mb=self.config.gen_prefix_cache_mb,
                        prefill_chunk=self.config.gen_prefill_chunk,
                        kv_block_size=self.config.gen_kv_block_size,
                        kv_blocks=self.config.gen_kv_blocks,
                        kv_host_blocks=self.config.gen_kv_host_blocks,
                        kv_quantize=self.config.gen_kv_quantize,
                        prefix_sharing=self.config.gen_prefix_sharing,
                        mixed_step=self.config.gen_mixed_step,
                        mixed_token_budget=(
                            self.config.gen_mixed_token_budget),
                        state_rows=self.config.gen_state_rows,
                        # Unified stateless serving: one-shot /predict
                        # and /score requests admit as single-tick rows
                        # beside this lane's decode streams (one pool,
                        # one admission queue, one set of counters).
                        infer_engine=(self.engine if self._unified
                                      else None),
                        score_provider=(self._get_scorer
                                        if self._unified else None),
                        **self._continuous_spec_kwargs(),
                        # TP lanes build their own mesh over THIS
                        # lane's device slice (tp_device_offset keeps
                        # in-process TP lanes on disjoint chips); the
                        # engine's single-device pin is mutually
                        # exclusive.
                        tp=int(self.config.tp),
                        tp_devices=self._tp_devices(),
                        device=(None if int(self.config.tp) > 1
                                else getattr(engine, "_device", None)))
                    # Per-tick mixed_step spans land in the lane's ring.
                    self.generator.tracer = self.tracer
                    self.generator.trace_node = self.node_id
                    # Observability plane (all default off):
                    # --trace-stitch makes export snapshots carry the
                    # stream's trace context; --flight-recorder arms the
                    # per-tick ring behind /admin/timeline.
                    self.generator.trace_stitch = bool(
                        getattr(self.config, "trace_stitch", False))
                    flight = int(getattr(self.config,
                                         "flight_recorder", 0) or 0)
                    if flight > 0:
                        self.generator.configure_flight_recorder(
                            flight, getattr(self.config,
                                            "flight_dump_dir", None))
                    if self.config.gen_prefix_fetch:
                        # Fleet prefix tier: the scheduler calls this
                        # on its prefill thread for hinted misses; the
                        # worker owns transport, the per-lane in-flight
                        # cap, and the per-fetch timeout — the
                        # scheduler owns verification and the splice.
                        self.generator.prefix_fetch = \
                            self._fetch_prefix_peer
                else:
                    from tpu_engine.runtime.generator import Generator

                    self.generator = Generator(
                        self.engine.spec, params=self.engine.params,
                        dtype=self.config.dtype,
                        step_chunk=self.config.gen_step_chunk,
                        device=getattr(engine, "_device", None))
                    self._gen_processor = BatchProcessor(
                        self.config.gen_max_batch_size,
                        self.config.batch_timeout_ms,
                        self._process_gen_batch,
                        name=f"{self.node_id}-gen-batcher",
                        observer=self._batch_observer,
                    )
                    self._gen_processor.start()
            except ValueError as e:
                if self.config.gen_continuous_spec_k > 0:
                    # The operator explicitly asked for speculation: any
                    # construction failure (non-decoder draft model,
                    # draft max_seq too small for k, non-generating
                    # target) is a misconfiguration, not the quiet
                    # "this model can't generate" lane fallback.
                    raise RuntimeError(
                        f"speculative lane misconfigured: {e}") from e
                self.generator = None
        elif self._unified and model_family == "stateless":
            # Unified stateless serving: config-less models (mlp/resnet/
            # onnx graphs) get a continuous scheduler whose rows are ALL
            # one-shot — /predict misses join the same admission queue,
            # deadline governance, brownout tiers, and counters as every
            # generative lane in the fleet. n_slots mirrors the retired
            # batch lane's max batch so dispatch width is wire-identical.
            from tpu_engine.runtime.scheduler import ContinuousGenerator

            self.generator = ContinuousGenerator(
                self.engine.spec,
                params=getattr(self.engine, "params", None),
                dtype=self.config.dtype,
                n_slots=self.config.max_batch_size,
                prefix_cache_mb=0,
                infer_engine=self.engine,
                device=getattr(engine, "_device", None))
            self.generator.tracer = self.tracer
            self.generator.trace_node = self.node_id
            flight = int(getattr(self.config,
                                 "flight_recorder", 0) or 0)
            if flight > 0:
                self.generator.configure_flight_recorder(
                    flight, getattr(self.config, "flight_dump_dir",
                                    None))
        elif self.config.gen_continuous_spec_k > 0:
            # Config-less models skip generator construction entirely, so
            # the ValueError conversion above can never fire for them —
            # guard the skip path too, or --spec-k on a non-generating
            # model silently serves without a decode lane.
            raise RuntimeError(
                f"speculative lane misconfigured: model "
                f"'{getattr(self.engine.spec, 'name', self.config.model)}' "
                f"has no generation lane to speculate on")
        # Worker-level counters, distinct from the LRU's own accounting
        # (reference worker_node.cpp:141-142).
        self._total_requests = 0
        self._cache_hits = 0
        self._counter_lock = threading.Lock()
        # Fleet prefix tier transport state (--prefix-fetch): the
        # per-lane in-flight cap, a small peer-client cache for the
        # default HTTP transport, and an optional in-process transport
        # installed by combined-mode wiring (set_prefix_fetch_transport).
        self._prefix_fetch_sem = threading.BoundedSemaphore(
            max(1, int(getattr(self.config,
                               "gen_prefix_fetch_inflight", 2) or 1)))
        self._prefix_fetch_transport = None
        self._prefix_peers: dict = {}
        self._prefix_peers_lock = threading.Lock()
        # Fault injection (BASELINE config 5): the reference injects faults
        # by killing worker processes (README.md:322-349); in-process lanes
        # need an explicit hook. While set, every request raises — the
        # gateway's breaker sees it exactly like a dead worker.
        self._injected_fault: Optional[str] = None
        # Slow-lane fault (resilience scenarios): latency added to every
        # request while set — the lane is SLOW, not dead, which the
        # breaker alone cannot answer (hedging/deadlines do).
        self._injected_latency_s: float = 0.0
        self._fault_listeners: list = []
        # Resilience: bounded queue depth + drain (lame-duck) mode.
        # max_queue_depth=0 keeps admission unbounded (reference behavior).
        # Overload control (default off): the AIMD limiter replaces the
        # static cap with a latency-driven limit, and tier fractions
        # shed lowest-priority-first under depth pressure.
        # Start from the operator's static cap when one is configured —
        # the adaptive limit REPLACES max_queue_depth, so it must begin
        # where the operator's judgment left off, not at an arbitrary
        # midpoint.
        self._aimd = (AIMDLimit(max_limit=self.config.adaptive_depth_max,
                                start=self.config.max_queue_depth or None)
                      if self.config.adaptive_depth else None)
        self._tiered = bool(self.config.priority_admission)
        self._admission = AdmissionController(
            self.config.max_queue_depth, self.node_id,
            tier_fracs=TIER_ADMIT_FRAC if self._tiered else None,
            limiter=self._aimd)
        # Staged brownout (default off): a control loop reads saturation
        # signals every brownout_interval_s and walks the degradation
        # ladder (DESIGN.md "Overload control"); each transition drops an
        # `overload` marker span so escalations+restores == spans.
        self._brownout: Optional[BrownoutController] = None
        self._brownout_clamps = 0
        self._brownout_prev = {"starved": 0, "missed": 0}
        self._brownout_stop = threading.Event()
        self._brownout_thread: Optional[threading.Thread] = None
        if self.config.brownout:
            self._brownout = BrownoutController()
            self._brownout_thread = threading.Thread(
                target=self._brownout_loop,
                name=f"{self.node_id}-brownout", daemon=True)
            self._brownout_thread.start()
        # EWMA of recent miss-path per-request service time (µs), feeding
        # deadline-aware early rejection: a request whose remaining budget
        # cannot cover the typical miss is shed before it occupies a
        # batch row.
        self._service_ewma_us: Optional[float] = None
        # Bumped by reload_weights: in-flight /infer results computed
        # under an older generation must not enter the cleared cache. The
        # lock makes check+put atomic against bump+clear — a bare compare
        # would only narrow the race, not close it.
        self._weights_gen = 0
        self._reload_lock = threading.Lock()
        # In-flight coalescing: concurrent identical misses share ONE
        # execution. The reference deliberately lacks this — simultaneous
        # identical requests all enter the batch because the cache is only
        # written after the batch returns (worker_node.cpp:70-73;
        # SURVEY.md §3.2 flags it as a decision point). Followers wait on
        # the leader's event and reuse its encoded result.
        self._inflight: dict = {}
        self._inflight_lock = threading.Lock()
        # (total, hits) served on this lane's behalf outside this process's
        # Python path — the native HTTP front reports through here.
        self.external_counters = None
        # NOTE: self.tracer was created near the top of __init__ (the
        # engine, batchers, and generation scheduler all hold references
        # to it); a second assignment here would orphan their recorder —
        # their spans (xla_compile, mixed_step) would never export.

    # -- fault injection -------------------------------------------------------

    # Wire-facing beam cap: each distinct width compiles (and permanently
    # caches) its own while_loop executable and multiplies the KV cache by
    # the width — an unclamped client value is a compile/memory DoS.
    MAX_BEAM_WIDTH = 8

    def _validate_beam(self, beam_width, temperature, top_p, top_k,
                       rep_penalty, stop_tokens,
                       length_penalty: float = 1.0,
                       min_p: float = 0.0) -> None:
        if beam_width == 1:
            return  # non-beam paths never read length_penalty
        if not math.isfinite(length_penalty) or abs(length_penalty) > 10:
            # json.loads accepts NaN/Infinity; a non-finite penalty makes
            # every beam's normalized score NaN and silently returns [].
            raise ValueError(
                f"length_penalty must be finite in [-10, 10], got "
                f"{length_penalty}")
        if not 1 <= beam_width <= self.MAX_BEAM_WIDTH:
            raise ValueError(
                f"beam_width must be in [1, {self.MAX_BEAM_WIDTH}], got "
                f"{beam_width}")
        # Beam decode (batch lane's Generator only): deterministic,
        # incompatible with sampling controls by construction.
        if self._continuous or self._speculative:
            raise ValueError("beam_width > 1 needs gen_scheduler=batch")
        if (temperature > 0 or top_p < 1.0 or top_k > 0
                or rep_penalty != 1.0 or stop_tokens or min_p > 0):
            raise ValueError(
                "beam_width is deterministic: temperature/top_p/top_k/"
                "min_p/repetition_penalty/stop_tokens do not apply")


    def _tp_devices(self):
        """This lane's tensor-parallel device slice: ``tp`` devices
        starting at ``tp_device_offset`` (combined mode hands each
        in-process lane a disjoint slice; standalone workers keep
        offset 0 = the first tp devices). None when tp == 1. A slice
        running past the local devices is a loud startup error —
        silently wrapping would stack two lanes on one chip."""
        tp = int(self.config.tp)
        if tp <= 1:
            return None
        import jax

        off = int(self.config.tp_device_offset)
        devices = jax.devices()
        if off < 0 or off + tp > len(devices):
            raise RuntimeError(
                f"--tp {tp} at device offset {off} needs devices "
                f"[{off}, {off + tp}) but only {len(devices)} local "
                f"device(s) exist")
        return devices[off:off + tp]

    _AUTO_DRAFT = {"gpt2": "distilgpt2", "gpt2-small-test": "gpt2-small-test"}

    def _resolve_draft_spec(self):
        """Resolve the configured draft model (explicit gen_draft_model or
        the auto map) and optional checkpoint into (spec, params or None).
        Shared by the batch speculative lane and the continuous
        scheduler's --spec-draft model drafter. Raises RuntimeError on a
        misconfiguration so startup fails loudly."""
        from tpu_engine.models.registry import (
            create_model, _ensure_builtin_models_imported)

        draft_name = (self.config.gen_draft_model
                      or self._AUTO_DRAFT.get(self.engine.spec.name))
        if draft_name is None:
            raise RuntimeError(
                f"a draft model is required for "
                f"'{self.engine.spec.name}': set gen_draft_model "
                f"(--gen-draft-model)")
        _ensure_builtin_models_imported()
        # Same geometry sync the target path gets (worker init above): an
        # HF draft checkpoint dir's config.json overrides registry-default
        # shape-invariant fields (rope_theta etc.) so imported weights
        # compute with the right architecture, not defaults.
        draft_kwargs = {}
        if self.config.gen_draft_path and os.path.isdir(
                self.config.gen_draft_path):
            from tpu_engine.models.import_weights import hf_spec_kwargs

            draft_kwargs = hf_spec_kwargs(self.config.gen_draft_path) or {}
        try:
            draft_spec = create_model(draft_name, **draft_kwargs)
        except KeyError as exc:
            raise RuntimeError(f"speculative lane misconfigured: unknown "
                               f"draft model {exc}")
        draft_params = None
        if self.config.gen_draft_path:
            draft_params = _load_model_path(draft_spec,
                                            self.config.gen_draft_path)
        return draft_spec, draft_params

    def _continuous_spec_kwargs(self) -> dict:
        """Continuous-speculation kwargs for ContinuousGenerator
        (--spec-k / --spec-draft). Empty when off. Misconfiguration
        raises RuntimeError — the continuous branch's ValueError handler
        means "this model can't generate", and silently dropping the
        decode lane over a spec typo must not pass for that."""
        k = int(self.config.gen_continuous_spec_k)
        if k <= 0:
            return {}
        if self.config.gen_kv_block_size <= 0:
            raise RuntimeError(
                "--spec-k requires the paged KV cache (--kv-block-size)")
        max_seq = getattr(self.engine.spec.config, "max_seq", None)
        if max_seq is not None and k > max_seq - 2:
            # Pre-checked here because ContinuousGenerator's ValueError
            # would be read as "this model can't generate" and silently
            # drop the decode lane.
            raise RuntimeError(
                f"--spec-k {k} cannot fit a verify window in the "
                f"model's max_seq {max_seq}")
        if self.config.gen_spec_draft not in ("ngram", "model"):
            # Pre-checked so make_drafter's ValueError can't be read as
            # "this model can't generate" and silently drop the lane.
            raise RuntimeError(
                f"--spec-draft must be 'ngram' or 'model', got "
                f"{self.config.gen_spec_draft!r}")
        kw = {"spec_k": k, "spec_draft": self.config.gen_spec_draft}
        if self.config.gen_spec_draft == "model":
            draft_spec, draft_params = self._resolve_draft_spec()
            target_vocab = getattr(self.engine.spec.config, "vocab", None)
            if (target_vocab is not None
                    and draft_spec.config.vocab != target_vocab):
                raise RuntimeError(
                    f"speculative lane misconfigured: draft vocab "
                    f"{draft_spec.config.vocab} != target {target_vocab}")
            if draft_params is None:
                print(f"[{self.node_id}] WARNING: --spec-draft model "
                      f"'{draft_spec.name}' is randomly initialized (no "
                      f"gen_draft_path); expect ~zero acceptance — the "
                      f"ngram drafter is the better default", flush=True)
            kw["spec_draft_model"] = draft_spec
            kw["spec_draft_params"] = draft_params
        return kw

    def _build_speculative(self):
        """Construct the speculative-decoding lane (gen_scheduler=
        "speculative"): resolve the draft model (explicit config or the
        auto map), load optional draft weights, share the target's params
        with the engine.

        Error contract: the caller treats ValueError as "this model can't
        generate" (non-transformer targets fall back to no generation lane,
        same as the other schedulers), so ONLY the target-isn't-a-decoder
        case may raise ValueError here. Every speculative-specific
        misconfiguration (unresolvable draft, vocab mismatch, bad k) is
        re-raised as RuntimeError so startup fails loudly instead of
        silently serving without a generation lane."""
        from tpu_engine.models.transformer import TransformerConfig
        from tpu_engine.runtime.speculative import SpeculativeGenerator

        tgt_cfg = getattr(self.engine.spec, "config", None)
        if not isinstance(tgt_cfg, TransformerConfig) or not tgt_cfg.causal:
            raise ValueError(
                f"model '{self.engine.spec.name}' is not a decoder "
                "transformer; generation unsupported")
        draft_spec, draft_params = self._resolve_draft_spec()
        if draft_params is None:
            # A random-init draft accepts ~nothing: the lane degrades to
            # pure overhead (nothing is accepted, every round pays). Loud
            # warning, not an error — random drafts are the test fixture.
            print(f"[{self.node_id}] WARNING: speculative draft "
                  f"'{draft_spec.name}' is randomly initialized (no "
                  f"gen_draft_path); expect ~zero acceptance and worse "
                  f"throughput than gen_scheduler=batch", flush=True)
        try:
            return SpeculativeGenerator(
                self.engine.spec, draft_spec, params=self.engine.params,
                draft_params=draft_params, k=self.config.gen_spec_k,
                dtype=self.config.dtype,
                device=getattr(self.engine, "_device", None))
        except ValueError as exc:
            raise RuntimeError(f"speculative lane misconfigured: {exc}")

    def handle_score(self, request: dict) -> dict:
        """Teacher-forced scoring: per-token log P(completion | prompt) in
        one forward pass — the evals/perplexity API (lm-eval-harness
        loglikelihood shape). Wire: {request_id, prompt_tokens,
        completion_tokens} -> {request_id, logprobs, total_logprob,
        node_id}. Works under every gen_scheduler (a dedicated scorer
        shares the lane's params; first call compiles its bucket)."""
        if self._injected_fault is not None:
            raise RuntimeError(f"fault injected: {self._injected_fault}")
        self._check_model(request)
        from tpu_engine.models.transformer import TransformerConfig

        cfg = getattr(self.engine.spec, "config", None)
        if not isinstance(cfg, TransformerConfig) or not cfg.causal:
            # Teacher-forced next-token logprobs are a decoder-LM notion;
            # encoders (BERT dialect) reject with the scoring message, not
            # a confusing generation error from deeper in the stack.
            raise ValueError(
                f"model '{self.config.model}' does not support scoring")
        deadline = Deadline.from_request(request)
        tier = self._request_tier(request)
        with self._traced_request(request, "score") as span:
            with self._admitted(deadline, trace=(span.ctx,
                                                 span.request_id),
                                tier=tier):
                return self._score_admitted(request, deadline, span.ctx)

    def _score_admitted(self, request: dict,
                        deadline: Optional[Deadline],
                        tctx=None) -> dict:
        with self._counter_lock:
            self._total_requests += 1
        completion = [int(t) for t in request["completion_tokens"]]
        if not completion:
            raise ValueError("completion_tokens must be non-empty")
        item = _ScoreItem(request["request_id"],
                          [int(t) for t in request["prompt_tokens"]],
                          completion)
        scorer = self._get_scorer()
        total = max(len(item.prompt), 1) + len(completion)
        largest = scorer._prompt_buckets[-1]
        if total > largest:
            # Validate BEFORE the item joins a shared batch: one over-long
            # request must 400 alone, never poison its co-batched group.
            raise ValueError(
                f"prompt+completion length {total} exceeds the largest "
                f"sequence bucket {largest}")
        t0 = time.perf_counter()
        # Concurrent evals requests (the lm-eval-harness shape) batch into
        # one bucketed forward instead of N sequential batch-1 forwards.
        if self._score_unified():
            # Unified stateless serving: the score joins the continuous
            # scheduler as a single-tick row — same slot pool, deadlines,
            # brownout, and counters as the lane's decode streams. The
            # scheduler groups co-pending score rows into ONE bucketed
            # forward per tick (the retired score-batcher's semantics).
            sink = (TraceSink(self.tracer, self.node_id,
                              item.request_id, tctx)
                    if tctx is not None else None)
            fut = self.generator.submit_score(
                item.prompt, item.completion, deadline=deadline,
                sink=sink, tag=item.request_id)
            lps, _us = fut.result(
                timeout=(600.0 if deadline is None
                         else max(5.0, deadline.remaining_s() + 5.0)))
        else:
            lps = self._score_processor().process(item, deadline=deadline)
        return {
            "request_id": item.request_id,
            "logprobs": lps,
            "total_logprob": float(sum(lps)),
            "node_id": self.node_id,
            "score_time_us": int((time.perf_counter() - t0) * 1e6),
        }

    def _get_scorer(self):
        """The lane's scoring Generator: the batch scheduler's own
        Generator when it has one (shared executable caches), else a lazy
        dedicated instance sharing the lane's (possibly reloaded) params."""
        from tpu_engine.runtime.generator import Generator

        if isinstance(self.generator, Generator):
            return self.generator
        with self._counter_lock:
            scorer = getattr(self, "_scorer", None)
            if scorer is None:
                scorer = Generator(
                    self.engine.spec, params=self.engine.params,
                    dtype=self.config.dtype,
                    device=getattr(self.engine, "_device", None))
                self._scorer = scorer
        # Track hot reloads: params is a cheap reference swap.
        scorer.params = self.engine.params
        return scorer

    def _score_processor(self):
        proc = getattr(self, "_score_proc", None)
        if proc is None:
            with self._counter_lock:
                proc = getattr(self, "_score_proc", None)
                if proc is None:
                    proc = BatchProcessor(
                        self.config.max_batch_size,
                        self.config.batch_timeout_ms,
                        self._process_score_batch,
                        name=f"{self.node_id}-score-batcher",
                    )
                    proc.start()
                    self._score_proc = proc
        return proc

    def _process_score_batch(self, items):
        scorer = self._get_scorer()
        out = scorer.score([it.prompt for it in items],
                           [it.completion for it in items])
        return out

    def _check_model(self, request: dict) -> None:
        """A request addressed to a specific model must never be answered
        by a lane serving a different one (multi-model routing sends it to
        the right sub-ring; this guards misdirected/direct-port hits)."""
        want = request.get("model")
        have = getattr(self.engine.spec, "name", None)
        if want is not None and have is not None and str(want) != have:
            raise ValueError(
                f"this lane serves model '{have}', not '{want}'")

    def reload_weights(self, model_path: str) -> dict:
        """Hot weight reload: load a checkpoint for the SERVED architecture
        and swap it into every lane (one-shot engine + generation
        scheduler) without pausing serving. Swap semantics: a one-shot
        /infer batch completes atomically on whichever params it captured;
        a decode stream mid-flight picks up the new weights from its NEXT
        chunk (stop the lane first for a hard cut). Caches of old-weight
        results (/infer LRU, prefix cache) are invalidated, and late
        writes from in-flight old-weight work are fenced by a generation
        stamp. Architecture mismatches are rejected with the old weights
        still serving. (The reference's only weight-update path is
        restarting the worker process.)"""
        params = _load_model_path(self.engine.spec, model_path)
        if params is None:
            raise ValueError(f"no loadable weights at '{model_path}'")
        return self.apply_weights(params, source=model_path)

    def apply_weights(self, params, source: str = "<params>") -> dict:
        """The swap half of reload_weights — combined mode loads the
        checkpoint once and applies it per lane."""
        self.engine.set_params(params)  # validates + quantizes + places
        if self.generator is not None:
            if hasattr(self.generator, "set_params"):
                self.generator.set_params(self.engine.params)
            else:
                self.generator.params = self.engine.params
        with self._reload_lock:
            self._weights_gen += 1
            self.cache.clear()  # cached results came from old weights
        return {"ok": True, "node_id": self.node_id, "model_path": source}

    def inject_fault(self, reason: str = "injected") -> None:
        self._injected_fault = reason
        for listener in self._fault_listeners:
            listener(False)

    def inject_latency(self, seconds: float) -> None:
        """Slow-lane fault: every request sleeps this long before serving.
        The lane stays HEALTHY (no breaker trip from the fault itself) —
        exactly the failure mode deadlines and hedging exist for."""
        self._injected_latency_s = max(0.0, float(seconds))

    def heal(self) -> None:
        self._injected_fault = None
        self._injected_latency_s = 0.0
        for listener in self._fault_listeners:
            # A draining lane stays disabled at the native front even once
            # healed — drain outranks health for new admissions.
            listener(not self._admission.draining)

    def _maybe_slow(self) -> None:
        if self._injected_latency_s > 0:
            time.sleep(self._injected_latency_s)

    # -- overload control (priority tiers + staged brownout) -------------------

    def _request_tier(self, request: dict) -> Optional[int]:
        """The request's priority tier when an overload feature reads it
        (tiered admission or brownout clamping); None otherwise — with
        both off, the ``priority`` field is ignored entirely, additive
        and wire-compatible (MIGRATION.md). An unknown value with a
        feature ON is a 400, same contract as every validated field."""
        if not self._tiered and self._brownout is None:
            return None
        return parse_priority(request)

    def _brownout_clamp(self, max_new: int, tier: Optional[int]) -> int:
        """Stage-4 degradation: below-top-tier generate requests get
        their token budget clamped — the cheapest way to keep serving a
        low tier at all once every earlier stage is engaged. Top-tier
        work is never clamped."""
        bo = self._brownout
        if (bo is None or tier is None or tier >= TOP_TIER
                or bo.stage < BROWNOUT_STAGES.index("clamp")):
            return max_new
        clamp = max(1, int(self.config.brownout_clamp_tokens))
        if max_new > clamp:
            self._brownout_clamps += 1  # GIL-safe info counter
            return clamp
        return max_new

    def _brownout_signals(self) -> dict:
        """Collect the saturation components for one control-loop
        evaluation, each normalized so 1.0 = at the red line. All
        signals already exist — this only reads them."""
        comps = {}
        adm = self._admission
        limit = adm.effective_limit()
        # Queue pressure: admitted depth vs the concurrency limit, or —
        # unbounded lanes — vs twice the decode batch (the point where
        # queued work can no longer all be in a batch).
        nominal = limit or 2 * max(1, self.config.gen_max_batch_size)
        comps["queue_depth"] = adm.depth / nominal
        missed = adm.shed_deadline
        gen = self.generator
        st = None
        if gen is not None and hasattr(gen, "stats"):
            try:
                st = gen.stats()
            except Exception:
                st = None
        if st:
            # Decode-loop tick age vs the stall threshold (default red
            # line 2 s when none is configured): a loop spending whole
            # seconds inside one dispatch is saturated long before it is
            # wedged.
            age = st.get("last_tick_age_s")
            stall = float(self.config.scheduler_stall_s or 0.0) or 2.0
            if age is not None:
                comps["tick_age"] = age / stall
            kv = st.get("kv_pool") or {}
            if kv:
                # Pool starvation events and deferred admissions: rows
                # already competing for blocks.
                comps["pool_pending"] = (kv.get("pending_admissions", 0)
                                         / max(1, self.n_gen_slots()))
                starved = st.get("pool_starved", 0)
                if starved > self._brownout_prev["starved"]:
                    comps["pool_starved"] = 1.0
                self._brownout_prev["starved"] = starved
            missed += st.get("deadline_cancelled", 0)
        # Deadline misses since the last evaluation: work is already
        # arriving dead — the clearest "past the red line" signal.
        if missed > self._brownout_prev["missed"]:
            comps["deadline_miss"] = 1.0
        self._brownout_prev["missed"] = missed
        return comps

    def n_gen_slots(self) -> int:
        return max(1, int(self.config.gen_max_batch_size))

    def _apply_brownout(self, action: str, comps: dict) -> None:
        """Apply the controller's current stage to the lane and drop the
        matching ``overload`` marker span (one per transition — the
        escalations+restores counters and these spans must agree;
        fault_injection --overload asserts it)."""
        stage = self._brownout.stage
        gen = self.generator
        if gen is not None and hasattr(gen, "set_brownout"):
            gen.set_brownout(
                budget_frac=BROWNOUT_BUDGET_FRAC if stage >= 1 else 1.0,
                suspend_spec=stage >= 2,
                defer_swap_in=stage >= 3)
        ctx = TraceContext.root(f"brownout:{self.node_id}").child()
        binding = max(comps, key=comps.get) if comps else ""
        self.tracer.record(
            "brownout", "overload", self.node_id, 0,
            trace_id=ctx.trace_id, span_id=ctx.span_id,
            start_ts=time.time(),
            attrs={"action": action, "stage": stage,
                   "stage_name": BROWNOUT_STAGES[stage],
                   "binding_signal": binding})

    def _brownout_loop(self) -> None:
        """The control loop: read signals, walk the ladder, apply. Stage
        changes are the only side effects; a failed evaluation skips the
        sample (the loop must degrade the LANE, never kill it)."""
        interval = max(0.05, float(self.config.brownout_interval_s))
        while not self._brownout_stop.wait(interval):
            try:
                comps = self._brownout_signals()
                action = self._brownout.evaluate(comps)
                if action is not None:
                    self._apply_brownout(action, comps)
            except Exception:
                continue  # a torn stats read is a skipped sample

    @contextlib.contextmanager
    def _traced_request(self, request: dict, op: str):
        """Worker-root span scope shared by the blocking request paths
        (/infer, /generate, /score): parse the caller's traceparent (or
        derive a root from request_id), yield a `_RootSpan` whose ``ctx``
        parents every stage child, and record the root — wall time,
        outcome (ok / shed kind / error), plus whatever attrs the body
        added — however the body exits."""
        parent = TraceContext.from_request(request)
        request_id = str(request.get("request_id", ""))
        ctx = (parent.child() if parent is not None
               else TraceContext.root(request_id))
        span = _RootSpan(ctx, request_id)
        t0 = time.perf_counter()
        start = time.time()
        try:
            yield span
            span.attrs["outcome"] = "ok"
        except ShedError as exc:
            span.attrs["outcome"] = exc.kind
            raise
        finally:
            self.tracer.record(
                request_id, op, self.node_id,
                (time.perf_counter() - t0) * 1e6,
                cached=span.cached, trace_id=ctx.trace_id,
                span_id=ctx.span_id,
                parent_id=parent.span_id if parent is not None else None,
                start_ts=start, attrs=span.attrs)

    @contextlib.contextmanager
    def _admitted(self, deadline, trace=None, tier=None):
        """Admission scope shared by every blocking request path: admit
        (drain/depth/tier/expired-deadline can shed -> wire 503), apply
        the slow-lane fault, and ALWAYS release. The streaming path
        manages release by hand — its in-flight window is the iterator's
        life, not this frame's.

        ``tier``: the request's priority tier for tiered admission (None
        = untiered, the pre-overload-control behavior). A request that
        completes normally feeds its wall time to the AIMD limiter —
        latency observed WITH queueing included, which is exactly the
        congestion signal the limit adapts to.

        ``trace``: optional (TraceContext, request_id) — records an
        ``admission`` stage span (child of the worker root) whose duration
        covers the admit decision AND any injected slow-lane latency, so a
        slowed lane's traces show WHERE the time went. A shed records the
        span with the refusal kind before re-raising."""
        t0 = time.perf_counter()
        start = time.time()

        def _span(outcome):
            if trace is None:
                return
            ctx, request_id = trace
            child = ctx.child()
            self.tracer.record(
                request_id, "admission", self.node_id,
                (time.perf_counter() - t0) * 1e6,
                trace_id=child.trace_id, span_id=child.span_id,
                parent_id=ctx.span_id, start_ts=start,
                attrs={"outcome": outcome})

        try:
            self._admission.admit(deadline, tier=tier)
        except ShedError as exc:
            exc.stage = exc.stage or "worker_admission"
            _span(exc.kind)
            raise
        ok = False
        try:
            self._maybe_slow()
            _span("admitted")
            yield
            ok = True
        finally:
            self._admission.release()
            if ok and self._aimd is not None:
                self._aimd.observe(time.perf_counter() - t0)

    # -- drain (lame-duck) -----------------------------------------------------

    def drain(self) -> str:
        """Refuse new admissions (503 + Retry-After) while in-flight work
        completes — the lame-duck half of graceful removal. The gateway's
        ``remove_worker(drain=True)`` and ``/admin/drain`` drive this.
        Fault listeners fire too: the native C++ front must stop answering
        a draining lane's cache hits (its hit path never enters Python, so
        the admission check alone cannot reach it). Idempotent: a second
        drain answers the named ``already-draining`` status instead of
        re-running the side effects."""
        status = self._admission.drain()
        if status == "already-draining":
            return status
        gen = self.generator
        if gen is not None and hasattr(gen, "set_draining"):
            gen.set_draining(True)
        for listener in self._fault_listeners:
            listener(False)
        return status

    def undrain(self) -> str:
        """Inverse of :meth:`drain`; ``not-draining`` names the no-op
        (undrain of a lane that never drained — idempotent, never
        raises)."""
        status = self._admission.undrain()
        if status == "not-draining":
            return status
        gen = self.generator
        if gen is not None and hasattr(gen, "set_draining"):
            gen.set_draining(False)
        if self._injected_fault is None:  # don't resurrect a faulted lane
            for listener in self._fault_listeners:
                listener(True)
        return status

    @property
    def draining(self) -> bool:
        return self._admission.draining

    # -- live stream migration (DESIGN.md "Live stream migration") -------------

    def handle_migrate_export(self, request: dict) -> dict:
        """/admin/migrate: export ONE live stream's row — tokens
        emitted, sampling state, remaining budget, and its KV block
        chain — so the gateway can adopt it on another lane with zero
        re-prefilled tokens. The local stream ends with a retryable
        ``migrated`` terminal event. Refusals (unknown stream, mid-
        prefill row, non-paged lane) come back ``{"ok": False,
        "reason"}`` — never an error: the caller's fallback is the
        replay resume, which needs nothing from this lane."""
        rid = request.get("request_id")
        if not rid:
            raise ValueError("request_id is required")
        gen = self.generator
        if gen is None or not hasattr(gen, "export_row"):
            return {"ok": False, "node_id": self.node_id,
                    "reason": "this lane has no continuous decode "
                              "scheduler to export from"}
        timeout_s = float(request.get("timeout_s", 10.0))
        out = gen.export_row(str(rid), timeout_s=timeout_s,
                             wait_prefill=bool(
                                 request.get("wait_prefill", False)),
                             cancel=bool(request.get("cancel", False)))
        out["node_id"] = self.node_id
        return out

    # -- fleet prefix tier (DESIGN.md "Fleet-wide prefix tier") ----------------

    def handle_export_prefix(self, request: dict) -> dict:
        """/admin/export_prefix: serve a peer lane's prefix fetch — the
        longest radix chain matching the requested token prefix,
        serialized under one pool-lock pass (device-resident and
        host-demoted blocks alike; NO stream state — this is a cache
        read, not a migration). Refusals (draining lane, no scheduler,
        no matching chain) come back ``{"ok": False, "node_id",
        "reason"}`` and never raise: the fetching peer's fallback is
        local prefill, which needs nothing from this lane. The drain
        refusal names this node so a stale directory entry is
        attributable at the fetcher."""
        gen = self.generator
        if gen is None or not hasattr(gen, "export_prefix"):
            return {"ok": False, "node_id": self.node_id,
                    "reason": "this lane has no continuous decode "
                              "scheduler to export from"}
        if self.draining:
            return {"ok": False, "node_id": self.node_id,
                    "reason": f"lane {self.node_id} is draining"}
        tokens = request.get("tokens")
        if not isinstance(tokens, list) or not tokens:
            return {"ok": False, "node_id": self.node_id,
                    "reason": "request carries no token prefix"}
        max_blocks = request.get("max_blocks")
        out = gen.export_prefix(
            tokens, max_blocks=(int(max_blocks)
                                if max_blocks is not None else None))
        out["node_id"] = self.node_id
        return out

    def set_prefix_fetch_transport(self, fn) -> None:
        """Install an in-process peer transport (combined mode): a
        callable ``(hint, payload) -> dict`` replacing the default
        HTTP POST to the hint's address — in-process lanes have no
        URL to dial."""
        self._prefix_fetch_transport = fn

    def _fetch_prefix_peer(self, hint: dict, tokens,
                           max_blocks: int) -> Optional[dict]:
        """The fetch callable installed on the scheduler
        (--prefix-fetch): pull the hinted peer's chain, classifying
        every transport outcome into the fallback-ladder rung the
        scheduler counts (``peer_unreachable`` / ``peer_refused`` /
        ``timeout`` / ``inflight_capped``). Runs on the scheduler's
        prefill thread; the semaphore acquire is non-blocking so a
        thundering herd on one hot prefix degrades to local prefill,
        never a convoy. Returns None for a self-hint (a retry landed
        the request on the owner itself — nothing to fetch)."""
        if hint.get("lane") == self.node_id:
            return None
        if not self._prefix_fetch_sem.acquire(blocking=False):
            return {"ok": False, "rung": "inflight_capped"}
        try:
            payload = {"tokens": [int(t) for t in tokens],
                       "max_blocks": int(max_blocks)}
            timeout_s = max(0.1, float(getattr(
                self.config, "gen_prefix_fetch_timeout_s", 5.0)))
            if self._prefix_fetch_transport is not None:
                try:
                    out = self._prefix_fetch_transport(hint, payload)
                except Exception:
                    return {"ok": False, "rung": "peer_unreachable"}
            else:
                addr = hint.get("addr")
                if not addr:
                    return {"ok": False, "rung": "peer_unreachable",
                            "reason": "hint carries no peer address"}
                try:
                    out = self._prefix_peer_client(addr).export_prefix(
                        payload, timeout_s=timeout_s)
                except (socket.timeout, TimeoutError):
                    return {"ok": False, "rung": "timeout"}
                except Exception as exc:
                    if "timed out" in str(exc).lower():
                        return {"ok": False, "rung": "timeout"}
                    return {"ok": False, "rung": "peer_unreachable"}
            if not isinstance(out, dict) or not out.get("ok"):
                return {"ok": False, "rung": "peer_refused",
                        "reason": (out or {}).get("reason")
                        if isinstance(out, dict) else "malformed reply"}
            return {"ok": True, "chain": out.get("chain"),
                    "blocks": out.get("blocks")}
        finally:
            self._prefix_fetch_sem.release()

    def _prefix_peer_client(self, addr: str):
        """One cached HTTP client per peer address (the default fetch
        transport). Bounded: directory capacity bounds distinct hint
        addresses far below any worrying count, but cap anyway."""
        from tpu_engine.serving.clients import HttpWorkerClient

        with self._prefix_peers_lock:
            client = self._prefix_peers.get(addr)
            if client is None:
                if len(self._prefix_peers) >= 64:
                    self._prefix_peers.clear()
                client = HttpWorkerClient(
                    addr, timeout_s=max(0.1, float(getattr(
                        self.config, "gen_prefix_fetch_timeout_s", 5.0))),
                    pool_size=max(1, int(getattr(
                        self.config, "gen_prefix_fetch_inflight", 2) or 1)))
                self._prefix_peers[addr] = client
            return client

    def handle_timeline(self, request: Optional[dict] = None) -> dict:
        """/admin/timeline: the continuous scheduler's flight-recorder
        ring (per-tick records, newest last) plus dump bookkeeping.
        GET reads; POST {"dump": reason} forces a postmortem artifact.
        With the recorder unconfigured (the default) the payload says so
        and carries no timeline — the endpoint itself is additive."""
        gen = self.generator
        if gen is None or not hasattr(gen, "flight_timeline"):
            return {"node_id": self.node_id, "enabled": False,
                    "reason": "this lane has no continuous scheduler"}
        if request and request.get("dump"):
            dump = gen.flight_dump(str(request["dump"]))
            return {"node_id": self.node_id,
                    "enabled": dump is not None, "dumped": dump}
        n = int(request.get("n", 0)) if request else 0
        out = gen.flight_timeline(n or None)
        out["node_id"] = self.node_id
        return out

    def flight_dump(self, reason: str) -> Optional[dict]:
        """Force a flight-recorder dump (gateway degraded-fleet entry
        trigger). None when the lane has no armed recorder."""
        gen = self.generator
        if gen is None or not hasattr(gen, "flight_dump"):
            return None
        return gen.flight_dump(reason)

    def handle_profile(self, request: Optional[dict] = None) -> dict:
        """/admin/profile (worker): jax.profiler capture bounded in
        scheduler ticks. Requires --profile-dir. POST {"ticks": N}
        starts a capture the decode loop stops after N ticks;
        {"action": "stop"} stops early; {"action": "status"} / GET
        reports the countdown. Lanes without a continuous scheduler
        fall back to unbounded start/stop."""
        profile_dir = getattr(self.config, "profile_dir", None)
        request = request or {}
        action = request.get("action")
        gen = self.generator
        ticked = gen is not None and hasattr(gen, "start_profile")
        if action == "status":
            out = {"node_id": self.node_id, "profile_dir": profile_dir}
            if ticked:
                out.update(gen.profile_status())
            return out
        if action == "stop":
            from tpu_engine.utils import tracing

            res = gen.stop_profile() if ticked else tracing.profiler_stop()
            return {"node_id": self.node_id, **res}
        if not profile_dir:
            return {"node_id": self.node_id,
                    "error": "profiling not configured "
                             "(start the worker with --profile-dir)"}
        log_dir = request.get("log_dir") or profile_dir
        ticks = int(request.get("ticks", 0) or 0)
        if ticks > 0 and ticked:
            res = gen.start_profile(log_dir, ticks)
        else:
            from tpu_engine.utils import tracing

            res = tracing.profiler_start(log_dir)
        return {"node_id": self.node_id, **res}

    def set_role(self, role: str) -> dict:
        """/admin/role: flip this lane's serving role at runtime
        (fleet rebalancing under diurnal load — the gateway rides
        /admin/drain + stream migration around the flip). Role is
        advisory routing metadata: the lane keeps serving whatever it
        receives, so the flip itself is safe mid-traffic."""
        role = str(role)
        if role not in ("prefill", "decode", "both"):
            raise ValueError(f"role must be prefill|decode|both, "
                             f"got {role!r}")
        if role != "both" and (
                not self._continuous
                or (self.config.gen_kv_block_size <= 0
                    and getattr(self.engine.spec, "state_family", None)
                    != "state_slab")):
            raise ValueError(
                "a dedicated role requires the continuous scheduler "
                "with the paged KV cache (--kv-block-size > 0)")
        self.config.role = role
        return {"ok": True, "node_id": self.node_id, "role": role}

    @property
    def role(self) -> str:
        return self.config.role

    def on_fault_change(self, listener) -> None:
        """Register listener(healthy: bool) — the native HTTP front uses
        this to stop serving a faulted lane's cache hits in C++."""
        self._fault_listeners.append(listener)

    # -- request path ---------------------------------------------------------

    @staticmethod
    def _cache_key(input_data, shape=None) -> bytes:
        blob = np.asarray(input_data, dtype=np.float32).tobytes()
        if shape is not None:
            blob = np.asarray(shape, np.int64).tobytes() + b"|" + blob
        return blob

    def _infer_core(self, request: dict) -> Tuple[str, bytes, bool, int]:
        """Shared /infer flow → (request_id, pre-encoded JSON fragment of
        output_data, cached?, inference_time_us).

        The fragment is cached alongside the array: serializing ~1000
        floats costs ~670 µs in json.dumps but 1 µs to splice pre-encoded —
        on a ~99% hit-rate workload (the reference's own benchmark) that
        serialization dominated the whole request path.

        Tracing: the worker-side root span (op ``infer``) covers the full
        worker wall time — admission through response fragment ready —
        with per-stage children (admission, cache_lookup, queue_wait,
        batch_form, device_compute, serialize). Its parent is the
        caller's ``traceparent`` span when supplied; otherwise the root
        derives its trace_id from request_id, so gateway and worker
        correlate with zero wire change."""
        if self._injected_fault is not None:
            raise RuntimeError(f"fault injected: {self._injected_fault}")
        self._check_model(request)
        deadline = Deadline.from_request(request)
        tier = self._request_tier(request)
        with self._traced_request(request, "infer") as span:
            # Resilience: admission BEFORE the request counts — a shed
            # request never skews the reference-exact /health counters,
            # only its own (additive) admission block. Expired/overloaded/
            # draining raise here and surface as 503 + Retry-After.
            with self._admitted(deadline, trace=(span.ctx,
                                                 span.request_id),
                                tier=tier):
                with self._counter_lock:
                    self._total_requests += 1
                out = self._infer_admitted(request, deadline, span.ctx)
                span.cached = out[2]
                span.attrs["inference_time_us"] = out[3]
                return out

    def _infer_admitted(self, request: dict, deadline: Optional[Deadline],
                        tctx: TraceContext) -> Tuple[str, bytes, bool, int]:
        request_id = request["request_id"]
        input_data = request["input_data"]
        shape = request.get("shape")
        if shape is not None:
            shape = tuple(int(d) for d in shape)

        key = self._cache_key(input_data, shape)
        cl0 = time.perf_counter()
        cl_start = time.time()
        frag = self.cache.get(key)
        child = tctx.child()
        self.tracer.record(
            request_id, "cache_lookup", self.node_id,
            (time.perf_counter() - cl0) * 1e6,
            trace_id=child.trace_id, span_id=child.span_id,
            parent_id=tctx.span_id, start_ts=cl_start,
            attrs={"hit": frag is not None})
        if frag is not None:
            with self._counter_lock:
                self._cache_hits += 1
            # Reference reports a fixed fake latency on hits (:65).
            return request_id, frag, True, self.config.fake_cached_latency_us

        while True:
            # Miss path: deadline-aware early rejection against the
            # measured service-time EWMA — a doomed request sheds here for
            # the cost of a 503 instead of occupying a batch row it cannot
            # use. (Re-checked per coalescing round: this request's OWN
            # budget governs.)
            est = self._service_ewma_us
            self._admission.check_deadline(
                deadline, None if est is None else est / 1e6)

            with self._inflight_lock:
                entry = self._inflight.get(key)
                leader = entry is None
                if leader:
                    entry = _Inflight()
                    self._inflight[key] = entry
            if leader:
                break
            w0 = time.perf_counter()
            w_start = time.time()
            if not entry.event.wait(
                    timeout=clamp_timeout(deadline, 120.0)):
                if deadline is not None and deadline.expired():
                    raise DeadlineExceeded(
                        "deadline expired waiting on coalesced result")
                raise RuntimeError("coalesced request timed out")
            if entry.error is not None:
                if isinstance(entry.error, DeadlineExceeded):
                    # The LEADER's budget expired — a per-request fact,
                    # not a property of the input. This follower's budget
                    # may be fine: retire the dead entry (the leader's own
                    # pop may not have run yet; leaving it would make this
                    # loop spin on it) and recompute — next round it
                    # either joins a live leader or leads itself.
                    with self._inflight_lock:
                        if self._inflight.get(key) is entry:
                            self._inflight.pop(key)
                    continue
                # Re-raise the leader's exception unchanged so client-input
                # error types (KeyError/TypeError/ValueError) keep their
                # no-breaker-penalty classification in LocalWorkerClient —
                # a coalesced bad input must not count as a lane failure.
                raise entry.error
            child = tctx.child()
            self.tracer.record(
                request_id, "coalesced_wait", self.node_id,
                (time.perf_counter() - w0) * 1e6,
                trace_id=child.trace_id, span_id=child.span_id,
                parent_id=tctx.span_id, start_ts=w_start,
                attrs={"leader_time_us": entry.time_us})
            return request_id, entry.frag, False, entry.time_us

        try:
            gen0 = self._weights_gen  # stamp BEFORE the compute
            result = self._dispatch_infer(
                _BatchItem(request_id, input_data, shape, trace=tctx),
                deadline)
            s0 = time.perf_counter()
            s_start = time.time()
            frag = _encode_output(result.output_data)
            child = tctx.child()
            self.tracer.record(
                request_id, "serialize", self.node_id,
                (time.perf_counter() - s0) * 1e6,
                trace_id=child.trace_id, span_id=child.span_id,
                parent_id=tctx.span_id, start_ts=s_start)
            # A hot reload between compute and put would otherwise re-seed
            # the freshly cleared cache with an old-weight result forever;
            # check+put must be atomic against apply_weights' bump+clear.
            with self._reload_lock:
                if gen0 == self._weights_gen:
                    self.cache.put(key, frag)
            entry.frag = frag
            entry.time_us = result.inference_time_us
            # EWMA (0.2 step) of the miss-path service time — feeds the
            # early-rejection estimate above.
            t = float(result.inference_time_us)
            self._service_ewma_us = (t if self._service_ewma_us is None
                                     else 0.8 * self._service_ewma_us + 0.2 * t)
        except BaseException as exc:
            entry.error = exc
            raise
        finally:
            entry.event.set()
            with self._inflight_lock:
                self._inflight.pop(key, None)
        return request_id, frag, False, result.inference_time_us

    def handle_infer(self, request: dict) -> dict:
        """Serve one /infer payload; wire schema identical to the reference
        (``worker_node.cpp:50-83``). Additive field: optional "shape"
        [h, w, c] for mixed-shape models (engine shape buckets)."""
        request_id, frag, cached, time_us = self._infer_core(request)
        return {
            "request_id": request_id,
            "output_data": json.loads(frag),
            "node_id": self.node_id,
            "cached": cached,
            "inference_time_us": time_us,
        }

    def handle_infer_raw(self, request: dict) -> bytes:
        """handle_infer, already serialized: the full response JSON built by
        splicing the cached output fragment — no float re-encoding."""
        request_id, frag, cached, time_us = self._infer_core(request)
        return (b'{"request_id": ' + json.dumps(request_id).encode()
                + b', "output_data": ' + frag
                + b', "node_id": ' + self._node_id_json
                + b', "cached": ' + (b"true" if cached else b"false")
                + b', "inference_time_us": ' + str(time_us).encode() + b"}")

    def _infer_unified(self) -> bool:
        """True when /infer misses ride the continuous scheduler as
        single-tick rows (unified stateless serving) instead of the
        legacy batch lane. Requires a scheduler that accepted an
        infer_engine — test fakes and non-continuous lanes fall back."""
        gen = self.generator
        return (self._unified and gen is not None
                and bool(getattr(gen, "accepts_oneshot", False)))

    def _score_unified(self) -> bool:
        gen = self.generator
        return (self._unified and gen is not None
                and bool(getattr(gen, "accepts_score", False)))

    def _dispatch_infer(self, item: _BatchItem,
                        deadline: Optional[Deadline]) -> _BatchResult:
        """Miss-path dispatch seam: the unified lane submits one
        single-tick scheduler row (one slot pool shared with decode
        streams — same deadlines, brownout, shedding, counters); legacy
        lanes keep the dedicated batch processor. Result and exception
        surface (DeadlineExceeded, engine errors) are identical either
        way, so the coalescing/cache/EWMA machinery upstream never knows
        which lane answered."""
        if not self._infer_unified():
            return self.batch_processor.process(item, deadline=deadline)
        sink = (TraceSink(self.tracer, self.node_id, item.request_id,
                          item.trace)
                if getattr(item, "trace", None) is not None else None)
        fut = self.generator.submit_infer(
            item.input_data, shape=item.shape, deadline=deadline,
            sink=sink, tag=item.request_id)
        out, time_us = fut.result(
            timeout=(600.0 if deadline is None
                     else max(5.0, deadline.remaining_s() + 5.0)))
        return _BatchResult(out, time_us)

    def _batch_observer(self, items, timing) -> None:
        """BatchProcessor tracing hook (dispatch thread): per-request
        ``queue_wait`` spans plus one shared ``batch_form`` span per
        member — the in-queue portion of latency the flat recorder could
        never attribute. Runs after the batch's futures resolve; span
        wall-clock is reconstructed from the observer call time."""
        end_wall = time.time()
        formed_at = end_wall - timing.compute_us / 1e6
        for it, wait_us in zip(items, timing.queue_wait_us):
            ctx = getattr(it, "trace", None)
            if ctx is None:
                continue
            qw = ctx.child()
            self.tracer.record(
                it.request_id, "queue_wait", self.node_id, wait_us,
                trace_id=qw.trace_id, span_id=qw.span_id,
                parent_id=ctx.span_id, start_ts=formed_at - wait_us / 1e6)
            bf = ctx.child()
            self.tracer.record(
                it.request_id, "batch_form", self.node_id,
                timing.batch_form_us, batch_size=len(items),
                trace_id=bf.trace_id, span_id=bf.span_id,
                parent_id=ctx.span_id,
                start_ts=formed_at - timing.batch_form_us / 1e6,
                attrs={"timed_out": timing.timed_out})

    def _record_device_spans(self, items, elapsed_us: float,
                             op: str = "device_compute") -> None:
        """One ``device_compute`` child span per traced batch member —
        duration is the whole batch's device leg (the exact measurement
        ``inference_time_us`` divides by batch size), batch_size carries
        the divisor."""
        start_wall = time.time() - elapsed_us / 1e6
        n = len(items)
        for it in items:
            ctx = getattr(it, "trace", None)
            if ctx is None:
                continue
            child = ctx.child()
            self.tracer.record(
                it.request_id, op, self.node_id, elapsed_us, batch_size=n,
                trace_id=child.trace_id, span_id=child.span_id,
                parent_id=ctx.span_id, start_ts=start_wall)

    def _process_batch(self, items: List[_BatchItem]) -> List[_BatchResult]:
        """Lockstep path — runs only when the engine lacks batch_submit
        (plain/fake engines); pipelined engines use _submit/_collect below."""
        start = time.perf_counter()
        shapes = ([it.shape for it in items]
                  if any(it.shape is not None for it in items) else None)
        outputs = self.engine.batch_predict(
            [it.input_data for it in items], shapes=shapes)
        elapsed_us = (time.perf_counter() - start) * 1e6
        per_request_us = int(elapsed_us / max(1, len(items)))
        self._record_device_spans(items, elapsed_us)
        return [_BatchResult(out, per_request_us) for out in outputs]

    def _submit_batch(self, items: List[_BatchItem]):
        """Pipeline dispatch half: stage + enqueue device work, no blocking.
        The batcher keeps `pipeline_depth` of these in flight so round-trips
        to the device overlap instead of serializing."""
        start = time.perf_counter()
        shapes = ([it.shape for it in items]
                  if any(it.shape is not None for it in items) else None)
        handle = self.engine.batch_submit(
            [it.input_data for it in items], shapes=shapes)
        return handle, start, items

    def _collect_batch(self, submitted) -> List[_BatchResult]:
        """Blocking half. `inference_time_us` semantics differ deliberately
        from the reference (worker_node.cpp:123 divides the bare execute
        time): here elapsed spans submit→collect, i.e. the batch's full
        residence in the device pipeline, including transfer and the
        overlap window behind up to pipeline_depth-1 older batches. That is
        the latency a caller actually experienced for the device leg; the
        execute-only number would undercount on a link-dominated setup."""
        handle, start, items = submitted
        outputs = self.engine.batch_collect(handle)
        elapsed_us = (time.perf_counter() - start) * 1e6
        per_request_us = int(elapsed_us / max(1, len(items)))  # cf. worker_node.cpp:123
        self._record_device_spans(items, elapsed_us)
        return [_BatchResult(out, per_request_us) for out in outputs]

    # -- generation path -------------------------------------------------------

    def handle_generate(self, request: dict) -> dict:
        """Serve one /generate payload: autoregressive decode with batching.

        Wire: {request_id, prompt_tokens, max_new_tokens?, eos_id?,
        temperature?, seed?} → {request_id, tokens, node_id,
        generate_time_us}. No reference counterpart (the reference can only
        run one-shot graphs); field style matches /infer.
        """
        if self.generator is None or getattr(self.generator,
                                             "_stateless", False):
            # A stateless-family lane DOES carry a continuous scheduler
            # (its rows are all one-shot), but that is not a generation
            # lane — keep the reference wire contract (ValueError → 400).
            raise ValueError(f"model '{self.config.model}' does not support generation")
        if self._injected_fault is not None:
            raise RuntimeError(f"fault injected: {self._injected_fault}")
        self._check_model(request)
        deadline = Deadline.from_request(request)
        tier = self._request_tier(request)
        with self._traced_request(request, "generate") as span:
            with self._admitted(deadline, trace=(span.ctx,
                                                 span.request_id),
                                tier=tier):
                return self._generate_admitted(request, deadline,
                                               span.ctx, tier=tier)

    def _generate_admitted(self, request: dict,
                           deadline: Optional[Deadline],
                           tctx: TraceContext,
                           tier: Optional[int] = None) -> dict:
        with self._counter_lock:
            self._total_requests += 1
        item = _GenItem(
            request_id=request["request_id"],
            prompt=[int(t) for t in request["prompt_tokens"]],
            max_new_tokens=self._brownout_clamp(
                int(request.get("max_new_tokens", 32)), tier),
            eos_id=int(request.get("eos_id", -1)),
            temperature=float(request.get("temperature", 0.0)),
            seed=int(request.get("seed", 0)),
            top_p=float(request.get("top_p", 1.0)),
            top_k=_clamp_top_k(request.get("top_k", 0)),
            repetition_penalty=float(
                request.get("repetition_penalty", 1.0)),
            stop_tokens=tuple(int(t)
                              for t in request.get("stop_tokens", ())),
            beam_width=int(request.get("beam_width", 1)),
            length_penalty=float(request.get("length_penalty", 1.0)),
            min_p=_validate_min_p(request.get("min_p", 0.0)),
            trace=tctx,
        )
        self._validate_beam(item.beam_width, item.temperature, item.top_p,
                            item.top_k, item.repetition_penalty,
                            item.stop_tokens, item.length_penalty,
                            item.min_p)
        # Validate stopping params BEFORE the item can join a shared batch
        # — a malformed request must 400 alone, never poison its
        # co-batched group (the batch lane would otherwise surface
        # expand_stopping_params' error to every request in the group).
        expand_stopping_params(1, item.repetition_penalty,
                               [list(item.stop_tokens)]
                               if item.stop_tokens else None)
        if self._speculative and (item.top_p < 1.0 or item.top_k > 0
                                  or item.repetition_penalty != 1.0
                                  or item.min_p > 0):
            # Reject BEFORE the item enters a shared batch: rejection
            # sampling is exact for the temperature distribution only, and
            # one filtered request must not poison its co-batched group.
            raise ValueError(
                "speculative scheduler supports temperature sampling only "
                "(top_p/top_k/repetition_penalty unavailable; use "
                "gen_scheduler=continuous)")
        if self._continuous:
            t0 = time.perf_counter()
            fut = self.generator.submit(
                item.prompt, max_new_tokens=item.max_new_tokens,
                eos_id=item.eos_id, temperature=item.temperature,
                seed=item.seed, top_p=item.top_p, top_k=item.top_k,
                repetition_penalty=item.repetition_penalty,
                stop_tokens=list(item.stop_tokens), min_p=item.min_p,
                deadline=deadline,
                sink=TraceSink(self.tracer, self.node_id,
                               item.request_id, tctx),
                tag=item.request_id,
                # Fleet prefix tier: the gateway-attached hint rides
                # the payload; inert unless --prefix-fetch is on.
                prefix_hint=(request.get("prefix_hint")
                             if self.config.gen_prefix_fetch else None))
            # The scheduler itself cancels expired rows between chunks
            # (the future then raises DeadlineExceeded); the +5 s slack
            # keeps this outer wait a backstop, never the arbiter.
            tokens = fut.result(
                timeout=600 if deadline is None
                else max(5.0, deadline.remaining_s() + 5.0))
            elapsed_us = int((time.perf_counter() - t0) * 1e6)
            result = _GenResult(tokens, elapsed_us)
        else:
            result = self._gen_processor.process(item, deadline=deadline)
        return {
            "request_id": item.request_id,
            "tokens": result.tokens,
            "node_id": self.node_id,
            "generate_time_us": result.generate_time_us,
        }

    def handle_generate_stream(self, request: dict):
        """Streaming /generate: returns an iterator of SSE event byte
        chunks. Under the continuous scheduler tokens stream at
        iteration-level granularity (fresh tokens after each decode chunk);
        under the batch scheduler the full result arrives as one event —
        same wire contract, coarser cadence. Events:

          data: {"tokens": [..]}          incremental tokens
          data: {"done": true, "request_id", "tokens", "node_id",
                 "generate_time_us"}      terminal summary (or "error")
        """
        if self.generator is None or getattr(self.generator,
                                             "_stateless", False):
            # Same contract as handle_generate: a stateless-family
            # lane's scheduler has no decode loop to stream from.
            raise ValueError(
                f"model '{self.config.model}' does not support generation")
        if self._injected_fault is not None:
            raise RuntimeError(f"fault injected: {self._injected_fault}")
        self._check_model(request)
        # Deadline/admission EAGERLY too: an expired or shed request must
        # 503 before the 200 SSE stream is committed.
        deadline = Deadline.from_request(request)
        # Parse/validate EVERY field EAGERLY — after the iterator is handed
        # back, the response is already committed to a 200 SSE stream, and a
        # bad request must be a 400 like the blocking endpoint's (on both
        # scheduler paths).
        request_id = request["request_id"]
        if request.get("migrate_import") is not None:
            # Live stream migration continuation: the snapshot carries
            # every decode parameter — the surrounding payload's fields
            # are routing metadata only.
            return self._stream_import(request, deadline,
                                       self._request_tier(request))
        prompt = [int(t) for t in request["prompt_tokens"]]
        tier = self._request_tier(request)
        max_new = self._brownout_clamp(
            int(request.get("max_new_tokens", 32)), tier)
        eos_id = int(request.get("eos_id", -1))
        temperature = float(request.get("temperature", 0.0))
        seed = int(request.get("seed", 0))
        top_p = float(request.get("top_p", 1.0))
        top_k = _clamp_top_k(request.get("top_k", 0))
        rep_pen = float(request.get("repetition_penalty", 1.0))
        stop_toks = [int(t) for t in request.get("stop_tokens", ())]
        beam_width = int(request.get("beam_width", 1))
        length_penalty = float(request.get("length_penalty", 1.0))
        min_p_val = _validate_min_p(request.get("min_p", 0.0))
        # Same eager validation as the blocking endpoint: a malformed
        # request must 400 before the 200 SSE stream is committed.
        expand_stopping_params(1, rep_pen,
                               [stop_toks] if stop_toks else None)
        self._validate_beam(beam_width, temperature, top_p, top_k,
                            rep_pen, stop_toks, length_penalty, min_p_val)
        if self._speculative and (top_p < 1.0 or top_k > 0
                                  or rep_pen != 1.0 or min_p_val > 0):
            # Must fire HERE, before the iterator commits a 200 SSE stream
            # — same 400 the blocking endpoint gives this payload.
            raise ValueError(
                "speculative scheduler supports temperature sampling only "
                "(top_p/top_k/repetition_penalty unavailable; use "
                "gen_scheduler=continuous)")
        normalized = {"request_id": request_id, "prompt_tokens": prompt,
                      "max_new_tokens": max_new, "eos_id": eos_id,
                      "temperature": temperature, "seed": seed,
                      "top_p": top_p, "top_k": top_k,
                      "repetition_penalty": rep_pen,
                      "stop_tokens": stop_toks,
                      "beam_width": beam_width,
                      "length_penalty": length_penalty,
                      "min_p": min_p_val}
        if "priority" in request:
            # Tiered admission / brownout clamping must see the tier on
            # the one-shot path's inner handle_generate too.
            normalized["priority"] = request["priority"]
        if deadline is not None:
            # Forward the REMAINING budget (deadline propagation).
            normalized["deadline_ms"] = max(0.0, deadline.remaining_ms())
        if not self._continuous:
            # Eager shed check so drain/overload/expired 503s BEFORE the
            # 200 SSE stream commits (same contract as the continuous
            # path below); released immediately — handle_generate admits
            # for real on first iteration, and a shed that slips into the
            # gap still surfaces as the stream's terminal error event.
            self._admission.admit(deadline, tier=tier)
            self._admission.release()
            one_shot_parent = TraceContext.from_request(request)
            one_shot_ctx = (one_shot_parent.child()
                            if one_shot_parent is not None
                            else TraceContext.root(request_id))

            def one_shot():
                try:
                    # handle_generate admits (depth/drain/deadline) itself.
                    result = self.handle_generate(normalized)
                except Exception as exc:  # terminal error event, stream ends
                    yield sse_event(self._stream_error(
                        exc, request_id, one_shot_ctx.trace_id, 0))
                    return
                yield sse_event({"tokens": result["tokens"]})
                yield sse_event({"done": True, **result})
            return one_shot()

        # Continuous path: admit before the stream commits; depth is held
        # until the event iterator finishes (the stream IS the in-flight
        # work). An expired deadline raises here -> wire 503, not a 200.
        parent = TraceContext.from_request(request)
        tctx = (parent.child() if parent is not None
                else TraceContext.root(request_id))
        t_start_wall = time.time()
        t_admit = time.perf_counter()
        self._admission.admit(deadline, tier=tier)
        try:
            self._maybe_slow()
            with self._counter_lock:
                self._total_requests += 1
            q = self._stream_outbox()
            t0 = time.perf_counter()
            # Disaggregated handoff (gateway-stamped): park the row
            # after prefill for the export-after-prefill command; the
            # park window bounds how long a row can wait before local
            # decode resumes (the colocated fallback).
            handoff_kw = {}
            if request.get("handoff") and hasattr(self.generator,
                                                  "export_row"):
                # Clamped: a client-supplied park window must never pin
                # a slot + KV chain indefinitely (the scheduler clamps
                # again as a backstop).
                handoff_kw = {
                    "handoff": True,
                    "handoff_park_s": min(120.0, max(
                        0.1,
                        float(request.get("handoff_park_ms",
                                          5000.0)) / 1000.0))}
            fut = self.generator.submit(
                prompt, max_new_tokens=max_new, eos_id=eos_id,
                temperature=temperature, seed=seed, top_p=top_p, top_k=top_k,
                repetition_penalty=rep_pen, stop_tokens=stop_toks,
                min_p=min_p_val, stream=q, deadline=deadline,
                sink=TraceSink(self.tracer, self.node_id, request_id, tctx),
                tag=request_id,
                # Fleet prefix tier: the gateway-attached hint rides
                # the payload; inert unless --prefix-fetch is on.
                prefix_hint=(request.get("prefix_hint")
                             if self.config.gen_prefix_fetch else None),
                **handoff_kw)
        except BaseException:
            self._admission.release()
            raise
        return self._continuous_stream_events(
            q, fut, request_id, tctx, parent, t0, t_start_wall, t_admit)

    def _stream_import(self, request: dict,
                       deadline: Optional[Deadline], tier: Optional[int]):
        """Continuation half of live stream migration: adopt an exported
        row (the ``migrate_import`` snapshot) and stream its REMAINING
        tokens — no prefill, no re-emitted prefix. Rides the normal
        /generate/stream surface so the gateway journal splices it like
        any other segment, and admission applies like any stream (a
        draining or overloaded destination sheds 503 before the 200
        commits — the orchestrator's fallback ladder handles it)."""
        gen = self.generator
        if gen is None or not hasattr(gen, "submit_import"):
            raise ValueError(
                "migrate_import requires a continuous-scheduler lane "
                "with the paged KV cache")
        request_id = request["request_id"]
        snap = request["migrate_import"]
        parent = TraceContext.from_request(request)
        if parent is None and isinstance(snap, dict):
            # Cross-lane trace stitching: an export snapshot from a
            # --trace-stitch lane carries the exporting row's trace
            # context even when the dispatch payload itself is
            # traceless — the adopted row's spans re-parent under the
            # SAME trace the source lane recorded (additive snapshot
            # key; absent on un-stitched exports).
            parent = TraceContext.from_request(snap)
        tctx = (parent.child() if parent is not None
                else TraceContext.root(request_id))
        t_start_wall = time.time()
        t_admit = time.perf_counter()
        self._admission.admit(deadline, tier=tier)
        try:
            self._maybe_slow()
            with self._counter_lock:
                self._total_requests += 1
            q = self._stream_outbox()
            t0 = time.perf_counter()
            # ValueError (malformed snapshot) raises HERE -> wire 400
            # before the 200 SSE stream commits.
            fut = gen.submit_import(
                snap, stream=q, deadline=deadline,
                sink=TraceSink(self.tracer, self.node_id, request_id,
                               tctx),
                tag=request_id)
        except BaseException:
            self._admission.release()
            raise
        return self._continuous_stream_events(
            q, fut, request_id, tctx, parent, t0, t_start_wall, t_admit)

    def _stream_outbox(self) -> StreamOutbox:
        """The queue a streamed request's tokens leave the lane by, with
        the stream's clock and the lane's counts for whoever drives its
        events out."""
        return StreamOutbox(
            StreamClock(), getattr(self.generator, "stream_counts", None))

    def _continuous_stream_events(self, q, fut, request_id, tctx, parent,
                                  t0, t_start_wall, t_admit):
        """The continuous-scheduler SSE event iterator, shared by fresh
        submissions and migration imports. Owns the admission release.
        One `next` takes one item from `q` (the first `next` past the
        end of the stream takes none), and it says so (`EventStream`):
        a front's stream writer may then drive it, calling `next` only
        when an item waits; anyone else iterates it as a generator."""
        way, counts = q.clock, q.counts  # the token events' way out, summed

        def events():
            sent = 0  # tokens relayed to the client so far (resume offset)
            ttft_us = None  # receipt by the lane -> first token event out
            completed = False
            try:
                while True:
                    try:
                        item = q.get(timeout=STREAM_STALL_S)
                    except queue.Empty:
                        self._segment_span(request_id, tctx, parent, t0,
                                           t_start_wall, "stalled", way)
                        yield sse_event(self._stream_error(
                            RuntimeError("generation stalled (no tokens "
                                         f"for {STREAM_STALL_S:.0f}s)"),
                            request_id, tctx.trace_id, sent))
                        return
                    if item is None:
                        break
                    sent += len(item)
                    t_woke = way.woke(item, q.driven)
                    if ttft_us is None:
                        ttft_us = int((t_woke - t_admit) * 1e6)
                    try:
                        yield sse_event({"tokens": item})
                    finally:
                        # A writer's pass closes (and counts) what it
                        # sends; what is still open here went out on
                        # the thread that iterates.
                        if way.delivered() and not q.driven:
                            counts.handler_event(q.handback or UNREGISTERED)
                elapsed_us = int((time.perf_counter() - t0) * 1e6)
                try:
                    tokens = fut.result(timeout=10)
                except Exception as exc:
                    self._segment_span(
                        request_id, tctx, parent, t0, t_start_wall,
                        "exported" if getattr(exc, "migrated", False)
                        else "error", way)
                    yield sse_event(self._stream_error(
                        exc, request_id, tctx.trace_id, sent))
                    return
                self.tracer.record(
                    request_id, "generate_stream", self.node_id,
                    elapsed_us, trace_id=tctx.trace_id,
                    span_id=tctx.span_id,
                    parent_id=(parent.span_id if parent is not None
                               else None),
                    start_ts=t_start_wall,
                    attrs=(None if ttft_us is None
                           else {"ttft_us": ttft_us, **way.attrs()}))
                completed = True
                yield sse_event({"done": True, "request_id": request_id,
                                 "tokens": tokens, "node_id": self.node_id,
                                 "generate_time_us": elapsed_us})
            finally:
                self._admission.release()
                # Streams feed the AIMD window too (admit -> clean
                # finish) — on a stream-only lane the limit must still
                # see the latency it exists to react to.
                if completed and self._aimd is not None:
                    self._aimd.observe(time.perf_counter() - t_admit)
        return EventStream(events(), q)

    def _segment_span(self, request_id, tctx, parent, t0, t_start_wall,
                      outcome: str, way: StreamClock) -> None:
        """Root span for a stream SEGMENT that did not complete on this
        lane (exported row, lane fault, stall). The stage spans already
        recorded under ``tctx.span_id`` must not dangle: a mobile
        stream's stitched tree needs every serving lane's segment root,
        and even a single lane's /trace/export should never ship
        orphans (the completion path records the same span with no
        ``segment`` attr). `way`: what the segment's token events took
        on their way out, as on a completed stream's span."""
        self.tracer.record(
            request_id, "generate_stream", self.node_id,
            (time.perf_counter() - t0) * 1e6,
            trace_id=tctx.trace_id, span_id=tctx.span_id,
            parent_id=(parent.span_id if parent is not None else None),
            start_ts=t_start_wall,
            attrs={"segment": outcome, **way.attrs()})

    @staticmethod
    def _stream_error(exc: BaseException, request_id: str, trace_id: str,
                      tokens_emitted: int) -> dict:
        """Terminal error event for a failed stream — no longer opaque: it
        carries everything a client (or the gateway's stream journal)
        needs to RESUME the generation elsewhere. ``retryable``
        distinguishes lane faults (another lane can continue the stream
        byte-identically) from spent budgets and bad requests;
        ``tokens_emitted`` is the resume offset (prompt ⧺ that many
        already-received tokens); ``trace_id`` joins the event to the
        request's trace tree. An exception may pre-classify itself with a
        ``retryable`` attribute (the scheduler's _recover row events do)."""
        retryable = getattr(exc, "retryable", None)
        if retryable is None:
            if isinstance(exc, DeadlineExceeded):
                retryable = False  # the budget is spent: no lane can help
            elif isinstance(exc, ShedError):
                retryable = True   # overload/drain: healthy lanes elsewhere
            elif isinstance(exc, (KeyError, ValueError, TypeError)):
                retryable = False  # the request is at fault
            else:
                retryable = True   # lane/device fault
        out = {"done": True, "error": str(exc)[:300],
               "retryable": bool(retryable),
               "request_id": request_id, "trace_id": trace_id,
               "tokens_emitted": int(tokens_emitted)}
        if getattr(exc, "migrated", False):
            # The row was EXPORTED (live stream migration): the
            # gateway's journal splices the destination's continuation
            # instead of replay-resuming; a journal-less client can
            # still resume manually like any retryable terminal.
            out["migrated"] = True
        if getattr(exc, "import_refused", False):
            # A migration import THIS lane refused post-splice
            # (checksum, geometry, pool pressure): the gateway counts
            # the replay fallback against migration, not the lane.
            out["import_refused"] = True
        if isinstance(exc, ShedError):
            # Policy refusal from a HEALTHY lane: the gateway's failover
            # journal resumes these WITHOUT a breaker penalty (the same
            # shed-vs-fault split _try_node applies at admission).
            out["shed"] = True
        return out

    def _process_gen_batch(self, items: List[_GenItem]) -> List[_GenResult]:
        """Group by eos_id (a compile-time scalar of the decode executable);
        temperature and seed are per-row vectors, so mixed sampling params
        share one compiled batch. The batch runs to the group's max
        max_new_tokens; per-request counts are truncated after."""
        results: List[Optional[_GenResult]] = [None] * len(items)
        groups = {}
        for idx, it in enumerate(items):
            if it.beam_width > 1:
                # Beam requests run alone (beams occupy the batch axis).
                t0 = time.perf_counter()
                row = self.generator.beam_search(
                    it.prompt, beam_width=it.beam_width,
                    max_new_tokens=it.max_new_tokens, eos_id=it.eos_id,
                    length_penalty=it.length_penalty)
                results[idx] = _GenResult(
                    row[: it.max_new_tokens],
                    int((time.perf_counter() - t0) * 1e6))
                continue
            groups.setdefault(it.eos_id, []).append(idx)
        for eos_id, idxs in groups.items():
            t0 = time.perf_counter()
            max_new = max(items[i].max_new_tokens for i in idxs)
            toks = self.generator.generate(
                [items[i].prompt for i in idxs], max_new_tokens=max_new,
                eos_id=eos_id,
                temperature=[items[i].temperature for i in idxs],
                seed=[items[i].seed for i in idxs],
                top_p=[items[i].top_p for i in idxs],
                top_k=[items[i].top_k for i in idxs],
                repetition_penalty=[items[i].repetition_penalty
                                    for i in idxs],
                stop_tokens=[list(items[i].stop_tokens) for i in idxs],
                min_p=[items[i].min_p for i in idxs],
                # The speculative generator is single-dispatch by design
                # and takes no fused flag.
                **({} if self._speculative
                   else {"fused": self.config.gen_decode_fused}))
            group_elapsed_us = (time.perf_counter() - t0) * 1e6
            self._record_device_spans([items[i] for i in idxs],
                                      group_elapsed_us)
            # Reference semantic: per-request time = batch_duration /
            # batch_size, per group (worker_node.cpp:123).
            elapsed_us = int(group_elapsed_us / max(1, len(idxs)))
            for i, row in zip(idxs, toks):
                results[i] = _GenResult(row[: items[i].max_new_tokens], elapsed_us)
        return results

    # -- observability --------------------------------------------------------

    def latency_histograms(self) -> dict:
        """Named Prometheus histograms beyond the stage-latency family:
        the decode lane's TTFT and inter-token-latency distributions
        (`utils.metrics.render_named_histograms` renders them at
        /metrics). Empty for lanes without a continuous scheduler."""
        gen = self.generator
        if gen is None or not hasattr(gen, "ttft_hist"):
            return {}
        if getattr(gen, "_stateless", False):
            # One-shot rows have no first-token or inter-token moments;
            # a stateless-family lane keeps its /metrics text identical
            # to the retired batch lane's.
            return {}
        return {
            "tpu_engine_ttft_seconds": {self.node_id: gen.ttft_hist},
            "tpu_engine_itl_seconds": {self.node_id: gen.itl_hist},
        }

    def get_health(self) -> dict:
        """Exact /health schema (``worker_node.cpp:85-103``)."""
        m = self.batch_processor.get_metrics()
        with self._counter_lock:
            total, hits = self._total_requests, self._cache_hits
        if self.external_counters is not None:
            ext_total, ext_hits = self.external_counters()
            total += ext_total
            hits += ext_hits
        out = {
            "healthy": self._injected_fault is None,
            "node_id": self.node_id,
            "model": getattr(self.engine.spec, "name", None),  # additive
            "total_requests": total,
            "cache_hits": hits,
            "cache_size": self.cache.size(),
            "cache_hit_rate": self.cache.hit_rate(),
            "batch_processor": m.as_dict(),
        }
        if self.config.role != "both":
            # Additive, and only for dedicated-role lanes: a default
            # fleet's /health stays byte-identical (absent key = "both"
            # — the gateway's role discovery reads it that way).
            out["role"] = self.config.role
        if int(self.config.tp) > 1:
            # Additive topology label (absent key = one chip — the
            # gateway's topology-aware ring reads it that way): this
            # lane spans a `model`-axis mesh slice of tp devices, so
            # its virtual nodes should carry a per-chip weight instead
            # of one lane == one chip.
            from tpu_engine.parallel.mesh import tp_topology_label

            out["topology"] = tp_topology_label(self.config.tp)
        # Additive (reference schema untouched — its parsers ignore extra
        # keys): decode-lane scheduler counters for transformer workers.
        if self.generator is not None and hasattr(self.generator, "stats"):
            try:
                gstats = self.generator.stats()
            except Exception:
                gstats = None
            if gstats is not None:
                if getattr(self.generator, "_stateless", False):
                    # Unified stateless serving on a stateless-family
                    # lane: the scheduler IS the batch lane now, so its
                    # one-shot dispatch counters FOLD into the
                    # wire-exact 4-key batch_processor block instead of
                    # growing /health a "generator" key the reference
                    # schema (worker_node.cpp:85-103) never had. A
                    # defaults-on mlp lane answers byte-compatible.
                    st = gstats.get("stateless") or {}
                    bp = out["batch_processor"]
                    rows = (int(st.get("infer_rows", 0))
                            + int(st.get("score_rows", 0)))
                    disp = int(st.get("dispatches", 0))
                    prev_rows = (float(bp["avg_batch_size"])
                                 * int(bp["total_batches"]))
                    bp["total_batches"] = int(bp["total_batches"]) + disp
                    bp["full_batches"] = (int(bp["full_batches"])
                                          + int(st.get("full_dispatches",
                                                       0)))
                    if bp["total_batches"] > 0:
                        bp["avg_batch_size"] = ((prev_rows + rows)
                                                / bp["total_batches"])
                else:
                    out["generator"] = gstats
                # Scheduler liveness: a wedged decode loop (stuck inside a
                # device dispatch) is process-alive but cannot serve —
                # last-tick age is the only signal that sees it. With
                # scheduler_stall_s > 0 a stale loop flips the lane
                # unhealthy, so the gateway's prober ejects it like a
                # dead process instead of breakers tripping one victim
                # request at a time.
                age = gstats.get("last_tick_age_s")
                stall = float(self.config.scheduler_stall_s or 0.0)
                if stall > 0 and age is not None and age > stall:
                    out["healthy"] = False
                    out["scheduler_stalled"] = True
        # Fleet prefix tier seed (additive, gated on --prefix-fetch so
        # defaults-off /health bytes stay identical): bounded top-K
        # radix chain summaries the gateway prober turns into directory
        # entries — never a full-tree dump.
        if (self.config.gen_prefix_fetch and self.generator is not None
                and hasattr(self.generator, "prefix_fingerprints")):
            try:
                out["prefix_fingerprints"] = \
                    self.generator.prefix_fingerprints()
            except Exception:
                pass
        # Additive, and only once admission control has anything to say
        # (a defaults-only lane keeps the reference-exact key set).
        dropped = self.batch_processor.deadline_dropped
        if self._gen_processor is not None:
            dropped += self._gen_processor.deadline_dropped
        score_proc = getattr(self, "_score_proc", None)
        if score_proc is not None:
            dropped += score_proc.deadline_dropped
        if self._infer_unified() or self._score_unified():
            # One-shot rows the scheduler cancelled at their deadline
            # count exactly like the retired batch lane's drops.
            try:
                dropped += int((self.generator.stats().get("stateless")
                                or {}).get("deadline_dropped", 0))
            except Exception:
                pass
        if self._admission.active or dropped:
            adm = self._admission.as_dict()
            adm["deadline_dropped"] = dropped
            out["admission"] = adm
        # Additive, gated on the flag: the staged brownout controller's
        # current stage, pressure, and transition counters.
        if self._brownout is not None:
            bo = self._brownout.as_dict()
            bo["clamped_requests"] = self._brownout_clamps
            out["brownout"] = bo
        return out

    def stop(self) -> None:
        self._brownout_stop.set()
        if self._brownout_thread is not None:
            self._brownout_thread.join(timeout=5)
            self._brownout_thread = None
        self.batch_processor.stop()
        if getattr(self, "_score_proc", None) is not None:
            self._score_proc.stop()
        if self._gen_processor is not None:
            self._gen_processor.stop()
        if self._continuous and self.generator is not None:
            self.generator.stop()
