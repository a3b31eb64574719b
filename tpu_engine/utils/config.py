"""Configuration for workers and the gateway.

The reference hardcodes every tunable at compile time (cache 1000 entries
``worker_node.cpp:33``; batch 32 / 20 ms ``:35-36``; breaker 5/2/30 s
``gateway.cpp:20-22``; 150 vnodes ``consistent_hash.h:12``; gateway port 8000
``gateway.cpp:198``; 5 s client timeouts ``:32-33``) and tells users to edit
the source (``README.md:302-320``). Here the same defaults are real config:
dataclasses overridable from CLI flags and environment variables.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple


@dataclasses.dataclass
class WorkerConfig:
    port: int = 8001
    node_id: str = "worker_1"
    model: str = "resnet50"  # registry name, see tpu_engine.models.registry
    model_path: Optional[str] = None  # optional weights checkpoint
    cache_capacity: int = 1000          # reference worker_node.cpp:33
    max_batch_size: int = 32            # reference worker_node.cpp:35
    batch_timeout_ms: float = 20.0      # reference worker_node.cpp:36
    batch_linger_ms: float = 0.0        # TPU extension: accumulation window
    dtype: str = "bfloat16"             # MXU-native compute dtype
    # Weight-only quantization ("int8" | None): dense/conv kernels stored
    # int8 + per-out-channel scales (ops.quant) — halves weight HBM bytes,
    # the bandwidth-bound decode path's budget. Applies to every lane of
    # the worker (one-shot /infer and all /generate schedulers).
    quantize: Optional[str] = None
    batch_buckets: Tuple[int, ...] = (1, 2, 4, 8, 16, 32)
    # Mixed-shape serving (BASELINE config 4): per-sample input shapes the
    # engine compiles executables for; requests carry "shape": [h, w, c].
    shape_buckets: Optional[Tuple[Tuple[int, ...], ...]] = None
    fake_cached_latency_us: int = 50    # reference worker_node.cpp:65
    # Miss-path pipeline: number of dispatched batches in flight before the
    # batcher blocks collecting the oldest (engine.batch_submit/collect).
    # >1 overlaps host↔device round-trips; 1 = reference-style lockstep.
    # Not measured on the installed stack (PERF.md).
    pipeline_depth: int = 4
    gen_max_batch_size: int = 8         # decode-lane batcher (transformers)
    # Decode steps per compiled chunk (host syncs once per chunk). Larger
    # chunks amortize the per-dispatch round trip at the cost of admission
    # granularity (requests join the continuous batch between chunks). The
    # value is not measured on the installed stack (PERF.md); changing it
    # is a performance change and needs a chip run.
    gen_step_chunk: int = 16
    # "batch": collect a batch, decode it to completion (generator.py).
    # "continuous": iteration-level scheduling — requests join/leave the
    # running decode batch between chunks (scheduler.py). Continuous is the
    # default: requests join and leave between chunks instead of waiting
    # for a whole batch to finish. Its speed against the batch lane is not
    # measured on this stack (no cell runs the batch lane).
    # "speculative": batch-mode lane where a DRAFT model proposes
    # gen_spec_k tokens per round and the target verifies them in one
    # windowed pass (runtime.speculative); temperature sampling only.
    gen_scheduler: str = "continuous"
    # Draft model for the speculative scheduler. None = auto by target
    # (gpt2 -> distilgpt2); set explicitly for other families.
    gen_draft_model: Optional[str] = None
    gen_draft_path: Optional[str] = None  # draft weights checkpoint
    gen_spec_k: int = 4                 # speculation depth (draft tokens/round)
    # Continuous-scheduler prefix cache (MB of device KV blocks, 0 = off):
    # an exact repeat of a prompt skips its prefill forward at admission
    # (runtime.scheduler._PrefixCache) — the KV-level analog of the /infer
    # result LRU for repeated system prompts.
    gen_prefix_cache_mb: int = 64
    # Chunked prefill (continuous scheduler): prompts longer than this
    # admit via window-decode dispatches so decode chunks interleave
    # instead of stalling behind one long prompt forward (0 = off).
    gen_prefill_chunk: int = 256
    # Paged KV cache (continuous scheduler; runtime.kv_blocks). 0 keeps
    # the dense per-slot cache (current behavior). >0 switches to a
    # block pool of this many columns per block: rows reserve blocks for
    # the tokens they actually hold instead of max_seq each, and the
    # radix tree maps shared prompt prefixes onto already-filled blocks
    # (prefill resumes mid-prompt). Must divide every prompt bucket
    # (16/32/64... all work with the default buckets).
    gen_kv_block_size: int = 0
    # Pool size in blocks (0 = auto: the dense layout's capacity,
    # n_slots * ceil(max_seq/block) + the null block). At equal HBM the
    # paged pool admits several times more concurrent short rows.
    gen_kv_blocks: int = 0
    # Hierarchical host-RAM KV tier (paged mode with prefix sharing;
    # --kv-host-blocks): this many pinned host-RAM blocks under the
    # device pool. LRU eviction DEMOTES cold radix leaves' blocks to the
    # host tier instead of destroying them; a radix hit on a demoted
    # prefix swaps the blocks back in (async, on the prefill thread)
    # instead of recomputing its prefill — host RAM becomes prefix-cache
    # capacity. 0 (default) = no tier (evictions destroy, as before).
    gen_kv_host_blocks: int = 0
    # Quantized KV blocks (paged mode only; --kv-quantize): "int8" stores
    # block payloads int8 with per-(layer, slot, kv-head) f32 scales —
    # roughly half the KV bytes per block, so the same HBM budget holds
    # ~2x the blocks (and the host tier gets the same capacity +
    # swap-bandwidth multiplier). Tokens quantize exactly once at block
    # write; COW / radix re-adoption / demotion / swap-in copy int8 +
    # scale verbatim. Quantized greedy streams are deterministic but not
    # byte-identical to the bf16 pool (MIGRATION.md). "" (default) =
    # today's full-precision pool, byte-identical.
    gen_kv_quantize: str = ""
    # Block-level radix prefix sharing (paged mode only): shared system
    # prompts skip their prefill compute and share KV blocks
    # copy-on-write. Off = paging without sharing.
    gen_prefix_sharing: bool = True
    # Fleet prefix tier (--prefix-fetch; requires continuous + paged +
    # prefix sharing): a miss whose request carries a gateway-attached
    # prefix_hint pulls the matched radix chain from the owning peer
    # (/admin/export_prefix) instead of recomputing it — the per-lane
    # prefill-skip becomes a fleet property. Every fetch failure falls
    # back to local prefill. Off (default) = hints inert, wire bytes
    # identical.
    gen_prefix_fetch: bool = False
    # Per-fetch transport budget in seconds: a peer that cannot ship
    # the chain inside it counts ``timeout`` and the stream recomputes
    # locally.
    gen_prefix_fetch_timeout_s: float = 5.0
    # Per-lane in-flight fetch cap: a thundering herd on one hot prefix
    # degrades to local prefill (``inflight_capped``), not a convoy of
    # blocked prefill threads.
    gen_prefix_fetch_inflight: int = 2
    # Chooses nothing: a lane with gen_kv_block_size > 0 (or a state
    # slab) always steps by the ragged tick, the dense cache by two
    # paths. Kept because the `serving` blocks under benchmarks/ name it
    # (ROADMAP: the benchmark item that drops it); True without a pool
    # or on a stateless model is refused at start-up.
    gen_mixed_step: bool = False
    # Per-tick new-token budget of the ragged tick (decode rows count 1
    # each; the rest splits over admitting rows' prefill chunks and caps
    # the compiled chunk width). 0 = auto (gen_prefill_chunk).
    gen_mixed_token_budget: int = 0
    # Continuous speculative decoding (paged mode only): each tick a drafter proposes up to this many tokens per
    # decode row and the tick's ONE ragged dispatch verifies every
    # window, advancing rows 1..k+1 tokens per dispatch. Greedy streams
    # byte-identical to plain decode for any draft; 0 = off (--spec-k).
    gen_continuous_spec_k: int = 0
    # Drafter for continuous speculation (--spec-draft): "ngram" = the
    # host-side prompt-lookup drafter (no second model, no extra
    # dispatches); "model" = greedy proposals from gen_draft_model
    # (one extra draft dispatch per drafted row per tick).
    gen_spec_draft: str = "ngram"
    # Batch scheduler only: run each group's decode as ONE fused dispatch
    # (lax.while_loop, zero per-chunk host syncs; identical streams).
    # Worth enabling where dispatch latency is high; costs one compile per
    # (batch, prompt, output-capacity) bucket triple.
    gen_decode_fused: bool = False
    # Unified stateless serving (DESIGN.md "Unified stateless serving"):
    # one-shot /infer and /score requests admit as SINGLE-TICK rows in
    # the continuous scheduler beside decode rows — one scheduler, one
    # capacity pool, one set of counters; the legacy batch_processor
    # lane is a compatibility shim. Wire schemas, outputs, and cache-hit
    # semantics are byte-identical either way (the tick's dispatch IS
    # the engine's batched forward). --no-unified-stateless restores the
    # dedicated batch lane. Requires gen_scheduler=continuous (any
    # other scheduler keeps the batch lane regardless).
    unified_stateless: bool = True
    # Recurrent state serving (state_slab family ONLY — SSD/Mamba
    # models): capacity of the fixed-size state slab pool in rows. Each
    # live stream owns exactly ONE (n_layers, state_dim) f32 row for its
    # whole life — constant in sequence length — so this is the family's
    # "KV capacity" knob. 0 = auto (gen_max_batch_size + the null row).
    # Loud RuntimeError on a kv_paged model (--state-rows).
    gen_state_rows: int = 0
    # Tensor-parallel serving (--tp; DESIGN.md "Tensor-parallel
    # serving"): the continuous scheduler serves ONE model sharded over
    # this many local devices on a 1-axis `model` mesh — params place by
    # the registry-declared partition rule (heads-axis QKV/MLP,
    # replicated norms/embeddings), the paged KV pool shards its H_kv
    # axis, and every tick stays one SPMD ragged dispatch. Requires the
    # continuous scheduler with the paged KV cache; unshardable families
    # (mamba2/state_slab) refuse loudly at startup. 1 (default) =
    # today's single-device path, wire-byte-identical.
    tp: int = 1
    # First local-device index of this lane's tp-device mesh slice
    # (combined mode assigns lane i offset i*tp so in-process TP lanes
    # own DISJOINT chip slices instead of all stacking on devices
    # [0, tp)). Must leave tp devices past it; standalone workers
    # (one lane per process) keep the default 0.
    tp_device_offset: int = 0
    # Admission control (resilience layer): maximum concurrently admitted
    # requests on this lane; excess is shed with 503 + Retry-After instead
    # of queueing unboundedly. 0 = unbounded (reference behavior).
    max_queue_depth: int = 0
    # -- overload control (serving/overload.py; DESIGN.md "Overload
    # control"). All default off: with defaults, admission behavior and
    # wire schemas are byte-identical to the layer above. ----------------
    # Priority-tiered admission (--priority-admission): requests may
    # carry "priority": interactive | batch | background; under depth
    # pressure each tier admits only up to its fraction of the
    # concurrency limit (background 70%, batch 85%, interactive 100%),
    # so the lowest tier always sheds first. Off = the field is ignored.
    priority_admission: bool = False
    # AIMD adaptive concurrency (--adaptive-depth): replace the static
    # max_queue_depth cap with a limit driven by observed latency vs the
    # sliding-window baseline — additive increase while latency tracks
    # the baseline, multiplicative decrease past 2x it. Bounded above by
    # adaptive_depth_max.
    adaptive_depth: bool = False
    adaptive_depth_max: int = 64
    # Staged brownout (--brownout): a control loop reads saturation
    # signals (decode-loop tick age, admission depth vs limit, pool
    # starvation, deadline-miss rate) every brownout_interval_s and
    # walks the degradation ladder with hysteresis — shrink the mixed
    # token budget, suspend speculative drafting, defer host-tier
    # swap-ins, clamp low-tier token budgets — BEFORE any shed fires,
    # restoring in reverse as pressure clears.
    brownout: bool = False
    brownout_interval_s: float = 0.25
    # Stage-4 ("clamp") max_new_tokens ceiling for below-top-tier
    # generate requests.
    brownout_clamp_tokens: int = 32
    # Disaggregated serving role (--role; DESIGN.md "Disaggregated
    # serving"): "prefill" | "decode" | "both". Advisory for the
    # gateway's role-aware routing — a "both" fleet (default) behaves
    # byte-identically to today, and a lane of EITHER dedicated role
    # still serves any request it receives (the fallback ladder depends
    # on that: a replay resume must be admittable anywhere). "prefill"
    # lanes are where the gateway lands fresh /generate(/stream) work;
    # finished prefills ship their KV chain to a "decode" lane via the
    # export-after-prefill handoff. Flippable at runtime (/admin/role).
    role: str = "both"
    # Tracing ring-buffer capacity (spans kept per lane, utils.tracing).
    # On by default — recording is lock-guarded ring writes, ~1 µs/span.
    # 0 disables span recording AND the /metrics stage histograms.
    trace_capacity: int = 2048
    # Cross-lane trace stitching (--trace-stitch; DESIGN.md
    # "Observability plane"): export_row snapshots carry the stream's
    # trace context (one additive "traceparent" snapshot field + a
    # gated "trace" header on the KV chain), so a stream's spans
    # re-parent under the SAME trace across handoff / migration /
    # crash-resume hops and the gateway can stitch one tree. Off
    # (default) = snapshots and chain wire bytes identical to today.
    trace_stitch: bool = False
    # jax.profiler capture directory (--profile-dir): arms
    # POST /admin/profile on this worker — {"ticks": N} starts a device
    # trace that the continuous scheduler stops after N ticks (the
    # on-chip campaign's capture primitive); {"action": "stop"} stops
    # early. None (default) = endpoint reports unconfigured.
    profile_dir: Optional[str] = None
    # Per-tick flight recorder (--flight-recorder; continuous scheduler
    # only): ring capacity in ticks. Each tick appends one bounded
    # record (rows by state, token budget used, dispatch wall time,
    # queue/park/held depths, pool occupancy incl. host tier and slab
    # rows); /admin/timeline reads the ring and anomalies (_recover,
    # deadline-miss bursts, degraded fleet entry) auto-dump it as a
    # postmortem artifact. 0 (default) = off, zero per-tick work.
    flight_recorder: int = 0
    # Directory for anomaly postmortem JSON dumps (flight-recorder
    # ring + anomaly name + scheduler stats). None = keep the dump
    # in memory only (served by /admin/timeline as "last_dump").
    flight_dump_dir: Optional[str] = None
    # Scheduler liveness (continuous decode lane): /health reports the
    # decode loop's last-tick age, and when this threshold is > 0 a lane
    # whose loop has not ticked for this many seconds reads unhealthy —
    # a wedged device loop is process-alive but cannot serve, and only
    # liveness (not request success) can see that. 0 (default) reports
    # the age without flipping health. Set it comfortably above the
    # worst first-request XLA compile on the deployment's backend.
    scheduler_stall_s: float = 0.0

    @classmethod
    def from_env(cls, **overrides) -> "WorkerConfig":
        cfg = cls(**overrides)
        # $MODEL_PATH honored like the reference (worker_node.cpp:154-168).
        env_model = os.environ.get("MODEL_PATH")
        if env_model and not cfg.model_path:
            cfg.model_path = env_model
        return cfg


@dataclasses.dataclass
class GatewayConfig:
    port: int = 8000                    # reference gateway.cpp:198
    virtual_nodes: int = 150            # reference consistent_hash.h:12
    failure_threshold: int = 5          # reference gateway.cpp:20
    success_threshold: int = 2          # reference gateway.cpp:21
    breaker_timeout_s: float = 30.0     # reference gateway.cpp:22
    worker_timeout_s: float = 5.0       # reference gateway.cpp:32-33
    gen_timeout_s: float = 120.0        # /generate: decode loop + compile
    default_worker_port: int = 8080     # reference parseUrl gateway.cpp:139,147

    # -- resilience layer (serving/resilience.py). Defaults are all
    # off/permissive: with them, routing behavior and wire schemas are
    # byte-identical to the breaker-only gateway above. --------------------

    # Deadline applied to requests that carry no "deadline_ms" field
    # (None = no deadline, reference behavior). Expired requests are shed
    # at admission with 503 + Retry-After; mid-route expiry stops the
    # failover march.
    default_deadline_ms: Optional[float] = None
    # Suggested client Retry-After (seconds) on a shed (503) response.
    shed_retry_after_s: float = 1.0
    # Exponential backoff between failover attempts:
    # min(base * 2^attempt, max) * jitter in [1-j, 1+j]. base 0 = the
    # reference's immediate ring-order failover (no sleep).
    retry_backoff_base_ms: float = 0.0
    retry_backoff_max_ms: float = 1000.0
    retry_jitter: float = 0.5
    # Global retry budget: failover retries are allowed while retries <=
    # ratio * requests (+ min) over the sliding window. None = unlimited
    # (reference behavior); 0.1 = the SRE-standard "retries may add at
    # most 10% load".
    retry_budget_ratio: Optional[float] = None
    retry_budget_min: int = 10
    retry_budget_window_s: float = 10.0
    # Hedged dispatch (idempotent ops: /infer, /score): when the primary
    # lane exceeds the hedge latency quantile, fire the next ring lane and
    # take whichever answers first. Off by default.
    hedge_enabled: bool = False
    hedge_quantile: float = 0.95        # threshold = quantile of recent latency
    hedge_min_ms: float = 50.0          # floor under the quantile threshold
    hedge_min_samples: int = 20         # before this, hedge_min_ms alone rules

    # Crash-tolerant streaming (--failover-streams): the gateway journals
    # every /generate/stream token event it relays and, on a retryable
    # mid-stream failure (lane death, transport error, truncation,
    # drain), re-dispatches to another ring lane as a RESUME — prompt ⧺
    # emitted tokens, max_tokens offset by the emitted count — splicing
    # the continuation into one seamless stream (byte-identical to an
    # uninterrupted run: sampling keys fold per absolute position). Off
    # (default) keeps today's terminate-with-error behavior.
    failover_streams: bool = False
    # Resume attempts per stream; each also consumes the retry budget.
    failover_max_resumes: int = 3
    # Live stream migration (--migrate-streams): graceful removal
    # (remove_worker(drain=True)) EXPORTS each journaled in-flight
    # /generate/stream off the draining lane — KV block chain + stream
    # state over the wire — and resumes it mid-stream on another lane
    # with ZERO re-prefilled tokens, splicing the continuation
    # byte-identically. Implies the stream journal (the PR 6 machinery
    # is the fallback ladder: checksum mismatch, full destination,
    # transfer timeout, or destination death all land on the replay
    # resume). Off (default) keeps today's shed+replay drain semantics
    # and wire bytes.
    migrate_streams: bool = False
    # Per-stream transfer budget (export + continuation dispatch),
    # always clamped to the stream's ORIGINAL deadline.
    migrate_timeout_s: float = 30.0
    # Graceful-drain call bound: remove_worker(drain=True) gives the
    # lane this long to acknowledge /admin/drain, then counts the
    # failure and proceeds with removal — a wedged lane must never hang
    # membership changes.
    drain_timeout_s: float = 10.0
    # Disaggregated prefill/decode serving (--disagg; DESIGN.md
    # "Disaggregated serving"): while the fleet has at least one
    # prefill-role lane AND a distinct decode-capable lane,
    # /generate(/stream) routes to a prefill lane (prefix-affinity
    # fingerprint restricted to prefill-capable lanes when
    # --prefix-affinity is on, else the request_id hash over them),
    # which prefills into its block pool, parks the row, and ships the
    # finished KV chain + sampling snapshot to a decode lane picked by
    # load — the gateway splices the continuation into one seamless
    # stream with ZERO re-prefilled tokens. Every failure on the hop
    # (export refused, no destination, transfer timeout, checksum
    # refusal, dead lane) lands on the existing fallback ladder —
    # local decode on the source, then the replay resume — always
    # byte-identical. Off (default), or with an all-"both" fleet,
    # routing and wire bytes are identical to today.
    disagg: bool = False
    # Per-stream handoff budget: export-after-prefill + continuation
    # dispatch, clamped to the stream's original deadline. Also the
    # source row's park window (a handoff whose orchestrator died
    # resumes local decoding after this long).
    handoff_timeout_s: float = 30.0
    # Proactive lane health prober (--health-probe-interval): a gateway
    # background thread GETs every lane's /health at this interval and
    # EJECTS lanes from routing after `health_probe_failures` consecutive
    # failures (restoring them on the next success) — dead workers leave
    # rotation in O(probe interval) instead of one breaker trip per
    # victim request. 0 (default) = no prober.
    health_probe_interval_s: float = 0.0
    health_probe_failures: int = 3

    # Prefix-affinity routing (--prefix-affinity): /generate and
    # /generate/stream route on a BLOCK-ALIGNED fingerprint of the
    # prompt's leading tokens instead of request_id, so requests sharing
    # a prefix (fleet-wide system prompts) converge on the lane whose
    # radix tree already holds those KV blocks — the per-worker 88%
    # prefill-skip becomes a fleet-wide win instead of re-paying the
    # prefix once per lane. Fallback to ring order (the pre-affinity
    # behavior) when the prompt has no full block to fingerprint, the
    # affinity lane is ejected/broken, or it is imbalanced (below). Off
    # (default) keeps routing byte-identical to the request_id ring.
    prefix_affinity: bool = False
    # Fingerprint granularity: MUST match the workers' --kv-block-size —
    # the radix tree shares full blocks only, so a fingerprint over a
    # partial block would converge requests that share nothing reusable.
    affinity_block_size: int = 16
    # Fingerprint covers at most this many leading blocks: requests that
    # agree on them converge even when their prompts diverge later (the
    # shared-system-prompt shape); the cap keeps distinct long prompts
    # from all being "unique" fingerprints with no convergence.
    affinity_prefix_blocks: int = 4
    # Imbalance fallback: when > 0, the affinity lane is skipped (ring
    # order instead) once it has received this many more generate
    # dispatches than its least-loaded ring peer within the window —
    # convergence must not turn one hot prefix into one dead lane.
    # 0 (default) = always honor affinity.
    affinity_max_imbalance: int = 0
    # Fleet prefix tier directory (--prefix-fetch on the serve command):
    # a bounded fingerprint -> {lane, blocks, generation} map seeded
    # from lane /health radix summaries (prober sweeps) and
    # post-completion updates; generate-class requests whose
    # fingerprint names a DIFFERENT lane get a prefix_hint attached so
    # the serving lane can fetch the chain peer-to-peer. Works with
    # affinity off (the affinity-defeating-ring case is the point).
    # Off (default) = no directory, payloads and /stats byte-identical.
    prefix_directory: bool = False
    # Directory capacity in fingerprints (LRU beyond it): bounds gateway
    # memory no matter how many distinct prefixes the fleet sees.
    prefix_directory_capacity: int = 512
    affinity_window_s: float = 10.0

    # -- adaptive overload control (serving/overload.py; DESIGN.md
    # "Overload control"). All default off: with defaults, routing
    # behavior and wire schemas are byte-identical to the layers above.

    # Master switch (--overload-control): priority-tiered gateway
    # admission against the in-flight gauge below, plus load-derived
    # Retry-After on every shed (base shed_retry_after_s scaled by
    # measured pressure instead of the constant).
    overload_control: bool = False
    # Gateway-wide concurrent-request gauge the tier fractions apply to
    # (background sheds at 70% of it, batch at 85%, interactive at
    # 100%). 0 = no gauge: tier admission is off and Retry-After derives
    # from the recent shed rate instead.
    overload_max_inflight: int = 0
    # Per-tenant token-bucket rate limiter (--tenant-rate): requests
    # carry an optional "tenant" key; each tenant sustains this many
    # requests/s (burst below) and excess sheds 503 + the bucket's
    # actual refill time — one tenant's burst cannot starve the fleet.
    # 0 = off. Independent of overload_control (rate fairness is useful
    # alone).
    tenant_rate: float = 0.0
    tenant_burst: float = 0.0           # bucket depth (0 = auto: 2x rate)

    # -- elastic fleet (serving/autoscaler.py; DESIGN.md "Elastic
    # fleet"). Master switch --autoscale: a gateway-side control loop
    # reads per-lane overload pressure (AIMD depth / queue fill /
    # brownout tier), journaled active streams, and ring topology
    # weights, then spawns lanes from the configured provider and
    # retires them through the PR 11 drain+migrate ladder (zero tokens
    # lost; replay resume is the last rung, never the plan). Off
    # (default): no controller thread, no /stats "fleet" block, wire
    # bytes identical to the static fleet. /admin/fleet manual actions
    # work either way. Engaging --autoscale forces migrate_streams on —
    # scale-down without live migration would shed tokens.
    autoscale: bool = False
    # Control-loop tick interval.
    autoscale_interval_s: float = 1.0
    # Fleet size clamps: the controller never drains below min_lanes and
    # never spawns above max_lanes (0 = no upper clamp / provider
    # capacity rules). Clamped decisions count as decisions_held.
    autoscale_min_lanes: int = 1
    autoscale_max_lanes: int = 0
    # Pressure thresholds: mean fleet pressure (1.0 = lanes saturated)
    # above up_pressure spawns a lane; below down_pressure retires one.
    # The gap between them is the hysteresis dead band.
    autoscale_up_pressure: float = 0.75
    autoscale_down_pressure: float = 0.25
    # Minimum seconds between ACTUATED decisions (spawn/retire/flip) —
    # suppressed ticks count as decisions_held.
    autoscale_cooldown_s: float = 5.0
    # Spawn bound: a provider lane that has not answered a passing
    # /health probe within this window is destroyed and the fleet enters
    # the named "spawn-wedged" degraded state (still serving).
    autoscale_spawn_timeout_s: float = 30.0
    # Role-rebalance arm (requires --disagg): when the observed
    # prefill:decode pressure ratio exceeds this band (or drops below
    # its inverse), one lane flips role through the /admin/role
    # drain+migrate+undrain path; the arm re-arms only once the ratio
    # returns inside band/2 (hysteresis). <= 1 disables the arm.
    autoscale_rebalance_band: float = 0.0

    # Tracing ring-buffer capacity for the gateway's own spans (route +
    # per-attempt children + resilience decision markers). 0 disables.
    trace_capacity: int = 2048

    # -- observability plane (DESIGN.md "Observability plane"). All
    # default off: with defaults, /stats, /health, routing behavior and
    # wire bytes are byte-identical to the layers above. -----------------

    # Cross-lane trace stitching (--trace-stitch): every
    # /generate/stream dispatch carries the stream's trace context, the
    # stream ledger records which lanes served each request_id (admit /
    # handoff / migrate / resume hops), and GET /admin/trace/<rid>
    # merges the fragments from every lane's ring into ONE
    # Perfetto-loadable tree with hop-boundary marker spans. Requires
    # workers started with --trace-stitch too for snapshot propagation.
    trace_stitch: bool = False
    # Stream-ledger capacity: completed request_ids kept for stitching
    # (bounded FIFO; live streams are never evicted before completion).
    trace_ledger_capacity: int = 512
    # SLO objectives (--slo-ttft-p99-ms / --slo-itl-p99-ms /
    # --slo-completion-p99-ms): declarative per-fleet latency targets in
    # milliseconds, 0 = objective not set. Burn is computed from the
    # existing tpu_engine_ttft/itl_seconds histograms (no new
    # measurement path): violations = samples above the bucket boundary
    # covering the target, error budget = 1 - slo_target, burn rate =
    # windowed violation fraction / budget (1.0 = burning exactly the
    # budget; >1 = on track to exhaust it). Surfaced at /admin/slo, as
    # an additive /stats "slo" block, and as tpu_engine_slo_* metrics.
    slo_ttft_p99_ms: float = 0.0
    slo_itl_p99_ms: float = 0.0
    slo_completion_p99_ms: float = 0.0
    # Objective quantile target (0.99 = "99% of samples under the
    # threshold"), i.e. error budget 1%.
    slo_target: float = 0.99
    # Sliding window for burn-rate accounting, seconds.
    slo_window_s: float = 300.0
    # Feed SLO burn into FleetAutoscaler pressure (--autoscale-slo-feed;
    # requires --autoscale and at least one objective): fleet pressure
    # becomes max(lane pressure, min(1, burn/2)) so a burning error
    # budget can trigger scale-up even while queue depths look calm.
    autoscale_slo_feed: bool = False
