"""Continuous-batching decode scheduler (vLLM-style iteration-level
scheduling, static shapes).

SURVEY.md §7 hard part (c): "decode loops don't fit the one-shot
batchPredict contract; needs a decode-step scheduler". runtime.generator
solved it batch-at-a-time: a batch runs to completion before the next
starts, so one long request convoys everything behind it. This scheduler
closes the gap: a FIXED-shape decode batch runs forever, and requests join
and leave between chunks —

How a lane is stepped follows from what it holds, and no caller chooses:

- **The dense per-slot cache** (`kv_block_size` 0): `n_slots` rows over one
  preallocated KV cache (L, n_slots, max_seq, H, D), stepped by TWO PATHS.
  All shapes static: the decode chunk and the per-bucket prefill/insert
  executables each compile exactly once. *Admission*: a new request
  prefills alone on a (1, prompt-bucket) executable — on the PREFILL
  THREAD, so admission compute never stalls the decode loop's host side —
  then its KV slice is written into a free row (`dynamic_update_slice` on
  the row axis) with per-row `pos`/`start`. *Decode* runs `step_chunk`
  steps of `transformer_decode_rows` a dispatch — every row carries its
  own cache position, so rows admitted at different times decode side by
  side. Finished rows (EOS or budget) free their slot between chunks; idle
  rows burn lanes of an already-launched batch, not wall-clock.
- **A block pool or a state slab** (`kv_block_size` > 0, or the
  state_slab family) steps by the RAGGED TICK: the prefill thread is pure
  batch formation (bucket pick + radix lookup), and each tick issues ONE
  ragged dispatch (`transformer_step_rows_ragged`, or the family's own
  step) serving decode rows (1 token each) and admitting rows' budgeted
  prefill chunks together — admission work rides the decode dispatch
  instead of contending with it on the device queue.
- Sampling is the generator's per-row fold_in(seed, position) scheme, so a
  seeded request emits identical tokens whether it was admitted into an
  empty, full, or draining batch — and whichever stepping or cache layout
  served it (tested).

`submit()` returns a Future; a daemon thread runs the admit→decode→emit
loop. `generate()` is a blocking convenience with the same signature as
Generator.generate.
"""

from __future__ import annotations

import collections
import json
import os
import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from tpu_engine.models.registry import ModelSpec, create_model, _ensure_builtin_models_imported
from tpu_engine.models.ssd import (
    SSDConfig,
    flatten_states,
    ssd_state_dim,
    ssd_window_scan,
    unflatten_states,
)
from tpu_engine.models.transformer import (
    TransformerConfig,
    init_caches,
    pool_write_slots,
    second_half_slots,
    transformer_decode_rows,
    transformer_decode_window,
    transformer_prefill,
    transformer_step_rows_ragged,
)
from tpu_engine.ops.attention import KVCache
from tpu_engine.ops.latent_attention import class_counts
from tpu_engine.ops.moe import traced_tilings
from tpu_engine.ops.paged_attention import walk_counts
from tpu_engine.runtime.generator import (
    _DTYPES,
    SAMPLER_BODIES,
    _sample,
    apply_repetition_penalty,
    reveal_block,
    sample_block,
    right_pad_prompt,
    sampler_body,
    start_host_copies,
    token_counts,
)
from tpu_engine.runtime.kv_blocks import (
    BlockPool,
    PoolExhausted,
    StateRowPool,
    StateSlabPool,
)
from tpu_engine.utils.deadline import Deadline, DeadlineExceeded
from tpu_engine.utils.metrics import LatencyHistogram
from tpu_engine.utils.sampling import (
    MAX_STOP_TOKENS,
    clamp_top_k,
    expand_sampling_params,
    expand_stopping_params,
    truncate_at_stops,
)
from tpu_engine.utils.streams import StreamCounts
from tpu_engine.utils.tracing import (
    TickClock,
    compile_counter,
    gc_counter,
    step_part,
    tick_name,
)


class StreamDelta(list):
    """A streamed request's fresh tokens as they go into its queue: the
    list its reader always got, and `t_put`, when it was put
    (``time.perf_counter``; `_push_stream` sets it), so that the reader
    can say how long the tokens lay there (the lane's `generate_stream`
    span, `wake_us_*`)."""

    __slots__ = ("t_put",)


@dataclass
class _Request:
    prompt: List[int]
    max_new: int
    eos_id: int
    temperature: float
    seed: int
    top_p: float
    top_k: int
    rep_penalty: float = 1.0
    stop_tokens: List[int] = field(default_factory=list)
    min_p: float = 0.0
    future: Future = field(default_factory=Future)
    # Streaming: freshly-visible tokens are pushed as lists between decode
    # chunks; None is the end-of-stream sentinel (the future then holds the
    # final result or the error). `streamed` counts tokens already pushed.
    stream: Optional["queue.Queue"] = None
    streamed: int = 0
    # Resilience: expired requests are refused before prefill and
    # cancelled between decode chunks (the row frees for live work).
    deadline: Optional[Deadline] = None
    # Tracing (utils.tracing.TraceSink, optional): the scheduler records
    # queue_wait (submit→prefill start), slot_wait (hand-off to the
    # decode loop→a row), prefill, and decode stage spans against the
    # request's worker-root span. None = zero overhead.
    sink: Optional[object] = None
    t_submit: float = 0.0
    t_ready: float = 0.0
    t_admit: float = 0.0
    # Migration: `tag` names the row for export_row (the worker passes
    # request_id); `migrate` holds an import chain snapshot — the row
    # resumes mid-stream from another lane's exported state instead of
    # prefilling (DESIGN.md "Live stream migration").
    tag: Optional[str] = None
    migrate: Optional[dict] = None
    # Fleet prefix tier (DESIGN.md "Fleet-wide prefix tier"): a
    # gateway-attached hint naming the lane whose radix tree holds the
    # deepest known chain for this prompt's fingerprint. A miss with a
    # hint pulls the chain from that peer on the prefill thread and
    # splices it through the radix re-adoption path; every failure rung
    # falls back to local prefill (never strands the stream).
    prefix_hint: Optional[dict] = None
    # Disaggregated serving (DESIGN.md "Disaggregated serving"): a
    # handoff request PARKS after prefill — the row holds its first
    # token and KV chain, skipping decode ticks, until the gateway's
    # export command ships it to a decode lane (or `park_s` seconds
    # pass and the row decodes locally — the colocated fallback, so a
    # handoff whose orchestrator died can never strand a client).
    # `park_until` is stamped at HOLD time (prefill completion): a slow
    # prefill must not eat the export window.
    handoff: bool = False
    park_s: float = 5.0
    park_until: float = 0.0
    # Unified stateless serving (DESIGN.md "Unified stateless serving"):
    # a one-shot payload — ("infer", input_data, shape) or
    # ("score", prompt_tokens, completion_tokens) — admitted as a
    # single-tick row beside decode rows and prefill chunks. The row
    # holds no KV/slab state; _tick_stateless runs the grouped forward
    # and resolves the future with (result, per_request_time_us). None
    # = a normal generative request.
    oneshot: Optional[tuple] = None


class _FlightTick:
    """A mixed tick that is enqueued and whose results the host has not
    read yet: what `_land_tick` needs to apply them, as the tick was
    formed. `nxt`, `done` (and a routed model's `moe_rows`) are the
    step's outputs, still on the device; `reqs` the rows' requests when
    it was formed (a row freed, or its slot given on, while the tick ran
    takes nothing from it); `active` the rows whose sample is real,
    `completing` those of them whose chunk ends a prompt, `decode` those
    that advance a stream, `pos` the rows' positions with this tick's
    advance and no later one's; `overlapped`: enqueued while the tick
    before's results were unread. On a block-decoding lane `nxt` is the
    rows' blocks (B, L), `active` (and `decode`) the rows in a denoise
    pass, `commit` those in a commit pass, `tail` how many prompt tokens
    head each row's block, and `pos` a row's block start."""

    __slots__ = ("nxt", "done", "moe_rows", "reqs", "active", "completing",
                 "decode", "pos", "starved", "sampler", "fed",
                 "prefill_tokens", "n_decode", "width", "overlapped",
                 "commit", "tail")

    def __init__(self, **fields):
        for name, value in fields.items():
            setattr(self, name, value)

    def sampled(self, r: int, req: "_Request") -> bool:
        """This tick samples a token for `req` in row `r`."""
        return bool(self.active[r]) and self.reqs[r] is req

    def stepped(self, r: int, req: "_Request") -> bool:
        """This tick steps `req` in row `r` as a generating row: it
        samples for it, or runs its block's commit pass."""
        return self.reqs[r] is req and bool(
            self.active[r] or (self.commit is not None and self.commit[r]))


def take_from_prev(tokens, done, prev_nxt, prev_done, from_prev):
    """Inside a compiled mixed step: the rows of `from_prev` take their
    input token (column 0 of `tokens`) from `prev_nxt`, the token the
    step before sampled for them and the host has not read yet, and ride
    as done rows if `prev_done` says that token ended them."""
    tokens = tokens.at[:, 0].set(
        jnp.where(from_prev, prev_nxt, tokens[:, 0]))
    return tokens, done | (from_prev & prev_done)


def take_block_from_prev(tokens, done, prev_blk, prev_done, from_prev,
                         run: int, mask_id: int):
    """`take_from_prev` where a generating row's step is its block of
    `run` tokens: the rows of `from_prev` take columns [0, run) of
    `tokens` from `prev_blk`, the block the step before left them (-1
    where a position is still masked). Returns (tokens with `mask_id`
    where masked, what the embedding reads; the rows' blocks (B, run) as
    they stand before this pass; done)."""
    blk = jnp.where(from_prev[:, None], prev_blk, tokens[:, :run])
    tokens = tokens.at[:, :run].set(blk)
    return (jnp.where(tokens < 0, mask_id, tokens), blk,
            done | (from_prev & prev_done))


class TickBlock:
    """The layout of a mixed tick's control block: everything the host
    hands the compiled step per row, as ONE int32 array `(B, cols)` made
    fresh every tick, a slot a row. One host→device transfer a tick is a
    quarter of a millisecond on a v5e host whatever its size, and the
    fifteen small arrays a tick used to make were three quarters of a
    short decode tick's period. The columns, in order:

    - one each of `pos0`, `qlen`, `sample_slot`, `fold_pos`, `seeds`,
      `topks`, `eos_vec` (int32 as they are), `active`, `done`,
      `from_prev` (bool as 0 / 1), `temps`, `topps`, `minps` (float32,
      their bits), then `state_rows` on a lane whose rows own a state
      row, `reveal` on a block-decoding lane (how many positions a row's
      denoise pass reveals), then `pens` (float32 bits) and `stops`
      (`MAX_STOP_TOKENS` columns) in the `controls` variant;
    - `tokens`: `width` columns;
    - `tables`: a row's block table, and a second kind's after it on a
      windowed lane (`table_widths`).

    `pack` fills a block from numpy arrays, `unpack` takes one apart
    inside the compiled step by static slices: a field comes back with
    the type, shape and bits it went in with, so a step sees the
    arguments it saw when each was an array of its own. The layout
    follows what the lane is and the tick's `width` and `controls`, all
    static to the step's program."""

    INT = ("pos0", "qlen", "sample_slot", "fold_pos", "seeds", "topks",
           "eos_vec")
    BOOL = ("active", "done", "from_prev")
    FLOAT = ("temps", "topps", "minps")

    def __init__(self, width: int, table_widths: Sequence[int],
                 state_rows: bool = False, controls: bool = False,
                 reveal: bool = False):
        # name -> (first column, columns or None for a (B,) field, kind)
        self.fields: dict = {}
        self.cols = 0

        def add(name, kind="int", columns=None):
            self.fields[name] = (self.cols, columns, kind)
            self.cols += 1 if columns is None else int(columns)

        for kind, names in (("int", self.INT), ("bool", self.BOOL),
                            ("float", self.FLOAT)):
            for name in names:
                add(name, kind)
        if state_rows:
            add("state_rows")
        if reveal:
            add("reveal")
        if controls:
            add("pens", "float")
            add("stops", columns=MAX_STOP_TOKENS)
        add("tokens", columns=width)
        self.n_tables = len(table_widths)
        for k, n in enumerate(table_widths):
            add(f"tables{k}", columns=n)

    def pack(self, tables, **fields) -> np.ndarray:
        """A fresh `(B, cols)` block of `fields` (numpy arrays by the
        layout's names) and `tables` (a kind of block each)."""
        fields.update({f"tables{k}": t for k, t in enumerate(tables)})
        if fields.keys() != self.fields.keys():
            raise ValueError("a tick's fields are not its block's: "
                             f"{sorted(fields.keys() ^ self.fields.keys())}")
        block = np.empty((len(fields["pos0"]), self.cols), np.int32)
        for name, (at, columns, kind) in self.fields.items():
            value = fields[name]
            if kind == "float":
                value = value.view(np.int32)
            if columns is None:
                block[:, at] = value
            else:
                block[:, at:at + columns] = value
        return block

    def unpack(self, block) -> dict:
        """Inside the compiled step: every field of `block` by name, as
        it went in; `tables` a tuple, a kind of block each."""
        out = {}
        for name, (at, columns, kind) in self.fields.items():
            value = (block[:, at] if columns is None
                     else block[:, at:at + columns])
            if kind == "bool":
                value = value != 0
            elif kind == "float":
                value = jax.lax.bitcast_convert_type(value, jnp.float32)
            out[name] = value
        out["tables"] = tuple(out.pop(f"tables{k}")
                              for k in range(self.n_tables))
        return out


class _StaleAdmission(RuntimeError):
    """A prefilled item's pool pins/gather predate a pool rebuild
    (device recovery): the single request fails, the scheduler keeps
    serving (no second recovery)."""


class StreamMigratedAway(RuntimeError):
    """A live row was exported to another lane (export_row): its local
    stream ends HERE, and this exception resolves the local future. The
    gateway's migration orchestrator splices the destination's
    continuation; a client talking to the worker directly can resume
    manually from ``tokens_emitted`` (the same contract as the PR 6
    retryable error events — `migrated` marks the cause)."""

    def __init__(self, message: str, tokens_emitted: int):
        super().__init__(message)
        self.retryable = True
        self.migrated = True
        self.tokens_emitted = int(tokens_emitted)


class ImportRefused(RuntimeError):
    """A migration import the destination could not honor — checksum
    mismatch, incompatible pool geometry, or the pool cannot hold the
    chain while keeping the live-row reserve free. RETRYABLE by
    construction: the stream's journal falls back to the PR 6 replay
    resume, which needs nothing from this lane. ``import_refused``
    rides the terminal error event so the gateway attributes the
    fallback to the MIGRATION (counter honesty), not to a lane fault
    (no breaker penalty — the lane is healthy, the transfer wasn't)."""

    retryable = True
    import_refused = True


class _PrefixCache:
    """Byte-budget LRU of prefilled (logits, KV-block) pairs keyed by the
    exact (prompt bucket, prompt tokens). Repeated prompts — system
    prompts, the reference benchmark's 10-distinct-input workload — skip
    the prompt forward pass entirely at admission. Sampling params stay
    OUT of the key: logits are seed-independent, and the first token is
    sampled per-request from the cached logits, so a seeded request's
    stream is identical hit or miss (tested). Touched only by the single
    prefill thread; stats reads from other threads are GIL-safe."""

    def __init__(self, budget_bytes: int):
        from collections import OrderedDict

        self.budget = int(budget_bytes)
        self._items: "OrderedDict[tuple, tuple]" = OrderedDict()
        self.bytes = 0
        self.hits = 0
        self.misses = 0

    @staticmethod
    def _nbytes(logits, caches) -> int:
        return int(logits.size * logits.dtype.itemsize
                   + caches.k.size * caches.k.dtype.itemsize
                   + caches.v.size * caches.v.dtype.itemsize)

    def get(self, key):
        if self.budget <= 0:
            return None  # disabled: no phantom miss counting
        item = self._items.get(key)
        if item is None:
            self.misses += 1
            return None
        self._items.move_to_end(key)
        self.hits += 1
        return item[0], item[1]

    def put(self, key, logits, caches) -> None:
        if self.budget <= 0 or key in self._items:
            return
        nbytes = self._nbytes(logits, caches)
        if nbytes > self.budget:
            return  # one giant prompt must not flush the whole cache
        while self.bytes + nbytes > self.budget and self._items:
            _, (_, _, evicted) = self._items.popitem(last=False)
            self.bytes -= evicted
        self._items[key] = (logits, caches, nbytes)
        self.bytes += nbytes

    def stats(self) -> dict:
        return {"entries": len(self._items), "bytes": self.bytes,
                "hits": self.hits, "misses": self.misses}


class ContinuousGenerator:
    def __init__(
        self,
        model: Union[str, ModelSpec],
        params=None,
        rng_seed: int = 0,
        dtype: str = "bfloat16",
        n_slots: int = 8,
        prompt_buckets: Optional[Sequence[int]] = None,
        step_chunk: int = 8,
        max_seq: Optional[int] = None,
        device=None,
        prefix_cache_mb: int = 64,
        prefill_chunk: int = 256,
        kv_block_size: int = 0,
        kv_blocks: int = 0,
        kv_host_blocks: int = 0,
        kv_quantize: str = "",
        prefix_sharing: bool = True,
        mixed_step: bool = False,
        mixed_token_budget: int = 0,
        spec_k: int = 0,
        spec_draft: str = "ngram",
        spec_draft_model=None,
        spec_draft_params=None,
        state_rows: int = 0,
        tp: int = 1,
        tp_devices=None,
        infer_engine=None,
        score_provider=None,
    ):
        """`kv_block_size` > 0 switches the KV cache from one dense
        (L, n_slots, max_seq, H, D) tensor to the PAGED layout: a block
        pool (runtime.kv_blocks) of `kv_blocks` blocks of that many
        columns each (0 = auto: the dense layout's capacity), per-row
        block tables, and — with `prefix_sharing` — a radix tree that
        maps any shared prompt prefix onto already-filled blocks and
        resumes prefill mid-prompt. 0 (default) keeps the dense cache:
        behavior, compiled executables, and streams are exactly the
        pre-paging scheduler's.

        `kv_quantize` "int8" (paged mode only) stores block payloads
        int8 with per-(layer, block slot, kv-head) f32 scales — about
        half the KV bytes per block, so the same HBM holds ~2x the
        blocks (runtime.kv_blocks "Quantized block payloads"). Tokens
        quantize exactly once, at their block write (admission scatter,
        in-dispatch prefill chunks, decode appends); COW, radix
        re-adoption, and host-tier demotion/swap-in copy int8 + scale
        verbatim; both attention read paths (ops.paged_attention quant
        variants) apply the scales inside the read, so rounding error
        comes only from the one-time write. Quantized greedy streams
        are deterministic run-to-run but NOT byte-identical to the bf16
        pool (MIGRATION.md); "" (default) keeps today's full-precision
        pool byte-identical.

        `kv_host_blocks` > 0 (paged mode with prefix sharing) adds the
        HIERARCHICAL HOST TIER under the device pool: LRU eviction
        demotes cold radix leaves' blocks to pinned host buffers instead
        of destroying them, and a radix hit on a demoted prefix swaps
        the blocks back in on the prefill thread (overlapped with batch
        formation) instead of recomputing that prefill. Promotion never
        starves live rows: it takes free blocks first, may displace
        LRU-colder resident leaves (demoted, not destroyed), and must
        leave one free block per active row after the swap-in, else the
        lookup stops at the resident prefix and the tail recomputes
        (counted ``swap_in_deferred``).

        A lane with a pool (or the state_slab family's slab) steps by
        the token-budgeted ragged tick: each tick forms ONE ragged
        batch of (decode rows x 1 token) + (admitting rows x a prefill
        chunk) and issues exactly one compiled dispatch
        (transformer_step_rows_ragged) — admission work rides the
        decode dispatch instead of queueing beside it, so a long prompt
        cannot head-of-line-block in-flight rows' tokens. The prefill
        thread is pure batch formation (bucket pick + radix lookup; no
        device work). `mixed_token_budget` caps new tokens per tick
        (decode rows count 1 each; the remainder is split over
        admitting rows' chunks, and also caps the compiled chunk width)
        so per-tick latency stays bounded; 0 = auto (prefill_chunk).
        Seeded streams are byte-identical to the dense cache's
        (tested). `mixed_step` is accepted and chooses nothing (see
        where `self._mixed` is set).

        `tp` > 1 (paged kv_paged family only) serves the model
        TENSOR-PARALLEL over a 1-axis ``model`` mesh of that many
        devices (the first `tp` local devices, or `tp_devices`):
        params place by the registry-declared partition rule
        (models.registry.tp_shardings — heads-axis QKV/MLP up,
        row-parallel wo/proj, replicated norms/embeddings), the block
        pool shards its heads (scale arrays alongside on int8
        pools), and every pool-donating executable pins its pool
        outputs to the same sharding, so each tick stays ONE SPMD
        ragged dispatch with donation intact. Greedy streams are
        byte-identical to the tp=1 arm on this backend (tested; logits
        agree to ~1e-6 — the same empirical basis as the mixed-vs-dense
        stream identity). Unshardable families (state_slab — the
        mamba2 conv tail/slab) refuse loudly; `device` is mutually
        exclusive with `tp`.

        `spec_k` > 0 (a block pool only) turns on
        CONTINUOUS SPECULATIVE DECODING: each tick a host-side drafter
        proposes up to spec_k tokens per decode row (`spec_draft`
        "ngram" = the deterministic prompt-lookup drafter, no second
        model; "model" = greedy proposals from `spec_draft_model`, one
        extra draft dispatch per drafted row), and the tick's ONE ragged
        dispatch verifies every row's window (decode rows become
        q_len = proposals+1 ragged rows beside any prefill chunks),
        advancing each row by its accepted prefix plus one
        corrected/bonus token — 1..spec_k+1 tokens per dispatch. Greedy
        streams are byte-identical to plain continuous/mixed decode for
        ANY draft (the verify loop re-derives every token with the same
        fold_in(seed, position) sampling rule, penalties and stop lists
        included); temperature>0 rows without filters take the
        rejection-sampling path — unbiased draws from the target
        distribution, deterministic per seed, but NOT byte-equal to
        plain decode (MIGRATION.md); rows carrying top_p/top_k/min_p or
        sampled-with-controls are simply not drafted (q_len 1 — plain,
        byte-identical). Rejected draft tails leave stale KV the
        position masks hide; blocks over-allocated for the speculation
        horizon are returned as a row's remaining budget shrinks."""
        if isinstance(model, str):
            _ensure_builtin_models_imported()
            model = create_model(model)
        # Family dispatch (registry framing — VirtualFlow in PAPERS.md):
        # the model's DECLARED state family selects which autoregressive
        # state machinery this scheduler builds — never an isinstance
        # probe (the registry's contract: consumers fence on the
        # declaration). "kv_paged" = the transformer families' growing
        # KV chain (dense or block pool); "state_slab" = the SSD/Mamba
        # families' fixed-size recurrent state rows (StateSlabPool).
        # Everything above the state layer — admission, deadlines,
        # streams, brownout, crash recovery, migration — is
        # family-independent and shared. Bare stand-in specs without a
        # declaration (test fakes) derive it from their config, the
        # same rule ModelSpec.__post_init__ applies.
        fam = getattr(model, "state_family", None)
        if not fam:
            fam = ("state_slab" if isinstance(model.config, SSDConfig)
                   else "kv_paged")
        self._slab = fam == "state_slab"
        # "kv_latent" (models.moonlight): a kv_paged chain whose blocks
        # hold a latent and a rope key, stepped by its own ragged step.
        # Everything the pool does by block id — tables, radix sharing,
        # refcounts — is shared; what assumes K and V of H_kv*D lanes is
        # fenced below (`_fence_tick_only_family`).
        # "kv_windowed" (models.laguna): a kv_paged chain in blocks of two
        # kinds, one pool and one table a row each. Full-attention layers
        # keep every block in `_pool`, as every other paged family;
        # sliding-window layers keep theirs in `_wpool` and give the ones
        # behind the window back inside the tick whose position passes
        # them (`_slide_window_blocks`).
        self._windowed = fam == "kv_windowed"
        # "kv_and_state" (models.olmo_hybrid, models.kimi_linear,
        # models.falcon_h1): a chain over a pool that holds the layers
        # that attend AND one row of a state pool (`_spool`: the
        # recurrent mixers' state and conv tail), one model, one row,
        # two pools; a layer is in one of them or, where it has both
        # mixers, in both. What a block holds (K and
        # V a head, or a latent) is the model's `kv_block_kinds[0]` and
        # changes nothing here. A row takes both at admission (parked
        # when blocks are short; a state row is its slot's own and cannot
        # be) and gives both back together; the step reads and writes
        # both in place.
        self._hybrid = fam == "kv_and_state"
        # A family whose step of the mixed tick is its own (and runs over
        # the tick's tokens) declares it, with the experts the lane's
        # weights hold (registry.ModelSpec).
        self._ragged_step = getattr(model, "ragged_step", None)
        self._held_experts = getattr(model, "held", None)
        # A model whose generating rows denoise a block of tokens over
        # several ticks declares it (registry.BlockDecode; None: a row
        # samples one token a tick). `_run`: the tokens a generating row
        # feeds a tick.
        self._block = getattr(model, "block_decode", None)
        self._run = self._block.block_length if self._block else 1
        # A model that applies its layers several times a token declares
        # how often (registry `ModelSpec.passes`; 1: once). Its own step
        # runs the passes; here they are counted.
        self._passes = int(getattr(model, "passes", 1) or 1)
        # Unified stateless serving (DESIGN.md): score/infer/embed
        # models admit as SINGLE-TICK rows — no autoregressive state at
        # all, so every state-machinery branch below is skipped and the
        # shared layers (admission, deadlines, brownout, tracing,
        # recovery) serve them unchanged. Generative lanes can ALSO
        # carry one-shot rows (submit_infer/submit_score beside decode
        # streams) — that path needs no family branch because one-shot
        # rows never touch the family's state machinery.
        self._stateless = fam == "stateless"
        if self._slab:
            if not isinstance(model.config, SSDConfig):
                # The slab machinery's step functions are the SSD
                # mixer's; a new recurrent architecture joins by
                # carrying (or subclassing) an SSDConfig, not by
                # declaration alone.
                raise ValueError(
                    f"model '{model.name}' declares state family "
                    f"'state_slab' but its config is not an SSDConfig "
                    f"(the slab step functions are models.ssd's)")
        elif not self._stateless and (
                not isinstance(model.config, TransformerConfig)
                or not model.config.causal):
            raise ValueError(f"model '{model.name}' is not a decoder "
                             f"transformer")
        self.spec = model
        self.cfg = model.config
        self._dtype = _DTYPES[dtype]
        if self._stateless:
            # One-shot rows have no sequence axis and cfg may be None
            # entirely (mlp/resnet/ONNX graphs): max_seq survives only
            # as the prompt-bucket bound of the (never exercised)
            # generative machinery below.
            self.max_seq = int(max_seq) if max_seq else 16
        else:
            self.max_seq = min(max_seq or self.cfg.max_seq,
                               self.cfg.max_seq)
        self.n_slots = int(n_slots)
        self._step_chunk = int(step_chunk)
        if prompt_buckets is None:
            b, prompt_buckets = 16, []
            while b < self.max_seq:
                prompt_buckets.append(b)
                b *= 2
            prompt_buckets.append(self.max_seq)
        self._prompt_buckets = tuple(sorted(
            {min(int(p), self.max_seq) for p in prompt_buckets}))
        self._device = device
        # Tensor-parallel serving (DESIGN.md "Tensor-parallel serving"):
        # fences first — every misconfiguration is a LOUD error naming
        # the contract, never a silently single-device lane.
        self._tp = int(tp)
        self._tp_mesh = None
        self._kv_pin = None     # the pool's (and int8 scales') sharding pin
        if self._tp > 1:
            if device is not None:
                raise ValueError(
                    "tp > 1 builds its own device mesh; `device` is "
                    "mutually exclusive with tensor-parallel serving")
            from tpu_engine.models.registry import tp_unshardable_reason

            if self._slab:
                reason = (tp_unshardable_reason(model)
                          or "the state_slab family declares no "
                             "shardable heads axis")
                raise RuntimeError(
                    f"model '{model.name}' cannot serve "
                    f"tensor-parallel (tp={self._tp}): {reason}")
            if int(kv_block_size) <= 0:
                raise ValueError(
                    "tp > 1 requires the paged KV cache "
                    "(set kv_block_size > 0): the dense per-slot cache "
                    "has no sharded pool layout")
            reason = tp_unshardable_reason(model)
            if reason is not None:
                raise RuntimeError(
                    f"model '{model.name}' cannot serve "
                    f"tensor-parallel (tp={self._tp}): {reason}")
            from tpu_engine.parallel.mesh import tp_mesh

            self._tp_mesh = tp_mesh(self._tp, tp_devices)
        self.params = params if params is not None else model.init(
            jax.random.PRNGKey(rng_seed))
        if self._tp_mesh is not None:
            # Registry-declared placement: heads-axis QKV/MLP up,
            # row-parallel wo/proj, replicated norms/embeddings — the
            # scheduler never re-derives partition specs per call site.
            from tpu_engine.models.registry import tp_shardings

            self.params = jax.device_put(
                self.params, tp_shardings(model, self.params,
                                          self._tp_mesh))
        elif device is not None:
            self.params = jax.device_put(self.params, device)
        self._place_step_params()

        # Device state: one persistent KV cache + per-row vectors. Paged
        # mode replaces the dense per-slot cache with a block pool +
        # per-row block tables (runtime.kv_blocks); everything else —
        # row vectors, sampling, admission — is layout-independent.
        self._paged = int(kv_block_size) > 0
        if self._slab:
            # Family fences, loud and specific (the registry declares
            # capabilities; a silently ignored knob would be worse than
            # a refusal — MIGRATION.md's misconfiguration contract).
            if self._paged or int(kv_blocks) > 0:
                raise ValueError(
                    "the state_slab family has no paged KV cache: "
                    "kv_block_size/kv_blocks apply to kv_paged models "
                    "(state capacity is state_rows)")
            if int(kv_host_blocks) > 0:
                raise ValueError(
                    "kv_host_blocks applies to the kv_paged family's "
                    "block pool; the state_slab family has no "
                    "demotable KV blocks")
            if kv_quantize:
                raise ValueError(
                    "kv_quantize applies to the kv_paged family's "
                    "block pool; the state_slab family's slab stays "
                    "full precision")
            if int(spec_k) > 0:
                raise ValueError(
                    "speculative decoding (spec_k > 0) requires the "
                    "kv_paged family: the state_slab recurrence has no "
                    "KV verify window")
        elif self._stateless:
            # Family fences, loud and specific (MIGRATION.md's
            # misconfiguration contract): one-shot rows hold NO
            # autoregressive state, so every generative-state knob is a
            # refusal, never silently inert.
            if self._paged or int(kv_blocks) > 0:
                raise ValueError(
                    "the stateless family has no KV cache: "
                    "kv_block_size/kv_blocks apply to kv_paged models")
            if int(kv_host_blocks) > 0:
                raise ValueError(
                    "kv_host_blocks applies to the kv_paged family's "
                    "block pool; the stateless family holds no KV "
                    "blocks")
            if kv_quantize:
                raise ValueError(
                    "kv_quantize applies to the kv_paged family's "
                    "block pool; the stateless family holds no KV "
                    "blocks")
            if int(spec_k) > 0:
                raise ValueError(
                    "speculative decoding (spec_k > 0) requires the "
                    "kv_paged family: one-shot rows have no decode "
                    "loop to speculate")
            if mixed_step:
                raise ValueError(
                    "mixed_step merges prefill and decode dispatches; "
                    "the stateless family has neither (one-shot rows "
                    "already ride one grouped dispatch per tick)")
            if int(state_rows) > 0:
                raise ValueError(
                    "state_rows applies to the state_slab family; the "
                    "stateless family has no recurrent state")
        elif int(state_rows) > 0:
            raise ValueError(
                "state_rows applies to the state_slab family; model "
                f"'{model.name}' serves the "
                f"{getattr(model, 'state_family', 'kv_paged')} family")
        if fam in self._TICK_ONLY_WHY:
            self._fence_tick_only_family(
                model, fam, kv_host_blocks=kv_host_blocks,
                kv_quantize=kv_quantize, spec_k=spec_k,
                prefix_sharing=prefix_sharing)
        if int(kv_host_blocks) > 0 and not self._paged:
            raise ValueError("kv_host_blocks requires the paged KV cache "
                             "(set kv_block_size > 0)")
        self._quant = bool(kv_quantize)
        if self._quant and not self._paged:
            raise ValueError("kv_quantize requires the paged KV cache "
                             "(set kv_block_size > 0)")
        self._caches = None
        self._pool: Optional[BlockPool] = None
        self._spool: Union[StateSlabPool, StateRowPool, None] = None
        if self._slab:
            # Fixed-size recurrent state rows: the whole per-stream
            # autoregressive state is one (n_layers, state_dim) f32 row
            # — constant in sequence length, so "KV capacity" becomes
            # "state capacity" (rows) for this family. No radix tree:
            # recurrent prefixes are not block-addressable (the pool's
            # stats say so loudly).
            rows = int(state_rows) or self.n_slots + 1
            self._spool = StateSlabPool(self.cfg.n_layers,
                                        ssd_state_dim(self.cfg), rows,
                                        device=device)
            # Slab row id each scheduler slot owns (-1 = none).
            # Decode-thread-owned like the paged row tables.
            self._slab_rows: List[int] = [-1] * self.n_slots
            self._prefix_sharing = False
            # Admissions deferred on row exhaustion, retried as rows
            # free — the same parking the paged pool uses for blocks.
            self._pending: "collections.deque" = collections.deque()
        if self._paged:
            bs = int(kv_block_size)
            if self.cfg.sliding_window is not None:
                raise ValueError("paged KV cache does not support "
                                 "sliding_window models yet")
            bad = [b for b in self._prompt_buckets if b % bs]
            if bad:
                raise ValueError(
                    f"kv_block_size={bs} must divide every prompt bucket "
                    f"(violates {bad}); pick a power of two <= "
                    f"{self._prompt_buckets[0]}")
            width = -(-self.max_seq // bs)  # blocks per full-length row
            nb = int(kv_blocks) if kv_blocks else self.n_slots * width + 1
            if nb < width + 1:
                raise ValueError(
                    f"kv_blocks={nb} cannot hold even one max_seq row "
                    f"({width} blocks + the null block)")
            if int(kv_host_blocks) > 0 and not prefix_sharing:
                raise ValueError("kv_host_blocks requires prefix_sharing "
                                 "(the host tier holds radix entries)")
            # A model that STATES what its pool holds (`kv_block_kinds`:
            # a family with layers of several kinds, whose `_pool` holds
            # its full-attention layers alone at the lanes the model
            # states; a model applied several times, whose pool is deeper
            # than its weights) sizes the pool by that, any other by its
            # own config.
            kinds = getattr(self.cfg, "kv_block_kinds", None)
            self._pool = BlockPool(kinds[0] if kinds else self.cfg,
                                   nb, bs, self._dtype, device,
                                   host_blocks=int(kv_host_blocks),
                                   quantize=str(kv_quantize),
                                   mesh=self._tp_mesh)
            if self._tp > 1:
                # Pool-output pins for every donating executable: the
                # output sharding must EQUAL the input's or donation is
                # wasted (and XLA free to re-lay the pool per tick).
                self._kv_pin = self._pool.kv_sharding
            self._tables = np.zeros((self.n_slots, width), np.int32)
            self._row_blocks: List[List[int]] = [[] for _ in
                                                 range(self.n_slots)]
            self._prefix_sharing = bool(prefix_sharing)
            # Admissions deferred on pool pressure, retried as rows free.
            self._pending: "collections.deque" = collections.deque()
            if self._hybrid:
                # The recurrent layers' rows, `n_slots + 1` as the slab
                # family's are: slot s owns row s + 1, row 0 is the null
                # row a free slot points at.
                self._spool = StateRowPool(
                    self.cfg.n_linear_layers, self.cfg.state_row_shapes,
                    self.n_slots, device=device)
        elif not (self._slab or self._stateless):
            self._caches = init_caches(self.cfg, self.n_slots, self.max_seq,
                                       self._dtype)
            if device is not None:
                self._caches = jax.device_put(self._caches, device)
        self._pos = np.zeros((self.n_slots,), np.int32)      # next write col
        self._start = np.zeros((self.n_slots,), np.int32)    # first valid col
        self._tok = np.zeros((self.n_slots,), np.int32)      # last emitted
        self._seeds = np.zeros((self.n_slots,), np.int32)
        self._temps = np.zeros((self.n_slots,), np.float32)
        self._topps = np.ones((self.n_slots,), np.float32)
        self._topks = np.zeros((self.n_slots,), np.int32)
        self._minps = np.zeros((self.n_slots,), np.float32)
        self._pens = np.ones((self.n_slots,), np.float32)
        self._stops = np.full((self.n_slots, MAX_STOP_TOKENS), -1, np.int32)
        # Device-resident context-token counts (repetition-penalty state),
        # donated through decode chunks like the KV cache. LAZY: the
        # (n_slots, vocab) buffer allocates only when the first request
        # carrying a penalty or stop list arrives — default traffic pins
        # no memory and pays no admission bookkeeping for the feature.
        self._counts = None
        self._done = np.ones((self.n_slots,), bool)          # sampling mask
        self._row_req: List[Optional[_Request]] = [None] * self.n_slots
        self._row_emitted: List[List[int]] = [[] for _ in range(self.n_slots)]
        # Disaggregated handoff holds: a True slot is a live row parked
        # after prefill (first token emitted, KV chain complete) waiting
        # for the gateway's export-after-prefill command — excluded from
        # decode dispatch so a prefill-role lane never spends decode-tick
        # work on rows it is about to ship. Decode-thread-owned like the
        # row tables.
        self._held: List[bool] = [False] * self.n_slots

        self._queue: "queue.Queue[Optional[_Request]]" = queue.Queue()
        # Live stream migration: (tag, Future) export commands enqueued
        # by worker threads, served by the decode loop between ticks —
        # the quiesce point (no dispatch is in flight, the row's host
        # state and pool blocks are mutually consistent). queue.Queue:
        # its own lock, no registry entry needed.
        self._migrate_q: "queue.Queue[tuple]" = queue.Queue()
        # Export commands waiting on a row's prefill (wait_prefill):
        # re-checked at every tick boundary, decode-thread-owned.
        self._export_waiting: List[tuple] = []
        # Handoff cancels that arrived BEFORE the row parked (still
        # queued or prefilling): remembered so the row skips its park
        # instead of waiting out the full window for an orchestrator
        # that already gave up. Decode-thread-owned; bounded.
        self._hold_cancel_tags: "collections.deque" = collections.deque(
            maxlen=64)
        # Prefilled requests ready for row insertion: (req, row_caches,
        # first_tok, pb, L). The prefill thread fills this so admission work
        # (prompt forward + first-token sample, with its host sync) never
        # stalls in-flight rows' decode chunks (round-1 VERDICT: admission
        # ran serially on the decode thread → head-of-line latency).
        # Bounded: each entry pins a prefilled KV block on device, so the
        # prefill thread must stop at ~one batch's worth of ready blocks and
        # leave the rest of a burst waiting un-prefilled in _queue.
        self._ready: "queue.Queue[Optional[tuple]]" = queue.Queue(
            maxsize=max(1, self.n_slots))
        self._exe_lock = threading.Lock()
        self._prefill_exe = None
        self._insert_exe = {}  # {with_counts flag: compiled insert}
        self._decode_exe = {}  # {controls flag: compiled chunk}
        self._stats = {"admitted": 0, "completed": 0, "chunks": 0}
        # deadline_cancelled is bumped from BOTH the prefill and decode
        # threads; a bare read-modify-write would drop counts under
        # contention. Every other _stats key is decode-thread-only.
        self._stats_lock = threading.Lock()
        # Unified stateless serving: `infer_engine` (an InferenceEngine,
        # or any object with batch_predict / batch_submit+batch_collect)
        # enables submit_infer one-shot rows; `score_provider` (a
        # callable returning a scoring Generator — callable so hot
        # reloads refresh params per dispatch) enables submit_score.
        # Either may ride a GENERATIVE lane too: one-shot rows and
        # decode rows then share this one slot pool, admission queue,
        # deadline governance, and brownout ladder. The gated
        # "stateless" stats block exists iff one-shot rows can — a
        # generative-only lane's /stats and /health bytes are
        # unchanged. Created HERE (not on first admission) so no
        # cross-thread dict mutation ever races stats() scrapes.
        self._infer_engine = infer_engine
        self._score_provider = score_provider
        self._oneshot = (self._stateless or infer_engine is not None
                         or score_provider is not None)
        if self._oneshot:
            self._stats["stateless"] = {
                "admitted": 0, "completed": 0, "failed": 0,
                "ticks": 0, "dispatches": 0, "infer_rows": 0,
                "score_rows": 0, "full_dispatches": 0,
                "deadline_dropped": 0,
            }
        # One-shot staging lane (unbounded): prefilled one-shot requests
        # wait HERE, not in the slot-bounded _ready queue. They are
        # transient members of the next tick's grouped dispatch — freed
        # within the tick — so making them queue FIFO behind generative
        # admissions (which hold a slot for a whole stream's lifetime)
        # would starve single-tick work behind multi-second residents
        # AND clog _ready ahead of decode admissions. Deadlines are
        # enforced at drain time every tick.
        self._oneshot_ready: "queue.Queue[_Request]" = queue.Queue()
        self._prefix_cache = _PrefixCache(int(prefix_cache_mb) * (1 << 20))
        # Chunked prefill: prompts longer than this admit via a sequence
        # of window-decode dispatches instead of one monolithic prefill,
        # so in-flight rows' decode chunks interleave at dispatch
        # granularity instead of stalling behind a long prompt (0 = off).
        self._prefill_chunk = int(prefill_chunk)
        self._window_exe = None
        # How a lane is stepped follows from what it holds: a block pool
        # or a state slab steps by the ragged tick (ONE dispatch per
        # tick), the dense per-slot cache by the two-path chunk loop. The
        # `mixed_step` argument chooses nothing: benchmarks/ and
        # tests/benchmarks/ still pass it (ROADMAP: the benchmark item
        # that drops it), so it stays accepted, and only `True` on a lane
        # that has nothing to step raggedly is refused.
        if mixed_step and not (self._paged or self._slab):
            raise ValueError("mixed_step requires the paged KV cache "
                             "(set kv_block_size > 0)")
        self._mixed = self._paged or self._slab
        # Continuous speculative decoding (paged layouts only): drafts
        # verified inside the per-tick ragged dispatch.
        self._spec_k = int(spec_k)
        self._spec = self._spec_k > 0
        self._drafter = None
        if self._spec:
            if not self._paged:
                raise ValueError("speculative decoding (spec_k > 0) "
                                 "requires the paged KV cache (set "
                                 "kv_block_size > 0)")
            if self._spec_k > self.max_seq - 2:
                raise ValueError(f"spec_k={self._spec_k} cannot fit a "
                                 f"verify window in max_seq={self.max_seq}")
            from tpu_engine.runtime.speculative import make_drafter

            self._drafter = make_drafter(
                spec_draft, self._spec_k, draft_model=spec_draft_model,
                draft_params=spec_draft_params, dtype=self._dtype,
                device=device)
            dcfg = getattr(self._drafter, "cfg", None)
            if dcfg is not None and dcfg.vocab != self.cfg.vocab:
                raise ValueError(f"draft vocab {dcfg.vocab} != target "
                                 f"vocab {self.cfg.vocab}")
            self._stats["spec"] = {
                "k": self._spec_k, "draft": self._drafter.name,
                "ticks": 0, "dispatches": 0, "proposed_tokens": 0,
                "accepted_tokens": 0, "emitted_tokens": 0,
                # (row, tick) pairs that emitted: emitted/row_ticks is
                # the mean per-ROW advance per dispatch — the honest
                # speculation win (plain ragged ticks are exactly 1.0;
                # emitted/dispatches alone would conflate co-batching).
                "row_ticks": 0,
                "draft_dispatches": 0, "tail_blocks_released": 0,
            }
        # A pool's decode rows advance one token per tick (spec off) and
        # up to spec_k+1 in spec mode, so block growth and admission
        # headroom reserve exactly that horizon.
        self._decode_horizon = self._spec_k + 1
        if self._block is not None:
            # A generating row writes its whole block every pass.
            self._decode_horizon = self._run
            if int(kv_block_size) % self._run:
                raise ValueError(
                    f"kv_block_size={kv_block_size} must hold whole blocks "
                    f"of {self._run} tokens (model '{model.name}' decodes "
                    f"by blocks; the cache's end is a pool block's)")
        if self._mixed:
            budget = int(mixed_token_budget) or (self._prefill_chunk
                                                 if self._prefill_chunk > 0
                                                 else 256)
            self._mixed_budget = max(1, budget)
            # No tick of `_tick_mixed` feeds more slots than the token
            # budget plus a token a row: the static size of its step's
            # token list (`_mixed_step_exe`, held to in `_tick_formed`).
            self._tick_max_tokens = self._mixed_budget + self.n_slots
            if self._block is not None:
                # A generating row counts as its run in the budget, and
                # the first prefilling row is owed one block whatever the
                # runs left of it.
                self._tick_max_tokens = self._run + max(
                    self._mixed_budget, self.n_slots * self._run)
            # Per-row chunk cap == compiled ragged width. Exactly two
            # compiled widths exist per controls variant (1 and the cap):
            # a narrower final chunk pads with null-block slots instead of
            # compiling its own executable.
            self._chunk_cap = max(1, min(
                self._prefill_chunk if self._prefill_chunk > 0 else budget,
                budget))
            if self._block is not None:
                # Chunks are whole blocks: their starts stay multiples of
                # the block length.
                self._chunk_cap = self._chunk_cap // self._run * self._run
                if self._chunk_cap < self._run:
                    raise ValueError(
                        f"the prefill chunk and the token budget must hold "
                        f"a block of {self._run} tokens (model "
                        f"'{model.name}' decodes by blocks)")
            self._prefilling = [False] * self.n_slots
            self._row_prompt: List[Optional[np.ndarray]] = \
                [None] * self.n_slots
            self._row_prompt_toks: List[Optional[List[int]]] = \
                [None] * self.n_slots
            self._row_L = [0] * self.n_slots
            self._row_w0 = [0] * self.n_slots
            self._tick_blocks: dict = {}
            self._stats["mixed"] = {
                "ticks": 0, "dispatches": 0, "prefill_tokens": 0,
                "decode_tokens": 0, "coscheduled_ticks": 0,
                # Ticks by the body of `_sample` their kept rows asked
                # for (`generator.sampler_body`); they sum to `ticks`.
                "sample_greedy_ticks": 0, "sample_plain_ticks": 0,
                "sample_filtered_ticks": 0,
                # Ticks enqueued while the tick before's results were
                # not yet read, and row-ticks stepped past an end the
                # host learned one tick late (`_land_tick`).
                "overlapped_ticks": 0, "lagged_rows": 0,
                # Host→device arrays `_tick_mixed` made while it formed
                # its ticks, counted where each is made.
                "form_transfers": 0,
                "token_budget": self._mixed_budget,
                "chunk_cap": self._chunk_cap,
            }
            if self._block is not None:
                # A block-decoding lane's rows by the pass they ran
                # (counted as their tick lands), the blocks whose last
                # denoise pass landed, and the run a row feeds a tick.
                # `decode_tokens` counts output tokens as their block is
                # finished.
                self._stats["mixed"].update(
                    denoise_passes=0, commit_passes=0, blocks_finished=0,
                    block_decode={
                        **self._block._asdict(),
                        "runs_ahead": self._block.reveal
                        != "low_confidence_dynamic"})
                # The current block of each row as the host last knew it
                # (-1: still masked), how many of its positions are masked
                # once the ticks enqueued so far have run, and how many
                # prompt tokens head it (the first block alone).
                self._blk_known = np.full((self.n_slots, self._run), -1,
                                          np.int32)
                self._blk_masked = np.zeros((self.n_slots,), np.int32)
                self._blk_tail = np.zeros((self.n_slots,), np.int32)
            if self._passes > 1:
                # A lane whose model applies its layers several times a
                # token: the planes of its pool (a plane a (pass, layer))
                # with the bytes a token takes in them, and the layer
                # applications its ticks ran, `kv_planes` a tick.
                self._stats["mixed"].update(
                    ut_steps=self._passes,
                    kv_planes=self._pool.cfg.n_layers,
                    kv_bytes_per_token=self._pool.bytes_per_block() // bs,
                    layer_passes=0)
            if self._ragged_step is not None and getattr(
                    self.cfg, "n_moe_layers", 0):
                # What the expert layers routed, summed over ticks: the
                # step returns each layer's per-expert row counts with
                # the tick's other results. Padding slots form no pair.
                self._moe_rows = np.zeros(
                    (self.cfg.n_moe_layers, self.cfg.n_routed), np.int64)
                self._stats["moe"] = {"assignments": 0,
                                      "experts_touched": 0}
                if self._held_experts is not None:
                    # A lane that holds a share of the experts: the
                    # pairs that formed a row HERE, of all it routed.
                    self._stats["moe"]["assignments_held"] = 0
            if self._windowed:
                # A row's window blocks: at most the window, a chunk and
                # a block of tokens (`_slide_window_blocks`), so a pool
                # of that bound a slot can never run out.
                per_row = -(-(self.cfg.window + self._chunk_cap) // bs) + 1
                self._wpool = BlockPool(
                    self.cfg.kv_block_kinds[1], self.n_slots * per_row + 1,
                    bs, self._dtype, device)
                self._wtables = np.zeros_like(self._tables)
                # The table entries [first, end) a row holds.
                self._wspan = np.zeros((self.n_slots, 2), np.int64)
                self._wfreed = 0
        # TTFT / inter-token-latency histograms — the two numbers mixed
        # stepping exists to improve, scrapeable at /metrics
        # (tpu_engine_ttft_seconds / tpu_engine_itl_seconds) on every
        # scheduler mode. ITL samples are per stream delivery: the gap
        # since the row's previous visible tokens.
        self.ttft_hist = LatencyHistogram()
        self.itl_hist = LatencyHistogram()
        self._row_last_emit = [0.0] * self.n_slots
        # Optional tracing (set by the serving worker): per-tick
        # `mixed_step` spans carrying prefill_tokens/decode_rows attrs.
        self.tracer = None
        self.trace_node = "scheduler"
        # The tick clock every mixed/spec tick function marks (phases on
        # the `mixed_step` span, profiler annotations, host gap), and
        # the process-wide compile counter it reads `compile_us` from.
        self._compiles = compile_counter()
        self._gcs = gc_counter()
        self._clock = TickClock(self._compiles, self._gcs)
        # Streams: who handed this lane's token events to their sockets
        # (`stats()["stream"]`; an outbox carries it to the front), and
        # the wakes of the fronts' writers that the puts since the last
        # `_wake_streams` marked a stream ready with.
        self.stream_counts = StreamCounts()
        self._stream_wakes: set = set()
        # The mixed tick's pipeline, one tick deep (`_tick_mixed`).
        self._reset_flight()
        # Per-row prefill accounting for the `prefill` span: ticks that
        # fed the row, and ticks (with their summed duration) in which
        # the row was prefilling and the token budget gave it nothing.
        self._row_chunks = [0] * self.n_slots
        self._row_starved_ticks = [0] * self.n_slots
        self._row_starved_us = [0.0] * self.n_slots
        self._tick_starved: List[int] = []
        self._tick_sampler = SAMPLER_BODIES[0]
        # Staged brownout degradations (set_brownout; driven by the
        # serving worker's overload control loop, DESIGN.md "Overload
        # control"). Plain attribute writes from the control thread,
        # read per tick/lookup by the decode and prefill threads —
        # floats/bools are GIL-atomic, and a one-tick-stale read only
        # shifts WHEN a degradation engages, never correctness. All
        # three degrade WORK SHAPE, not stream content: greedy streams
        # stay byte-identical under every stage.
        self._bo_budget_frac = 1.0   # mixed-step token budget multiplier
        self._bo_spec_off = False    # suspend speculative drafting
        self._bo_defer_swap = False  # defer host-tier swap-ins
        # Drain visibility (elastic fleet): set by the worker's
        # drain/undrain, read by stats() to surface how much live work
        # a lame-duck lane still holds (the autoscaler's scale-down
        # watch). Plain GIL-atomic bool, same discipline as the
        # brownout flags above; False at defaults keeps /health and
        # /stats bytes identical.
        self._draining_flag = False
        # Liveness: stamped at the top of every decode-loop iteration.
        # The loop iterates continuously even when idle (bounded admission
        # waits), so a growing age means the loop is WEDGED — inside a
        # hung device dispatch — not merely quiet. The prefill thread
        # blocks when idle, so its signal is a busy-age instead: set while
        # a prompt's forward pass runs, None otherwise. stats() reports
        # the max of the two as last_tick_age_s. /health surfaces the age
        # (WorkerConfig.scheduler_stall_s turns it into unhealthy).
        self._last_tick = time.monotonic()
        self._prefill_busy_since = None
        # Cross-lane trace stitching (set by the serving worker when
        # --trace-stitch is on): _do_export snapshots then carry the
        # stream's trace context (additive "traceparent" snapshot field
        # + a gated "trace" chain header) so the importing lane
        # re-parents its spans under the SAME trace. Off = snapshot and
        # chain wire bytes identical to today.
        self.trace_stitch = False
        # Fleet prefix tier (set post-construction by the serving
        # worker when --prefix-fetch is on): a callable
        # ``(hint, tokens, max_blocks) -> dict | None`` that pulls a
        # radix chain from the hinted peer — the worker owns transport,
        # timeout, and the in-flight cap; the scheduler owns
        # verification, allocation, and the splice. None keeps every
        # hint inert (defaults-off: zero prefill-path work).
        self.prefix_fetch = None
        # Per-tick flight recorder (DESIGN.md "Observability plane"):
        # a bounded ring of per-tick records — rows by state, token
        # budget used, dispatch wall time, queue/park/held depths, pool
        # occupancy — the postmortem black box. Configured
        # post-construction by the serving worker
        # (configure_flight_recorder); capacity 0 = off, zero per-tick
        # work. The ring is written by the decode thread and read by
        # scrape threads (/admin/timeline), hence the lock.
        self._flight_capacity = 0
        self._flight_ring: "collections.deque" = collections.deque(maxlen=1)
        self._flight_lock = threading.Lock()
        self._flight_dump_dir = None
        self._flight_last_dump = None
        self._flight_dumps = 0
        self._flight_last_dump_ts = 0.0
        # Previous cumulative counter readings (per-tick deltas) plus a
        # rolling 10 s deadline-miss window for burst detection.
        # Decode-thread-owned.
        self._flight_prev: dict = {}
        self._flight_miss_window: "collections.deque" = collections.deque()
        # jax.profiler capture bounded in scheduler ticks
        # (start_profile): armed by /admin/profile, counted down at the
        # top of each decode tick, stopped on reaching zero.
        self._profile_ticks_left = 0
        self._profile_result = None
        self._running = True
        self._prefill_thread = threading.Thread(
            target=self._prefill_loop, name="continuous-prefill", daemon=True)
        self._prefill_thread.start()
        self._thread = threading.Thread(target=self._loop,
                                        name="continuous-decode", daemon=True)
        self._thread.start()

    # -- compiled stages -------------------------------------------------------

    def _prefill(self):
        """Standalone prompt forward for one request: touches NO shared
        state, so the prefill thread can run it concurrently with the
        decode thread's chunks. Returns (last-token logits (V,), the
        request's own (L, 1, pb, H, D) KV block). One jitted fn — distinct
        prompt-bucket widths recompile automatically."""
        if self._prefill_exe is not None:
            return self._prefill_exe
        with self._exe_lock:
            if self._prefill_exe is None:
                cfg, dtype = self.cfg, self._dtype

                def prefill_one(params, tokens, attn_mask, pos_ids):
                    row_caches = init_caches(cfg, 1, tokens.shape[1], dtype)
                    logits, row_caches = transformer_prefill(
                        params, tokens, row_caches, cfg, dtype=dtype,
                        attn_mask=attn_mask, pos_ids=pos_ids)
                    return logits[0], row_caches

                self._prefill_exe = jax.jit(prefill_one)
            return self._prefill_exe

    def _window(self):
        """One prefill window: consume W prompt tokens against the
        request's own (1, pb) cache via transformer_decode_window —
        semantically identical to the same slice of a monolithic causal
        prefill (write-before-attend + kpos <= col masking), but each
        window is its own dispatch, so the decode thread's chunks slot in
        between. Returns (logits (1, W, V), caches)."""
        if self._window_exe is not None:
            return self._window_exe
        with self._exe_lock:
            if self._window_exe is None:
                cfg, dtype = self.cfg, self._dtype

                def window(params, tokens, caches, pos0, start, head):
                    return transformer_decode_window(
                        params, tokens, caches, pos0, cfg, dtype=dtype,
                        start_vec=start, head=head)

                self._window_exe = jax.jit(window, donate_argnums=(2,),
                                           static_argnums=(5,))
            return self._window_exe

    def _insert(self, with_counts: bool):
        """Row insertion into the shared cache — decode-thread only (the
        only compiled stage besides decode that owns/donates the shared
        KV buffer). Two variants: only admissions that carry penalty/stop
        state also splice their token-count row (distinct pb block widths
        recompile automatically)."""
        exe = self._insert_exe.get(with_counts)
        if exe is not None:
            return exe
        with self._exe_lock:
            if with_counts not in self._insert_exe:

                def insert_kv(caches, row_k, row_v, row):
                    k = jax.lax.dynamic_update_slice(
                        caches.k, row_k.astype(caches.k.dtype),
                        (0, row, 0, 0, 0))
                    v = jax.lax.dynamic_update_slice(
                        caches.v, row_v.astype(caches.v.dtype),
                        (0, row, 0, 0, 0))
                    return type(caches)(k, v)

                if with_counts:
                    def insert_row(caches, row_k, row_v, row, counts,
                                   row_counts):
                        counts = jax.lax.dynamic_update_slice(
                            counts, row_counts[None, :], (row, 0))
                        return insert_kv(caches, row_k, row_v, row), counts

                    self._insert_exe[True] = jax.jit(
                        insert_row, donate_argnums=(0, 4))
                else:
                    self._insert_exe[False] = jax.jit(
                        insert_kv, donate_argnums=(0,))
            return self._insert_exe[with_counts]

    def _ensure_counts(self):
        if self._counts is None:
            counts = jnp.zeros((self.n_slots, self.cfg.vocab), jnp.int32)
            if self._device is not None:
                counts = jax.device_put(counts, self._device)
            self._counts = counts
        return self._counts

    def _decode(self, controls: bool):
        """Compiled decode chunk. `controls` (compile-time) exists in two
        variants: the penalty/stop machinery ((B, V) counts scatter, stop
        matching) compiles only into the variant used while ANY live row
        carries a penalty or stop list — default traffic pays nothing.
        Correctness of switching: a pen=1 row's penalty is the identity
        whatever its (possibly stale) counts hold, and a penalized row
        forces the controls variant for its whole lifetime, so ITS counts
        are always maintained."""
        exe = self._decode_exe.get(controls)
        if exe is not None:
            return exe
        with self._exe_lock:
            if controls not in self._decode_exe:
                cfg, dtype, chunk = self.cfg, self._dtype, self._step_chunk

                def decode_chunk(params, caches, tok, pos, start, done,
                                 seeds, temps, topps, topks, minps,
                                 eos_vec, counts=None, pens=None,
                                 stops=None):
                    rows = jnp.arange(tok.shape[0])

                    def body(carry, _):
                        if controls:
                            caches, tok, pos, done, counts = carry
                        else:
                            caches, tok, pos, done = carry
                            counts = None
                        logits, caches = transformer_decode_rows(
                            params, tok, caches, pos, cfg, dtype=dtype,
                            start_vec=start)
                        if controls:
                            logits = apply_repetition_penalty(
                                logits, counts, pens)
                        nxt = _sample(logits, seeds, pos + 1 - start, temps,
                                      topps, topks, minps, kept=~done)
                        nxt = jnp.where(done, eos_vec, nxt)
                        if controls:
                            counts = counts.at[rows, nxt].add(
                                (~done).astype(jnp.int32))
                        done = done | (nxt == eos_vec)
                        if controls:
                            done = done | jnp.any(nxt[:, None] == stops,
                                                  axis=1)
                        # Only live rows advance their write position (and
                        # never past the last cache column).
                        pos = jnp.where(done, pos,
                                        jnp.minimum(pos + 1,
                                                    caches.k.shape[2] - 1))
                        if controls:
                            return (caches, nxt, pos, done, counts), nxt
                        return (caches, nxt, pos, done), nxt

                    if controls:
                        (caches, tok, pos, done, counts), toks = \
                            jax.lax.scan(body,
                                         (caches, tok, pos, done, counts),
                                         None, length=chunk)
                        return caches, tok, pos, done, counts, toks.T
                    (caches, tok, pos, done), toks = jax.lax.scan(
                        body, (caches, tok, pos, done), None, length=chunk)
                    return caches, tok, pos, done, toks.T

                self._decode_exe[controls] = jax.jit(
                    decode_chunk,
                    donate_argnums=(1, 12) if controls else (1,))
            return self._decode_exe[controls]

    # -- paged compiled stages -------------------------------------------------

    # Why a family served by the mixed tick alone lacks a capability.
    _TICK_ONLY_WHY = {
        "kv_latent": ("a latent block holds one latent and one rope key "
                      "a token, not a K and a V a head", "latent read"),
        "kv_windowed": ("a window layer's blocks are given back as the "
                        "row's position passes them: a freed block can "
                        "serve no prefix hit, go to no host tier and ride "
                        "no chain, and the window read takes no int8 "
                        "scales and no verify window",
                        "window read over blocks of two kinds"),
        "kv_and_state": ("a row's recurrent state is one fixed-size row, "
                         "not block-addressable, whatever its blocks hold "
                         "(K and V a head, or a latent) and whichever "
                         "layers keep it (some, or every one beside its "
                         "blocks): it serves no "
                         "prefix hit (snapshots at block boundaries are "
                         "not kept), goes to no host tier, takes no int8 "
                         "scales, cannot be rolled back past a rejected "
                         "draft and rides no chain",
                         "state row beside the block chain"),
        "kv_block_decode": ("a generating row's last block is rewritten "
                            "by every denoise pass until its commit and a "
                            "tick yields no single token a row: there is "
                            "no verify window over a run, an int8 slot "
                            "would be requantized a pass, a block in "
                            "denoising rides no chain and goes to no host "
                            "tier, and prefix reuse at block-aligned "
                            "boundaries is not wired",
                            "block-causal read of a run"),
        "kv_looped": ("the model's own step scans one set of layers "
                      "several times over a pool a plane a (pass, layer) "
                      "deep: it takes no int8 scales and no verify "
                      "window, and a chain of that depth is carried by "
                      "no tested wire format or host tier",
                      "read of a plane a pass and layer"),
    }

    def _fence_tick_only_family(self, model, fam, *, kv_host_blocks,
                                kv_quantize, spec_k,
                                prefix_sharing) -> None:
        """Start-up fences of the kv_latent, kv_windowed and kv_and_state
        families (registry FAMILY_CAPABILITIES): what the family's pool or
        pools cannot do yet is refused by name, never served wrong.
        (`tp > 1` is refused above through the model's unshardable TP
        rule.)"""
        name = f"model '{model.name}' ({fam} family)"
        why, read = self._TICK_ONLY_WHY[fam]
        if not self._paged:
            raise ValueError(
                f"{name} is served by the mixed tick over the block "
                f"pool only: set kv_block_size > 0 (the dense per-slot "
                f"cache has no {read})")
        for flag, value, cap in (
                ("kv_quantize", kv_quantize, "kv_quantize"),
                ("kv_host_blocks", int(kv_host_blocks), "kv_host_tier"),
                ("spec_k", int(spec_k), "spec_decode"),
                ("prefix_sharing", prefix_sharing, "prefix_sharing")):
            if value and not model.supports(cap):
                raise ValueError(
                    f"{flag} needs the '{cap}' capability, which {name} "
                    f"does not declare: {why}")

    def _refuse_chain(self, what: str) -> Optional[str]:
        """The chain wire format carries every block of a row, a K and a
        V of H_kv*D lanes each, and nothing else of the row: migration,
        handoff and prefix fetch refuse for a latent pool, for one that
        frees window blocks and for a row that also owns a state row."""
        fam = getattr(self.spec, "state_family", None)
        if fam not in self._TICK_ONLY_WHY:
            return None
        return (f"{what} needs the 'migration' capability, which the "
                f"{fam} family does not declare (the chain wire format "
                f"carries a K and a V a head for every block of the row, "
                f"and no state row)")

    def _pin_pool_out(self, caches, scales=None):
        """TRACED helper for the pool-donating executables: constrain
        their pool (and scale) outputs to the pool's tensor-parallel
        sharding, so output sharding provably equals input sharding —
        donation holds and XLA never re-lays the pool mid-serve.
        Identity when tp == 1 (the compiled programs are unchanged
        byte-for-byte). Pool and scales carry their heads on the last
        of four axes: one spec pins both."""
        if self._kv_pin is None:
            return caches if scales is None else (caches, scales)
        wsc = jax.lax.with_sharding_constraint
        caches = KVCache(wsc(caches.k, self._kv_pin),
                         wsc(caches.v, self._kv_pin))
        if scales is None:
            return caches
        scales = KVCache(wsc(scales.k, self._kv_pin),
                         wsc(scales.v, self._kv_pin))
        return caches, scales

    def _paged_attn_fn(self):
        """The ragged paged attention read this lane's step executables
        trace: the int8 or the full-precision variant by the pool, and
        under tp > 1 run once per head shard — a Mosaic kernel cannot be
        partitioned by GSPMD, and heads are independent (no collective
        inside)."""
        from tpu_engine.ops import paged_attention as pa

        fn = (pa.default_quant_ragged_attention() if self._quant
              else pa.default_ragged_attention())
        if self._tp_mesh is not None:
            fn = pa.shard_over_heads(fn, self._tp_mesh)
        return fn

    def _tick_block(self, width: int, controls: bool) -> TickBlock:
        """This lane's control block layout at a tick's `width`, with or
        without the `controls` fields."""
        layout = self._tick_blocks.get((width, controls))
        if layout is None:
            tables = [self._tables] + ([self._wtables] if self._windowed
                                       else [])
            layout = self._tick_blocks[(width, controls)] = TickBlock(
                width, [t.shape[1] for t in tables],
                state_rows=self._hybrid, controls=controls,
                reveal=self._block is not None)
        return layout

    def _mixed_step_exe(self, width: int, controls: bool):
        """Compiled mixed step: ONE ragged dispatch serving decode rows
        (q_len 1) and prefill-chunk rows (q_len up to `width`) together —
        forward, KV pool writes, and sampling fused. The host's per-row
        inputs arrive as one control block (`TickBlock`, which states
        the layout) and are taken apart here; of them
        `sample_slot` picks the logits slot to sample (decode: 0;
        completing prefill: L-1-pos0), `fold_pos` is the sampled token's
        logical position (the fold_in(seed, position) rule every path
        shares), `active` marks rows whose sample is REAL this tick
        (mid-prompt rows ride along without emitting or touching
        counts). `prev_nxt` and `prev_done` are the step before's own
        `nxt` and `done`, still on the device, and `from_prev` the rows
        that take them: such a row's input token is the one that step
        sampled, which the host has not read yet, and it rides as a done
        row if that token ended it. They are data: exactly two widths
        compile per controls variant (1 and the chunk cap)."""
        key = ("mixed", width, controls)
        exe = self._decode_exe.get(key)
        if exe is not None:
            return exe
        with self._exe_lock:
            if key not in self._decode_exe:
                cfg, dtype = self.cfg, self._dtype
                quant = self._quant
                own_step, held = self._ragged_step, self._held_experts
                max_tokens = self._tick_max_tokens
                if own_step is None:
                    attn_fn = self._paged_attn_fn()

                layout = self._tick_block(width, controls)
                hybrid = self._hybrid
                blockwise = self._block
                if blockwise is not None and (controls or own_step is None):
                    raise RuntimeError(
                        "a block-decoding lane steps by its model's own "
                        "ragged step, without the controls variant")

                def step_core(params, caches, scales, block, prev_nxt,
                              prev_done, counts):
                    with step_part("plan"):
                        f = layout.unpack(block)
                        # The rows' table; one of each a kind of block on
                        # a windowed lane, (table, state rows) on a hybrid
                        # one.
                        tables = f["tables"]
                        if len(tables) == 1:
                            tables, = tables
                        if hybrid:
                            tables = (tables, f["state_rows"])
                        pos0, qlen, sample_slot, eos_vec = (
                            f["pos0"], f["qlen"], f["sample_slot"],
                            f["eos_vec"])
                        if blockwise is not None:
                            # A generating row's step is its block,
                            # carried on the device from pass to pass; the
                            # head reads the run's slots of every row.
                            tokens, blk, done = take_block_from_prev(
                                f["tokens"], f["done"], prev_nxt,
                                prev_done, f["from_prev"],
                                blockwise.block_length, blockwise.mask_id)
                            sample_slot = jnp.broadcast_to(
                                jnp.arange(blockwise.block_length)[None, :],
                                blk.shape)
                        else:
                            tokens, done = take_from_prev(
                                f["tokens"], f["done"], prev_nxt, prev_done,
                                f["from_prev"])
                    # sample_slot gathers the hidden state BEFORE the LM
                    # head: one (B, vocab) projection per tick, not W.
                    if own_step is not None:
                        # `caches` and `tables`: the pool's pair and the
                        # rows' table, or one of each a kind of block.
                        logits, caches, moe_rows = own_step(
                            params, tokens, caches, tables, pos0, qlen,
                            cfg, dtype=dtype, sample_slot=sample_slot,
                            held=held, max_tokens=max_tokens)
                    elif quant:
                        logits, caches, scales = \
                            transformer_step_rows_ragged(
                                params, tokens, caches, tables, pos0,
                                qlen, cfg, dtype=dtype, attn_fn=attn_fn,
                                sample_slot=sample_slot, scales=scales,
                                max_tokens=max_tokens)
                    else:
                        logits, caches = transformer_step_rows_ragged(
                            params, tokens, caches, tables, pos0, qlen,
                            cfg, dtype=dtype, attn_fn=attn_fn,
                            sample_slot=sample_slot, max_tokens=max_tokens)
                    if blockwise is not None:
                        # A denoise pass: a proposal and its confidence at
                        # each of the run's positions, of which the rule
                        # reveals some; a commit pass, a chunk and a free
                        # row leave the block as it came. The host reads
                        # the block's end (EOS, a stop token) off the
                        # block itself.
                        with step_part("sample"):
                            live = f["active"] & ~done
                            x0, conf = sample_block(
                                logits, f["seeds"], pos0, f["temps"],
                                f["topps"], f["topks"], f["minps"], live)
                        with step_part("sample/reveal"):
                            nxt = reveal_block(
                                blk, x0, conf,
                                jnp.where(live, f["reveal"], 0),
                                blockwise.reveal, blockwise.threshold)
                        return (self._pin_pool_out(caches), nxt, done,
                                moe_rows)
                    with step_part("sample"):
                        rows = jnp.arange(tokens.shape[0])
                        if controls:
                            logits = apply_repetition_penalty(
                                logits, counts, f["pens"])
                        # The sampler's body is chosen by the rows whose
                        # sample is real: a released slot's controls stay
                        # where admission put them.
                        live = f["active"] & ~done
                        nxt = _sample(logits, f["seeds"], f["fold_pos"],
                                      f["temps"], f["topps"], f["topks"],
                                      f["minps"], kept=live)
                        nxt = jnp.where(live, nxt, eos_vec)
                        if controls:
                            counts = counts.at[rows, nxt].add(
                                live.astype(jnp.int32))
                        done = done | (live & (nxt == eos_vec))
                        if controls:
                            done = done | (live & jnp.any(
                                nxt[:, None] == f["stops"], axis=1))
                    if quant:
                        caches, scales = self._pin_pool_out(caches,
                                                            scales)
                    else:
                        caches = self._pin_pool_out(caches)
                    out = (caches,) + ((scales,) if quant else ())
                    out += (nxt, done)
                    if controls:
                        out += (counts,)
                    if own_step is not None:
                        out += (moe_rows,)
                    return out

                if quant:
                    def mixed_step(params, caches, scales, block, prev_nxt,
                                   prev_done, counts=None):
                        return step_core(params, caches, scales, block,
                                         prev_nxt, prev_done, counts)
                    donate = (1, 2, 6) if controls else (1, 2)
                else:
                    def mixed_step(params, caches, block, prev_nxt,
                                   prev_done, counts=None):
                        return step_core(params, caches, None, block,
                                         prev_nxt, prev_done, counts)
                    donate = (1, 5) if controls else (1,)
                # As the tick's span says it: a block-decoding lane's
                # narrow tick is `run` slots wide and reads width 1.
                run = blockwise.block_length if blockwise else 1
                mixed_step.__name__ = tick_name(
                    1 if width == run else width, run)
                self._decode_exe[key] = jax.jit(mixed_step,
                                                donate_argnums=donate)
            return self._decode_exe[key]

    def _spec_step_exe(self, width: int, controls: bool,
                       stochastic: bool = False):
        """Compiled speculative step: ONE ragged dispatch scoring every
        row's verify window — decode rows carry [pending token, draft_1..
        draft_n] (q_len = n+1), mixed-mode admitting rows their prefill
        chunk — then an unrolled spec_k+1-slot accept/emit loop over the
        window's per-position logits (`transformer_step_rows_ragged`
        sample_width). Slot j's logits are conditioned on the draft
        prefix, which equals the true stream exactly while the chain
        holds, so:

        - deterministic rows (temperature 0 — penalties, stops, and
          filter knobs included) re-derive each token with the exact
          plain-path `_sample(fold_in(seed, position))` rule and chain
          while the draft matches it: byte-identical streams for any
          draft, counts evolving sequentially inside the window;
        - temperature>0 rows with n_draft > 0 take the shared
          rejection-sampling rule against the deterministic proposal
          (accept d with prob p(d), residual = p minus d's mass —
          `runtime.speculative.rejection_acceptance` with a point-mass
          q), unbiased but not byte-equal;
        - completing prefill rows (n_draft 0, sample_slot = L-1-w0) fall
          out as the j=0 iteration — the same single sample the plain
          mixed step takes.

        Rows emit 1..spec_k+1 tokens; EOS/stop hits stop the chain and
        later slots emit eos_vec. Exactly two ragged widths compile per
        (controls, stochastic) variant (spec_k+1 and max(chunk cap,
        spec_k+1)); `stochastic` is a COMPILE-TIME flag like `controls`
        — the all-greedy common case never traces the per-slot (B, V)
        softmax + tagged uniform/categorical draws whose results it
        would discard."""
        key = ("spec", width, controls, stochastic)
        exe = self._decode_exe.get(key)
        if exe is not None:
            return exe
        with self._exe_lock:
            if key not in self._decode_exe:
                from tpu_engine.runtime.speculative import (
                    _TAG_ACCEPT,
                    _TAG_RESID,
                    _tagged_categorical,
                    _tagged_uniform,
                )

                cfg, dtype = self.cfg, self._dtype
                quant = self._quant
                attn_fn = self._paged_attn_fn()
                S = self._spec_k + 1

                def spec_core(params, caches, scales, tables, tokens,
                              pos0, qlen, sample_slot, fold0, n_draft,
                              stoch, active, done, seeds, temps, topps,
                              topks, minps, eos_vec, counts, pens, stops):
                    if quant:
                        logits, caches, scales = \
                            transformer_step_rows_ragged(
                                params, tokens, caches, tables, pos0,
                                qlen, cfg, dtype=dtype, attn_fn=attn_fn,
                                sample_slot=sample_slot, sample_width=S,
                                scales=scales)
                    else:
                        logits, caches = transformer_step_rows_ragged(
                            params, tokens, caches, tables, pos0, qlen,
                            cfg, dtype=dtype, attn_fn=attn_fn,
                            sample_slot=sample_slot, sample_width=S)
                    with step_part("sample"):
                        b, w = tokens.shape
                        rows = jnp.arange(b)
                        run_counts = counts
                        alive = active & ~done
                        new_done = done
                        n_emit = jnp.zeros((b,), jnp.int32)
                        # Draft slots whose token the target kept (the chain
                        # held). Counted on-device because the host cannot
                        # infer it from n_emit alone: a stream that stops ON
                        # an accepted draft token has no corrected/bonus
                        # slot, so "emitted - 1" would undercount.
                        n_acc = jnp.zeros((b,), jnp.int32)
                        use_sto = stoch & (n_draft > 0)
                        t_safe = jnp.maximum(temps, 1e-6)
                        emitted = []
                        for j in range(S):
                            lg = logits[:, j]
                            lg_p = (apply_repetition_penalty(lg, run_counts,
                                                             pens)
                                    if controls else lg)
                            fold = fold0 + j
                            det = _sample(lg_p, seeds, fold, temps, topps,
                                          topks, minps, kept=alive)
                            # The draft token this slot must reproduce for
                            # the chain to continue (decode rows: window slot
                            # j+1; prefill/undrafted rows never chain).
                            didx = jnp.minimum(sample_slot + j + 1, w - 1)
                            d_next = tokens[rows, didx]
                            has_draft = j < n_draft
                            det_chain = has_draft & (d_next == det)
                            if stochastic:
                                # Rejection sampling vs the point-mass
                                # proposal, for temp>0 drafted rows.
                                p = jax.nn.softmax(lg / t_safe[:, None],
                                                   axis=-1)
                                u = _tagged_uniform(seeds, fold, _TAG_ACCEPT)
                                acc = has_draft & (u < p[rows, d_next])
                                resid = p.at[rows, d_next].set(0.0)
                                resid = jnp.where(has_draft[:, None],
                                                  resid, p)
                                tot = jnp.sum(resid, axis=-1, keepdims=True)
                                dist = jnp.where(
                                    tot > 0,
                                    resid / jnp.maximum(tot, 1e-30), p)
                                corr = _tagged_categorical(
                                    seeds, fold, _TAG_RESID,
                                    jnp.log(jnp.maximum(dist, 1e-30)))
                                sto_tok = jnp.where(acc, d_next, corr)
                                tok_j = jnp.where(use_sto, sto_tok, det)
                                chain = jnp.where(use_sto, acc, det_chain)
                            else:
                                tok_j = det
                                chain = det_chain
                            tok_j = jnp.where(alive, tok_j, eos_vec)
                            if controls:
                                run_counts = run_counts.at[rows, tok_j].add(
                                    alive.astype(jnp.int32))
                            emitted.append(tok_j)
                            n_emit = n_emit + alive.astype(jnp.int32)
                            n_acc = n_acc + (alive & chain).astype(jnp.int32)
                            stop_j = alive & (tok_j == eos_vec)
                            if controls:
                                stop_j = stop_j | (alive & jnp.any(
                                    tok_j[:, None] == stops, axis=1))
                            new_done = new_done | stop_j
                            alive = alive & ~stop_j & chain
                        out = jnp.stack(emitted, axis=1)          # (B, S)
                    if quant:
                        caches, scales = self._pin_pool_out(caches,
                                                            scales)
                    else:
                        caches = self._pin_pool_out(caches)
                    res = (caches,) + ((scales,) if quant else ())
                    res += (out, n_emit, n_acc, new_done)
                    if controls:
                        res += (run_counts,)
                    return res

                if quant:
                    def spec_step(params, caches, scales, tables, tokens,
                                  pos0, qlen, sample_slot, fold0, n_draft,
                                  stoch, active, done, seeds, temps,
                                  topps, topks, minps, eos_vec,
                                  counts=None, pens=None, stops=None):
                        return spec_core(params, caches, scales, tables,
                                         tokens, pos0, qlen, sample_slot,
                                         fold0, n_draft, stoch, active,
                                         done, seeds, temps, topps, topks,
                                         minps, eos_vec, counts, pens,
                                         stops)
                    donate = (1, 2, 19) if controls else (1, 2)
                else:
                    def spec_step(params, caches, tables, tokens, pos0,
                                  qlen, sample_slot, fold0, n_draft,
                                  stoch, active, done, seeds, temps,
                                  topps, topks, minps, eos_vec,
                                  counts=None, pens=None, stops=None):
                        return spec_core(params, caches, None, tables,
                                         tokens, pos0, qlen, sample_slot,
                                         fold0, n_draft, stoch, active,
                                         done, seeds, temps, topps, topks,
                                         minps, eos_vec, counts, pens,
                                         stops)
                    donate = (1, 18) if controls else (1,)
                spec_step.__name__ = tick_name(width, kind="spec")
                self._decode_exe[key] = jax.jit(spec_step,
                                                donate_argnums=donate)
            return self._decode_exe[key]

    # -- state-slab compiled stages (the state_slab family's step fns) ---------
    #
    # The SSD family's autoregressive step is models.ssd.ssd_step_rows —
    # an O(1) recurrence per row instead of a KV-cache read. The stages
    # below thread (and donate) the slab pool exactly like the paged
    # stages thread the block pool, and the mixed body reuses
    # the SAME sampling/penalty/stop logic (fold_in(seed, position)), so
    # streams are family-portable in every property the scheduler
    # promises: seeded determinism, deadline cancel, crash replay,
    # migration splice, brownout.

    def _slab_zero(self):
        """Zero a freshly-allocated slab row (admission: the
        prompt's state accumulates IN the slab across ticks, so the row
        must not inherit a previous occupant's bytes)."""
        key = ("slab_zero",)
        exe = self._decode_exe.get(key)
        if exe is not None:
            return exe
        with self._exe_lock:
            if key not in self._decode_exe:
                def zero(slab, rid):
                    return slab.at[:, rid].set(0.0)

                self._decode_exe[key] = jax.jit(zero, donate_argnums=(0,))
            return self._decode_exe[key]

    def _slab_mixed_exe(self, width: int, controls: bool):
        """Compiled mixed step for the state_slab family: ONE dispatch
        per tick serving decode rows (1 recurrence step) and admitting
        rows' budgeted prefill chunks (up to `width` masked steps from
        the state carried in their slab row) — the family's
        `_mixed_step_exe`. `step_ok` marks rows whose STATE may advance
        this tick (prefilling rows and live decode rows; done and
        parked-handoff rows are frozen); `active`/`sample_slot`/
        `fold_pos` follow the paged mixed contract exactly, so the
        budget rule, brownout scaling, and stream identity carry over
        unchanged. Exactly two widths compile per controls variant
        (1 and the chunk cap)."""
        key = ("slab_mixed", width, controls)
        exe = self._decode_exe.get(key)
        if exe is not None:
            return exe
        with self._exe_lock:
            if key not in self._decode_exe:
                cfg = self.cfg

                def mixed_step(params, slab, row_ids, tokens, qlen,
                               sample_slot, fold_pos, step_ok, active,
                               done, seeds, temps, topps, topks, minps,
                               eos_vec, counts=None, pens=None,
                               stops=None):
                    rows = jnp.arange(tokens.shape[0])
                    states = unflatten_states(slab[:, row_ids], cfg)
                    # The ONE window primitive: a frozen row is simply a
                    # row with zero valid steps.
                    kept, states = ssd_window_scan(
                        params, tokens, states,
                        jnp.where(step_ok, qlen, 0), sample_slot, cfg)
                    if controls:
                        kept = apply_repetition_penalty(kept, counts,
                                                        pens)
                    live = active & ~done
                    nxt = _sample(kept, seeds, fold_pos, temps, topps,
                                  topks, minps, kept=live)
                    nxt = jnp.where(live, nxt, eos_vec)
                    if controls:
                        counts = counts.at[rows, nxt].add(
                            live.astype(jnp.int32))
                    done = done | (live & (nxt == eos_vec))
                    if controls:
                        done = done | (live & jnp.any(
                            nxt[:, None] == stops, axis=1))
                    slab = slab.at[:, row_ids].set(flatten_states(states))
                    out = (slab, nxt, done)
                    if controls:
                        out += (counts,)
                    return out

                self._decode_exe[key] = jax.jit(
                    mixed_step,
                    donate_argnums=(1, 16) if controls else (1,))
            return self._decode_exe[key]

    @staticmethod
    def _spec_eligible(req: _Request) -> bool:
        """Rows the drafter may propose for. Deterministic (greedy) rows
        always qualify — the verify loop re-derives each token with the
        exact plain-path rule, penalties/stops included, so the stream
        is byte-identical for any draft. temperature>0 rows qualify only
        without filters/penalties/stops: the rejection-sampling residual
        composes with none of them (such rows ride at q_len 1 — plain)."""
        if req.temperature == 0.0:
            return True
        return (req.top_p >= 1.0 and req.top_k == 0 and req.min_p == 0.0
                and req.rep_penalty == 1.0 and not req.stop_tokens)

    # -- public API ------------------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               eos_id: int = -1, temperature: float = 0.0, seed: int = 0,
               top_p: float = 1.0, top_k: int = 0,
               repetition_penalty: float = 1.0, stop_tokens=None,
               min_p: float = 0.0, stream=None,
               deadline: Optional[Deadline] = None,
               sink=None, tag: Optional[str] = None,
               handoff: bool = False,
               handoff_park_s: float = 5.0,
               prefix_hint: Optional[dict] = None) -> Future:
        """Enqueue one request; resolves to its generated token list.
        `stream`: optional queue.Queue — fresh token lists are pushed as
        they decode (iteration-level granularity), then a None sentinel.
        `repetition_penalty`/`stop_tokens` follow Generator.generate's
        semantics (HF-style penalty; <=8 stop ids ending the row like
        EOS). `deadline`: optional Deadline — the future resolves with
        DeadlineExceeded if it expires before prefill or mid-decode (the
        row is freed; already-streamed tokens stand). `sink`: optional
        utils.tracing.TraceSink — the scheduler records queue_wait /
        prefill / decode stage spans for this request against it.
        `handoff` (paged mode): park the row after prefill — first
        token emitted, decode ticks skipped — for up to
        `handoff_park_s` seconds awaiting an export-after-prefill
        command (export_row(wait_prefill=True)); past the park window
        the row decodes locally like any other (the colocated
        fallback). Ignored on dense layouts (nothing to export).
        `prefix_hint` (fleet prefix tier): a gateway-attached
        ``{"lane", "addr", "fingerprint", "blocks"}`` naming the peer
        whose radix tree holds the deepest known chain for this
        prompt — inert unless --prefix-fetch installed a fetch
        callable."""
        if self._stateless:
            raise RuntimeError(
                f"model '{self.spec.name}' serves the stateless "
                f"family: no generation lane (the one-shot surfaces "
                f"are submit_infer/submit_score)")
        if not self._running:
            raise RuntimeError("scheduler stopped")
        pens, stops = expand_stopping_params(1, repetition_penalty,
                                             [list(stop_tokens)]
                                             if stop_tokens else None)
        if not 0.0 <= float(min_p) <= 1.0:
            raise ValueError(f"min_p must be in [0, 1], got {min_p}")
        if self._block is not None and pens[0] != 1.0:
            raise ValueError(
                f"repetition_penalty is not served by model "
                f"'{self.spec.name}': it decodes by blocks of "
                f"{self._run} tokens and the penalty's counts are not "
                f"carried to a block's positions (stop tokens are: a "
                f"block that reveals one ends the row)")
        # Deterministic capacity clamp: the out_of_cache backstop
        # (_maybe_complete) fires only after a whole decode chunk, so a
        # row stopping THERE ends with a chunk-alignment-dependent ±1
        # tokens (L mod step_chunk differs between an uninterrupted run
        # and a (prompt ⧺ emitted) failover resume of the same stream).
        # Clamping the budget to the row's reachable capacity makes the
        # budget rule — which is exact and alignment-independent — always
        # fire first: same total wherever the stream is resumed.
        max_new_tokens = min(int(max_new_tokens),
                             max(0, self.max_seq - 1 - len(prompt)))
        req = _Request(list(prompt), int(max_new_tokens), int(eos_id),
                       float(temperature), int(seed), float(top_p),
                       clamp_top_k(top_k), rep_penalty=pens[0],
                       stop_tokens=stops[0], min_p=float(min_p),
                       stream=stream, deadline=deadline, sink=sink,
                       t_submit=time.perf_counter(),
                       tag=str(tag) if tag is not None else None,
                       prefix_hint=dict(prefix_hint)
                       if isinstance(prefix_hint, dict) else None,
                       handoff=bool(handoff) and (self._paged
                                                  or self._slab)
                       and self._block is None,
                       # Clamped: a parked row pins a slot + KV chain,
                       # so the window must stay bounded no matter what
                       # the caller passed.
                       park_s=min(300.0, max(0.1,
                                             float(handoff_park_s))))
        self._queue.put(req)
        return req.future

    # -- unified stateless serving (DESIGN.md "Unified stateless serving") -----

    @property
    def accepts_oneshot(self) -> bool:
        """True when this scheduler can serve one-shot /infer rows
        (constructed with an infer_engine)."""
        return self._infer_engine is not None

    @property
    def accepts_score(self) -> bool:
        """True when this scheduler can serve one-shot /score rows
        (constructed with a score_provider)."""
        return self._score_provider is not None

    def submit_infer(self, input_data, shape=None,
                     deadline: Optional[Deadline] = None,
                     sink=None, tag: Optional[str] = None) -> Future:
        """Enqueue ONE stateless forward as a single-tick row in the
        continuous batch: the request rides the same admission queue,
        deadline checks, brownout ladder, and tracing spans as decode
        rows, and the tick's grouped dispatch runs the model forward
        once — no KV/slab allocation. Resolves to
        ``(output_row, per_request_time_us)``; the output is
        byte-identical to InferenceEngine.batch_predict's row for the
        same co-batched inputs (the dispatch IS that engine call)."""
        if self._infer_engine is None:
            raise RuntimeError(
                "submit_infer requires an infer_engine: construct the "
                "scheduler with infer_engine=<InferenceEngine> "
                "(DESIGN.md 'Unified stateless serving')")
        if not self._running:
            raise RuntimeError("scheduler stopped")
        req = _Request([], 0, -1, 0.0, 0, 1.0, 0,
                       deadline=deadline, sink=sink,
                       t_submit=time.perf_counter(),
                       tag=str(tag) if tag is not None else None,
                       oneshot=("infer", input_data,
                                tuple(int(d) for d in shape)
                                if shape is not None else None))
        # Straight to the one-shot staging lane: the prefill thread
        # contributes nothing to a one-shot (no prompt forward), and
        # routing through _queue would strand single-tick work behind a
        # generate admission blocked on a full _ready. queue_wait span
        # and deadline check happen at drain time (_tick_stateless).
        self._oneshot_ready.put(req)
        return req.future

    def submit_score(self, prompt_tokens, completion_tokens,
                     deadline: Optional[Deadline] = None,
                     sink=None, tag: Optional[str] = None) -> Future:
        """Enqueue one teacher-forced scoring request as a single-tick
        row (per-token log P(completion | prompt), one forward). On a
        generative lane this shares the decode rows' slot pool — one
        scheduler, one capacity pool, one set of counters. Resolves to
        ``(logprobs, per_request_time_us)``."""
        if self._score_provider is None:
            raise RuntimeError(
                "submit_score requires a score_provider: construct "
                "the scheduler with score_provider=<callable returning "
                "a scoring Generator>")
        if self._block is not None:
            raise ValueError(
                f"/score is not served by model '{self.spec.name}': it "
                f"decodes by blocks, and a teacher-forced causal read "
                f"gives no log-probability of a token under its "
                f"block-causal mask")
        if not self._running:
            raise RuntimeError("scheduler stopped")
        req = _Request([], 0, -1, 0.0, 0, 1.0, 0,
                       deadline=deadline, sink=sink,
                       t_submit=time.perf_counter(),
                       tag=str(tag) if tag is not None else None,
                       oneshot=("score",
                                [int(t) for t in prompt_tokens],
                                [int(t) for t in completion_tokens]))
        self._oneshot_ready.put(req)  # see submit_infer
        return req.future

    # -- live stream migration (DESIGN.md "Live stream migration") -------------

    def export_row(self, tag: str, timeout_s: float = 10.0,
                   wait_prefill: bool = False,
                   cancel: bool = False) -> dict:
        """Quiesce and export ONE live row by its submit() tag: snapshot
        the stream state (emitted tokens, sampling key position, penalty
        counts' inputs, stop ids, remaining budget) plus its KV block
        chain (kv_blocks.export_chain — dtype-preserving, checksummed,
        generation-stamped), then END the local stream with a
        ``StreamMigratedAway`` terminal (retryable, ``migrated`` marked).
        The command runs on the DECODE thread between ticks — the
        quiesce point: no dispatch is in flight, so host row state and
        pool bytes are mutually consistent without pausing the lane.
        Thread-safe; returns ``{"ok": True, ...snapshot...}`` or
        ``{"ok": False, "reason": ...}`` (mid-prefill rows, finished
        rows, unknown tags — the caller falls back to the replay
        resume, which these cases cost nothing extra).

        ``wait_prefill`` (disaggregated serving): instead of refusing a
        row that has not finished prefill (or not yet admitted), the
        command PARKS on the decode loop and exports at the first tick
        boundary after the row's prefill completes — the
        export-after-prefill half of the steady-state prefill→decode
        handoff. Bounded by ``timeout_s``; a row that never appears
        refuses at the bound. ``cancel``: release a handoff HOLD
        instead of exporting (the orchestrator found no destination) —
        the row resumes normal decoding immediately."""
        if not (self._paged or self._slab):
            return {"ok": False,
                    "reason": "migration requires the paged KV cache"}
        refused = self._refuse_chain("row export")
        if refused:
            return {"ok": False, "reason": refused}
        if not self._running:
            return {"ok": False, "reason": "scheduler stopped"}
        fut: Future = Future()
        opts: dict = {}
        if cancel:
            opts["cancel"] = True
        elif wait_prefill:
            opts["wait_until"] = time.monotonic() + max(0.1,
                                                        float(timeout_s))
        self._migrate_q.put((str(tag), fut, opts))
        try:
            return fut.result(timeout=timeout_s + 1.0)
        except Exception as exc:
            return {"ok": False, "reason": f"export failed: {exc}"}

    def submit_import(self, snapshot: dict, stream=None,
                      deadline: Optional[Deadline] = None, sink=None,
                      tag: Optional[str] = None) -> Future:
        """Adopt an exported row MID-STREAM: the chain's KV bytes enter
        free blocks verbatim (radix re-adopt where this lane already
        caches a prompt prefix) and decoding resumes at the exported
        position — ZERO re-prefilled tokens. Byte-identity with an
        uninterrupted run follows from the same positional-fold argument
        as the PR 6 replay resume (sampling keys fold on absolute
        position; penalties/stops recompute from prompt ⧺ emitted) plus
        the verbatim KV bytes. Raises ValueError on a malformed snapshot
        (wire 400, before any stream commits); recoverable refusals —
        checksum, geometry, pool pressure — resolve the future with
        ``ImportRefused`` (retryable → the gateway's replay fallback)."""
        if not self._running:
            raise RuntimeError("scheduler stopped")
        if not (self._paged or self._slab):
            raise ValueError("migration import requires the paged KV "
                             "cache (kv_block_size > 0)")
        refused = self._refuse_chain("migration import")
        if refused:
            raise ValueError(refused)
        if not isinstance(snapshot, dict):
            raise ValueError("migration snapshot must be an object")
        missing = [k for k in ("prompt", "emitted", "pos", "tok",
                               "max_new", "chain") if k not in snapshot]
        if missing:
            raise ValueError(f"migration snapshot missing {missing}")
        stop_list = [int(t) for t in snapshot.get("stop_tokens", ())]
        pens, stops = expand_stopping_params(
            1, float(snapshot.get("repetition_penalty", 1.0)),
            [stop_list] if stop_list else None)
        emitted = [int(t) for t in snapshot["emitted"]]
        req = _Request(
            [int(t) for t in snapshot["prompt"]],
            int(snapshot["max_new"]), int(snapshot.get("eos_id", -1)),
            float(snapshot.get("temperature", 0.0)),
            int(snapshot.get("seed", 0)),
            float(snapshot.get("top_p", 1.0)),
            clamp_top_k(snapshot.get("top_k", 0)),
            rep_penalty=pens[0], stop_tokens=stops[0],
            min_p=float(snapshot.get("min_p", 0.0)),
            stream=stream, deadline=deadline, sink=sink,
            t_submit=time.perf_counter(),
            tag=str(tag) if tag is not None else None)
        req.migrate = snapshot
        # Tokens the source already delivered: the continuation stream
        # pushes only what comes AFTER them.
        req.streamed = min(int(snapshot.get("streamed", len(emitted))),
                           len(emitted))
        self._queue.put(req)
        return req.future

    # -- fleet prefix tier (DESIGN.md "Fleet-wide prefix tier") ----------------

    def export_prefix(self, tokens: Sequence[int],
                      max_blocks: Optional[int] = None) -> dict:
        """Serialize the longest radix chain matching ``tokens`` for a
        peer lane's fetch (/admin/export_prefix): ``chain_nodes`` +
        ``export_chain`` under ONE pool-lock acquisition — eviction
        only runs inside alloc under the same lock, so the chain needs
        no pins, no promotion, no LRU stamping. Device-resident and
        host-demoted nodes serialize alike (the host tier reads its
        slab directly); NO stream state ships — this is a cache read,
        not a migration. Refusals return ``{"ok": False, "reason"}``
        and never raise (the fetching peer falls back to local
        prefill)."""
        refused = self._refuse_chain("prefix export")
        if refused:
            return {"ok": False, "reason": refused}
        if not self._paged or not self._prefix_sharing:
            return {"ok": False,
                    "reason": "prefix export requires the paged KV "
                              "cache with prefix sharing on"}
        if not self._running:
            return {"ok": False, "reason": "scheduler stopped"}
        toks = [int(t) for t in tokens]
        pool = self._pool
        with pool.lock:
            nodes = pool.radix.chain_nodes(toks)
            if max_blocks is not None:
                nodes = nodes[:max(0, int(max_blocks))]
            if not nodes:
                return {"ok": False, "reason": "no matching prefix chain"}
            chain = pool.export_chain(nodes)
        return {"ok": True, "blocks": len(nodes), "chain": chain}

    def prefix_fingerprints(self, top_k: int = 8,
                            max_tokens: int = 256) -> List[dict]:
        """Bounded top-K radix chain summaries (deepest first) for the
        gateway prober's directory seed — ``{"tokens", "blocks"}``
        entries, never a full-tree dump. Empty off the paged/sharing
        layouts."""
        if not self._paged or not self._prefix_sharing:
            return []
        pool = self._pool
        with pool.lock:
            return pool.radix.top_chains(top_k=top_k, max_tokens=max_tokens)

    # -- disaggregated handoff holds (DESIGN.md "Disaggregated serving") -------

    def _handoff_stats(self) -> dict:
        """The additive ``handoff`` stats block, created on first touch
        (defaults-off /stats and /health bytes stay identical). Bumps
        hold ``_stats_lock`` like the migration block."""
        h = self._stats.get("handoff")
        if h is None:
            h = self._stats["handoff"] = {
                "holds": 0, "park_expired": 0, "hold_cancelled": 0,
            }
        return h

    def _bump_handoff(self, field: str, n: int = 1) -> None:
        with self._stats_lock:
            self._handoff_stats()[field] += n

    def _maybe_hold(self, row: int, req: _Request) -> None:
        """Park a handoff row that just finished prefill (decode
        thread): the slot keeps its first token and KV chain but skips
        decode ticks until the export command arrives or the park
        window passes. A row that already completed (EOS/budget at the
        first token) has nothing to hand off."""
        if not req.handoff or self._row_req[row] is not req:
            return
        if req.tag is not None and req.tag in self._hold_cancel_tags:
            # The orchestrator cancelled while the row was still
            # queued/prefilling: skip the park entirely.
            self._hold_cancel_tags.remove(req.tag)
            self._bump_handoff("hold_cancelled")
            return
        self._held[row] = True
        req.park_until = time.monotonic() + req.park_s
        self._bump_handoff("holds")

    def _unpark_expired(self) -> None:
        """Decode loop, once per iteration: a held row whose park window
        passed resumes normal decoding — the colocated fallback when the
        gateway's export never came (orchestrator death, cancelled
        handoff race). The relayed stream simply continues from the
        source lane, byte-identical to an undisaggregated run."""
        now = time.monotonic()
        for r, req in enumerate(self._row_req):
            if req is not None and self._held[r] and now >= req.park_until:
                self._held[r] = False
                self._bump_handoff("park_expired")

    def _migration_stats(self) -> dict:
        """The additive ``migration`` stats block, created on first
        touch (defaults-off /stats and /health bytes stay identical).
        All bumps hold ``_stats_lock``: exports/imports land on the
        decode thread but checksum rejections on the prefill thread."""
        m = self._stats.get("migration")
        if m is None:
            m = self._stats["migration"] = {
                "exported_rows": 0, "exported_tokens": 0,
                "imported_rows": 0, "imported_tokens": 0,
                "imported_chain_tokens": 0, "import_rejected": 0,
                "export_refused": 0,
            }
        return m

    def _bump_migration(self, field: str, n: int = 1) -> None:
        with self._stats_lock:
            self._migration_stats()[field] += n

    def _prefix_fetch_stats(self) -> dict:
        """The additive ``prefix_fetch`` stats block (fleet prefix
        tier), created on first touch — defaults-off /stats and
        /health bytes stay identical. Every bump holds ``_stats_lock``
        (attempts land on the prefill thread, scrapes anywhere). One
        ``prefix_fetch`` stage span is recorded per attempt
        (counters==spans: ``attempted`` equals the span count)."""
        p = self._stats.get("prefix_fetch")
        if p is None:
            p = self._stats["prefix_fetch"] = {
                "attempted": 0, "spliced": 0, "blocks_spliced": 0,
                "prefill_tokens_skipped_remote": 0,
                "peer_unreachable": 0, "peer_refused": 0, "timeout": 0,
                "inflight_capped": 0, "checksum_failed": 0,
                "geometry_mismatch": 0, "stale_generation": 0,
                "pool_full": 0, "no_gain": 0,
            }
        return p

    def _bump_prefix_fetch(self, field: str, n: int = 1) -> None:
        with self._stats_lock:
            self._prefix_fetch_stats()[field] += n

    def _serve_exports(self) -> None:
        """Drain pending export commands — called by the decode loop at
        the top of every iteration (the tick boundary). Commands whose
        row has not finished prefill yet (wait_prefill, the
        disaggregated handoff shape) re-park until the next boundary,
        bounded by their own deadline. A command ships or releases a
        row as the last tick LEFT it, so the tick in flight is landed
        before the first is served, and that is decided here, with the
        commands in hand: `export_row` puts from another thread, and a
        look at the queue before this call would miss the command that
        arrives in between."""
        pending = self._export_waiting
        self._export_waiting = []
        while True:
            try:
                pending.append(self._migrate_q.get_nowait())
            except queue.Empty:
                break
        if pending:
            self._drain_tick()
        for tag, fut, opts in pending:
            if fut.done():
                continue
            try:
                if opts.get("cancel"):
                    result = self._cancel_hold(tag)
                else:
                    result = self._do_export(tag, opts)
            except Exception as exc:  # never kill the loop over an export
                result = {"ok": False, "reason": f"export failed: {exc}"}
            if result is None:  # row not exportable YET: re-check next tick
                self._export_waiting.append((tag, fut, opts))
                continue
            if not fut.done():
                fut.set_result(result)

    def _cancel_hold(self, tag: str) -> dict:
        """Release a handoff hold (the orchestrator is not coming): the
        row resumes normal decoding at the next tick. A row that has
        not PARKED yet (still queued or prefilling) has its future park
        cancelled instead — it must never wait out a window nobody will
        collect. ok:False — there is no snapshot; ``cancelled`` reports
        whether a hold existed or was pre-empted."""
        row = next((r for r, req in enumerate(self._row_req)
                    if req is not None and req.tag == tag), None)
        if row is not None:
            req = self._row_req[row]
            was_held = self._held[row]
            self._held[row] = False
            cancelled = was_held or req.handoff
            req.handoff = False  # mixed mid-prefill: skip the park too
            if cancelled:
                self._bump_handoff("hold_cancelled")
            return {"ok": False, "cancelled": cancelled,
                    "reason": "handoff hold cancelled" if cancelled
                    else "no held row with this tag"}
        # Not admitted yet: remember the cancel so _maybe_hold skips
        # the park when the row finally lands.
        if tag not in self._hold_cancel_tags:
            self._hold_cancel_tags.append(tag)
        return {"ok": False, "cancelled": False,
                "reason": "no live row with this tag; park pre-cancelled"}

    def _do_export(self, tag: str, opts: Optional[dict] = None) -> dict:
        """Decode-thread half of export_row (the row is quiescent here:
        `_serve_exports` lands the tick in flight before it calls this).
        On success the row is GONE from this lane:
        stream flushed + ended with StreamMigratedAway, blocks released
        (radix-shared prefix blocks survive in the tree), slot freed.
        Returns None when a ``wait_until``-carrying command must re-park
        (row still queued/prefilling and the bound has not passed)."""
        waiting = (opts is not None
                   and opts.get("wait_until") is not None
                   and time.monotonic() < opts["wait_until"])
        row = next((r for r, req in enumerate(self._row_req)
                    if req is not None and req.tag == tag), None)
        if row is None:
            if waiting:
                return None  # not admitted yet (queued or prefilling)
            return {"ok": False, "reason": "no live row with this tag"}
        req = self._row_req[row]
        if self._prefilling[row]:
            if waiting:
                return None  # prefill chunks still running
            # Nothing emitted yet — a replay resume re-prefills exactly
            # what an import would have to ship; refusing is free.
            self._bump_migration("export_refused")
            return {"ok": False, "reason": "row is mid-prefill"}
        if self._done[row]:
            self._bump_migration("export_refused")
            return {"ok": False, "reason": "row already finishing"}
        pos = int(self._pos[row])
        # Cross-lane trace stitching (gated on the worker's
        # --trace-stitch AND the request actually being traced): the
        # snapshot carries the row's trace context so the importing
        # lane re-parents its spans under the SAME trace, and the KV
        # chain carries the matching telemetry header. Both additive;
        # un-stitched exports keep today's wire bytes exactly.
        trace_hdr = None
        if self.trace_stitch and req.sink is not None:
            trace_hdr = {"trace_id": req.sink.ctx.trace_id,
                         "parent_id": req.sink.ctx.span_id}
        if self._slab:
            # The whole autoregressive state is ONE slab row — it ships
            # as a one-pseudo-block chain over the same wire format, so
            # the gateway's drain/migration/handoff orchestration needs
            # no family awareness at all.
            t0 = time.perf_counter()
            with self._spool.lock:
                chain = self._spool.export_row_chain(
                    self._slab_rows[row])
            if trace_hdr is not None:
                chain = dict(chain, trace=trace_hdr)
            if req.sink is not None:
                dur_us = (time.perf_counter() - t0) * 1e6
                req.sink.stage("state_export", dur_us,
                               start_ts=time.time() - dur_us / 1e6,
                               state_bytes=self._spool.bytes_per_row())
            prompt = list(req.prompt)
        else:
            pool = self._pool
            bs = pool.block_size
            n_chain = (pos - 1) // bs + 1 if pos > 0 else 0
            with pool.lock:
                chain = pool.export_chain(self._row_blocks[row][:n_chain],
                                          trace=trace_hdr)
            # The bucket-truncated prompt is what the row's 0-aligned
            # columns actually hold (same formula as admission).
            pb = next((b for b in self._prompt_buckets
                       if b >= len(req.prompt)), self._prompt_buckets[-1])
            prompt = req.prompt[-pb:]
        emitted = list(self._row_emitted[row])
        # Flush everything visible BEFORE the terminal, so the relayed
        # stream and the snapshot agree on the resume offset.
        self._push_stream(row, req)
        snap = {
            "ok": True, "tag": tag,
            "prompt": [int(t) for t in prompt],
            "emitted": [int(t) for t in emitted],
            "streamed": int(req.streamed),
            "pos": pos, "tok": int(self._tok[row]),
            "max_new": int(req.max_new), "eos_id": int(req.eos_id),
            "temperature": float(req.temperature), "seed": int(req.seed),
            "top_p": float(req.top_p), "top_k": int(req.top_k),
            "min_p": float(req.min_p),
            "repetition_penalty": float(req.rep_penalty),
            "stop_tokens": [int(t) for t in req.stop_tokens],
            "chain": chain,
        }
        if trace_hdr is not None:
            # The importing worker parses this exactly like a request
            # traceparent (TraceContext.from_request), so the resumed
            # row's spans join the exporting row's trace tree. Additive:
            # submit_import tolerates unknown snapshot keys.
            snap["traceparent"] = req.sink.ctx.to_traceparent()
        exc = StreamMigratedAway(
            f"stream migrated off this lane after {req.streamed} tokens",
            tokens_emitted=req.streamed)
        self._fail_request(req, exc)
        self._row_req[row] = None
        self._row_emitted[row] = []
        self._done[row] = True
        self._release_row_blocks(row)
        self._clear_mixed_row(row)
        with self._stats_lock:
            m = self._migration_stats()
            m["exported_rows"] += 1
            m["exported_tokens"] += len(emitted)
        return snap

    def generate(self, prompts, max_new_tokens: int = 32, eos_id: int = -1,
                 temperature=0.0, seed=0, top_p=1.0, top_k=0,
                 repetition_penalty=1.0, stop_tokens=None,
                 min_p=0.0) -> List[List[int]]:
        """Blocking convenience over submit() (Generator-compatible)."""
        n = len(prompts)
        temps, seeds, topps, topks, minps = expand_sampling_params(
            n, temperature, seed, top_p, top_k, min_p)
        pens, stops = expand_stopping_params(n, repetition_penalty,
                                             stop_tokens)
        futs = [self.submit(p, max_new_tokens, eos_id, temps[i], seeds[i],
                            topps[i], topks[i], pens[i], stops[i],
                            minps[i])
                for i, p in enumerate(prompts)]
        return [f.result(timeout=600) for f in futs]

    def _place_step_params(self) -> None:
        """Build `_step_params`, the tree every compiled step of this
        lane is handed: what the model's family declares
        (`ModelSpec.step_weights`: the dense transformer's kernels cast
        to the lane's dtype once, where the step would cast them every
        tick), else `self.params` itself. `self.params` stays the master
        tree — the engine's, `/infer`'s and `/score`'s; the drafter keeps
        its own. `_weights` (stats) counts the master's bytes and the
        step tree's OWN leaves, the copies: 0 where the steps read the
        master tree itself."""
        make = getattr(self.spec, "step_weights", None)
        step = make(self.params, self._dtype) if make else self.params
        master = jax.tree.leaves(self.params)
        self._weights = {
            "master_bytes": sum(leaf.nbytes for leaf in master),
            "step_bytes": sum(
                copy.nbytes for copy, leaf
                in zip(jax.tree.leaves(step), master) if copy is not leaf),
            "step_dtype": jnp.dtype(self._dtype).name,
        }
        self._step_params = step

    def set_params(self, params) -> None:
        """Hot weight swap. The prefix cache holds (logits, KV) computed
        under the OLD weights — serving them against new weights would mix
        models mid-stream, so it empties with the swap (paged mode: the
        radix tree clears the same way; blocks still pinned by in-flight
        rows free as those rows finish). In-flight rows finish their
        current chunk on whichever params reference the chunk captured;
        subsequent chunks use the new weights (acceptable for a reload;
        stop the scheduler first for a hard cut)."""
        self.params = params
        self._place_step_params()
        self._prefix_cache = _PrefixCache(self._prefix_cache.budget)
        if self._paged:
            with self._pool.lock:
                self._pool.radix.clear()

    def set_brownout(self, budget_frac: float = 1.0,
                     suspend_spec: bool = False,
                     defer_swap_in: bool = False) -> None:
        """Apply one brownout stage's degradations (idempotent; restore
        = call with the defaults). ``budget_frac`` scales the mixed-step
        per-tick token budget (the compiled chunk cap is untouched, so
        no stage ever compiles a new executable width);
        ``suspend_spec`` stops the drafter proposing (verify windows
        collapse to plain q_len-1 rows through the same compiled
        dispatch — greedy streams byte-identical); ``defer_swap_in``
        makes radix hits on demoted prefixes stop at the resident
        prefix (counted ``swap_in_deferred``) instead of promoting."""
        self._bo_budget_frac = min(1.0, max(0.05, float(budget_frac)))
        self._bo_spec_off = bool(suspend_spec)
        self._bo_defer_swap = bool(defer_swap_in)

    def set_draining(self, draining: bool) -> None:
        """Mark the lane lame-duck (worker drain/undrain): stats() adds
        a ``drain_pressure`` gauge — live rows over slots — while set,
        the signal the elastic-fleet controller watches to see a
        retiring lane empty out. Routing/admission are the worker's
        job; the scheduler only reports."""
        self._draining_flag = bool(draining)

    def _effective_mixed_budget(self) -> int:
        """The per-tick token budget currently in force: the configured
        budget scaled by the brownout fraction (floored at 1 so the
        budget rule's admission-progress guarantee survives)."""
        f = self._bo_budget_frac
        if f >= 1.0:
            return self._mixed_budget
        return max(1, int(self._mixed_budget * f))

    def stats(self) -> dict:
        now = time.monotonic()
        busy = self._prefill_busy_since
        age = max(now - self._last_tick,
                  (now - busy) if busy is not None else 0.0)
        rows = self._row_req  # lint: lockfree-ok GIL-safe scrape snapshot
        out = dict(self._stats, n_slots=self.n_slots,
                   active=int(sum(r is not None for r in rows)),
                   last_tick_age_s=round(age, 3),
                   prefix_cache=self._prefix_cache.stats(),
                   compile=self._compiles.snapshot(),
                   gc=self._gcs.snapshot(),
                   stream=self.stream_counts.snapshot(),
                   weights=dict(self._weights))
        if self._mixed:
            # Snapshot, not the live nested dict — callers diff stats()
            # across time (bench warm-up subtraction) and must not see
            # their baseline mutate under them.
            out["mixed"] = dict(self._stats["mixed"])
        if "moe" in self._stats:
            # Gated additive block (lanes whose step routes experts).
            # `experts_touched`: (layer, expert) pairs that took at least
            # one row, summed over ticks.
            # `tilings`: the tiles each grouped product was traced with,
            # "<pairs>x<K>x<N>" -> "tm,tk,tn" ("xla": none stated).
            out["moe"] = dict(self._stats["moe"],
                              rows_by_expert=self._moe_rows.tolist(),
                              tilings=traced_tilings())
        if self._spec:
            spec = dict(self._stats["spec"])
            spec["accept_ratio"] = (
                round(spec["accepted_tokens"]
                      / max(1, spec["proposed_tokens"]), 4)
                if spec["proposed_tokens"] else None)
            spec["tokens_per_dispatch"] = (
                round(spec["emitted_tokens"] / spec["dispatches"], 3)
                if spec["dispatches"] else None)
            spec["tokens_per_row_dispatch"] = (
                round(spec["emitted_tokens"] / spec["row_ticks"], 3)
                if spec["row_ticks"] else None)
            out["spec"] = spec
        if self._oneshot:
            # Unified stateless serving (gated, additive): one-shot row
            # accounting. Snapshot under the lock — deadline_dropped is
            # bumped from the prefill thread (same rule as
            # deadline_cancelled); everything else is decode-thread-only.
            with self._stats_lock:
                out["stateless"] = dict(self._stats["stateless"])
        if self._tp > 1:
            # Additive, present ONLY on tensor-parallel lanes
            # (defaults-off /stats and /health bytes stay identical):
            # the mesh-shape label the topology-aware gateway ring
            # reads from /health.
            from tpu_engine.parallel.mesh import tp_topology_label

            out["tp"] = tp_topology_label(self._tp)
        if self._paged:
            out["kv_pool"] = self._pool.stats()
            out["kv_pool"]["pending_admissions"] = \
                len(self._pending)  # lint: lockfree-ok GIL-safe deque len
            if self._windowed:
                # Gated additive keys: blocks of each kind the rows hold
                # now, and the window blocks given back so far.
                window = self._wpool.stats()
                out["kv_pool"].update(
                    full_blocks_held=(out["kv_pool"]["blocks_total"]
                                      - out["kv_pool"]["blocks_free"]),
                    window_blocks_total=window["blocks_total"],
                    window_blocks_held=(window["blocks_total"]
                                        - window["blocks_free"]),
                    window_blocks_freed=self._wfreed)
            if self._hybrid:
                # The state pool beside the block pool; and, in the block
                # pool's own sample, the bytes of both kinds the rows
                # hold now (one reading of the two, for their ratio) and
                # the lanes a token takes in the pool's two tensors (K
                # and V a head: equal; a latent pool's differ).
                out["state_pool"] = state = self._spool.stats()
                held = (out["kv_pool"]["blocks_total"]
                        - out["kv_pool"]["blocks_free"])
                out["kv_pool"].update(
                    kv_bytes_held=held * self._pool.bytes_per_block(),
                    state_bytes_held=(state["rows_held"]
                                      * state["bytes_per_row"]),
                    block_lanes=list(self._pool.cfg.kv_lanes))
        if self._slab:
            # Gated additive block (the state_slab family's kv_pool
            # analog): a kv_paged lane's /stats and /health bytes never
            # carry this key.
            out["state_pool"] = self._spool.stats()
            out["state_pool"]["pending_admissions"] = \
                len(self._pending)  # lint: lockfree-ok GIL-safe deque len
        if "migration" in self._stats:
            # Snapshot, not the live nested dict (same rule as "mixed").
            with self._stats_lock:
                out["migration"] = dict(self._stats["migration"])
        if "handoff" in self._stats:
            # Disaggregated prefill→decode handoff holds (additive,
            # created on first hold — defaults-off bytes identical).
            with self._stats_lock:
                ho = dict(self._stats["handoff"])
            ho["held_rows"] = int(sum(  # lint: lockfree-ok GIL-safe scrape
                1 for h in self._held if h))
            out["handoff"] = ho
        if "prefix_fetch" in self._stats:
            # Fleet prefix tier fetch ladder (additive, created on the
            # first fetch attempt — defaults-off bytes identical).
            with self._stats_lock:
                out["prefix_fetch"] = dict(self._stats["prefix_fetch"])
        # Additive, present only while the lane is draining (elastic
        # fleet scale-down watch; defaults-off stats bytes unchanged):
        # live-row occupancy of a lame-duck lane — 0.0 means the drain
        # has fully emptied and removal costs nothing.
        if self._draining_flag:
            out["drain_pressure"] = round(
                out["active"] / max(1, self.n_slots), 4)
        # Additive, present only while a brownout degradation is engaged
        # (defaults-off stats bytes unchanged).
        if (self._bo_budget_frac < 1.0 or self._bo_spec_off
                or self._bo_defer_swap):
            out["brownout"] = {"budget_frac": self._bo_budget_frac,
                               "spec_suspended": self._bo_spec_off,
                               "swap_in_deferred": self._bo_defer_swap}
        # Additive, present only with the flight recorder configured
        # (defaults-off stats bytes unchanged).
        if self._flight_capacity:
            with self._flight_lock:
                ticks_recorded = len(self._flight_ring)
            fl = {"capacity": self._flight_capacity,
                  "ticks_recorded": ticks_recorded,
                  "dumps": self._flight_dumps}
            last = self._flight_last_dump
            if last is not None:
                fl["last_anomaly"] = last["anomaly"]
            out["flight"] = fl
        return out

    # -- flight recorder / bounded profiler (observability plane) -------------

    def configure_flight_recorder(self, capacity: int,
                                  dump_dir: Optional[str] = None) -> None:
        """Arm the per-tick flight recorder (serving worker, at startup —
        before traffic). capacity = ring length in ticks; 0 keeps it off
        (zero per-tick work, no /stats block)."""
        capacity = max(0, int(capacity))
        with self._flight_lock:
            self._flight_capacity = capacity
            self._flight_ring = collections.deque(maxlen=max(1, capacity))
            self._flight_dump_dir = dump_dir

    def _flight_sample(self, tick_wall_s: float) -> None:
        """One bounded per-tick record (decode thread). Everything read
        here is decode-thread-owned or a GIL-atomic scrape; the only
        lock taken is the ring's (vs /admin/timeline readers)."""
        st = self._stats
        cur = {"chunks": st.get("chunks", 0),
               "admitted": st.get("admitted", 0),
               "completed": st.get("completed", 0),
               "deadline_cancelled": st.get("deadline_cancelled", 0)}
        mixed = st.get("mixed")
        if mixed:
            cur["prefill_tokens"] = mixed["prefill_tokens"]
            cur["decode_tokens"] = mixed["decode_tokens"]
        prev, self._flight_prev = self._flight_prev, cur
        rows = self._row_req
        rec = {"ts": round(time.time(), 6),
               "tick_wall_ms": round(tick_wall_s * 1e3, 3),
               "active": int(sum(r is not None for r in rows)),
               "held": int(sum(1 for h in self._held if h)),
               "queued": self._queue.qsize(),
               "ready": self._ready.qsize()}
        for k, v in cur.items():
            rec[k] = v - prev.get(k, 0)
        if self._mixed:
            rec["parked"] = len(self._pending)
            rec["prefilling"] = int(sum(1 for p in self._prefilling if p))
        if self._paged:
            ps = self._pool.stats()
            pool = {"blocks_free": ps["blocks_free"],
                    "blocks_total": ps["blocks_total"]}
            host = ps.get("host")
            if host:
                pool["host_blocks_used"] = host["blocks_used"]
            rec["pool"] = pool
        elif self._slab:
            ss = self._spool.stats()
            rec["pool"] = {"rows_free": ss["rows_free"],
                           "rows_total": ss["rows_total"]}
        if self._draining_flag:
            rec["draining"] = True
        if self._bo_budget_frac < 1.0 or self._bo_spec_off:
            rec["brownout_budget_frac"] = self._bo_budget_frac
        with self._flight_lock:
            self._flight_ring.append(rec)
        # Deadline-miss burst: >= 4 misses inside a rolling 10 s window
        # is an anomaly worth a postmortem artifact, not just a counter.
        dmiss = rec.get("deadline_cancelled", 0)
        if dmiss:
            now_m = time.monotonic()
            self._flight_miss_window.append((now_m, dmiss))
            while (self._flight_miss_window
                   and self._flight_miss_window[0][0] < now_m - 10.0):
                self._flight_miss_window.popleft()
            if sum(n for _, n in self._flight_miss_window) >= 4:
                self._flight_miss_window.clear()
                self._flight_anomaly("deadline_miss_burst")

    def flight_dump(self, reason: str) -> Optional[dict]:
        """Force a postmortem dump (gateway degraded-fleet entry, or an
        operator via POST /admin/timeline). Returns the dump descriptor,
        or None with the recorder off."""
        return self._flight_anomaly(str(reason), force=True)

    def _flight_anomaly(self, reason: str,
                        force: bool = False) -> Optional[dict]:
        """Dump the ring as a postmortem artifact, named for the anomaly
        (_recover, deadline_miss_burst, fleet_degraded, operator).
        Rate-limited to one dump per 10 s unless forced — a crash loop
        must not turn the dump dir into its own incident."""
        if not self._flight_capacity:
            return None
        now_m = time.monotonic()
        with self._flight_lock:
            if not force and now_m - self._flight_last_dump_ts < 10.0:
                return None
            self._flight_last_dump_ts = now_m
            ring = list(self._flight_ring)
        scalars = {k: v for k, v in dict(self._stats).items()
                   if not isinstance(v, dict)}
        dump = {"anomaly": reason, "ts": time.time(),
                "node": self.trace_node, "ticks": len(ring),
                "stats": scalars, "timeline": ring}
        path = None
        if self._flight_dump_dir:
            try:
                os.makedirs(self._flight_dump_dir, exist_ok=True)
                path = os.path.join(
                    self._flight_dump_dir,
                    f"flight_{self.trace_node}_"
                    f"{int(dump['ts'] * 1e3)}_{reason}.json")
                with open(path, "w") as f:
                    json.dump(dump, f)
            except OSError:
                path = None  # telemetry must never take down serving
        last = {"anomaly": reason, "ts": dump["ts"],
                "ticks": len(ring), "path": path}
        with self._flight_lock:
            self._flight_dumps += 1
            self._flight_last_dump = last
        return last

    def flight_timeline(self, n: Optional[int] = None) -> dict:
        """The /admin/timeline payload: ring contents (newest last) plus
        dump bookkeeping. Read-side; safe from any thread."""
        with self._flight_lock:
            ring = list(self._flight_ring)
        if n:
            ring = ring[-int(n):]
        return {"enabled": bool(self._flight_capacity),
                "capacity": self._flight_capacity,
                "ticks": len(ring),
                "dumps": self._flight_dumps,
                "last_dump": self._flight_last_dump,
                "timeline": ring}

    def start_profile(self, log_dir: str, ticks: int) -> dict:
        """jax.profiler capture bounded in SCHEDULER TICKS: start the
        device trace now; the decode loop stops it after `ticks` more
        ticks (the serving loop's natural unit — one ragged dispatch per
        tick in mixed mode), so a capture brackets exactly the dispatch
        cadence the on-chip campaign wants to study."""
        from tpu_engine.utils import tracing

        res = tracing.profiler_start(log_dir)
        if res.get("ok"):
            self._profile_result = None
            self._profile_ticks_left = max(1, int(ticks))
            res["ticks"] = self._profile_ticks_left
        return res

    def stop_profile(self) -> dict:
        from tpu_engine.utils import tracing

        self._profile_ticks_left = 0
        res = tracing.profiler_stop()
        self._profile_result = res
        return res

    def profile_status(self) -> dict:
        return {"ticks_left": self._profile_ticks_left,
                "last_result": self._profile_result}

    def stop(self) -> None:
        self._running = False
        self._queue.put(None)  # wakes prefill; forwarded to decode via _ready
        self._prefill_thread.join(timeout=10)
        self._thread.join(timeout=10)
        # Post-join sweep: a prefilled item whose put landed after the
        # decode thread's exit drain would otherwise strand its caller.
        while True:
            try:
                item = self._ready.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                self._discard_item(item)
                self._fail_request(item[0], RuntimeError("scheduler stopped"))
        # One-shot staging lane: anything still queued never dispatched.
        while True:
            try:
                req = self._oneshot_ready.get_nowait()
            except queue.Empty:
                break
            self._fail_request(req, RuntimeError("scheduler stopped"))

    # -- scheduler loop --------------------------------------------------------

    def _free_rows(self) -> List[int]:
        return [r for r in range(self.n_slots) if self._row_req[r] is None]

    def _cancel_deadline(self, req: _Request, message: str) -> None:
        """Fail one request with DeadlineExceeded and count it (lock: the
        prefill and decode threads both cancel)."""
        with self._stats_lock:
            self._stats["deadline_cancelled"] = (
                self._stats.get("deadline_cancelled", 0) + 1)
            if req.oneshot is not None:
                # The unified lane's analog of the batch lane's
                # deadline_dropped counter — the worker folds it into
                # the wire-compatible /health admission block.
                self._stats["stateless"]["deadline_dropped"] += 1
        self._fail_request(req, DeadlineExceeded(message))

    def _count_admission_dispatch(self, n: int = 1) -> None:
        """Device dispatches issued by the ADMISSION side: the dense
        cache's prefill forwards/windows and row inserts, and an import's
        chain write — the dispatches the ragged tick folds into the
        decode tick. chunks + admission_dispatches is the dense lane's
        dispatch count, beside a pool lane's `dispatches`. Lock: the
        prefill and decode threads both increment."""
        with self._stats_lock:
            self._stats["admission_dispatches"] = (
                self._stats.get("admission_dispatches", 0) + n)

    @staticmethod
    def _fail_request(req: _Request, exc: BaseException) -> None:
        """Resolve a request with an error AND unblock its stream consumer
        (a dropped sentinel would hang an SSE reader forever)."""
        if not req.future.done():
            req.future.set_exception(exc)
        if req.stream is not None:
            wake = req.stream.put(None)
            if wake is not None:
                wake()  # any thread fails a request: no tick to wait for

    def _prefill_loop(self) -> None:
        """Prefill thread: drains submissions, runs each prompt's forward
        pass + first-token sample (the host-sync-heavy admission work), and
        hands (req, kv-block, first token) to the decode loop via `_ready`.
        In-flight rows' decode chunks never stall behind a long prompt
        (round-1 VERDICT: serial admission on the decode thread caused
        head-of-line latency). A prefill failure is per-request — nothing
        shared is touched here, so only that future errors."""
        while self._running:
            req = self._queue.get()
            if req is None:
                break
            # Liveness: the prefill thread blocks on the queue when idle
            # (no age signal there), but a device forward pass hung INSIDE
            # _run_prefill would wedge every admission while the decode
            # loop keeps idle-ticking — so stats() folds this busy-age
            # into last_tick_age_s alongside the decode heartbeat.
            self._prefill_busy_since = time.monotonic()
            try:
                if req.deadline is not None and req.deadline.expired():
                    # The client's budget ran out while the request queued
                    # — skip the prefill forward entirely.
                    self._cancel_deadline(req,
                                          "deadline expired before prefill")
                    continue
                t0 = time.perf_counter()
                if req.sink is not None:
                    req.sink.between("queue_wait", req.t_submit, t0)
                try:
                    item = self._run_prefill(req)
                except Exception as exc:
                    self._fail_request(req, exc)
                    continue
                if (req.sink is not None and not self._mixed
                        and req.oneshot is None):
                    # Mixed mode records its real (multi-tick) "prefill"
                    # span at prompt completion in _tick_mixed — staging
                    # the batch-formation wrapper here too would
                    # double-count the stage and pollute its histogram
                    # with ~µs samples. One-shot rows have no prefill at
                    # all (their device work is the tick's grouped
                    # dispatch — batch_form/device_compute spans there).
                    req.sink.between("prefill", t0,
                                     prompt_len=len(req.prompt))
                if req.oneshot is not None:
                    # Single-tick work stages on its own unbounded lane
                    # (see _oneshot_ready above) and joins the next
                    # tick's grouped dispatch directly.
                    self._oneshot_ready.put(req)
                    continue
                # Bounded put with a running check: if the decode loop
                # already exited, don't block forever on a full queue.
                # `slot_wait` starts here, so a put that blocks on a full
                # queue is inside it.
                req.t_ready = time.perf_counter()
                placed = False
                while self._running:
                    try:
                        self._ready.put(item, timeout=0.1)
                        placed = True
                        break
                    except queue.Full:
                        continue
                if not placed:
                    self._fail_request(req,
                                       RuntimeError("scheduler stopped"))
            finally:
                self._prefill_busy_since = None
        # Shutdown: fail whatever never got prefilled — a dropped future
        # would hang its caller for the full result() timeout.
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not None:
                self._fail_request(req, RuntimeError("scheduler stopped"))
        try:
            self._ready.put_nowait(None)  # propagate shutdown to decode loop
        except queue.Full:
            pass

    def _first_token(self, req: _Request, logits, prompt, L: int):
        """Sample the request's first token from its prefill logits at
        logical position L — the one sampling rule both cache layouts
        share (fold_in(seed, position): batch- and layout-independent).
        Returns (first_tok, row_counts or None)."""
        seed = int(req.seed) & 0x7FFFFFFF
        row_counts = None
        first_logits = jnp.asarray(logits)[None, :]
        if req.rep_penalty != 1.0 or req.stop_tokens:
            row_counts = token_counts([prompt], 1, self.cfg.vocab)
            if req.rep_penalty != 1.0:
                first_logits = apply_repetition_penalty(
                    first_logits, jnp.asarray(row_counts),
                    jnp.asarray([req.rep_penalty], jnp.float32))
        first = _sample(
            first_logits,
            jnp.asarray([seed], jnp.int32),
            jnp.asarray([L], jnp.int32),
            jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_p], jnp.float32),
            jnp.asarray([req.top_k], jnp.int32),
            jnp.asarray([req.min_p], jnp.float32))
        first_tok = int(first[0])
        if row_counts is not None:
            row_counts[0, first_tok] += 1  # first token joins the context
        return first_tok, row_counts

    def _promote_reserve(self) -> int:
        """Free blocks a host-tier promotion must leave behind: one per
        live row, so swapping a cold prefix back in can never starve the
        next tick's live-row block growth (or push rows into
        pool_starved early completion). Read without the pool lock —
        a ±1-row-stale reserve only shifts WHEN a promotion defers,
        never correctness."""
        rows = self._row_req  # lint: lockfree-ok documented ±1-stale read
        return sum(1 for r in rows if r is not None)

    def _swap_reserve(self) -> int:
        """The promote_reserve a radix lookup passes: the live-row
        reserve, or — under brownout swap-in deferral — the whole pool,
        which no promotion can satisfy, so every demoted hit stops at
        the resident prefix and counts ``swap_in_deferred`` (the
        degradation stays visible in the same counter the reserve rule
        already uses)."""
        if self._bo_defer_swap:
            return self._pool.num_blocks
        return self._promote_reserve()

    def _record_swap_in(self, req: _Request, swapped: int,
                        t0: float) -> None:
        """One ``swap_in`` stage span per lookup that promoted demoted
        blocks — the trace-side proof a radix hit on the host tier was
        served by a swap-in, not a recompute (fault_injection --offload
        and the affinity bench read the matching pool counters)."""
        if swapped and req.sink is not None:
            dur_us = (time.perf_counter() - t0) * 1e6
            req.sink.stage("swap_in", dur_us,
                           start_ts=time.time() - dur_us / 1e6,
                           blocks=swapped)

    def _fetch_prefix_splice(self, req: _Request, prompt: List[int],
                             matched: List[int], pool, gen: int,
                             pb: int) -> List[int]:
        """Fleet prefix tier fetch (prefill thread): pull the hinted
        peer's radix chain for this prompt and splice it PAST the local
        match through the radix re-adoption path — only the unmatched
        tail prefills afterward, accounted as
        ``prefill_tokens_skipped_remote``. Verification (geometry +
        checksum) runs BEFORE any allocation; the splice itself holds
        the pool lock once (generation check → live-row reserve →
        alloc → verbatim import → radix insert). Every failure rung —
        peer dead/draining/refused/timeout, checksum, stale pool
        generation, pool full, no gain over the local match — returns
        the local match unchanged: the stream recomputes locally,
        never strands. One ``prefix_fetch`` stage span per attempt
        (counters==spans; ``attempted`` equals the span count)."""
        hint = req.prefix_hint
        if not self._prefix_sharing or not isinstance(hint, dict):
            return matched
        bs = pool.block_size
        Leff = max(len(prompt), 1)
        # The last prompt block always recomputes (sampling params stay
        # OUT of the radix key), so blocks past (Leff-1)//bs save
        # nothing — and the row table caps the chain at pb//bs.
        max_useful = min((Leff - 1) // bs, pb // bs)
        m = len(matched)
        promised = int(hint.get("blocks") or 0)
        if max_useful <= m or (promised and promised <= m):
            return matched  # a fetch could not add anything: no attempt
        t0 = time.perf_counter()
        outcome = "spliced"
        spliced = 0
        chain = None
        try:
            res = self.prefix_fetch(hint, prompt, max_useful)
        except Exception:  # transport must never kill the prefill thread
            res = {"ok": False, "rung": "peer_unreachable"}
        if res is None:
            return matched  # self-hint (retry landed on the owner): skip
        if not res.get("ok"):
            rung = str(res.get("rung") or "peer_refused")
            outcome = rung if rung in ("peer_unreachable", "peer_refused",
                                       "timeout", "inflight_capped") \
                else "peer_refused"
        else:
            chain = res.get("chain")
            if not isinstance(chain, dict) or "blocks" not in chain:
                outcome = "geometry_mismatch"
            elif pool.chain_compatible(chain) is not None:
                outcome = "geometry_mismatch"
            elif not pool.verify_chain(chain):
                outcome = "checksum_failed"
        if outcome == "spliced":
            n_fetch = min(len(chain["blocks"]), max_useful)
            if n_fetch <= m:
                outcome = "no_gain"
            else:
                with pool.lock:
                    if pool.generation != gen:
                        outcome = "stale_generation"
                    elif not pool.can_alloc(n_fetch - m
                                            + self._promote_reserve()):
                        outcome = "pool_full"
                    else:
                        fresh = pool.alloc(n_fetch - m)
                        pool.import_chain(chain,
                                          chain["blocks"][m:n_fetch], fresh)
                        # Re-adoption path: existing nodes untouched,
                        # the spliced tail joins the tree (tree's own
                        # retain) — the row keeps the alloc reference,
                        # exactly the lookup-pin shape downstream code
                        # already releases.
                        pool.radix.insert(prompt[:n_fetch * bs],
                                          list(matched) + fresh)
                        matched = list(matched) + fresh
                        spliced = n_fetch - m
        dur_us = (time.perf_counter() - t0) * 1e6
        with self._stats_lock:
            p = self._prefix_fetch_stats()
            p["attempted"] += 1
            if spliced:
                p["spliced"] += 1
                p["blocks_spliced"] += spliced
                p["prefill_tokens_skipped_remote"] += spliced * bs
            else:
                p[outcome] += 1
        if req.sink is not None:
            req.sink.stage("prefix_fetch", dur_us,
                           start_ts=time.time() - dur_us / 1e6,
                           outcome=outcome, blocks=spliced,
                           peer=str(hint.get("lane") or ""))
        return matched

    def _run_prefill_mixed(self, req: _Request):
        """Mixed-mode batch formation (the prefill thread's whole job
        here): pick the bucket, take the radix pins, precompute the
        penalty counts — NO device work. The prompt's forward pass runs
        inside the decode thread's ragged ticks instead. Returns the
        9-tuple every pool item is (row_caches and first_tok slots None
        — both materialize in-dispatch), so every downstream path
        (deadline drop, pool-pressure parking, shutdown drain,
        `_discard_item`) handles one shape."""
        pool = self._pool
        pb = next((b for b in self._prompt_buckets if b >= len(req.prompt)),
                  self._prompt_buckets[-1])
        prompt = req.prompt[-pb:]
        L = len(prompt)
        matched: List[int] = []
        swapped = 0
        t0 = time.perf_counter()
        with pool.lock:
            gen = pool.generation
            if self._prefix_sharing:
                si0 = pool.swap_ins
                matched = pool.radix.lookup(          # pins for this row
                    prompt, promote_reserve=self._swap_reserve())
                swapped = pool.swap_ins - si0
        self._record_swap_in(req, swapped, t0)
        if req.sink is not None:
            dur_us = (time.perf_counter() - t0) * 1e6
            req.sink.stage("radix_lookup", dur_us,
                           start_ts=time.time() - dur_us / 1e6,
                           matched_tokens=len(matched) * pool.block_size)
        if self.prefix_fetch is not None and req.prefix_hint is not None:
            # Fleet prefix tier (mixed mode): the splice extends the
            # match before batch formation — the ragged tick's resume
            # point moves exactly like a deeper local hit.
            matched = self._fetch_prefix_splice(req, prompt, matched,
                                                pool, gen, pb)
        row_counts = None
        if req.rep_penalty != 1.0 or req.stop_tokens:
            # Prompt-token counts only — the first sampled token joins
            # in-dispatch (the ragged step's counts scatter).
            row_counts = token_counts([prompt], 1, self.cfg.vocab)
        return (req, None, None, pb, L, row_counts, matched, prompt, gen)

    def _run_prefill_import(self, req: _Request):
        """Import-side batch formation (prefill thread): the checksum
        and geometry gates run here — off the decode thread, before any
        block is allocated — then the radix lookup: a prompt prefix this
        lane already caches is RE-ADOPTED (pinned; demoted matches swap
        in through the existing promotion machinery) and only the rest
        of the chain ships bytes at admission. No prefill dispatch ever
        runs for an import — that is the whole point. Returns the same
        9-tuple shape as the other paged formation paths so every
        downstream path (deadline drop, discard, shutdown) works
        unchanged."""
        pool = self._pool
        snap = req.migrate
        chain = snap.get("chain")
        reason = None
        if not isinstance(chain, dict) or "blocks" not in chain:
            reason = "snapshot carries no block chain"
        if reason is None:
            reason = pool.chain_compatible(chain)
        if reason is None and not pool.verify_chain(chain):
            reason = "chain checksum mismatch"
        prompt = req.prompt
        bs = pool.block_size
        pos = int(snap["pos"])
        n_chain = (pos - 1) // bs + 1 if pos > 0 else 0
        if reason is None and pos > self.max_seq - 1:
            reason = (f"row position {pos} exceeds this lane's max_seq "
                      f"{self.max_seq}")
        if reason is None and len(chain["blocks"]) < n_chain:
            reason = (f"chain holds {len(chain['blocks'])} blocks but "
                      f"the row spans {n_chain}")
        if reason is not None:
            self._bump_migration("import_rejected")
            raise ImportRefused(f"migration import rejected: {reason}")
        matched: List[int] = []
        swapped = 0
        t0 = time.perf_counter()
        with pool.lock:
            gen = pool.generation
            if self._prefix_sharing:
                si0 = pool.swap_ins
                matched = pool.radix.lookup(
                    prompt, promote_reserve=self._swap_reserve())
                swapped = pool.swap_ins - si0
                # The tree indexes full PROMPT blocks only, so a match
                # can never extend past the chain — clamp as a backstop
                # (extra pins released, never leaked).
                if len(matched) > n_chain:
                    pool.release_many(matched[n_chain:])
                    matched = matched[:n_chain]
        self._record_swap_in(req, swapped, t0)
        row_counts = None
        if req.rep_penalty != 1.0 or req.stop_tokens:
            # Penalty counts replay from the FULL context — prompt plus
            # every emitted token — exactly what the source's counts
            # held (each sampled token joined its row's counts once).
            ctx = prompt + [int(t) for t in snap["emitted"]]
            row_counts = token_counts([ctx], 1, self.cfg.vocab)
        return (req, None, None, n_chain * bs, len(prompt), row_counts,
                matched, prompt, gen)

    def _run_prefill_mixed_slab(self, req: _Request):
        """Mixed-mode batch formation for the state_slab family: NO
        device work and no lookups at all (no radix to walk) — the
        prompt's recurrence runs inside the decode thread's ticks,
        accumulating state directly in the row's slab. Returns the
        shared 9-tuple item shape."""
        spool = self._spool
        prompt = list(req.prompt)
        L = len(prompt)
        with spool.lock:
            gen = spool.generation
        row_counts = None
        if req.rep_penalty != 1.0 or req.stop_tokens:
            row_counts = token_counts([prompt], 1, self.cfg.vocab)
        return (req, None, None, L, L, row_counts, [], prompt, gen)

    def _run_prefill_import_slab(self, req: _Request):
        """Import-side validation for a migrated state_slab stream
        (prefill thread): the checksum and geometry gates run here —
        off the decode thread, before any row is allocated — on the
        one-pseudo-block state chain. No prefill dispatch ever runs:
        the whole autoregressive state arrives in the chain."""
        spool = self._spool
        snap = req.migrate
        chain = snap.get("chain")
        reason = None
        if not isinstance(chain, dict) or "blocks" not in chain:
            reason = "snapshot carries no state chain"
        if reason is None:
            reason = spool.chain_compatible(chain)
        if reason is None and not spool.verify_chain(chain):
            reason = "chain checksum mismatch"
        pos = int(snap["pos"])
        if reason is None and pos > self.max_seq - 1:
            reason = (f"row position {pos} exceeds this lane's max_seq "
                      f"{self.max_seq}")
        if reason is not None:
            self._bump_migration("import_rejected")
            raise ImportRefused(f"migration import rejected: {reason}")
        prompt = [int(t) for t in snap["prompt"]]
        row_counts = None
        if req.rep_penalty != 1.0 or req.stop_tokens:
            ctx = prompt + [int(t) for t in snap["emitted"]]
            row_counts = token_counts([ctx], 1, self.cfg.vocab)
        with spool.lock:
            gen = spool.generation
        return (req, None, None, len(prompt), len(prompt), row_counts,
                [], prompt, gen)

    def _run_prefill(self, req: _Request):
        if req.oneshot is not None:
            # One-shot rows carry no prompt forward: the prefill thread
            # only contributes the queue_wait span and deadline check;
            # the device work happens in _tick_stateless's grouped
            # dispatch. Short item — _discard_item's len guard makes
            # the drain paths safe on it.
            return (req,)
        if self._slab:
            if req.migrate is not None:
                return self._run_prefill_import_slab(req)
            return self._run_prefill_mixed_slab(req)
        if self._paged:
            if req.migrate is not None:
                return self._run_prefill_import(req)
            return self._run_prefill_mixed(req)
        pb = next((b for b in self._prompt_buckets if b >= len(req.prompt)),
                  self._prompt_buckets[-1])
        prompt = req.prompt[-pb:]
        L = len(prompt)
        tokens = np.zeros((1, pb), np.int32)
        attn = np.zeros((1, pb), np.int32)
        pos_ids = np.zeros((1, pb), np.int32)
        tokens[0, pb - L:] = prompt
        attn[0, pb - L:] = 1
        pos_ids[0, pb - L:] = np.arange(L)

        # Prefix cache: an exact repeat of a (bucket, prompt) skips the
        # prompt forward entirely; the cached KV block is read-only (row
        # insertion copies it into the shared cache, never donates it), so
        # concurrent admissions can share one entry safely.
        # L is part of the key: left-padding zero-fills, and token id 0 is
        # a REAL vocab token, so [5] and [0, 5] serialize identically at
        # the same bucket — only the length tells them apart. A disabled
        # cache (budget 0) skips even the key serialization.
        # Capture the cache OBJECT once: set_params (hot reload) swaps
        # self._prefix_cache, and a put issued after the swap must land in
        # the abandoned old cache (GC'd), never seed the fresh one with
        # old-weight logits/KV.
        prefix_cache = self._prefix_cache
        cached = None
        if prefix_cache.budget > 0:
            key = (pb, L, tokens.tobytes())
            cached = prefix_cache.get(key)
        if cached is not None:
            logits, row_caches = cached
        else:
            w = self._prefill_chunk
            if 0 < w < pb:
                # Chunked prefill: ceil(pb/w) window dispatches; decode
                # chunks interleave between them instead of waiting out one
                # long prompt forward. A non-divisor chunk just gets one
                # narrower remainder window (its own compiled width) —
                # never a silent fallback to monolithic prefill.
                row_caches = init_caches(self.cfg, 1, pb, self._dtype)
                if self._device is not None:
                    row_caches = jax.device_put(row_caches, self._device)
                start_vec = jnp.asarray([pb - L], jnp.int32)
                win_exe = self._window()
                starts = list(range(0, pb, w))
                for w0 in starts:
                    # Interior windows exist only to write KV — skip their
                    # (W, vocab) LM-head matmul; the final window projects
                    # its last slot only.
                    head = "last" if w0 == starts[-1] else "none"
                    wlog, row_caches = win_exe(
                        self._step_params,
                        jnp.asarray(tokens[:, w0:min(w0 + w, pb)]),
                        row_caches, jnp.asarray([w0], jnp.int32),
                        start_vec, head)
                self._count_admission_dispatch(len(starts))
                logits = wlog[0, -1]
            else:
                logits, row_caches = self._prefill()(
                    self._step_params, jnp.asarray(tokens), jnp.asarray(attn),
                    jnp.asarray(pos_ids))
                self._count_admission_dispatch()
            if prefix_cache.budget > 0:
                prefix_cache.put(key, logits, row_caches)
        # First token from the prefill logits at logical position L (same
        # fold_in(seed, position) scheme as decode — batch-independent),
        # penalized by the PROMPT's token counts like every later step.
        first_tok, row_counts = self._first_token(req, logits, prompt, L)
        return req, row_caches, first_tok, pb, L, row_counts

    def _admit_mixed(self, item, row: int) -> None:
        """Mixed-mode admission (decode thread): allocate the bucket's
        blocks up front (radix-matched prefix blocks enter the table
        pinned), make the two write targets private, and mark the row
        PREFILLING — the prompt forward runs chunk-by-chunk inside the
        subsequent ragged ticks, writing KV straight into these blocks.
        Raises PoolExhausted (nothing consumed) when even eviction can't
        cover the allocation — the caller defers the admission."""
        (req, _rc, _ft, pb, L, row_counts, matched, prompt, gen) = item
        pool = self._pool
        bs = pool.block_size
        m = len(matched)
        Leff = max(L, 1)
        t0 = time.perf_counter()
        req.t_admit = t0
        first_col = min(L, self.max_seq - 1)  # first decode write column
        # Resume at the block boundary at/below the radix match; the last
        # prompt block always recomputes so logits for the first sample
        # come from this row's own forward (sampling params stay OUT of
        # the radix key).
        p0 = (min(m * bs, Leff - 1) // bs) * bs
        with pool.lock:
            if gen != pool.generation:
                raise _StaleAdmission(
                    "kv pool was rebuilt during this request's admission")
            cols = min(first_col + self._decode_horizon + 1, self.max_seq)
            need = max(pb // bs, (cols - 1) // bs + 1)
            fresh = pool.alloc(need - m)  # PoolExhausted -> defer
            table = list(matched) + fresh
            # Blocks this row will WRITE must be private: the resumed
            # window's first block (shared only on a whole-prompt match)
            # and the decode append block. The two indices coincide
            # whenever both are shared, so at most ONE copy ever happens
            # — a PoolExhausted here leaves no partial swap behind.
            try:
                for bi in sorted({p0 // bs, first_col // bs}):
                    wid, copied = pool.ensure_writable(table[bi])
                    if copied:
                        table[bi] = wid
            except PoolExhausted:
                pool.release_many(fresh)
                raise
            if self._hybrid:
                # The slot's own state row, taken with its blocks.
                self._spool.take(row)
            pool.prefix_hit_tokens += p0
            pool.prefilled_tokens += Leff - p0
        self._tables[row, :] = 0
        self._tables[row, :len(table)] = table
        self._row_blocks[row] = table
        if req.sink is not None:
            dur_us = (time.perf_counter() - t0) * 1e6
            req.sink.stage("kv_alloc", dur_us,
                           start_ts=time.time() - dur_us / 1e6,
                           blocks=len(table), shared_blocks=m)
        if row_counts is not None:
            self._counts = self._ensure_counts().at[row].set(
                jnp.asarray(row_counts[0]))
        self._set_row_params(req, row, pos=first_col, start=0)
        self._prefilling[row] = True
        self._reset_prefill_accounting(row)
        self._row_prompt[row] = right_pad_prompt(prompt, pb)[0]
        self._row_prompt_toks[row] = prompt
        self._row_L[row] = L
        self._row_w0[row] = p0
        self._row_emitted[row] = []
        self._done[row] = False
        self._stats["admitted"] += 1
        if self._block is not None:
            self._open_first_block(row, req, prompt, L)

    def _admit_import(self, item, row: int) -> None:
        """Decode-thread half of a migration import: allocate blocks for
        the chain plus the decode horizon (matched prefix blocks enter
        pinned), write the wire bytes VERBATIM into the fresh blocks
        (one batched donation under the pool lock), index the prompt in
        the radix tree, and restore the row's exact host state — pos,
        pending token, sampling vectors, emitted list. The next tick
        decodes it like any other row. Raises PoolExhausted when the
        pool cannot hold the chain while keeping the live-row reserve
        free (nothing consumed; the caller fails the import RETRYABLE —
        imports are never parked, their transfer window is bounded)."""
        (req, _rc, _ft, _pbx, L, row_counts, matched, prompt, gen) = item
        pool = self._pool
        bs = pool.block_size
        snap = req.migrate
        chain = snap["chain"]
        emitted = [int(t) for t in snap["emitted"]]
        pos = min(int(snap["pos"]), self.max_seq - 1)
        n_chain = (pos - 1) // bs + 1 if pos > 0 else 0
        m = len(matched)
        t0 = time.perf_counter()
        req.t_admit = t0
        with pool.lock:
            if gen != pool.generation:
                raise _StaleAdmission(
                    "kv pool was rebuilt during this import")
            cols = min(pos + self._decode_horizon + 1, self.max_seq)
            need = max(n_chain, (cols - 1) // bs + 1)
            # The live-row reserve rule: adopting a migrated stream must
            # never starve rows already decoding here (same rank order
            # as host-tier promotion — a refusal falls back to the
            # replay resume, which admits like any new request).
            reserve = self._promote_reserve()
            if not pool.can_alloc(need - m + reserve):
                raise PoolExhausted(
                    f"import needs {need - m} blocks + {reserve} "
                    f"reserve; {pool.free_blocks} free of "
                    f"{pool.num_blocks - 1}")
            fresh = pool.alloc(need - m)
            table = list(matched) + fresh
            try:
                wid, copied = pool.ensure_writable(table[pos // bs])
            except PoolExhausted:
                pool.release_many(fresh)
                raise
            if copied:
                table[pos // bs] = wid
            # Verbatim adoption of the unmatched chain tail: int8 +
            # scale or bf16 bytes land exactly as exported — zero
            # re-prefilled tokens, zero requantization.
            pool.import_chain(chain, chain["blocks"][m:n_chain],
                              fresh[:n_chain - m])
            if self._prefix_sharing:
                pool.radix.insert(prompt, table)
            pool.prefix_hit_tokens += m * bs
        self._count_admission_dispatch()
        self._tables[row, :] = 0
        self._tables[row, :len(table)] = table
        self._row_blocks[row] = table
        if req.sink is not None:
            dur_us = (time.perf_counter() - t0) * 1e6
            req.sink.stage("kv_import", dur_us,
                           start_ts=time.time() - dur_us / 1e6,
                           blocks=len(table), shared_blocks=m,
                           imported_blocks=n_chain - m)
        if row_counts is not None:
            self._counts = self._ensure_counts().at[row].set(
                jnp.asarray(row_counts[0]))
        self._set_row_params(req, row, pos=pos, start=0)
        self._tok[row] = int(snap["tok"])
        self._done[row] = False
        self._row_emitted[row] = emitted
        self._prefilling[row] = False
        self._row_prompt[row] = None
        self._row_L[row] = L
        self._row_w0[row] = 0
        self._row_prompt_toks[row] = prompt
        # No TTFT sample (the first token happened on the source lane);
        # ITL resumes from now — the migration gap shows up client-side.
        self._row_last_emit[row] = time.perf_counter()
        self._stats["admitted"] += 1
        with self._stats_lock:
            mig = self._migration_stats()
            mig["imported_rows"] += 1
            mig["imported_tokens"] += len(emitted)
            mig["imported_chain_tokens"] += (n_chain - m) * bs
        self._push_stream(row, req)
        self._maybe_complete(row)

    def _admit_slab_mixed(self, item, row: int) -> None:
        """Mixed-mode state_slab admission (decode thread): allocate the
        slab row, ZERO it (the prompt's recurrence accumulates in the
        slab across ticks, so a previous occupant's bytes must never
        leak into a fresh state), and mark the row PREFILLING — the
        prompt consumes inside subsequent ragged ticks under the shared
        token-budget rule."""
        (req, _st, _ft, _pb, L, row_counts, _m, prompt, gen) = item
        spool = self._spool
        t0 = time.perf_counter()
        req.t_admit = t0
        with spool.lock:
            if gen != spool.generation:
                raise _StaleAdmission(
                    "state slab pool was rebuilt during this request's "
                    "admission")
            rid = spool.alloc_row()  # PoolExhausted -> defer
            spool.slab = self._slab_zero()(spool.slab, jnp.int32(rid))
        self._slab_rows[row] = rid
        if req.sink is not None:
            dur_us = (time.perf_counter() - t0) * 1e6
            req.sink.stage("state_alloc", dur_us,
                           start_ts=time.time() - dur_us / 1e6,
                           state_row=rid)
        if row_counts is not None:
            self._counts = self._ensure_counts().at[row].set(
                jnp.asarray(row_counts[0]))
        self._set_row_params(req, row, pos=min(L, self.max_seq - 1),
                             start=0)
        self._prefilling[row] = True
        self._reset_prefill_accounting(row)
        self._row_prompt[row] = right_pad_prompt(prompt, max(L, 1))[0]
        self._row_prompt_toks[row] = prompt
        self._row_L[row] = L
        self._row_w0[row] = 0  # no radix resume: the prompt runs whole
        self._row_emitted[row] = []
        self._done[row] = False
        self._stats["admitted"] += 1

    def _admit_import_slab(self, item, row: int) -> None:
        """Decode-thread half of a state_slab migration import: one
        fresh row, the chain's state bytes written VERBATIM (bit-exact
        — the recurrence resumes exactly where the source lane stopped,
        zero re-prefilled tokens), host stream state restored. Raises
        PoolExhausted (nothing consumed) when no row is free — imports
        are never parked; the caller fails RETRYABLE into the replay
        fallback."""
        (req, _st, _ft, _pb, L, row_counts, _m, prompt, gen) = item
        spool = self._spool
        snap = req.migrate
        emitted = [int(t) for t in snap["emitted"]]
        pos = min(int(snap["pos"]), self.max_seq - 1)
        t0 = time.perf_counter()
        req.t_admit = t0
        with spool.lock:
            if gen != spool.generation:
                raise _StaleAdmission(
                    "state slab pool was rebuilt during this import")
            rid = spool.alloc_row()  # PoolExhausted -> ImportRefused
            spool.import_row_chain(snap["chain"], rid)
        self._slab_rows[row] = rid
        self._count_admission_dispatch()
        if req.sink is not None:
            dur_us = (time.perf_counter() - t0) * 1e6
            req.sink.stage("state_import", dur_us,
                           start_ts=time.time() - dur_us / 1e6,
                           state_row=rid,
                           state_bytes=spool.bytes_per_row())
        if row_counts is not None:
            self._counts = self._ensure_counts().at[row].set(
                jnp.asarray(row_counts[0]))
        self._set_row_params(req, row, pos=pos, start=0)
        self._tok[row] = int(snap["tok"])
        self._done[row] = False
        self._row_emitted[row] = emitted
        self._prefilling[row] = False
        self._row_prompt[row] = None
        self._row_L[row] = L
        self._row_w0[row] = 0
        self._row_prompt_toks[row] = prompt
        # No TTFT sample (the first token happened on the source lane);
        # ITL resumes from now — the migration gap shows up client-side.
        self._row_last_emit[row] = time.perf_counter()
        self._stats["admitted"] += 1
        with self._stats_lock:
            mig = self._migration_stats()
            mig["imported_rows"] += 1
            mig["imported_tokens"] += len(emitted)
        self._push_stream(row, req)
        self._maybe_complete(row)

    def _release_row_blocks(self, row: int) -> None:
        """Return a freed row's block references to the pool (blocks the
        radix tree also references survive at refcount >= 1). The
        state_slab family frees its one slab row the same way — every
        row-free path (completion, cancel, export, shutdown) funnels
        here, so the zero-leak invariant is family-wide."""
        if self._slab:
            rid = self._slab_rows[row]
            if rid >= 0:
                with self._spool.lock:
                    self._spool.release_row(rid)
                self._slab_rows[row] = -1
            return
        if self._windowed:
            first, end = self._wspan[row]
            with self._wpool.lock:
                self._wpool.release_many(
                    self._wtables[row, first:end].tolist())
            self._wtables[row, :] = 0
            self._wspan[row] = 0
        if self._hybrid:
            self._spool.give(row)
        if not self._paged or not self._row_blocks[row]:
            return
        with self._pool.lock:
            self._pool.release_many(self._row_blocks[row])
        self._row_blocks[row] = []
        self._tables[row, :] = 0

    def _discard_item(self, item) -> None:
        """Release a prefilled-but-never-admitted item's radix pins
        (deadline drop, shutdown drain). Safe on dense items; pins taken
        against a reset-away pool generation are void, not released."""
        if self._paged and item is not None and len(item) >= 9 and item[6]:
            with self._pool.lock:
                if item[8] == self._pool.generation:
                    self._pool.release_many(item[6])

    def _set_row_params(self, req: _Request, row: int, *, pos: int,
                        start: int) -> None:
        """Per-row sampling/stopping vectors — shared by every admission
        path (dense, paged, mixed)."""
        self._start[row] = start
        self._pos[row] = pos
        self._seeds[row] = int(req.seed) & 0x7FFFFFFF
        self._temps[row] = req.temperature
        self._topps[row] = req.top_p
        self._topks[row] = req.top_k
        self._minps[row] = req.min_p
        self._pens[row] = req.rep_penalty
        self._stops[row] = -1
        self._stops[row, :len(req.stop_tokens)] = req.stop_tokens
        self._row_req[row] = req

    def _first_token_metrics(self, req: _Request, row: int) -> None:
        """TTFT observation at the moment a request's first token exists."""
        now = time.perf_counter()
        self.ttft_hist.observe(max(0.0, now - req.t_submit))
        self._row_last_emit[row] = now

    def _admit(self, item, row: int) -> None:
        """Decode-thread half of admission, by what the lane holds: a
        slab row or a block chain is taken and the row set to prefill
        inside the ticks; the dense cache splices the prefilled KV block
        in and initialises the row's host-side state."""
        if item[0].oneshot is not None:
            # One-shot rows first — family-independent (no blocks, no
            # slab row, no cache splice), so a generative lane carrying
            # them never routes one into its state machinery.
            self._admit_stateless(item[0], row)
            return
        if self._slab:
            if item[0].migrate is not None:
                self._admit_import_slab(item, row)
            else:
                self._admit_slab_mixed(item, row)
            return
        if self._paged:
            if item[0].migrate is not None:
                self._admit_import(item, row)
            else:
                self._admit_mixed(item, row)
            return
        req, row_caches, first_tok, pb, L, row_counts = item
        req.t_admit = time.perf_counter()
        if row_counts is not None:
            self._caches, self._counts = self._insert(True)(
                self._caches, row_caches.k, row_caches.v, row,
                self._ensure_counts(), jnp.asarray(row_counts[0]))
        else:
            self._caches = self._insert(False)(
                self._caches, row_caches.k, row_caches.v, row)
        self._count_admission_dispatch()
        self._set_row_params(req, row, pos=pb, start=pb - L)
        self._tok[row] = first_tok
        self._row_emitted[row] = [first_tok]
        self._done[row] = ((req.eos_id >= 0 and first_tok == req.eos_id)
                           or first_tok in req.stop_tokens)
        self._stats["admitted"] += 1
        self._first_token_metrics(req, row)
        self._push_stream(row, req)  # first token flushes at admission
        self._maybe_complete(row)

    def _clear_mixed_row(self, row: int) -> None:
        """Drop a row's mixed-mode prefill / speculative state
        (completion, deadline cancel, recovery, shutdown): the row must
        never reappear in a later tick's ragged batch, and the drafter
        must never see a freed row's history. Handoff holds clear on
        every one of those paths too — a freed slot must never stay
        parked."""
        self._held[row] = False
        if self._mixed:
            self._prefilling[row] = False
            self._row_prompt[row] = None
            self._row_L[row] = 0
            self._row_w0[row] = 0
            self._row_prompt_toks[row] = None

    def _visible_tokens(self, row: int, req: _Request) -> List[int]:
        """The request's client-visible tokens so far: budget-capped and
        EOS-truncated (EOS excluded) — one definition shared by the final
        result and the streaming deltas so a stream never shows a token the
        result would retract."""
        return truncate_at_stops(self._row_emitted[row][:req.max_new],
                                 req.eos_id, req.stop_tokens)

    def _push_stream(self, row: int, req: _Request) -> None:
        if req.stream is None:
            return
        vis = self._visible_tokens(row, req)
        if len(vis) > req.streamed:
            fresh = StreamDelta(vis[req.streamed:])
            fresh.t_put = time.perf_counter()
            self._stream_put(req, fresh)
            req.streamed = len(vis)

    def _stream_put(self, req: _Request, item) -> None:
        """Put `item` into the request's stream. A queue with a reader
        blocked in `get` has told it; an outbox a front's writer drives
        (`utils/streams.py`) hands back that writer's wake, which the
        loop calls once it has put all of a tick's tokens
        (`_wake_streams`), not once a row."""
        wake = req.stream.put(item)
        if wake is not None:
            self._stream_wakes.add(wake)

    def _wake_streams(self) -> None:
        """Tell the writers that the puts since the last call marked a
        stream ready with: once, at the end of a tick's `apply`, so that
        one thread takes the tick's events up in one pass."""
        if self._stream_wakes:
            wakes, self._stream_wakes = self._stream_wakes, set()
            for wake in wakes:
                wake()

    def _row_ends(self, req: _Request, emitted_n: int, pos: int) -> bool:
        """Whether a row with `emitted_n` tokens out and its next write
        at column `pos` has had its last token, by what the host knows
        without reading one: its budget, or the cache's end (the
        backstop; `submit` clamps a budget to the cache, an imported
        snapshot's is as the source lane set it)."""
        if self._block is not None:
            # `pos`: where the row's next block would start.
            return (emitted_n >= req.max_new
                    or pos + self._run > self.max_seq)
        return emitted_n >= req.max_new or pos >= self.max_seq - 1

    def _last_sample_in_flight(self, r: int, req: _Request) -> bool:
        """The tick in flight, which samples for row r, brings the row's
        last token by the rule `_maybe_complete` will end it with there.
        On a block-decoding lane: it is the last denoise pass of the
        row's last block (its commit would store K and V nobody reads)."""
        emitted, pos = len(self._row_emitted[r]), int(self._pos[r])
        if self._block is None:
            return self._row_ends(req, emitted + 1, pos)
        return self._blk_masked[r] == 0 and self._row_ends(
            req, emitted + self._run - int(self._blk_tail[r]),
            pos + self._run)

    def _maybe_complete(self, row: int, pos: Optional[int] = None) -> None:
        """`pos`: the row's position as of the tick whose results are
        being applied, where `_pos` already holds the advance of a tick
        enqueued behind it (`_land_tick`)."""
        req = self._row_req[row]
        if req is None:
            return
        if req.oneshot is not None:
            # One-shot rows complete ONLY in _tick_stateless: their
            # budget is trivially met (max_new == 0), so the generative
            # completion sweep would resolve them empty. _loop_body
            # ticks them before any generative dispatch, so this guard
            # is a backstop, not the ordering contract.
            return
        emitted = self._row_emitted[row]
        hit_eos = req.eos_id >= 0 and req.eos_id in emitted
        if pos is None:
            pos = int(self._pos[row])
        if (hit_eos or self._row_ends(req, len(emitted), pos)
                or self._done[row]):
            toks = self._visible_tokens(row, req)
            self._push_stream(row, req)
            if req.sink is not None and req.t_admit:
                # The row's whole decode residence (admission→completion):
                # device chunks plus the idle lanes it rode along in.
                dur_us = (time.perf_counter() - req.t_admit) * 1e6
                req.sink.stage("decode", dur_us,
                               start_ts=time.time() - dur_us / 1e6,
                               tokens=len(toks))
            req.future.set_result(toks)
            if req.stream is not None:
                self._stream_put(req, None)  # end of stream
            self._row_req[row] = None
            self._row_emitted[row] = []
            self._done[row] = True
            self._release_row_blocks(row)
            self._clear_mixed_row(row)
            self._stats["completed"] += 1

    def _cancel_expired_rows(self) -> None:
        """Mid-generation deadline enforcement: a row whose client budget
        ran out is failed and freed BETWEEN chunks, so the next decode
        chunk spends its lane on a live request instead. Tokens already
        streamed stand; the future resolves with DeadlineExceeded."""
        for r, req in enumerate(self._row_req):
            if req is None or req.deadline is None:
                continue
            if req.deadline.expired():
                self._cancel_deadline(
                    req, "deadline exceeded mid-generation "
                    f"({len(self._row_emitted[r])} tokens emitted)")
                self._row_req[r] = None
                self._row_emitted[r] = []
                self._done[r] = True
                self._release_row_blocks(r)
                self._clear_mixed_row(r)

    # -- unified stateless rows (DESIGN.md "Unified stateless serving") --------

    def _admit_stateless(self, req: _Request, row: int) -> None:
        """Decode-thread half of one-shot admission: the row just holds
        the request until this tick's grouped dispatch — no KV splice,
        no slab write, no sampling vectors. `_done` stays True so the
        row never enters a generative dispatch mask."""
        req.t_admit = time.perf_counter()
        self._row_req[row] = req
        self._row_emitted[row] = []
        self._done[row] = True
        self._held[row] = False
        self._stats["stateless"]["admitted"] += 1
        self._stats["admitted"] += 1

    def _free_oneshot_row(self, row: int) -> None:
        self._row_req[row] = None
        self._row_emitted[row] = []
        self._done[row] = True
        self._held[row] = False

    def _run_infer_batch(self, inputs, shapes):
        """The one-shot /infer device leg: EXACTLY the engine's batched
        forward (bucketed pad + split), so unified outputs are
        byte-identical to the retired batch lane's for the same
        co-batched inputs. Prefers the split-phase API when the engine
        has one (same preference the batch lane had)."""
        eng = self._infer_engine
        shp = (list(shapes)
               if any(s is not None for s in shapes) else None)
        if hasattr(eng, "batch_submit"):
            return eng.batch_collect(eng.batch_submit(inputs, shapes=shp))
        return eng.batch_predict(inputs, shapes=shp)

    def _tick_stateless(self) -> None:
        """One-shot tick: drain this tick's pending one-shot requests
        (up to a brownout-scaled n_slots budget), group them by kind,
        and run ONE grouped forward per kind present — infer rows
        through the infer_engine's bucketed batch, score rows through
        the score_provider's teacher-forced forward. Members stamp a
        transient row when one is free (the ragged batch's bookkeeping
        and counters); overflow members ride the same grouped dispatch
        rowless. Either way they are freed WITHIN this tick, so
        single-tick work never queues behind — and never displaces —
        decode residents that hold slots for a stream's lifetime. Runs
        BEFORE the generative tick paths each iteration, so a one-shot
        row never meets _maybe_complete's budget sweep and a mixed
        generate+score lane finishes its single-tick work before
        spending the tick's decode dispatch."""
        st = self._stats["stateless"]
        budget = self.n_slots
        frac = self._bo_budget_frac
        if frac < 1.0:
            # Brownout: shrink the per-tick one-shot dispatch the same
            # way the mixed-step token budget shrinks (floored at 1 so
            # progress survives every stage); deferred requests stay
            # queued and dispatch next tick.
            budget = max(1, int(budget * frac))
        # Stragglers already holding rows (the _ready/_admit fallback
        # path) dispatch first; the snapshot also shields the second
        # kind's group from the first kind's row frees.
        pairs = [(r, self._row_req[r]) for r in range(self.n_slots)
                 if self._row_req[r] is not None
                 and self._row_req[r].oneshot is not None]
        free = self._free_rows() if len(pairs) < budget else []
        while len(pairs) < budget:
            try:
                req = self._oneshot_ready.get_nowait()
            except queue.Empty:
                break
            if req.deadline is not None and req.deadline.expired():
                self._cancel_deadline(
                    req, "deadline expired before one-shot dispatch")
                continue
            if req.sink is not None:
                # The prefill thread never sees one-shots, so the
                # queue_wait span (submit -> drain) stages here.
                wait_us = (time.perf_counter() - req.t_submit) * 1e6
                req.sink.stage("queue_wait", wait_us,
                               start_ts=time.time() - wait_us / 1e6)
            if free:
                self._admit_stateless(req, free[0])
                pairs.append((free.pop(0), req))
            else:
                req.t_admit = time.perf_counter()
                st["admitted"] += 1
                self._stats["admitted"] += 1
                pairs.append((None, req))
        if not pairs:
            return
        st["ticks"] += 1
        for kind in ("infer", "score"):
            group = [(r, q) for r, q in pairs if q.oneshot[0] == kind]
            if group:
                self._dispatch_oneshot(kind, group, st)

    def _dispatch_oneshot(self, kind: str, group, st: dict) -> None:
        reqs = [q for _r, q in group]
        t0 = time.perf_counter()
        try:
            if kind == "infer":
                outs = self._run_infer_batch(
                    [q.oneshot[1] for q in reqs],
                    [q.oneshot[2] for q in reqs])
            else:
                scorer = self._score_provider()
                outs = scorer.score([q.oneshot[1] for q in reqs],
                                    [q.oneshot[2] for q in reqs])
            if len(outs) != len(group):
                raise RuntimeError(
                    f"one-shot {kind} dispatch returned {len(outs)} "
                    f"results for {len(group)} rows")
        except Exception as exc:
            # A failed one-shot dispatch poisons exactly its co-batched
            # group — the retired batch lane's semantics. Nothing is
            # donated and no shared device state was touched, so the
            # scheduler keeps serving without a _recover.
            st["dispatches"] += 1
            st["failed"] += len(group)
            for r, q in group:
                self._fail_request(q, exc)
                if r is not None:
                    self._free_oneshot_row(r)
            return
        elapsed_us = (time.perf_counter() - t0) * 1e6
        per_us = max(1, int(elapsed_us / max(1, len(group))))
        st["dispatches"] += 1
        st[kind + "_rows"] += len(group)
        if len(group) >= self.n_slots:
            st["full_dispatches"] += 1
        for (r, req), out in zip(group, outs):
            if req.sink is not None:
                # Span parity with the retired batch lane (the worker's
                # _batch_observer/_record_device_spans): batch_form is
                # this row's admission→dispatch gap, device_compute the
                # whole group's device leg with the batch_size divisor.
                bf_us = max(0.0, (t0 - req.t_admit) * 1e6)
                req.sink.stage(
                    "batch_form", bf_us,
                    start_ts=time.time() - (elapsed_us + bf_us) / 1e6,
                    batch_size=len(group))
                req.sink.stage(
                    "device_compute", elapsed_us,
                    start_ts=time.time() - elapsed_us / 1e6,
                    batch_size=len(group))
            req.future.set_result((out, per_us))
            if req.stream is not None:
                self._stream_put(req, None)
            if r is not None:
                self._free_oneshot_row(r)
            st["completed"] += 1
            self._stats["completed"] += 1

    def _recover(self, exc: BaseException) -> None:
        """Device-step failure recovery. The prefill/decode executables
        donate ``self._caches``, so after a failed step the KV buffer may
        already be invalidated — every in-flight row's state is lost.
        Each row fails with a per-row RETRYABLE event (not the bare
        device error): the exception carries ``retryable=True`` and
        ``tokens_emitted``, so a streaming client — or the gateway's
        stream journal — can resume the generation on another lane from
        the exact emitted prefix instead of reading an opaque 500. Then
        rebuild the cache, reset slot state, assert the rebuilt
        pool/radix invariants, and keep the loop serving (a transient
        device error must not silently kill the daemon and hang all
        future /generate calls — ADVICE round 1, scheduler.py:310)."""
        for r, req in enumerate(self._row_req):
            if req is not None:
                n_emitted = len(self._visible_tokens(r, req))
                row_exc = RuntimeError(
                    f"row {r} lost to a device-step failure after "
                    f"{n_emitted} emitted tokens: {exc}")
                row_exc.retryable = True
                row_exc.tokens_emitted = n_emitted
                row_exc.__cause__ = exc
                self._fail_request(req, row_exc)
            self._row_req[r] = None
            self._row_emitted[r] = []
            self._clear_mixed_row(r)
        self._pos[:] = 0
        self._start[:] = 0
        self._tok[:] = 0
        self._done[:] = True
        self._stats["failures"] = self._stats.get("failures", 0) + 1
        # Postmortem black box: the ticks LEADING UP to a device-step
        # failure are exactly what a triage needs — dump them now, named
        # for the recovery, before the rebuild wipes the evidence.
        self._flight_anomaly(f"recover:{type(exc).__name__}")
        if self._paged:
            # The donated pool buffers may be invalid: rebuild the pool,
            # dropping the radix tree (its blocks died with the pool).
            with self._pool.lock:
                self._pool.reset()
                # Post-recover invariants, checked on the raw fields
                # under the lock (stats() re-locks): a violated rebuild
                # would corrupt every stream admitted afterwards, so it
                # must be loud, not latent.
                pool = self._pool
                violations = []
                if len(pool._free) != pool.num_blocks - 1:
                    violations.append(
                        f"free list {len(pool._free)} != "
                        f"{pool.num_blocks - 1}")
                if pool.radix.nodes != 0:
                    violations.append(
                        f"radix not empty ({pool.radix.nodes} nodes)")
                if int(np.sum(pool._ref[1:])) != 0:
                    violations.append("nonzero refcounts after reset")
            self._tables[:, :] = 0
            for r in range(self.n_slots):
                self._row_blocks[r] = []
            if self._windowed:
                with self._wpool.lock:
                    self._wpool.reset()
                self._wtables[:, :] = 0
                self._wspan[:, :] = 0
            if self._hybrid:
                self._spool.reset()  # lint: lockfree-ok tick thread's alone
            if violations:
                self._stats["recover_invariant_violations"] = (
                    self._stats.get("recover_invariant_violations", 0)
                    + len(violations))
                print(f"[scheduler] POST-RECOVER INVARIANT VIOLATED: "
                      f"{'; '.join(violations)}", flush=True)
        elif self._slab:
            # The donated slab may be invalid: rebuild the pool; row
            # ids issued against the old generation are void.
            with self._spool.lock:
                self._spool.reset()
                spool = self._spool
                violations = []
                if len(spool._free) != spool.num_rows - 1:
                    violations.append(
                        f"free list {len(spool._free)} != "
                        f"{spool.num_rows - 1}")
                if int(np.sum(spool._ref[1:])) != 0:
                    violations.append("nonzero refcounts after reset")
            for r in range(self.n_slots):
                self._slab_rows[r] = -1
            if violations:
                self._stats["recover_invariant_violations"] = (
                    self._stats.get("recover_invariant_violations", 0)
                    + len(violations))
                print(f"[scheduler] POST-RECOVER INVARIANT VIOLATED: "
                      f"{'; '.join(violations)}", flush=True)
        elif self._stateless:
            # One-shot rows hold no donated device state: nothing to
            # rebuild — failing the in-flight rows above was the whole
            # recovery.
            pass
        else:
            caches = init_caches(self.cfg, self.n_slots, self.max_seq,
                                 self._dtype)
            if self._device is not None:
                caches = jax.device_put(caches, self._device)
            self._caches = caches
        self._counts = None  # donated alongside — realloc lazily if needed
        # A tick in flight (the failed one, or one enqueued behind it on
        # buffers the failure took) is dropped with its rows, uncounted.
        self._reset_flight()

    def _loop(self) -> None:
        try:
            self._loop_body()
        finally:
            self._clock.idle()  # closes the loop's open annotation
            self._wake_streams()
            # Exit (stop() sentinel, _running flip, or the loop body itself
            # raising): mark the scheduler dead FIRST so submit() fails fast
            # and the prefill thread's bounded put stops retrying, then fail
            # every in-flight row and every already-prefilled item still
            # queued — a dropped future/sentinel would hang its blocking
            # caller or SSE reader.
            self._running = False
            self._inflight = None  # its rows fail with the others below
            exc = RuntimeError("scheduler stopped")
            for r, req in enumerate(self._row_req):
                if req is not None:
                    self._fail_request(req, exc)
                    self._row_req[r] = None
                    self._row_emitted[r] = []
                self._release_row_blocks(r)
                self._clear_mixed_row(r)
            if self._mixed:
                while self._pending:
                    item = self._pending.popleft()
                    self._discard_item(item)
                    self._fail_request(item[0], exc)
            while True:
                try:
                    item = self._ready.get_nowait()
                except queue.Empty:
                    break
                if item is not None:
                    self._discard_item(item)
                    self._fail_request(item[0], exc)
            # Pending export commands (queued AND parked wait_prefill
            # ones): answer, never strand the caller.
            stranded = list(self._export_waiting)
            self._export_waiting = []
            while True:
                try:
                    stranded.append(self._migrate_q.get_nowait())
                except queue.Empty:
                    break
            for _tag, fut, _opts in stranded:
                if not fut.done():
                    fut.set_result({"ok": False,
                                    "reason": "scheduler stopped"})

    def _ensure_capacity_paged(self) -> None:
        """Pre-tick block growth: every live row must own blocks through
        the columns the next tick can write (a write through an
        unallocated table entry would land in the null block and the row
        would attend garbage). A row the pool cannot grow — even after
        radix eviction — completes early with the tokens it has (counted
        in stats as pool_starved) rather than corrupting; admissions are
        deferred behind live-row growth, so this is the last resort."""
        pool = self._pool
        bs = pool.block_size
        for r, req in enumerate(self._row_req):
            if req is None or self._done[r]:
                continue  # done rows rewrite their own (allocated) column
            if self._held[r]:
                continue  # parked handoff rows decode nothing this tick
            if self._prefilling[r]:
                continue  # bucket + first-decode blocks reserved at admit
            last_col = min(int(self._pos[r]) + self._row_horizon(r, req),
                           self.max_seq - 1)
            need = last_col // bs + 1
            have = len(self._row_blocks[r])
            if need <= have:
                continue
            try:
                with pool.lock:
                    fresh = pool.alloc(need - have)
            except PoolExhausted:
                if self._inflight is not None:
                    # Its rows' ends may free blocks, and a row that
                    # ends early ends with every token it was stepped
                    # for: land the tick in flight, then look again.
                    self._drain_tick()
                    return self._ensure_capacity_paged()
                self._stats["pool_starved"] = (
                    self._stats.get("pool_starved", 0) + 1)
                self._done[r] = True
                self._maybe_complete(r)
                continue
            self._tables[r, have:need] = fresh
            self._row_blocks[r].extend(fresh)

    def _row_horizon(self, r: int, req: _Request) -> int:
        """Columns past `pos` the next tick may write for row r. Static
        (`_decode_horizon`) except under speculation, where a row nearing
        its token budget can only write its remaining tokens — the
        drafter caps proposals the same way, so allocation and the
        post-tick trim agree and never churn blocks."""
        if not self._spec:
            return self._decode_horizon
        return min(self._decode_horizon,
                   max(1, req.max_new - len(self._row_emitted[r])))

    def _trim_row_tail(self, r: int, req: _Request) -> None:
        """Return over-allocated speculation-horizon blocks: a verify
        window that crossed a block boundary may have allocated a block
        the row — after rejections, near its budget — can no longer
        write. The stale draft KV in retained blocks stays invisible via
        position masking; blocks wholly past the reachable horizon go
        back to the pool for other rows. Never touches radix-shared
        prefix blocks (they sit below `pos`, always within the horizon)."""
        bs = self._pool.block_size
        last_col = min(int(self._pos[r]) + self._row_horizon(r, req),
                       self.max_seq - 1)
        need = last_col // bs + 1
        blocks = self._row_blocks[r]
        if len(blocks) <= need:
            return
        with self._pool.lock:
            freed = self._pool.release_tail(blocks, need)
        if freed:
            self._tables[r, need:need + freed] = 0
            self._stats["spec"]["tail_blocks_released"] += freed

    def _complete_prefill_row(self, r: int, req: "_Request",
                              first_tok: int, done: bool,
                              pos: Optional[int] = None) -> None:
        """Prompt consumed: the row becomes a decode row. Index the
        now-filled prompt blocks in the radix tree (mixed mode inserts
        at COMPLETION — a cancelled mid-prefill row must never leave
        half-written blocks indexed), stamp the prefill span, and emit
        the first token. Shared by _tick_mixed and _tick_spec."""
        self._prompt_consumed(r, req)
        self._tok[r] = first_tok
        self._done[r] = done
        self._row_emitted[r] = [first_tok]
        self._first_token_metrics(req, r)
        self._push_stream(r, req)
        self._maybe_complete(r, pos)
        self._maybe_hold(r, req)

    def _prompt_consumed(self, r: int, req: "_Request") -> None:
        """Row r's prompt is in the pool: index its blocks in the radix
        tree and stamp the prefill span. What `_complete_prefill_row`
        begins with; all of it on a block-decoding lane, whose last chunk
        samples nothing (the row's first tokens come from its first
        block)."""
        self._prefilling[r] = False
        if self._prefix_sharing:
            with self._pool.lock:
                self._pool.radix.insert(self._row_prompt_toks[r],
                                        self._row_blocks[r])
        if req.sink is not None:
            now = time.perf_counter()
            req.sink.between("prefill", req.t_admit, now,
                             prompt_len=self._row_L[r],
                             chunks=self._row_chunks[r],
                             starved_ticks=self._row_starved_ticks[r],
                             starved_us=int(self._row_starved_us[r]))
            req.t_admit = now  # decode span start

    def _open_first_block(self, row: int, req: "_Request", prompt,
                          L: int) -> None:
        """Admission on a block-decoding lane: the blocks wholly inside
        the prompt are prefilled (`_row_L`), its tail of L mod run tokens
        opens the first generated block, masked behind it. A prompt
        shorter than a block has no prefill at all."""
        run = self._run
        head = L // run * run
        tail = L - head
        self._row_L[row] = head
        self._pos[row] = head
        self._blk_known[row] = -1
        self._blk_known[row, :tail] = prompt[head:L]
        self._blk_tail[row] = tail
        self._blk_masked[row] = run - tail
        if head == 0:
            self._prompt_consumed(row, req)
            self._maybe_complete(row)      # a budget of no token at all

    def _land_block_row(self, t: _FlightTick, r: int, req: "_Request",
                        block) -> int:
        """Row r's denoise pass has landed with `block` (L,), -1 where
        still masked. Its last one puts the block's tokens (those behind
        the prompt's tail) out as ONE event, whatever the reveal rule,
        and may end the row. Returns the tokens put out."""
        if int(t.pos[r]) == int(self._pos[r]):
            # Still the row's current block (no commit enqueued behind):
            # what a pass formed with nothing in flight starts from.
            self._blk_known[r] = block
            if self._inflight is None:      # no pass of it enqueued behind
                self._blk_masked[r] = int((block < 0).sum())
        if (block < 0).any():
            return 0
        fresh = [int(tok) for tok in block[int(t.tail[r]):]]
        out = fresh[:max(0, req.max_new - len(self._row_emitted[r]))]
        first = not self._row_emitted[r]
        self._row_emitted[r].extend(out)
        now = time.perf_counter()
        if first:
            self._first_token_metrics(req, r)
        elif out:
            gap = max(0.0, now - self._row_last_emit[r]) / len(out)
            for _ in out:
                self.itl_hist.observe(gap)
        self._row_last_emit[r] = now
        self._done[r] = any(tok == req.eos_id or tok in req.stop_tokens
                            for tok in fresh)
        self._push_stream(r, req)
        self._maybe_complete(r, pos=int(t.pos[r]) + self._run)
        return len(out)

    def _reset_flight(self) -> None:
        """No tick is in flight, and the two per-row inputs a step takes
        from the step before it (`_mixed_step_exe`) are zeros no row
        reads, placed as the step's own outputs are: another placement
        would be another signature of the same executable."""
        self._inflight: Optional[_FlightTick] = None
        prev = (np.zeros((self.n_slots,) + ((self._run,) if self._block
                                            else ()), np.int32),
                np.zeros((self.n_slots,), bool))
        if self._tp_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            prev = jax.device_put(prev, NamedSharding(self._tp_mesh,
                                                      PartitionSpec()))
        elif self._device is not None:
            prev = jax.device_put(prev, self._device)
        else:
            prev = (jnp.asarray(prev[0]), jnp.asarray(prev[1]))
        self._prev_nxt, self._prev_done = prev

    def _may_run_ahead(self) -> bool:
        """Whether the next mixed tick may be enqueued before the last
        one's results are read. Not while the lane must be quiescent
        between ticks, by what the loop can see: an export or handoff
        command re-parked for a row still in its prompt (it is served
        at every boundary, and `_serve_exports` would land each tick at
        once), a row is parked, or a handoff row's prompt is not
        consumed yet (`_maybe_hold` parks it when that tick's results
        are applied, and a parked row must not have been stepped on). A
        command that arrives while a tick is in flight is
        `_serve_exports`' to see."""
        if self._export_waiting:
            return False
        if (self._block is not None
                and self._block.reveal == "low_confidence_dynamic"):
            # The dynamic rule ends a block at a pass the host cannot
            # foresee: the next tick's passes are known once this one's
            # blocks are read.
            return False
        return not any(
            req is not None and (self._held[r] or (req.handoff
                                                   and self._prefilling[r]))
            for r, req in enumerate(self._row_req))

    def _drain_tick(self) -> None:
        """Outside the tick: land the tick in flight, if there is one. A
        device failure surfaces at its wait, as inside the tick."""
        if self._inflight is None:
            return
        try:
            self._land_tick()
        except Exception as exc:
            self._clock.idle()  # the tick raised between marks
            self._recover(exc)

    def _reset_prefill_accounting(self, row: int) -> None:
        self._row_chunks[row] = 0
        self._row_starved_ticks[row] = 0
        self._row_starved_us[row] = 0.0

    def _tick_formed(self, width: int, prefill_rows: List[int], chunk,
                     qlen, active, pos0=None,
                     max_tokens: Optional[int] = None) -> None:
        """The tick's batch is formed and its arguments are on their way:
        note which prefilling rows the token budget fed and which it
        starved, then mark the clock's `dispatch`. `ctx_tokens` is the
        context the attention kernel reads for the rows in the dispatch:
        `pos0 + qlen` (a decode row's pos + 1, a prefilling row's
        w0 + chunk); a recurrent step (no `pos0`) attends none. The
        uniform step reads every row by ONE call a layer, a row of the
        lane a row of the call: how that call's walk goes
        (`ops.paged_attention.walk_counts`: its live tiles, those whose
        first group the step before started, the tokens its DMAs fetch)
        is on the span beside it, and so is what ONE layer's pool write
        scatters (`write_slots`, `models.transformer.pool_write_slots`:
        the step's token list where the tick's caller states its bound,
        `max_tokens`, else every slot of the step) beside the tokens the
        tick holds (`write_tokens`), and the rows ONE layer's output
        projection and feed-forward compute (`out_slots`, `mlp_slots`:
        both `models.transformer.second_half_slots`, the same list under
        a dense feed-forward). A tick over a stated bound
        would lose the K/V of the tokens past it: it raises here, before the
        dispatch, and the loop counts it with the device's failures
        (`_recover`). `active`:
        the rows whose sample is real; the body of `_sample` they ask for
        (the step asks the same of the same controls on the device; a
        speculative step's first slot, its later ones never a dearer)
        goes on the tick's span as `sampler` and into the tick's
        `sample_*_ticks` counter."""
        n_tokens = int(qlen.sum())
        if max_tokens is not None and n_tokens > max_tokens:
            raise RuntimeError(
                f"a tick of {n_tokens} tokens is over the step's bound of "
                f"{max_tokens} (token budget {self._mixed_budget} + "
                f"{self.n_slots} rows)")
        self._tick_sampler = SAMPLER_BODIES[int(sampler_body(
            self._temps, self._topps, self._topks, self._minps,
            active & ~self._done))]
        self._clock.note(sampler=self._tick_sampler)
        self._tick_starved = []
        for r in prefill_rows:
            if chunk[r] > 0:
                self._row_chunks[r] += 1
            else:
                self._tick_starved.append(r)
        fed = qlen > 0
        ctx_tokens = (int((pos0[fed] + qlen[fed]).sum())
                      if pos0 is not None else 0)
        if pos0 is not None and self._ragged_step is None:
            live, warm, fetched = walk_counts(
                pos0, qlen, width=width,
                group=self.cfg.n_heads // self.cfg.kv_heads,
                kv_heads=self.cfg.kv_heads,
                block_size=self._pool.block_size)
            half_slots = second_half_slots(self.cfg, qlen.shape[0], width,
                                           max_tokens)
            self._clock.note(
                walk_live_tiles=live, walk_warm_tiles=warm,
                walk_tokens_fetched=fetched, write_tokens=n_tokens,
                write_slots=pool_write_slots(qlen.shape[0], width,
                                             max_tokens),
                out_slots=half_slots, mlp_slots=half_slots)
        self._clock.dispatch(width, int(fed.sum()), ctx_tokens)

    def _slide_window_blocks(self, pos0, qlen) -> None:
        """Before a tick's dispatch, for every row it feeds: give back the
        window blocks wholly behind the first column the row's first new
        token still sees, and take blocks through its last new token. A
        row so holds at most the window, a chunk and a block of tokens,
        the bound `_wpool` is sized by. Freed table entries become the
        null block, which the window read never walks. The tick's span
        says what the two kinds of layer read (`ctx_tokens_full`,
        `ctx_tokens_window`: the rooflines' bytes), in how many tiles of
        each class the full layers read it (`_attn_tiles`), what was
        freed, and how many of the rows it feeds (`rows_fed`) the window
        binds (`rows_past_window`: their first new column is at or past
        it)."""
        pool, window = self._wpool, self.cfg.window
        bs, width = pool.block_size, self._wtables.shape[1]
        freed = read = 0
        with pool.lock:
            for r in np.flatnonzero(qlen > 0):
                p0, q = int(pos0[r]), int(qlen[r])
                seen = max(p0 - window + 1, 0)
                read += p0 + q - seen
                lo, hi = seen // bs, min((p0 + q - 1) // bs + 1, width)
                first, end = self._wspan[r]
                if lo > first:
                    behind = self._wtables[r, first:min(lo, end)]
                    pool.release_many(behind.tolist())
                    freed += len(behind)
                    self._wtables[r, first:min(lo, end)] = 0
                    first = lo
                if hi > end:
                    end = max(end, first)
                    self._wtables[r, end:hi] = pool.alloc(hi - end)
                    end = hi
                self._wspan[r] = (first, end)
        self._wfreed += freed
        fed = qlen > 0
        self._clock.note(ctx_tokens_full=int((pos0[fed] + qlen[fed]).sum()),
                         ctx_tokens_window=read, window_blocks_freed=freed,
                         rows_fed=int(fed.sum()),
                         rows_past_window=int((pos0[fed] >= window).sum()),
                         **self._attn_tiles(qlen))

    def _attn_tiles(self, qlen, run_slots: int = 1) -> dict:
        """The live query tiles of a tick's full-attention read, by the
        class its step reads a row's run in
        (`ops.latent_attention.class_plan`): a row with one new token (on
        a block-decoding lane: a run of up to `run_slots`) is one short
        tile, a longer run ceil(q_len / height) tall ones."""
        short, tall = class_counts(qlen, self._chunk_cap,
                                   self.cfg.n_heads // self.cfg.kv_heads,
                                   run_slots)
        return {"attn_tiles_short": short, "attn_tiles_tall": tall}

    def _note_state_work(self, pos0, qlen) -> None:
        """What a tick of a lane with both kinds of state asks of each, on
        its span: the tokens that go through the chunked form of the
        recurrence (and the rows they belong to), the rows that take
        one step of it and the rows the step call's grid spans (the
        lane's slots: a row that takes no step costs an empty grid step
        and moves no byte), under the kernels' names in a trace (the
        model's `recurrence`: `gdn_*`, `kda_*` or `ssd_*`); the tokens the
        layers that attend read (`ctx_tokens_full` as a windowed lane's,
        with the tiles of each class that read them, or
        `ctx_tokens_latent` where the pool's two tensors differ in width:
        a latent pool, whose read has one class); and the state rows
        held."""
        fed = qlen > 0
        kernel = self.cfg.recurrence
        k_lanes, v_lanes = self._pool.cfg.kv_lanes
        read = "ctx_tokens_full" if k_lanes == v_lanes else "ctx_tokens_latent"
        self._clock.note(**{
            **(self._attn_tiles(qlen) if k_lanes == v_lanes else {}),
            f"{kernel}_chunk_tokens": int(qlen[qlen > 1].sum()),
            f"{kernel}_chunk_rows": int((qlen > 1).sum()),
            f"{kernel}_step_rows": int((qlen == 1).sum()),
            f"{kernel}_step_slots": len(qlen),
            read: int((pos0[fed] + qlen[fed]).sum()),
            "state_rows_held": self._spool.rows_held})

    def _note_block_work(self, pos0, qlen, active, commit) -> None:
        """What a tick of a block-decoding lane holds, on its span: the
        run a generating row feeds (`run_width`; the span's `width` stays
        a prompt chunk's), the rows in a denoise pass and in a commit
        pass, the tokens the block-causal read walks, the pairs it keeps
        and the tiles of each class that read them."""
        fed = qlen > 0
        run, blocks = self._run, qlen[fed].astype(np.int64) // self._run
        self._clock.note(
            run_width=run, denoise_rows=int(active.sum()),
            commit_rows=int(commit.sum()),
            ctx_tokens_full=int((pos0[fed] + qlen[fed]).sum()),
            # (query, key) pairs the block mask keeps: a query sees every
            # position up to its own block's end.
            attn_pairs=int((run * (blocks * pos0[fed] + run * blocks
                                   * (blocks + 1) // 2)).sum()),
            **self._attn_tiles(qlen, run))

    def _count_moe(self, rows, fed: int) -> None:
        """`rows` (L_moe, E): what each expert of each expert layer took
        this tick, back with the tick's other results; `fed`: the tokens
        the tick fed, each routed to `top_k` experts a layer (on a lane
        that holds a share of the experts, more pairs than formed a row
        here). Into stats()["moe"] and onto the tick's span (before
        `TickClock.end`)."""
        held, touched = int(rows.sum()), int((rows > 0).sum())
        assignments = fed * self.cfg.top_k * self.cfg.n_moe_layers
        self._moe_rows += rows
        moe = self._stats["moe"]
        moe["assignments"] += assignments
        moe["experts_touched"] += touched
        self._clock.note(moe_assignments=assignments,
                         moe_experts_touched=touched)
        if "assignments_held" in moe:
            moe["assignments_held"] += held
            self._clock.note(moe_assignments_held=held)

    def _tick_done(self, prefill_tokens: int, decode_rows: int, width: int,
                   spec: Optional[dict] = None,
                   starved: Optional[List[int]] = None) -> None:
        """End of a mixed or speculative tick: close the clock and record
        the tick's span(s). `mixed_step` keeps the fields its readers
        know (duration_us, width, prefill_tokens, decode_rows) and
        carries the clock's phases; a speculative tick also records
        `spec_verify`. `starved`:
        the rows the tick's budget starved, where the tick that ends is
        not the last one formed."""
        live = any(req is not None and not self._held[r]
                   for r, req in enumerate(self._row_req))
        start_ts, dur_us, phases = self._clock.end(live, self.trace_node)
        for r in (self._tick_starved if starved is None else starved):
            self._row_starved_ticks[r] += 1
            self._row_starved_us[r] += dur_us
        if self.tracer is None:
            return
        if spec is not None:
            self.tracer.record(
                "tick", "spec_verify", self.trace_node, dur_us,
                start_ts=start_ts,
                attrs={"decode_rows": int(decode_rows), **spec,
                       "width": int(width)})
        self.tracer.record(
            "tick", "mixed_step", self.trace_node, dur_us,
            start_ts=start_ts,
            attrs={"prefill_tokens": int(prefill_tokens),
                   "decode_rows": int(decode_rows),
                   "width": int(width), **phases})

    def _tick_mixed(self) -> None:
        """One mixed tick: form the ragged batch (decode rows x 1 token +
        admitting rows x a budgeted prefill chunk) and issue exactly ONE
        compiled dispatch. Budget rule: decode rows are always included
        (1 token each); the remaining budget splits over prefilling rows
        in row order — the first prefilling row always gets at least one
        token, so admission can never deadlock behind a saturated decode
        batch. What the step takes per row (positions, lengths, tokens,
        sampling controls, the block tables) goes to the device as ONE
        array made fresh here, the control block: `TickBlock` states its
        layout, `form_transfers` counts the arrays sent.

        A pipeline one tick deep: the tick is formed and enqueued BEFORE
        the results of the tick in flight are read and applied
        (`_land_tick`), so the host's work runs beside the device's.
        What a tick's form needs of the tick before is advanced when
        that tick is enqueued (`_pos`, `_row_w0`, `_prefilling`: they
        depend on positions alone); its tokens stay on the device, and
        a row whose sample is in flight takes column 0 from there
        (`from_prev`). A row whose LAST token is in flight (by its
        budget or the cache's end, both known here) is left out. An end
        the host cannot know yet (EOS, a stop token) leaves the row one
        tick too many in the batch: the step lets it ride as a done row
        (token discarded, counts untouched, its write confined to the
        column `pos`, whose block `_ensure_capacity_paged` holds for
        it), `_land_tick` releases it and counts `lagged_rows`; its
        emitted tokens are the synchronous order's, token for token.
        Where the lane must be quiescent between ticks
        (`_may_run_ahead`) the tick in flight is landed first and this
        one before the call returns: the synchronous order is the
        drained case of the same code."""
        ahead_ok = self._may_run_ahead()
        if not ahead_ok and self._inflight is not None:
            self._land_tick()
        prev = self._inflight
        pool = self._pool
        B = self.n_slots
        # A block-decoding lane (`ModelSpec.block_decode`): a generating
        # row feeds its block of `run` tokens every tick, a denoise pass
        # or the commit; everywhere else a row feeds one token.
        blockwise, run = self._block, self._run
        self._clock.begin()
        if prev is not None:
            self._clock.probe(prev.nxt.is_ready())
        eos_vec = np.full((B,), -1, np.int32)
        controls = False
        n_decode = 0
        prefill_rows: List[int] = []
        ending = [False] * B
        for r, req in enumerate(self._row_req):
            if req is None:
                continue
            if req.eos_id >= 0:
                eos_vec[r] = req.eos_id
            if blockwise is None and (req.rep_penalty != 1.0
                                      or req.stop_tokens):
                controls = True  # a block's stop tokens are the host's
            if self._held[r]:
                continue  # parked handoff rows: no budget, no decode slot
            if self._prefilling[r]:
                prefill_rows.append(r)
            elif (prev is not None and prev.sampled(r, req)
                  and self._last_sample_in_flight(r, req)):
                # The tick in flight brings this row's last token, by
                # the rule `_maybe_complete` will end it with there.
                ending[r] = True
            else:
                n_decode += 1
        if not n_decode and not prefill_rows:
            # Nothing to step: every row's last token is in flight, or
            # the last row left with the tick landed above.
            if prev is not None:
                self._land_tick()
            else:
                self._clock.idle()
            return
        budget_left = max(run, self._effective_mixed_budget()
                          - n_decode * run)
        chunk = np.zeros((B,), np.int32)
        for r in prefill_rows:
            remaining = max(self._row_L[r], 1) - self._row_w0[r]
            c = min(remaining, self._chunk_cap, budget_left)
            chunk[r] = max(0, c) // run * run    # whole blocks
            budget_left -= chunk[r]
        chunked = bool(prefill_rows) and chunk.max() > 0
        width = self._chunk_cap if chunked else run

        tokens = np.zeros((B, width), np.int32)
        pos0 = np.zeros((B,), np.int32)
        qlen = np.zeros((B,), np.int32)
        sample_slot = np.zeros((B,), np.int32)
        fold_pos = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        from_prev = np.zeros((B,), bool)
        completing = np.zeros((B,), bool)
        reveal = np.zeros((B,), np.int32)
        commit = np.zeros((B,), bool) if blockwise is not None else None
        prefill_tokens = 0
        for r, req in enumerate(self._row_req):
            if req is None or ending[r]:
                continue  # free rows: qlen 0, inactive, null-block writes
            if self._prefilling[r]:
                w0 = self._row_w0[r]
                c = int(chunk[r])
                Leff = max(self._row_L[r], 1)
                pos0[r] = w0
                qlen[r] = c
                prefill_tokens += c
                if c > 0:
                    tokens[r, :c] = self._row_prompt[r][w0:w0 + c]
                    if w0 <= Leff - 1 < w0 + c:
                        # This chunk reaches the prompt's last token: the
                        # dispatch samples the request's FIRST token from
                        # slot Leff-1-w0 at logical position L (the exact
                        # `_first_token` rule of the dense path).
                        completing[r] = True
                        if blockwise is not None:
                            continue  # the first block brings the tokens
                        active[r] = True
                        sample_slot[r] = Leff - 1 - w0
                        fold_pos[r] = self._row_L[r]
            elif blockwise is not None:
                # The row's block at its start, every pass of it. Masked
                # positions left (counts alone, known as ticks are
                # formed): a denoise pass reveals some of them; none: the
                # commit, whose K and V are the ones that stay.
                pos0[r] = fold_pos[r] = self._pos[r]
                qlen[r] = run
                if prev is not None and prev.sampled(r, req):
                    from_prev[r] = True  # its block is still on the device
                else:
                    tokens[r, :run] = self._blk_known[r]
                if self._blk_masked[r] > 0:
                    active[r] = not self._done[r]
                    reveal[r] = min(int(self._blk_masked[r]),
                                    blockwise.tokens_per_pass)
                else:
                    commit[r] = True
            else:
                pos0[r] = self._pos[r]
                qlen[r] = 1
                if prev is not None and prev.sampled(r, req):
                    from_prev[r] = True  # its token is still on the device
                else:
                    tokens[r, 0] = self._tok[r]
                fold_pos[r] = int(self._pos[r]) + 1
                # Parked handoff rows ride inactive (like done rows):
                # writes confined to the not-yet-valid column `pos`,
                # sampled token discarded, host state untouched below.
                active[r] = not self._done[r] and not self._held[r]

        # ONE dispatch, under the pool lock (it donates the pool buffers).
        # The rows' tables and controls go in a block made fresh here
        # (`TickBlock`): the loop changes them (admission, release) while
        # the step may still read it.
        if self._windowed:
            self._slide_window_blocks(pos0, qlen)
        with pool.lock:
            pool_args, tables = (pool.caches,), (self._tables,)
            if self._quant:
                pool_args += (pool.scales,)
            if self._windowed:
                # One of each a kind of block, (full, window).
                pool_args = ((pool.caches, self._wpool.caches),)
                tables += (self._wtables,)
            extra = {}
            if self._hybrid:
                # The block pool and the state pool, the rows' table and
                # their state rows; both pools are donated. The state
                # arrays are this thread's alone (no admission write, no
                # export: the family declares no chain), so the block
                # pool's lock is the only one the dispatch is ordered by.
                self._note_state_work(pos0, qlen)
                pool_args = ((pool.caches,
                              self._spool.slab),)  # lint: lockfree-ok tick thread's alone
                extra["state_rows"] = self._spool.rows
            if blockwise is not None:
                extra["reveal"] = reveal
                self._note_block_work(pos0, qlen, active, commit)
            if self._passes > 1:
                self._clock.note(ut_steps=self._passes,
                                 kv_planes=self._pool.cfg.n_layers)
            if controls:
                extra.update(pens=self._pens, stops=self._stops)
            block = self._tick_block(width, controls).pack(
                tables, tokens=tokens, pos0=pos0, qlen=qlen,
                sample_slot=sample_slot, fold_pos=fold_pos, active=active,
                done=self._done, from_prev=from_prev, seeds=self._seeds,
                temps=self._temps, topps=self._topps, topks=self._topks,
                minps=self._minps, eos_vec=eos_vec, **extra)
            self._stats["mixed"]["form_transfers"] += 1
            common = (self._step_params, *pool_args, jnp.asarray(block),
                      self._prev_nxt, self._prev_done)
            if prev is not None:
                # Before `_tick_formed` marks the dispatch, which reads
                # what the probes saw.
                self._clock.probe(prev.nxt.is_ready())
            self._tick_formed(width, prefill_rows, chunk, qlen, active,
                              pos0, self._tick_max_tokens)
            if controls:
                common += (self._ensure_counts(),)
            out = self._mixed_step_exe(width, controls)(*common)
            if self._windowed:
                pool.caches, self._wpool.caches = out[0]
            elif self._hybrid:
                (pool.caches,
                 self._spool.slab) = out[0]  # lint: lockfree-ok tick thread's alone
            else:
                pool.caches = out[0]
            if self._quant:
                pool.scales = out[1]
                out = out[2:]
            else:
                out = out[1:]
            moe_rows = None
            if self._ragged_step is not None:
                out, moe_rows = out[:-1], out[-1]
            if controls:
                nxt, done, self._counts = out
            else:
                nxt, done = out
        self._prev_nxt, self._prev_done = nxt, done
        start_host_copies(nxt, done,
                          *(() if moe_rows is None else (moe_rows,)))
        # What the next tick's form needs of this one depends on
        # positions alone and moves now; the tokens move when they land.
        decode = active & ~completing
        for r in prefill_rows:
            self._row_w0[r] += int(chunk[r])
            if completing[r]:
                self._prefilling[r] = False
        tail = None
        if blockwise is None:
            for r in np.flatnonzero(decode):
                self._pos[r] = min(int(self._pos[r]) + 1, self.max_seq - 1)
        else:
            # A denoise pass leaves fewer masked; a commit opens the row's
            # next block, all masked (the flight tick keeps this block's
            # start and tail).
            tick_pos, tail = self._pos.copy(), self._blk_tail.copy()
            self._blk_masked -= reveal
            for r in np.flatnonzero(commit):
                self._pos[r] += run
                self._blk_masked[r] = run
                self._blk_tail[r] = 0
                self._blk_known[r] = -1
        tick = _FlightTick(
            nxt=nxt, done=done, moe_rows=moe_rows,
            reqs=list(self._row_req), active=active, completing=completing,
            decode=decode,
            pos=self._pos.copy() if blockwise is None else tick_pos,
            starved=self._tick_starved,
            sampler=self._tick_sampler, fed=int(qlen.sum()),
            prefill_tokens=prefill_tokens, n_decode=n_decode,
            # The span's `width` is a chunk's compiled width: 1 where the
            # tick holds no prompt chunk, a run's width beside it
            # (`run_width`).
            width=width if chunked else 1,
            overlapped=int(prev is not None), commit=commit, tail=tail)
        if prev is not None:
            self._land_tick(behind=tick)
        else:
            self._inflight = tick
            if ahead_ok:
                self._clock.leave()  # the loop goes on beside the device
            else:
                self._land_tick()

    def _land_tick(self, behind: Optional[_FlightTick] = None) -> None:
        """Wait for the tick in flight and apply its results host-side:
        each stepped row's token, end and stream. `behind`: the tick
        enqueued after it, which a row that ends here by EOS or a stop
        token rides as a done row (`lagged_rows`)."""
        t, self._inflight = self._inflight, behind
        self._clock.wait()
        nxt = np.array(t.nxt)
        done_new = np.array(t.done)
        if t.moe_rows is not None and "moe" in self._stats:
            self._count_moe(np.asarray(t.moe_rows), t.fed)
        self._clock.apply()
        # Dispatch counted only past the host sync above — a device-step
        # failure surfaces asynchronously AT that sync (not at the
        # enqueue), and a recovered failure must leave dispatches and
        # ticks equal (the invariant scrapers and the bench assert),
        # also with a second tick enqueued behind the failed one: both
        # are dropped uncounted. Still a separate statement/site from
        # the tick counter below.
        self._stats["mixed"]["dispatches"] += 1

        m = self._stats["mixed"]
        m["ticks"] += 1
        m[f"sample_{t.sampler}_ticks"] += 1
        m["overlapped_ticks"] += t.overlapped
        m["prefill_tokens"] += t.prefill_tokens
        if self._block is None:
            m["decode_tokens"] += t.n_decode
        else:
            m["denoise_passes"] += int(t.decode.sum())
            m["commit_passes"] += int(t.commit.sum())
        if self._passes > 1:
            m["layer_passes"] += m["kv_planes"]
        if t.prefill_tokens and t.n_decode:
            m["coscheduled_ticks"] += 1

        blocks_finished = 0
        for r in range(self.n_slots):
            req = self._row_req[r]
            if req is None or req is not t.reqs[r]:
                continue  # freed, or its slot given on, while the tick ran
            if self._block is not None:
                if t.completing[r]:
                    self._prompt_consumed(r, req)
                    self._maybe_complete(r, pos=int(t.pos[r]))
                elif t.decode[r]:
                    m["decode_tokens"] += self._land_block_row(t, r, req,
                                                               nxt[r])
                    blocks_finished += int(not (nxt[r] < 0).any())
                else:
                    continue  # mid-prompt, starved, or a commit pass
            elif t.completing[r]:
                self._complete_prefill_row(r, req, int(nxt[r]),
                                           bool(done_new[r]),
                                           pos=int(t.pos[r]))
            elif t.decode[r]:
                tok_r = int(nxt[r])
                self._tok[r] = tok_r
                self._done[r] = bool(done_new[r])
                if req.max_new - len(self._row_emitted[r]) > 0:
                    self._row_emitted[r].append(tok_r)
                    now = time.perf_counter()
                    if self._row_last_emit[r] > 0:
                        self.itl_hist.observe(
                            max(0.0, now - self._row_last_emit[r]))
                    self._row_last_emit[r] = now
                self._push_stream(r, req)
                self._maybe_complete(r, pos=int(t.pos[r]))
            else:
                continue  # mid-prompt, starved or parked: nothing sampled
            if (self._row_req[r] is None and behind is not None
                    and behind.stepped(r, req)):
                # Ended by what only the device knew: the tick behind
                # steps it once more, as a done row.
                m["lagged_rows"] += 1

        self._wake_streams()  # one wake a tick, after the last row's put
        if self._block is not None:
            m["blocks_finished"] += blocks_finished
            self._clock.note(blocks_finished=blocks_finished)
        if behind is not None:
            self._clock.probe(behind.nxt.is_ready())
        self._tick_done(t.prefill_tokens, t.n_decode, t.width,
                        starved=t.starved)

    def _tick_spec(self) -> None:
        """One SPECULATIVE ragged tick — the spec_k>0 replacement for
        `_tick_mixed`. Host side: ask the drafter for up to spec_k
        deterministic proposals per eligible decode row, form ONE ragged
        batch (decode rows: q_len = proposals+1 verify windows;
        admitting rows: their budgeted prefill chunk), issue exactly one
        compiled dispatch, and advance each row by its accepted prefix
        plus the corrected/bonus token. Rejected tails leave stale KV
        past the new `pos` — invisible by position masking, overwritten
        (write-before-attend) when the stream reaches those columns."""
        pool = self._pool
        B = self.n_slots
        S = self._spec_k + 1
        self._clock.begin()
        eos_vec = np.full((B,), -1, np.int32)
        controls = False
        n_decode = 0
        prefill_rows: List[int] = []
        for r, req in enumerate(self._row_req):
            if req is None:
                continue
            if req.eos_id >= 0:
                eos_vec[r] = req.eos_id
            if req.rep_penalty != 1.0 or req.stop_tokens:
                controls = True
            if self._held[r]:
                continue  # parked handoff rows: no budget, no proposals
            if self._prefilling[r]:
                prefill_rows.append(r)
            else:
                n_decode += 1
        chunk = np.zeros((B,), np.int32)
        # Mixed budget rule unchanged: decode rows count 1 each (the
        # verify window RE-DERIVES tokens, it does not widen the
        # budgeted stream), remainder over admitting rows.
        budget_left = max(1, self._effective_mixed_budget() - n_decode)
        for r in prefill_rows:
            remaining = max(self._row_L[r], 1) - self._row_w0[r]
            c = min(remaining, self._chunk_cap, budget_left)
            chunk[r] = max(0, c)
            budget_left -= chunk[r]

        # Drafting (host-side, before batch formation). The cap keeps a
        # window inside both the row's token budget (never propose past
        # max_tokens) and the cache (window columns < max_seq).
        drafts: List[List[int]] = [[] for _ in range(B)]
        proposed = 0
        for r, req in enumerate(self._row_req):
            if (req is None or self._done[r] or self._held[r]
                    or self._bo_spec_off
                    or self._prefilling[r]):
                # Brownout spec suspension: no proposals — every row
                # rides q_len 1 through the same compiled dispatch
                # (greedy streams byte-identical, drafter work skipped).
                continue
            kcap = min(self._spec_k,
                       req.max_new - len(self._row_emitted[r]) - 1,
                       self.max_seq - 2 - int(self._pos[r]))
            if kcap <= 0 or not self._spec_eligible(req):
                continue
            em = self._row_emitted[r]
            scan = getattr(self._drafter, "max_scan", 0)
            if scan:
                # The drafter only scans its last max_scan tokens —
                # slice the tails BEFORE concatenating so a long prompt
                # costs O(max_scan), not O(L), of list copy per row per
                # tick on the decode thread.
                need = scan - len(em)
                pp = self._row_prompt_toks[r] or []
                ctx = (pp[-need:] if need > 0 else []) + em[-scan:]
            else:
                ctx = (self._row_prompt_toks[r] or []) + em
            d = self._drafter.propose(ctx, kcap)[:kcap]
            if d:
                drafts[r] = [int(t) for t in d]
                proposed += len(drafts[r])

        # Exactly two compiled ragged widths per controls variant:
        # S (decode-only ticks) and max(chunk cap, S) (ticks that carry
        # a prefill chunk).
        width = S
        if prefill_rows and chunk.max() > 0:
            width = max(self._chunk_cap, S)
        tokens = np.zeros((B, width), np.int32)
        pos0 = np.zeros((B,), np.int32)
        qlen = np.zeros((B,), np.int32)
        sample_slot = np.zeros((B,), np.int32)
        fold0 = np.zeros((B,), np.int32)
        n_draft = np.zeros((B,), np.int32)
        stoch = np.zeros((B,), bool)
        active = np.zeros((B,), bool)
        completing = [False] * B
        prefill_tokens = 0
        for r, req in enumerate(self._row_req):
            if req is None:
                continue
            if self._prefilling[r]:
                w0 = self._row_w0[r]
                c = int(chunk[r])
                Leff = max(self._row_L[r], 1)
                pos0[r] = w0
                qlen[r] = c
                prefill_tokens += c
                if c > 0:
                    tokens[r, :c] = self._row_prompt[r][w0:w0 + c]
                    if w0 <= Leff - 1 < w0 + c:
                        completing[r] = True
                        active[r] = True
                        sample_slot[r] = Leff - 1 - w0
                        fold0[r] = self._row_L[r]
            else:
                nd = len(drafts[r])
                pos0[r] = self._pos[r]
                qlen[r] = 1 + nd
                tokens[r, 0] = self._tok[r]
                if nd:
                    tokens[r, 1:1 + nd] = drafts[r]
                fold0[r] = int(self._pos[r]) + 1
                n_draft[r] = nd
                # Only DRAFTED temp>0 rows ever take the rejection path;
                # the flag below selects the compiled variant, so the
                # all-greedy common case never traces it.
                stoch[r] = req.temperature > 0 and nd > 0
                # Parked handoff rows ride inactive like done rows.
                active[r] = not self._done[r] and not self._held[r]
        stochastic = bool(stoch.any())

        # ONE dispatch, under the pool lock (it donates the pool buffers).
        with pool.lock:
            pool_args = (pool.caches,)
            if self._quant:
                pool_args += (pool.scales,)
            common = (self._step_params, *pool_args, jnp.asarray(self._tables),
                      jnp.asarray(tokens), jnp.asarray(pos0),
                      jnp.asarray(qlen), jnp.asarray(sample_slot),
                      jnp.asarray(fold0), jnp.asarray(n_draft),
                      jnp.asarray(stoch), jnp.asarray(active),
                      jnp.asarray(self._done), jnp.asarray(self._seeds),
                      jnp.asarray(self._temps), jnp.asarray(self._topps),
                      jnp.asarray(self._topks), jnp.asarray(self._minps),
                      jnp.asarray(eos_vec))
            self._tick_formed(width, prefill_rows, chunk, qlen, active,
                              pos0)
            if controls:
                out = self._spec_step_exe(width, True, stochastic)(
                    *common, self._ensure_counts(),
                    jnp.asarray(self._pens), jnp.asarray(self._stops))
            else:
                out = self._spec_step_exe(width, False, stochastic)(*common)
            pool.caches = out[0]
            if self._quant:
                pool.scales = out[1]
                out = out[2:]
            else:
                out = out[1:]
            if controls:
                emitted, n_emit, n_acc, done, self._counts = out
            else:
                emitted, n_emit, n_acc, done = out
        self._clock.wait()
        start_host_copies(emitted, n_emit, n_acc, done)
        emitted_h = np.array(emitted)
        n_emit_h = np.array(n_emit)
        n_acc_h = np.array(n_acc)
        done_new = np.array(done)
        self._clock.apply()
        # Dispatch counted only past the host sync (failure surfaces AT
        # the sync; a recovered failure must leave dispatches == ticks).
        # Separate statement/site from the tick counters below, so the
        # one-dispatch-per-tick invariant stays independently assertable.
        sp = self._stats["spec"]
        sp["dispatches"] += 1
        m = self._stats["mixed"]
        m["dispatches"] += 1

        sp["ticks"] += 1
        sp["proposed_tokens"] += proposed
        sp["draft_dispatches"] = getattr(self._drafter, "dispatches", 0)
        m["ticks"] += 1
        m[f"sample_{self._tick_sampler}_ticks"] += 1
        m["prefill_tokens"] += prefill_tokens
        if prefill_tokens and n_decode:
            m["coscheduled_ticks"] += 1

        accepted = 0
        decode_emitted = 0
        for r in list(range(B)):
            req = self._row_req[r]
            if req is None:
                continue
            if self._held[r]:
                continue  # parked: nothing was dispatched for this row
            if self._prefilling[r]:
                self._row_w0[r] += int(chunk[r])
                if not completing[r]:
                    continue
                self._complete_prefill_row(r, req, int(emitted_h[r, 0]),
                                           bool(done_new[r]))
                continue
            ne = int(n_emit_h[r])
            toks = [int(t) for t in emitted_h[r, :ne]]
            accepted += int(n_acc_h[r])
            decode_emitted += ne
            if ne:
                sp["row_ticks"] += 1
            self._done[r] = bool(done_new[r])
            if ne:
                self._tok[r] = toks[-1]
                # The done-marking token (EOS/stop) is never written to
                # the cache — same rule as plain decode's pos freeze.
                adv = ne - 1 if self._done[r] else ne
                self._pos[r] = min(int(self._pos[r]) + adv,
                                   self.max_seq - 1)
                need = req.max_new - len(self._row_emitted[r])
                if need > 0:
                    self._row_emitted[r].extend(toks[:need])
                    now = time.perf_counter()
                    if self._row_last_emit[r] > 0:
                        self.itl_hist.observe(
                            max(0.0, now - self._row_last_emit[r]))
                    self._row_last_emit[r] = now
            self._push_stream(r, req)
            self._maybe_complete(r)
            if self._row_req[r] is not None and not self._done[r]:
                self._trim_row_tail(r, req)
        sp["accepted_tokens"] += accepted
        sp["emitted_tokens"] += decode_emitted
        m["decode_tokens"] += decode_emitted

        self._tick_done(prefill_tokens, n_decode, width,
                        spec={"proposed": int(proposed),
                              "accepted": int(accepted)})

    def _tick_slab_mixed(self) -> None:
        """One mixed tick for the state_slab family: the SAME batch
        formation, token-budget rule, and post-processing as
        `_tick_mixed`, dispatched through the family's step function
        (`_slab_mixed_exe`) — admitting rows consume budgeted prompt
        chunks through the recurrence, decode rows advance one step,
        all in ONE dispatch. Brownout budget scaling, handoff holds,
        and stream identity carry over unchanged (tested)."""
        spool = self._spool
        B = self.n_slots
        self._clock.begin()
        eos_vec = np.full((B,), -1, np.int32)
        controls = False
        n_decode = 0
        prefill_rows: List[int] = []
        for r, req in enumerate(self._row_req):
            if req is None:
                continue
            if req.eos_id >= 0:
                eos_vec[r] = req.eos_id
            if req.rep_penalty != 1.0 or req.stop_tokens:
                controls = True
            if self._held[r]:
                continue  # parked handoff rows: no budget, no decode slot
            if self._prefilling[r]:
                prefill_rows.append(r)
            else:
                n_decode += 1
        budget_left = max(1, self._effective_mixed_budget() - n_decode)
        chunk = np.zeros((B,), np.int32)
        for r in prefill_rows:
            remaining = max(self._row_L[r], 1) - self._row_w0[r]
            c = min(remaining, self._chunk_cap, budget_left)
            chunk[r] = max(0, c)
            budget_left -= chunk[r]
        width = self._chunk_cap if prefill_rows and chunk.max() > 0 else 1

        tokens = np.zeros((B, width), np.int32)
        qlen = np.zeros((B,), np.int32)
        sample_slot = np.zeros((B,), np.int32)
        fold_pos = np.zeros((B,), np.int32)
        step_ok = np.zeros((B,), bool)
        active = np.zeros((B,), bool)
        completing = [False] * B
        prefill_tokens = 0
        for r, req in enumerate(self._row_req):
            if req is None:
                continue  # free rows: qlen 0, frozen, null-row writes
            if self._prefilling[r]:
                w0 = self._row_w0[r]
                c = int(chunk[r])
                Leff = max(self._row_L[r], 1)
                qlen[r] = c
                prefill_tokens += c
                step_ok[r] = c > 0
                if c > 0:
                    tokens[r, :c] = self._row_prompt[r][w0:w0 + c]
                    if w0 <= Leff - 1 < w0 + c:
                        completing[r] = True
                        active[r] = True
                        sample_slot[r] = Leff - 1 - w0
                        fold_pos[r] = self._row_L[r]
            else:
                qlen[r] = 1
                tokens[r, 0] = self._tok[r]
                fold_pos[r] = int(self._pos[r]) + 1
                # Parked handoff rows ride frozen (like done rows):
                # state untouched, sampled token discarded.
                active[r] = not self._done[r] and not self._held[r]
                step_ok[r] = active[r]
        row_ids = np.asarray([rid if rid >= 0 else 0
                              for rid in self._slab_rows], np.int32)

        # ONE dispatch, under the pool lock (it donates the slab).
        with spool.lock:
            common = (self._step_params, spool.slab, jnp.asarray(row_ids),
                      jnp.asarray(tokens), jnp.asarray(qlen),
                      jnp.asarray(sample_slot), jnp.asarray(fold_pos),
                      jnp.asarray(step_ok), jnp.asarray(active),
                      jnp.asarray(self._done), jnp.asarray(self._seeds),
                      jnp.asarray(self._temps), jnp.asarray(self._topps),
                      jnp.asarray(self._topks), jnp.asarray(self._minps),
                      jnp.asarray(eos_vec))
            self._tick_formed(width, prefill_rows, chunk, qlen, active)
            if controls:
                out = self._slab_mixed_exe(width, True)(
                    *common, self._ensure_counts(),
                    jnp.asarray(self._pens), jnp.asarray(self._stops))
            else:
                out = self._slab_mixed_exe(width, False)(*common)
            spool.slab = out[0]
            out = out[1:]
            if controls:
                nxt, done, self._counts = out
            else:
                nxt, done = out
        self._clock.wait()
        start_host_copies(nxt, done)
        nxt = np.array(nxt)
        done_new = np.array(done)
        self._clock.apply()
        # Dispatch counted only past the host sync (the `_tick_mixed`
        # rule: a recovered failure must leave dispatches == ticks).
        self._stats["mixed"]["dispatches"] += 1

        m = self._stats["mixed"]
        m["ticks"] += 1
        m[f"sample_{self._tick_sampler}_ticks"] += 1
        m["prefill_tokens"] += prefill_tokens
        m["decode_tokens"] += n_decode
        if prefill_tokens and n_decode:
            m["coscheduled_ticks"] += 1

        for r in list(range(B)):
            req = self._row_req[r]
            if req is None:
                continue
            if self._held[r]:
                continue  # parked: nothing was dispatched for this row
            if self._prefilling[r]:
                self._row_w0[r] += int(chunk[r])
                if not completing[r]:
                    continue
                self._complete_prefill_row(r, req, int(nxt[r]),
                                           bool(done_new[r]))
                continue
            tok_r = int(nxt[r])
            self._tok[r] = tok_r
            self._done[r] = bool(done_new[r])
            if not self._done[r]:
                self._pos[r] = min(int(self._pos[r]) + 1, self.max_seq - 1)
            if req.max_new - len(self._row_emitted[r]) > 0:
                self._row_emitted[r].append(tok_r)
                now = time.perf_counter()
                if self._row_last_emit[r] > 0:
                    self.itl_hist.observe(
                        max(0.0, now - self._row_last_emit[r]))
                self._row_last_emit[r] = now
            self._push_stream(r, req)
            self._maybe_complete(r)

        self._tick_done(prefill_tokens, n_decode, width)

    def _loop_body(self) -> None:
        # The lanes whose ticks are marked say which of the loop's
        # statements run (`TickClock.loop_part`); the dense cache's chunk
        # loop marks nothing.
        marked = self._mixed
        part = self._clock.loop_part if marked else lambda name: None
        while self._running:
            if marked:
                self._clock.admit()
            # The ticks that do not land through `_land_tick`, a row's
            # first token at admission, an expired or failed row.
            self._wake_streams()
            part("exports")
            now = time.monotonic()
            if self._flight_capacity:
                # One bounded record per tick; the wall delta since the
                # previous heartbeat IS the previous iteration's total
                # dispatch + bookkeeping time (idle waits included).
                self._flight_sample(now - self._last_tick)
            if self._profile_ticks_left > 0:
                # Tick-bounded jax.profiler capture (start_profile).
                self._profile_ticks_left -= 1
                if self._profile_ticks_left == 0:
                    from tpu_engine.utils import tracing

                    self._profile_result = tracing.profiler_stop()
            self._last_tick = now  # liveness heartbeat
            # Live rows' block growth outranks new admissions for pool
            # space (an admitted row must never be starved mid-stream by
            # a newcomer).
            if self._mixed:
                # Export commands run FIRST: between ticks the row is
                # quiescent (`_serve_exports` lands a tick in flight
                # before it serves one), and an export ahead of
                # admissions can never observe a half-admitted batch.
                self._serve_exports()
            part("capacity")
            if self._paged:
                self._ensure_capacity_paged()
            # Admit as many prefilled requests as there are free rows —
            # deferred (pool-pressure) admissions first, in arrival
            # order; block briefly when completely idle.
            part("admit")
            free = self._free_rows()
            admitted_any = False
            while free:
                from_pending = bool(self._mixed and self._pending)
                if from_pending:
                    item = self._pending[0]
                else:
                    try:
                        item = self._ready.get(
                            timeout=0.02 if not admitted_any
                            and len(free) == self.n_slots else 0.0)
                    except queue.Empty:
                        break
                if item is None:
                    return
                req = item[0]
                if req.deadline is not None and req.deadline.expired():
                    # Prefilled but the budget ran out before a row freed:
                    # drop the KV block instead of occupying a slot.
                    if from_pending:
                        self._pending.popleft()
                    self._discard_item(item)
                    self._cancel_deadline(
                        req, "deadline expired before row admission")
                    continue
                try:
                    self._admit(item, free[0])
                    free.pop(0)
                    if from_pending:
                        self._pending.popleft()
                    admitted_any = True
                    if req.sink is not None and req.t_ready:
                        # Hand-off by the prefill thread -> a row, time
                        # parked under pool pressure included; ends where
                        # the prefill (or alloc) stage starts.
                        req.sink.between("slot_wait", req.t_ready,
                                         req.t_admit, parked=from_pending)
                except PoolExhausted as exc:
                    if req.migrate is not None:
                        # Imports are never parked: their transfer runs
                        # under a bounded timeout, and the replay
                        # fallback needs nothing from this lane. Fail
                        # RETRYABLE, release the radix pins, move on.
                        if from_pending:
                            self._pending.popleft()
                        self._discard_item(item)
                        self._bump_migration("import_rejected")
                        self._fail_request(req, ImportRefused(
                            f"migration import refused: {exc}"))
                        continue
                    if self._slab:
                        # A state_slab request needs exactly ONE row,
                        # and the pool holds >= 1 usable row by
                        # construction — park until a completion frees
                        # one (no impossible-fit case, no pins to drop).
                        if not from_pending:
                            self._pending.append(item)
                        if all(r is None for r in self._row_req):
                            time.sleep(0.005)
                        break
                    # No blocks even after eviction. A request larger
                    # than the whole pool can never admit — fail it;
                    # otherwise park it until completions free blocks.
                    bs = self._pool.block_size
                    cols = min(min(item[4], self.max_seq - 1)
                               + self._decode_horizon + 1, self.max_seq)
                    nb_need = max(item[3] // bs, (cols - 1) // bs + 1)
                    if nb_need > self._pool.num_blocks - 1:
                        if from_pending:
                            self._pending.popleft()
                        self._discard_item(item)
                        self._fail_request(req, ValueError(
                            f"prompt needs {nb_need} KV blocks but the "
                            f"pool holds {self._pool.num_blocks - 1}"))
                        continue
                    if not from_pending:
                        # Park WITHOUT the radix pins: a parked item
                        # holding pins makes its prefix unevictable,
                        # and two mutually-pinned parked items with no
                        # live rows would starve each other forever.
                        # Dropping them is fully correct: the item
                        # re-prefills from position 0 at the retry (the
                        # request just shares nothing).
                        self._discard_item(item)
                        item = item[:6] + ([], item[7], item[8])
                        self._pending.append(item)
                    if all(r is None for r in self._row_req):
                        # Nothing decoding => nothing will free blocks
                        # except concurrent radix pins draining; don't
                        # spin at full speed waiting for them.
                        time.sleep(0.005)
                    break
                except _StaleAdmission as exc:
                    # Per-request casualty of a pool rebuild — fail it,
                    # keep admitting (the pool itself is healthy again).
                    if from_pending:
                        self._pending.popleft()
                    self._fail_request(req, exc)
                    continue
                except Exception as exc:
                    # Row insertion donates the shared cache — treat any
                    # admit failure as a device-state loss.
                    if from_pending:
                        self._pending.popleft()
                    self._fail_request(item[0], exc)
                    self._recover(exc)
                    break
            part("expire")
            self._cancel_expired_rows()
            if self._mixed:
                # Handoff holds past their park window resume decoding
                # (the colocated fallback — the export never came).
                self._unpark_expired()
            if self._oneshot:
                # One-shot rows dispatch and complete HERE, before the
                # generative tick paths: their budget rule (max_new ==
                # 0) must never meet _maybe_complete's sweep, and a
                # mixed generate+score tick serves its single-tick work
                # first (the rows free for next tick's admissions).
                self._tick_stateless()
            # One-shot rows never enter a generative dispatch: any
            # still-occupied slot here is a brownout-deferred row
            # waiting for next tick, not decodable work.
            live = [r for r in range(self.n_slots)
                    if self._row_req[r] is not None
                    and self._row_req[r].oneshot is None]
            if not live:
                self._drain_tick()  # its rows left while it ran
                self._clock.idle()
                continue
            if self._mixed and all(self._held[r] for r in live):
                # Only parked handoff rows: no dispatchable work this
                # tick — idle briefly instead of spinning while the
                # export command (or the park bound) arrives.
                self._clock.idle()
                time.sleep(0.002)
                continue

            if self._mixed:
                # A lane with a pool or a slab: ONE ragged dispatch serves
                # this tick's decode rows and prefill chunks together
                # (admission folded into the decode dispatch — no second
                # device path to contend). Speculation upgrades decode
                # rows to verify windows in the SAME single dispatch.
                try:
                    if self._spec:
                        self._tick_spec()
                    elif self._slab:
                        self._tick_slab_mixed()
                    else:
                        self._tick_mixed()
                except Exception as exc:
                    self._clock.idle()  # the tick raised between marks
                    self._recover(exc)
                continue

            try:
                # The dense per-slot cache (`kv_block_size` 0): one decode
                # chunk of `step_chunk` steps over the fixed batch. -1 marks
                # rows with EOS disabled (and free rows): sampled tokens are
                # in [0, vocab) so `nxt == -1` never fires; done rows emit
                # -1 (discarded), and the embedding lookup of -1 clips
                # harmlessly under jit.
                eos_vec = np.full((self.n_slots,), -1, np.int32)
                controls = False
                for r, req in enumerate(self._row_req):
                    if req is not None and req.eos_id >= 0:
                        eos_vec[r] = req.eos_id
                    if req is not None and (req.rep_penalty != 1.0
                                            or req.stop_tokens):
                        controls = True
                if controls:
                    (self._caches, tok, pos, done, self._counts,
                     toks) = self._decode(True)(
                        self._step_params, self._caches,
                        jnp.asarray(self._tok),
                        jnp.asarray(self._pos), jnp.asarray(self._start),
                        jnp.asarray(self._done), jnp.asarray(self._seeds),
                        jnp.asarray(self._temps), jnp.asarray(self._topps),
                        jnp.asarray(self._topks), jnp.asarray(self._minps),
                        jnp.asarray(eos_vec),
                        self._ensure_counts(), jnp.asarray(self._pens),
                        jnp.asarray(self._stops))
                else:
                    self._caches, tok, pos, done, toks = self._decode(False)(
                        self._step_params, self._caches,
                        jnp.asarray(self._tok),
                        jnp.asarray(self._pos), jnp.asarray(self._start),
                        jnp.asarray(self._done), jnp.asarray(self._seeds),
                        jnp.asarray(self._temps), jnp.asarray(self._topps),
                        jnp.asarray(self._topks), jnp.asarray(self._minps),
                        jnp.asarray(eos_vec))
                start_host_copies(tok, pos, done, toks)
                # np.array (copy): np.asarray of a jax.Array is read-only
                # and the admit path mutates these vectors in place.
                self._tok = np.array(tok)
                self._pos = np.array(pos)
                self._done = np.array(done)
                toks_host = np.asarray(toks)
            except Exception as exc:
                self._recover(exc)
                continue
            self._stats["chunks"] += 1

            for r, req in enumerate(self._row_req):
                if req is None:
                    continue
                need = req.max_new - len(self._row_emitted[r])
                if need > 0:
                    self._row_emitted[r].extend(
                        int(t) for t in toks_host[r, :need])
                    # ITL sample: the gap since this row's previous
                    # visible tokens (one per delivery — the cadence a
                    # streaming client actually sees).
                    now = time.perf_counter()
                    if self._row_last_emit[r] > 0:
                        self.itl_hist.observe(
                            max(0.0, now - self._row_last_emit[r]))
                    self._row_last_emit[r] = now
                self._push_stream(r, req)  # fresh tokens flush per chunk
                self._maybe_complete(r)
