"""Autoregressive generation runtime: bucketed prefill + chunked scan decode.

The decode-loop scheduler the reference cannot express (SURVEY.md §6 hard
part (c): "decode loops don't fit the one-shot batchPredict contract").
TPU-first structure:

- **Prefill** compiles once per (batch bucket, prompt bucket): mixed-length
  prompts are LEFT-padded to the bucket so every sample's last token lands
  in the same column and decode advances with one scalar position.
- **Decode** is a jitted `lax.scan` over a fixed step chunk — one
  executable regardless of requested token counts; the host loops chunks
  and early-stops between them when every row has hit EOS (one cheap sync
  per chunk, never per token).
- **KV caches** are static-shape device-resident arrays (L, B, max_seq, H, D)
  allocated per batch bucket; no per-token retracing, no host round-trips
  inside a chunk.

Sampling: greedy (temperature 0) or categorical, per-row inside the compiled
chunk. Each row's PRNG key is `fold_in(PRNGKey(row_seed), logical_position)`
— a function of the request's seed and its own token position only — so a
seeded request samples identical tokens regardless of which other requests
the dynamic batcher co-batched it with, or which bucket it landed in.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from tpu_engine.models.registry import ModelSpec, create_model, _ensure_builtin_models_imported
from tpu_engine.utils.sampling import (
    expand_sampling_params,
    expand_stopping_params,
    stop_matrix,
    truncate_at_stops,
)
from tpu_engine.models.transformer import (
    TransformerConfig,
    init_caches,
    transformer_decode_step,
    transformer_prefill,
)

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def start_host_copies(*arrays) -> None:
    """Kick off device→host copies for several arrays together — the
    subsequent blocking reads then share one round trip instead of
    paying one each."""
    for a in arrays:
        try:
            a.copy_to_host_async()
        except AttributeError:
            pass


def pick_bucket(buckets: Sequence[int], n: int) -> int:
    """Smallest bucket >= n (largest bucket when n exceeds them all) —
    the ONE bucketing rule every decode scheduler shares."""
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


def left_pad_batch(prompts: Sequence[Sequence[int]], bb: int, pb: int,
                   min_len: int = 0):
    """Left-pad prompts into (bb, pb) buckets — the shared batch-assembly
    step of every decode path (mixed-length batches are LEFT-padded so all
    rows end at column pb-1 and decode advances together).

    Returns (tokens, attn_mask, pos_ids, start) as numpy arrays. `min_len`
    forces at least that many valid trailing columns per row (the
    speculative scheduler's idle bucket rows need one valid column so
    their attention isn't fully masked); 0 leaves empty prompts fully
    padded (start == pb)."""
    tokens = np.zeros((bb, pb), np.int32)
    attn_mask = np.zeros((bb, pb), np.int32)
    pos_ids = np.zeros((bb, pb), np.int32)
    start = np.full((bb,), pb - min_len, np.int32)
    if min_len:
        attn_mask[:, pb - min_len:] = 1
        pos_ids[:, pb - min_len:] = np.arange(min_len)
    for r, p in enumerate(prompts):
        p = list(p)[-pb:]  # truncate over-long prompts from the left
        L = max(len(p), min_len)
        tokens[r, pb - len(p):] = np.asarray(p, np.int32)
        attn_mask[r, pb - L:] = 1
        pos_ids[r, pb - L:] = np.arange(L)
        start[r] = pb - L
    return tokens, attn_mask, pos_ids, start


def right_pad_prompt(prompt: Sequence[int], pb: int) -> np.ndarray:
    """(1, pb) RIGHT-padded token row — the paged scheduler's 0-aligned
    batch-assembly step (`left_pad_batch`'s counterpart): token i sits at
    column i, so a shared prefix lands at identical logical columns
    whatever bucket each prompt picked — the alignment block-level radix
    sharing keys on. Over-long prompts truncate from the left, same rule
    as every other decode path."""
    tokens = np.zeros((1, pb), np.int32)
    p = list(prompt)[-pb:]
    if p:
        tokens[0, :len(p)] = np.asarray(p, np.int32)
    return tokens


def apply_repetition_penalty(logits, counts, penalty):
    """HF-style repetition penalty. logits (B, V) f32; counts (B, V) int32
    occurrence counts of every token already in the row's context (prompt
    + generated); penalty (B,) with 1.0 = disabled. Seen tokens' positive
    logits divide by the penalty, negative multiply — shrinking their
    probability either way."""
    seen = counts > 0
    p = jnp.maximum(penalty, 1e-6)[:, None]
    return jnp.where(seen, jnp.where(logits > 0, logits / p, logits * p),
                     logits)


def token_counts(rows: "Sequence[Sequence[int]]", n_rows: int,
                 vocab: int) -> np.ndarray:
    """(n_rows, vocab) int32 occurrence counts of each row's tokens —
    the host-side seed of the device-resident counts buffer the decode
    loops update as they sample."""
    out = np.zeros((n_rows, vocab), np.int32)
    for r, toks in enumerate(rows):
        if len(toks):
            ids = np.asarray(toks, np.int64)
            ids = ids[(ids >= 0) & (ids < vocab)]
            np.add.at(out[r], ids, 1)
    return out


def sampler_body(temperature, top_p, top_k, min_p, kept=None):
    """Which of `_sample`'s three bodies a tick's rows ask for: 0 greedy
    (no kept row has temperature > 0), 1 plain (some row samples, none
    filters: top_p >= 1, top_k 0, min_p 0), 2 filtered. Array arithmetic
    only, so `_sample` asks it of traced controls on the device and the
    scheduler of the same controls as numpy arrays when it counts the
    tick (`SAMPLER_BODIES` names the answer). `kept` (B,) marks the rows
    whose sample is real; without one every row counts."""
    samples = temperature > 0
    if kept is not None:
        samples = samples & kept
    filters = samples & ((top_p < 1) | (top_k > 0) | (min_p > 0))
    return samples.any().astype("int32") + filters.any().astype("int32")


SAMPLER_BODIES = ("greedy", "plain", "filtered")


def _draw(key_seed, pos, lg):
    """One row's draw, the same for a filtered and an unfiltered row:
    key fold_in(PRNGKey(seed), position) over the row's scaled (and
    possibly masked) logits."""
    key = jax.random.fold_in(jax.random.PRNGKey(key_seed), pos)
    return jax.random.categorical(key, lg)


def _plain_row(key_seed, pos, lg, t):
    return _draw(key_seed, pos, lg / jnp.maximum(t, 1e-6))


def _filtered_row(key_seed, pos, lg, t, p, k_limit, p_min):
    lg = lg / jnp.maximum(t, 1e-6)
    sorted_lg = jnp.sort(lg)[::-1]
    # Nucleus filter: keep the top tokens whose cumulative softmax mass
    # reaches p (always at least one). p >= 1 keeps everything, WHATEVER
    # the float32 cumulative sum says: it can reach 1.0 before the last
    # token, and the tail it would mask is what an unfiltered row beside
    # no filtering row (the plain body) draws from.
    cum = jnp.cumsum(jax.nn.softmax(sorted_lg))
    k = jnp.where(p >= 1, lg.shape[-1],
                  jnp.minimum(jnp.sum(cum < p) + 1, lg.shape[-1]))
    # top_k caps the kept set (0 disables). NOTE: when both filters
    # are active this is min-of-counts over the UNFILTERED distribution
    # — HF instead renormalizes after top_k before applying top_p, so
    # its kept set can be strictly smaller; don't expect draw-level HF
    # parity with both filters on. Tokens TIED at the threshold logit
    # are all kept (same boundary behavior as HF's `logits <
    # topk[-1]` mask), so top_k=1 equals greedy only when the max
    # logit is unique — ties are broken by seed, not argmax order.
    k = jnp.where(k_limit > 0, jnp.minimum(k, k_limit), k)
    thresh = sorted_lg[k - 1]
    lg = jnp.where(lg >= thresh, lg, -jnp.inf)
    # min_p last, matching HF's warper order (temperature -> top_k ->
    # top_p -> min_p): the threshold is relative to the max logit —
    # always a survivor of the filters above, and renormalization
    # preserves logit differences, so "p_tok >= min_p * p_max over the
    # renormalized kept set" is exactly this mask. Applying it first
    # instead would shrink the nucleus (the -inf'd tail re-weights
    # cum above) and keep a slightly different set than HF.
    min_thresh = jnp.where(p_min > 0,
                           jnp.max(lg) + jnp.log(jnp.maximum(p_min,
                                                             1e-30)),
                           -jnp.inf)
    lg = jnp.where(lg >= min_thresh, lg, -jnp.inf)
    return _draw(key_seed, pos, lg)


def _greedy_body(greedy, *_):
    return greedy


def _plain_body(_greedy, seeds, positions, logits, temperature, *_):
    return jax.vmap(_plain_row)(seeds, positions, logits,
                                temperature).astype(jnp.int32)


def _filtered_body(_greedy, *rows):
    return jax.vmap(_filtered_row)(*rows).astype(jnp.int32)


# Module-level functions over explicit operands, not closures: a call
# outside `jit` (admission's first token) then finds the traced branches
# again and compiles the conditional once, not at every call.
_BODIES = (_greedy_body, _plain_body, _filtered_body)


def _sample(logits, seeds, positions, temperature, top_p=None, top_k=None,
            min_p=None, kept=None):
    """Per-row sampling: logits (B, V); seeds/positions/temperature/top_p/
    top_k/min_p (B,); kept (B,) bool, optional: the rows whose sample the
    caller keeps (a caller without one keeps every row's).

    Greedy where temperature == 0, else categorical — optionally filtered
    to the nucleus (smallest token set with cumulative probability >=
    top_p; top_p >= 1 keeps the WHOLE vocabulary), the top_k
    highest-logit tokens (0 = disabled), and/or min_p
    (keep tokens whose probability >= min_p x the max probability; 0 =
    disabled — in logit space that is simply lg >= max_lg + log(min_p),
    applied after temperature and after the nucleus/top_k filters,
    matching HF's warper order) — with key
    fold_in(PRNGKey(seed_r), position_r): deterministic per
    (seed, position) so co-batching and bucketing never change a request's
    tokens.

    The call does only what its kept rows ask for: ONE of three bodies
    runs, chosen on the device by `sampler_body` from the controls (a
    `lax.switch` on a scalar OUTSIDE the `vmap` over rows: under a `vmap`
    it would be a select and every body would run). Greedy: the argmax and
    nothing else. Plain: temperature and the draw, no sort. Filtered: the
    whole-vocabulary sort, cumulative sum and masks. A row's token does
    NOT depend on the body its call took: a greedy row reads the argmax in
    all three, and an unfiltered sampling row's logits reach the one
    `_draw` unmasked in the filtered body too. A row that is not kept may
    get a cheaper body's token (its own controls did not choose): never
    read it."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if top_p is None:
        top_p = jnp.ones(logits.shape[:1], jnp.float32)
    if top_k is None:
        top_k = jnp.zeros(logits.shape[:1], jnp.int32)
    if min_p is None:
        min_p = jnp.zeros(logits.shape[:1], jnp.float32)

    drawn = jax.lax.switch(
        sampler_body(temperature, top_p, top_k, min_p, kept), _BODIES,
        greedy, seeds, positions, logits, temperature, top_p, top_k, min_p)
    return jnp.where(temperature > 0, drawn, greedy)


def sample_block(logits, seeds, pos0, temperature, top_p, top_k, min_p,
                 kept):
    """`_sample` at the L positions of a row's block. logits: (B * L, V)
    float32, a row's L positions side by side, each the distribution of the
    token AT pos0 + i; the per-row controls (B,), each position's key
    fold_in(seed, its own position), the rule every path shares. Returns
    (x0 (B, L) int32, confidence (B, L) float32: soft-max(logits)[x0] in
    float32)."""
    b = seeds.shape[0]
    run = logits.shape[0] // b

    def rows(x):
        return jnp.repeat(x, run)

    positions = (pos0[:, None] + jnp.arange(run)[None, :]).reshape(-1)
    x0 = _sample(logits, rows(seeds), positions, rows(temperature),
                 rows(top_p), rows(top_k), rows(min_p), kept=rows(kept))
    chosen = jnp.take_along_axis(logits, x0[:, None], axis=-1)[:, 0]
    conf = jnp.exp(chosen - jax.nn.logsumexp(logits, axis=-1))
    return x0.reshape(b, run), conf.reshape(b, run)


def reveal_block(block, x0, conf, count, rule: str, threshold: float):
    """One denoise pass's reveal. block: (B, L) int32, -1 where a position
    is still masked (maskedness is state, never a token's value); x0,
    conf: (B, L) the pass's proposal and its confidence; count: (B,) how
    many positions the pass reveals at least (0: none, the row's block
    comes back as it went in). `rule`: "sequential" the `count` leftmost
    masked positions; "low_confidence_static" the `count` masked positions
    of largest confidence (ties to the left); "low_confidence_dynamic"
    every masked position whose confidence passes `threshold` where those
    are at least `count`, else the static choice. Returns the block with
    the chosen positions set to x0."""
    masked = block < 0
    at = jnp.arange(block.shape[1])
    if rule == "sequential":
        ahead = masked[:, None, :] & (at[None, :] < at[:, None])[None]
    else:
        # j goes before i: more confident, or as confident and to its left.
        ci, cj = conf[:, :, None], conf[:, None, :]
        ahead = masked[:, None, :] & (
            (cj > ci) | ((cj == ci) & (at[None, :] < at[:, None])[None]))
    take = masked & (ahead.sum(-1) < count[:, None])
    if rule == "low_confidence_dynamic":
        sure = masked & (conf > threshold)
        take = jnp.where((sure.sum(-1) >= count)[:, None]
                         & (count > 0)[:, None], sure, take)
    return jnp.where(take, x0, block)


def _decode_step_sampled(params, cfg, dtype, tok, caches, pos, start, done,
                         seeds, temps, topps, topks, minps, eos, controls,
                         counts, pens, stops):
    """One decode step + sampling + EOS/stop/counts bookkeeping — THE
    per-step semantics the chunked scan body and the fused while body
    share. One definition is what keeps their streams provably identical
    (the contract tests/test_fused_decode.py pins); `controls` is the
    compile-time penalty/stop flag (counts/pens/stops are None without
    it)."""
    logits, caches = transformer_decode_step(
        params, tok, caches, pos, cfg, dtype=dtype, start=start,
        pos_ids=pos - start)
    if controls:
        logits = apply_repetition_penalty(logits, counts, pens)
    # The sampled token sits at logical position pos+1-start in its own
    # sequence — fold that in so the stream is batch/bucket-independent.
    nxt = _sample(logits, seeds, pos + 1 - start, temps, topps, topks,
                  minps, kept=~done)
    nxt = jnp.where(done, eos, nxt)
    if controls:
        counts = counts.at[jnp.arange(nxt.shape[0]), nxt].add(
            (~done).astype(jnp.int32))
    done = done | (nxt == eos)
    if controls:
        done = done | jnp.any(nxt[:, None] == stops, axis=1)
    return caches, nxt, done, counts


class Generator:
    def __init__(
        self,
        model: Union[str, ModelSpec],
        params=None,
        rng_seed: int = 0,
        dtype: str = "bfloat16",
        batch_buckets: Sequence[int] = (1, 2, 4, 8),
        prompt_buckets: Optional[Sequence[int]] = None,
        step_chunk: int = 16,
        max_seq: Optional[int] = None,
        device=None,
        model_kwargs: Optional[dict] = None,
    ):
        if isinstance(model, str):
            _ensure_builtin_models_imported()
            model = create_model(model, **(model_kwargs or {}))
        if not isinstance(model.config, TransformerConfig):
            raise ValueError(f"model '{model.name}' is not a transformer "
                             "(no TransformerConfig); generation unsupported")
        if not model.config.causal:
            raise ValueError(f"model '{model.name}' is an encoder "
                             "(causal=False); autoregressive generation "
                             "requires a decoder LM")
        if tuple(model.output_shape) != (model.config.vocab,):
            raise ValueError(f"model '{model.name}' head is not an LM head "
                             f"over the vocab (output_shape={model.output_shape})")
        self.spec = model
        self.cfg: TransformerConfig = model.config
        self._dtype = _DTYPES[dtype]
        self.max_seq = min(max_seq or self.cfg.max_seq, self.cfg.max_seq)
        self._batch_buckets = tuple(sorted({max(1, int(b)) for b in batch_buckets}))
        if prompt_buckets is None:
            # Powers of two up to the model's full context — long prompts must
            # never be silently truncated below what the model can serve.
            b, prompt_buckets = 16, []
            while b < self.max_seq:
                prompt_buckets.append(b)
                b *= 2
            prompt_buckets.append(self.max_seq)
        self._prompt_buckets = tuple(sorted(
            {min(int(p), self.max_seq) for p in prompt_buckets}))
        self._step_chunk = step_chunk
        self._device = device
        self.params = params if params is not None else model.init(
            jax.random.PRNGKey(rng_seed))
        if device is not None:
            self.params = jax.device_put(self.params, device)
        self._prefill_exe: Dict[Tuple[int, int], object] = {}
        self._decode_exe: Dict[Tuple[int, bool], object] = {}
        self._fused_exe: Dict[Tuple[int, int, int, bool], object] = {}
        self._beam_exe: Dict[Tuple[int, int, int], object] = {}
        # Per-batch-bucket KV cache, reused across _generate_batch calls
        # (VERDICT r3 item 9: reallocating a donated cache every batch was
        # pure allocation churn). The prefill/decode executables donate it;
        # whatever buffer the last decode chunk returns is stored back.
        self._cache_pool: Dict[int, object] = {}
        self._lock = threading.Lock()

    # -- bucketing -------------------------------------------------------------

    def _bucket(self, buckets: Tuple[int, ...], n: int) -> int:
        return pick_bucket(buckets, n)

    @staticmethod
    def _out_cap(max_new: int) -> int:
        """Output-buffer capacity bucket (power of two >= max_new): ONE
        rounding rule for every single-dispatch mode, so a capacity change
        can't silently diverge between the fused and beam executables."""
        return 1 << (max_new - 1).bit_length() if max_new > 1 else 1

    def _put(self, x):
        """Device placement for host-built arrays — THE one placement rule
        every path (batch/fused/beam/score assembly) shares."""
        return (jax.device_put(x, self._device) if self._device is not None
                else jnp.asarray(x))

    def _pooled_cache(self, bb: int):
        """Pop the bucket's KV buffer from the pool (alloc+place on miss).
        Stale contents are never read: prefill rewrites [0, pb) and decode
        attends only within [start, pos]."""
        with self._lock:
            caches = self._cache_pool.pop(bb, None)
        if caches is None:
            caches = init_caches(self.cfg, bb, self.max_seq, self._dtype)
            if self._device is not None:
                caches = jax.device_put(caches, self._device)
        return caches

    def _return_cache(self, bb: int, caches) -> None:
        with self._lock:
            self._cache_pool.setdefault(bb, caches)

    # -- compiled stages -------------------------------------------------------

    def _prefill(self, bb: int, pb: int):
        key = (bb, pb)
        exe = self._prefill_exe.get(key)
        if exe is not None:
            return exe
        with self._lock:
            exe = self._prefill_exe.get(key)
            if exe is not None:
                return exe
            cfg, dtype = self.cfg, self._dtype

            def prefill(params, tokens, attn_mask, pos_ids, caches):
                return transformer_prefill(params, tokens, caches, cfg,
                                           dtype=dtype, attn_mask=attn_mask,
                                           pos_ids=pos_ids)

            self._prefill_exe[key] = jax.jit(prefill, donate_argnums=(4,))
            return self._prefill_exe[key]

    def _decode(self, bb: int, controls: bool = False):
        """Compiled decode chunk. `controls` is a COMPILE-TIME flag: the
        repetition-penalty/stop-token machinery ((B, V) counts buffer,
        per-step scatter-add, stop matching) exists only in the variant
        that needs it — default-sampling calls pay nothing for the
        feature (same pattern as speculative's static `stochastic`
        flag)."""
        key = (bb, controls)
        exe = self._decode_exe.get(key)
        if exe is not None:
            return exe
        with self._lock:
            exe = self._decode_exe.get(key)
            if exe is not None:
                return exe
            cfg, dtype, chunk = self.cfg, self._dtype, self._step_chunk

            def decode_chunk(params, caches, tok, pos0, start, done, seeds,
                             temperature, top_p, top_k, min_p, eos_id,
                             counts=None, rep_pen=None, stops=None):
                """Scan `chunk` decode steps. tok: (B,) last emitted token;
                seeds/temperature/top_p/top_k/rep_pen: per-row (B,)
                sampling params; counts: (B, V) context occurrence counts
                (repetition penalty state, updated as tokens sample);
                stops: (B, K) per-row stop-token ids padded with -1."""
                def body(carry, i):
                    if controls:
                        caches, tok, done, counts = carry
                    else:
                        caches, tok, done = carry
                        counts = None
                    caches, nxt, done, counts = _decode_step_sampled(
                        params, cfg, dtype, tok, caches, pos0 + i, start,
                        done, seeds, temperature, top_p, top_k, min_p,
                        eos_id, controls, counts, rep_pen, stops)
                    if controls:
                        return (caches, nxt, done, counts), nxt
                    return (caches, nxt, done), nxt

                if controls:
                    (caches, tok, done, counts), toks = jax.lax.scan(
                        body, (caches, tok, done, counts),
                        jnp.arange(chunk))
                    return caches, tok, done, counts, toks.T
                (caches, tok, done), toks = jax.lax.scan(
                    body, (caches, tok, done), jnp.arange(chunk))
                return caches, tok, done, toks.T  # (B, chunk)

            self._decode_exe[key] = jax.jit(
                decode_chunk,
                donate_argnums=(1, 12) if controls else (1,))
            return self._decode_exe[key]

    def _fused(self, bb: int, pb: int, cap: int, controls: bool):
        """One jitted function running prefill + the ENTIRE decode loop as
        a single dispatch (`lax.while_loop`, early exit on-device): zero
        host round-trips per token. This is what the speculative lane does
        minus the draft — it removes every per-chunk sync the chunked
        loop pays. Chunked decode remains the streaming/continuous path
        (tokens must surface mid-flight there); fused is for blocking
        batch calls. Streams are identical (same fold_in(seed, position)
        keys; tested)."""
        key = (bb, pb, cap, controls)
        exe = self._fused_exe.get(key)
        if exe is not None:
            return exe
        with self._lock:
            if key in self._fused_exe:
                return self._fused_exe[key]
            cfg, dtype = self.cfg, self._dtype
            max_seq = self.max_seq

            def run(params, tokens, attn_mask, pos_ids, start, alive,
                    caches, seeds, temps, topps, topks, minps, max_new,
                    eos_id, pens=None, stops=None, counts=None):
                rows = jnp.arange(bb)
                logits, caches = transformer_prefill(
                    params, tokens, caches, cfg, dtype=dtype,
                    attn_mask=attn_mask, pos_ids=pos_ids)
                if controls:
                    logits = apply_repetition_penalty(logits, counts, pens)
                first = _sample(logits, seeds, pb - start, temps, topps,
                                topks, minps)
                out_buf = jnp.zeros((bb, cap), jnp.int32).at[:, 0].set(first)
                n_out = jnp.ones((bb,), jnp.int32)
                done = (~alive) | (first == eos_id) | (max_new <= 1)
                if controls:
                    done = done | jnp.any(first[:, None] == stops, axis=1)
                    counts = counts.at[rows, first].add(
                        alive.astype(jnp.int32))

                def cond(carry):
                    done = carry[2]
                    pos = carry[4]
                    return jnp.any(~done) & (pos < max_seq)

                def body(carry):
                    if controls:
                        caches, tok, done, n_out, pos, out_buf, counts = carry
                    else:
                        caches, tok, done, n_out, pos, out_buf = carry
                        counts = None
                    done0 = done
                    caches, nxt, done, counts = _decode_step_sampled(
                        params, cfg, dtype, tok, caches, pos, start, done,
                        seeds, temps, topps, topks, minps, eos_id,
                        controls, counts, pens, stops)
                    write = (~done0) & (n_out < cap)
                    out_buf = out_buf.at[
                        rows, jnp.where(write, n_out, cap)
                    ].set(jnp.where(write, nxt, 0), mode="drop")
                    n_out = jnp.where(done0, n_out, n_out + 1)
                    done = done | (n_out >= max_new)
                    if controls:
                        return (caches, nxt, done, n_out, pos + 1, out_buf,
                                counts)
                    return caches, nxt, done, n_out, pos + 1, out_buf

                carry = (caches, first, done, n_out, jnp.int32(pb), out_buf)
                if controls:
                    carry = carry + (counts,)
                carry = jax.lax.while_loop(cond, body, carry)
                # Final caches return to the caller's pool — with the cache
                # donated (argnum 6), exactly ONE full KV buffer is live
                # at any point of the call, same as the chunked path.
                return carry[5], carry[3], carry[0]

            self._fused_exe[key] = jax.jit(run, donate_argnums=(6,))
            return self._fused_exe[key]

    def _beam(self, bw: int, pb: int, cap: int):
        """Compiled beam search for one request: beams ride the batch axis
        of one fused while_loop dispatch (beam candidates scored by
        summed log-probs; cache rows gathered on beam reorder — on TPU
        this is a contiguous batched gather of the dense cache, the
        layout ops.attention's decode path wants anyway). Returns every
        beam's tokens + raw scores; the host applies the length penalty
        and picks (normalization needs final lengths, which EOS decides)."""
        key = (bw, pb, cap)
        exe = self._beam_exe.get(key)
        if exe is not None:
            return exe
        with self._lock:
            if key in self._beam_exe:
                return self._beam_exe[key]
            cfg, dtype = self.cfg, self._dtype
            max_seq = self.max_seq

            def run(params, tokens, attn_mask, pos_ids, start1, caches,
                    max_new, eos_id):
                rows = jnp.arange(bw)
                logits, caches = transformer_prefill(
                    params, tokens, caches, cfg, dtype=dtype,
                    attn_mask=attn_mask, pos_ids=pos_ids)   # (1, V)
                logp0 = jax.nn.log_softmax(logits[0].astype(jnp.float32))
                scores, first = jax.lax.top_k(logp0, bw)    # (bw,), (bw,)
                first = first.astype(jnp.int32)
                # Broadcast the prompt's KV to every beam row.
                caches = jax.tree_util.tree_map(
                    lambda a: jnp.repeat(a, bw, axis=1), caches)
                start = jnp.repeat(start1, bw)
                out_buf = jnp.zeros((bw, cap), jnp.int32).at[:, 0].set(first)
                n_out = jnp.int32(1)
                done = (first == eos_id) | (max_new <= 1)

                def cond(c):
                    return (jnp.any(~c[2]) & (c[4] < max_seq)
                            & (c[3] < max_new))

                def body(c):
                    caches, tok, done, n_out, pos, out_buf, scores = c
                    logits, caches = transformer_decode_step(
                        params, tok, caches, pos, cfg, dtype=dtype,
                        start=start, pos_ids=pos - start)
                    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
                    # Live beams extend by any token; a finished beam
                    # survives as ONE candidate (unchanged score, re-emits
                    # EOS — trimmed on the host).
                    cand = jnp.where(done[:, None], -jnp.inf,
                                     scores[:, None] + logp)    # (bw, V)
                    eos_col = jnp.maximum(eos_id, 0)
                    cand = cand.at[rows, eos_col].set(
                        jnp.where(done, scores, cand[rows, eos_col]))
                    vals, idx = jax.lax.top_k(cand.reshape(-1), bw)
                    src = (idx // cfg.vocab).astype(jnp.int32)
                    nxt = (idx % cfg.vocab).astype(jnp.int32)
                    caches = jax.tree_util.tree_map(
                        lambda a: a[:, src], caches)
                    out_buf = out_buf[src]
                    done = done[src]
                    nxt = jnp.where(done, eos_id, nxt)
                    out_buf = out_buf.at[
                        rows, jnp.minimum(n_out, cap - 1)
                    ].set(jnp.where(done, out_buf[
                        rows, jnp.minimum(n_out, cap - 1)], nxt))
                    done = done | (nxt == eos_id)
                    return (caches, nxt, done, n_out + 1, pos + 1, out_buf,
                            vals)

                carry = (caches, first, done, n_out, jnp.int32(pb), out_buf,
                         scores)
                carry = jax.lax.while_loop(cond, body, carry)
                return carry[5], carry[6], carry[3]  # out_buf, scores, n

            self._beam_exe[key] = jax.jit(run)
            return self._beam_exe[key]

    def beam_search(self, prompt: Sequence[int], beam_width: int = 4,
                    max_new_tokens: int = 32, eos_id: int = -1,
                    length_penalty: float = 1.0) -> List[int]:
        """Deterministic beam decode of ONE prompt; returns the best beam
        (summed log-prob / len**length_penalty, GNMT-style). Beams occupy
        the batch axis of a single fused dispatch."""
        bw = int(beam_width)
        if bw < 1:
            raise ValueError(f"beam_width must be >= 1, got {beam_width}")
        prompt = list(prompt)
        pb = self._bucket(self._prompt_buckets,
                          min(max(len(prompt), 1), self.max_seq))
        max_new = max(1, min(int(max_new_tokens), self.max_seq - pb))
        cap = self._out_cap(max_new)
        tokens, attn_mask, pos_ids, start = left_pad_batch([prompt], 1, pb)
        put = self._put

        # Reuse the width-1 cache from the pool; the jit doesn't donate it
        # (the loop works on the bw-row tiled copy), so the buffer goes
        # straight back afterwards — no per-call allocation churn.
        caches = self._pooled_cache(1)
        out_buf, scores, _ = self._beam(bw, pb, cap)(
            self.params, put(tokens), put(attn_mask), put(pos_ids),
            put(start), caches, put(jnp.int32(max_new)),
            put(jnp.int32(eos_id)))
        self._return_cache(1, caches)
        out_buf = np.asarray(out_buf)
        scores = np.asarray(scores)
        best, best_norm = [], -np.inf
        for b in range(bw):
            row = truncate_at_stops(out_buf[b, :max_new].tolist(),
                                    eos_id, ())
            norm = scores[b] / max(len(row), 1) ** float(length_penalty)
            if norm > best_norm:
                best, best_norm = row, norm
        return best

    def _score_exe(self, bb: int, sb: int):
        """Compiled scorer: one causal forward over prompt+completion,
        gathering log P(token | prefix) at each completion position. No
        KV cache, no decode loop — scoring is prefill-shaped work the MXU
        likes (the evals/perplexity API; the reference has no analog)."""
        key = ("score", bb, sb)
        exe = self._prefill_exe.get(key)
        if exe is not None:
            return exe
        with self._lock:
            if key in self._prefill_exe:
                return self._prefill_exe[key]
            cfg, dtype = self.cfg, self._dtype

            def run(params, tokens, attn_mask):
                from tpu_engine.models.transformer import transformer_apply

                logits = transformer_apply(params, tokens, cfg,
                                           mask=attn_mask, dtype=dtype)
                logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
                # log P(tokens[:, i] | tokens[:, :i]) lives at row i-1.
                tgt = tokens[:, 1:, None]
                return jnp.take_along_axis(logp[:, :-1], tgt, -1)[..., 0]

            self._prefill_exe[key] = jax.jit(run)
            return self._prefill_exe[key]

    def score(self, prompts: Sequence[Sequence[int]],
              completions: Sequence[Sequence[int]]) -> List[List[float]]:
        """Per-token log-probabilities of each completion given its prompt
        (teacher-forced, one forward pass — what perplexity evals and
        lm-eval-harness loglikelihood requests need). Sequences RIGHT-pad
        to a shared bucket; returns len(completion) floats per row."""
        if len(prompts) != len(completions):
            raise ValueError("prompts and completions length mismatch")
        n = len(prompts)
        if n == 0:
            return []
        out: List[List[float]] = []
        max_bb = self._batch_buckets[-1]
        for i in range(0, n, max_bb):
            out.extend(self._score_batch(
                [list(p) for p in prompts[i:i + max_bb]],
                [list(c) for c in completions[i:i + max_bb]]))
        return out

    def _score_batch(self, prompts, completions) -> List[List[float]]:
        n = len(prompts)
        bb = self._bucket(self._batch_buckets, n)
        seqs = [(p or [0]) + c for p, c in zip(prompts, completions)]
        longest = min(max(len(s) for s in seqs), self.max_seq)
        sb = self._bucket(self._prompt_buckets, longest)
        tokens = np.zeros((bb, sb), np.int32)
        attn = np.zeros((bb, sb), np.int32)
        for r, s in enumerate(seqs):
            if len(s) > sb:
                raise ValueError(
                    f"prompt+completion length {len(s)} exceeds the "
                    f"largest sequence bucket {sb}")
            tokens[r, :len(s)] = np.asarray(s, np.int32)
            attn[r, :len(s)] = 1
        put = self._put

        lp = np.asarray(self._score_exe(bb, sb)(self.params, put(tokens),
                                                put(attn)))
        results = []
        for r in range(n):
            start = max(len(prompts[r]), 1)  # empty prompt consumes pad 0
            end = start + len(completions[r])
            results.append([float(x) for x in lp[r, start - 1:end - 1]])
        return results

    # -- generation ------------------------------------------------------------

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: int = 32,
        eos_id: int = -1,
        temperature: Union[float, Sequence[float]] = 0.0,
        seed: Union[int, Sequence[int]] = 0,
        top_p: Union[float, Sequence[float]] = 1.0,
        top_k: Union[int, Sequence[int]] = 0,
        repetition_penalty: Union[float, Sequence[float]] = 1.0,
        stop_tokens=None,
        min_p: Union[float, Sequence[float]] = 0.0,
        fused: bool = False,
    ) -> List[List[int]]:
        """Batched generation. Returns per-prompt generated token lists
        (EOS-truncated, EOS not included). `eos_id=-1` disables early stop.

        `temperature`, `seed` and `top_p` may be per-prompt sequences. A
        request with an explicit per-prompt seed samples the same tokens no
        matter how requests are batched. A scalar seed expands to seed+row
        so rows of one call still sample independently. `top_p < 1` applies
        nucleus filtering before the categorical draw.

        `repetition_penalty` (HF semantics, 1.0 = off) shrinks the
        probability of every token already in the row's context (prompt +
        generated). `stop_tokens`: up to 8 token ids (flat list shared by
        all rows, or per-row lists) that end the row like EOS (excluded
        from the result).

        `fused=True` runs prefill + the whole decode loop as ONE compiled
        dispatch (zero per-token host syncs; identical streams) — the
        fastest blocking mode on high-dispatch-latency links; chunked
        (default) is what the streaming/continuous paths build on."""
        if not prompts:
            return []
        n = len(prompts)
        temps, seeds, top_ps, top_ks, min_ps = expand_sampling_params(
            n, temperature, seed, top_p, top_k, min_p)
        pens, stops = expand_stopping_params(n, repetition_penalty,
                                             stop_tokens)
        out: List[List[int]] = []
        max_bb = self._batch_buckets[-1]
        run = self._generate_fused_batch if fused else self._generate_batch
        for i in range(0, n, max_bb):
            out.extend(run(
                [list(p) for p in prompts[i:i + max_bb]],
                max_new_tokens, eos_id, temps[i:i + max_bb],
                seeds[i:i + max_bb], top_ps[i:i + max_bb],
                top_ks[i:i + max_bb], pens[i:i + max_bb],
                stops[i:i + max_bb], min_ps[i:i + max_bb]))
        return out

    def _generate_fused_batch(self, prompts: List[List[int]], max_new: int,
                              eos_id: int, temps: List[float],
                              seeds: List[int], top_ps: List[float],
                              top_ks: List[int], pens: List[float],
                              stops: List[List[int]],
                              min_ps: List[float]) -> List[List[int]]:
        n = len(prompts)
        bb = self._bucket(self._batch_buckets, n)
        longest = max(1, max(len(p) for p in prompts))
        pb = self._bucket(self._prompt_buckets, min(longest, self.max_seq))
        max_new = max(1, min(max_new, self.max_seq - pb))
        cap = self._out_cap(max_new)
        controls = any(p != 1.0 for p in pens) or any(stops)

        tokens, attn_mask, pos_ids, start = left_pad_batch(prompts, bb, pb)
        alive = np.zeros((bb,), bool)
        alive[:n] = True
        put = self._put

        caches = self._pooled_cache(bb)

        temps_arr = np.zeros((bb,), np.float32)
        seeds_arr = np.zeros((bb,), np.int32)
        topp_arr = np.ones((bb,), np.float32)
        topk_arr = np.zeros((bb,), np.int32)
        minp_arr = np.zeros((bb,), np.float32)
        temps_arr[:n] = temps
        seeds_arr[:n] = [int(s) & 0x7FFFFFFF for s in seeds]
        topp_arr[:n] = top_ps
        topk_arr[:n] = top_ks
        minp_arr[:n] = min_ps
        args = [self.params, put(tokens), put(attn_mask), put(pos_ids),
                put(start), put(alive), caches, put(seeds_arr),
                put(temps_arr), put(topp_arr), put(topk_arr),
                put(minp_arr), put(jnp.int32(max_new)),
                put(jnp.int32(eos_id))]
        if controls:
            pens_arr = np.ones((bb,), np.float32)
            pens_arr[:n] = pens
            counts0 = token_counts([p[-pb:] for p in prompts], bb,
                                   self.cfg.vocab)
            args += [put(pens_arr), put(stop_matrix(stops, bb)),
                     put(counts0)]
        out_buf, n_out, caches = self._fused(bb, pb, cap, controls)(*args)
        self._return_cache(bb, caches)  # the loop's final buffer
        out_buf = np.asarray(out_buf)
        n_out = np.asarray(n_out)
        return [truncate_at_stops(
                    out_buf[r, :min(int(n_out[r]), max_new)].tolist(),
                    eos_id, stops[r])
                for r in range(n)]

    def _generate_batch(self, prompts: List[List[int]], max_new: int,
                        eos_id: int, temps: List[float],
                        seeds: List[int], top_ps: List[float],
                        top_ks: List[int], pens: List[float],
                        stops: List[List[int]],
                        min_ps: List[float]) -> List[List[int]]:
        n = len(prompts)
        bb = self._bucket(self._batch_buckets, n)
        longest = max(1, max(len(p) for p in prompts))
        pb = self._bucket(self._prompt_buckets, min(longest, self.max_seq))
        max_new = max(1, min(max_new, self.max_seq - pb))

        tokens, attn_mask, pos_ids, start = left_pad_batch(prompts, bb, pb)
        put = self._put

        caches = self._pooled_cache(bb)
        logits, caches = self._prefill(bb, pb)(
            self.params, put(tokens), put(attn_mask), put(pos_ids), caches)

        # Per-row sampling params, padded to the batch bucket.
        temps_arr = np.zeros((bb,), np.float32)
        seeds_arr = np.zeros((bb,), np.int32)
        topp_arr = np.ones((bb,), np.float32)
        topk_arr = np.zeros((bb,), np.int32)
        topk_arr[:n] = top_ks
        temps_arr[:n] = temps
        # Same normalization as the continuous scheduler (& 0x7FFFFFFF):
        # seeds >= 2**31 must sample identically under both gen_scheduler
        # settings (documented seeded-reproducibility contract).
        seeds_arr[:n] = [int(s) & 0x7FFFFFFF for s in seeds]
        topp_arr[:n] = top_ps
        minp_arr = np.zeros((bb,), np.float32)
        minp_arr[:n] = min_ps
        controls = any(p != 1.0 for p in pens) or any(stops)
        temps_dev, seeds_dev = put(temps_arr), put(seeds_arr)
        topp_dev, topk_dev = put(topp_arr), put(topk_arr)
        minp_dev = put(minp_arr)
        start_dev = put(start)

        # Bucket-padding rows start done: their outputs are discarded, and
        # a live pad row would block the all-done early exit forever when
        # EOS is disabled or stop tokens end the real rows.
        pad_done = jnp.asarray(np.arange(bb) >= n)

        if controls:
            pens_arr = np.ones((bb,), np.float32)
            pens_arr[:n] = pens
            pens_dev, stops_dev = put(pens_arr), put(stop_matrix(stops, bb))
            # First token comes from the prefill logits penalized by the
            # PROMPT's token counts.
            prompt_counts = token_counts([p[-pb:] for p in prompts], bb,
                                         self.cfg.vocab)
            logits = apply_repetition_penalty(logits, put(prompt_counts),
                                              pens_dev)
        first = _sample(logits, seeds_dev, pb - jnp.asarray(start_dev),
                        jnp.asarray(temps_dev), jnp.asarray(topp_dev),
                        jnp.asarray(topk_dev), jnp.asarray(minp_dev))
        done = pad_done | (first == eos_id)
        if controls:
            done = done | jnp.any(first[:, None] == stops_dev, axis=1)

        pieces = [np.asarray(first)[:, None]]
        if controls:
            # Counts seed = prompt + first token (host has first synced).
            np.add.at(prompt_counts, (np.arange(bb), pieces[0][:, 0]), 1)
            counts = put(prompt_counts)
        tok, pos = first, pb
        decode = self._decode(bb, controls)
        eos_dev = put(jnp.int32(eos_id))
        remaining = max_new - 1
        # max_new is clamped to max_seq - pb, so every *needed* step writes
        # in-bounds; a final partial chunk may run steps past max_seq whose
        # outputs are discarded by the truncation below.
        while remaining > 0 and pos < self.max_seq:
            if controls:
                caches, tok, done, counts, toks = decode(
                    self.params, caches, tok, pos, start_dev, done,
                    seeds_dev, temps_dev, topp_dev, topk_dev, minp_dev,
                    eos_dev, counts, pens_dev, stops_dev)
            else:
                caches, tok, done, toks = decode(
                    self.params, caches, tok, pos, start_dev, done,
                    seeds_dev, temps_dev, topp_dev, topk_dev, minp_dev,
                    eos_dev)
            start_host_copies(toks, done)
            pieces.append(np.asarray(toks))
            pos += self._step_chunk
            remaining -= self._step_chunk
            if bool(np.all(np.asarray(done))):
                break

        self._return_cache(bb, caches)
        gen = np.concatenate(pieces, axis=1)[:n, :max_new]
        return [truncate_at_stops(gen[r].tolist(), eos_id, stops[r])
                for r in range(n)]

    def stats(self) -> dict:
        return {
            "model": self.spec.name,
            "max_seq": self.max_seq,
            "batch_buckets": list(self._batch_buckets),
            "prompt_buckets": list(self._prompt_buckets),
            "step_chunk": self._step_chunk,
            "compiled_prefill": sorted(self._prefill_exe),
            "compiled_decode": sorted(self._decode_exe),
        }
