"""O(1)-state recurrent models: the SSD/Mamba (state_slab) workload
class (ops.ssd + models.ssd + runtime.kv_blocks.StateSlabPool +
scheduler family dispatch).

Contracts under test:
- State Space DUALITY: the chunked matmul-form prefill scan and the
  O(1) recurrence produce the same outputs and final state (max|Δ|
  bounded), at the ops level and through the whole model;
- PARTITION INVARIANCE of the serving recurrence: consuming a prompt in
  windows of any width produces bit-identical state — the property that
  makes budgeted chunks of any width and crash-replay
  (prompt ⧺ emitted) resumes agree;
- stream identity: greedy SSD streams are byte-identical across token
  budgets, across repeats, and across a replay-style resume; seeded
  sampling is deterministic;
- StateSlabPool discipline: null row, refcounts, PoolExhausted,
  deferred admissions under row exhaustion, zero-leak accounting on
  every row-free path (completion, deadline cancel, stop);
- registry capability metadata: every registered model declares a state
  family + capability flags, and family/scheduler mismatches fail with
  LOUD pinned RuntimeErrors at the scheduler AND worker layers;
- gated additive observability: state_pool appears only on slab lanes
  (kv_paged /stats and /health bytes untouched), tpu_engine_state_*
  renders in /metrics.
"""

import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models.registry import (
    FAMILY_CAPABILITIES,
    _ensure_builtin_models_imported,
    available_models,
    create_model,
)
from tpu_engine.models.ssd import (
    ssd_init_states,
    ssd_prefill_chunked,
    ssd_state_dim,
    ssd_step_rows,
    ssd_window_scan,
)
from tpu_engine.ops.ssd import ssd_chunked, ssd_parity_check, ssd_recurrent
from tpu_engine.runtime.kv_blocks import PoolExhausted, StateSlabPool
from tpu_engine.runtime.scheduler import ContinuousGenerator

_ensure_builtin_models_imported()


@pytest.fixture(scope="module")
def spec():
    return create_model("ssd-small-test")


@pytest.fixture(scope="module")
def params(spec):
    return spec.init(jax.random.PRNGKey(0))


def _gen(spec, params, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("step_chunk", 2)
    kw.setdefault("prefill_chunk", 8)
    return ContinuousGenerator(spec, params=params, dtype="float32", **kw)


# -- duality -----------------------------------------------------------------

def test_ops_duality_parity():
    r = ssd_parity_check()
    assert r["ok"], r
    # Non-multiple sequence length exercises the padding path; a chunk
    # larger than the sequence degenerates to one chunk.
    r2 = ssd_parity_check(batch=1, seq=11, chunk=32, seed=5)
    assert r2["ok"], r2


def test_ops_chunked_matches_recurrence_with_initial_state():
    rng = np.random.default_rng(7)
    b, t, h, p, n = 2, 24, 2, 4, 3
    x = jnp.asarray(rng.standard_normal((b, t, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.3, (b, t, h)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.2, 1.5, (h,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, t, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, t, n)), jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((b, h, p, n)), jnp.float32)
    y_r, f_r = ssd_recurrent(x, dt, A, B, C, initial_state=s0)
    y_c, f_c = ssd_chunked(x, dt, A, B, C, chunk=8, initial_state=s0)
    assert float(jnp.max(jnp.abs(y_r - y_c))) < 1e-4
    assert float(jnp.max(jnp.abs(f_r - f_c))) < 1e-4


def test_model_level_duality(spec, params):
    cfg = spec.config
    toks = jnp.asarray(np.array([[5, 9, 3, 17, 44, 2, 8, 11]], np.int32))
    L = toks.shape[1]
    kept, st = ssd_window_scan(params, toks, ssd_init_states(cfg, 1),
                               jnp.asarray([L]), jnp.asarray([L - 1]), cfg)
    lc, sc = ssd_prefill_chunked(params, toks, cfg)
    assert float(jnp.max(jnp.abs(kept - lc))) < 1e-3
    assert float(jnp.max(jnp.abs(st.ssm - sc.ssm))) < 1e-3
    assert float(jnp.max(jnp.abs(st.conv - sc.conv))) < 1e-3


def test_recurrence_partition_invariance_bitexact(spec, params):
    """Any window split of the prompt produces BIT-identical state —
    the property the serving path's byte-identity rests on."""
    cfg = spec.config
    prompt = np.array([5, 9, 3, 17, 44, 2, 8], np.int32)
    L = len(prompt)

    def run_windows(W):
        st = ssd_init_states(cfg, 1)
        conv, ssm = st.conv, st.ssm
        kept = None
        for w0 in range(0, L, W):
            nv = min(W, L - w0)
            win = np.zeros((1, W), np.int32)
            win[0, :nv] = prompt[w0:w0 + nv]
            kept, st = ssd_window_scan(
                params, jnp.asarray(win), type(st)(conv, ssm),
                jnp.asarray([nv]), jnp.asarray([nv - 1]), cfg)
            conv, ssm = st.conv, st.ssm
        return np.asarray(kept), np.asarray(conv), np.asarray(ssm)

    k3, c3, s3 = run_windows(3)
    k7, c7, s7 = run_windows(7)
    assert np.array_equal(c3, c7) and np.array_equal(s3, s7)
    assert np.array_equal(k3, k7)
    # ...and equal to plain token-by-token stepping.
    st = ssd_init_states(cfg, 1)
    for t in prompt:
        logits, st = ssd_step_rows(params, jnp.asarray([t]), st, cfg)
    assert np.array_equal(np.asarray(st.conv), c3)
    assert np.array_equal(np.asarray(st.ssm), s3)
    assert np.array_equal(np.asarray(logits), k3)


# -- registry capability metadata (satellite) --------------------------------

def test_every_registered_model_declares_family_and_capabilities():
    for name in available_models():
        m = create_model(name)
        assert m.state_family in FAMILY_CAPABILITIES, (name,
                                                       m.state_family)
        assert m.capabilities == FAMILY_CAPABILITIES[m.state_family]


def test_family_declarations():
    assert create_model("ssd-small-test").state_family == "state_slab"
    assert create_model("mamba2").state_family == "state_slab"
    assert create_model("gpt2-small-test").state_family == "kv_paged"
    assert create_model("mlp").state_family == "stateless"
    ssd = create_model("ssd-small-test")
    assert ssd.supports("generate") and ssd.supports("migration")
    assert not ssd.supports("spec_decode")
    assert not ssd.supports("paged_kv")


def test_scheduler_family_fences(spec, params):
    with pytest.raises(ValueError,
                       match="state_slab family has no paged KV cache"):
        ContinuousGenerator(spec, params=params, kv_block_size=16)
    with pytest.raises(ValueError, match="kv_quantize applies to"):
        ContinuousGenerator(spec, params=params, kv_quantize="int8")
    with pytest.raises(ValueError, match="kv_host_blocks applies to"):
        ContinuousGenerator(spec, params=params, kv_host_blocks=4)
    with pytest.raises(ValueError,
                       match="requires the kv_paged family"):
        ContinuousGenerator(spec, params=params, spec_k=2)
    with pytest.raises(ValueError,
                       match="state_rows applies to the state_slab"):
        ContinuousGenerator("gpt2-small-test", state_rows=8)


def test_worker_family_mismatch_runtime_errors(spec, params):
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig

    def eng():
        return InferenceEngine(spec, params, dtype="float32")

    with pytest.raises(RuntimeError,
                       match="state_slab-family models have no paged "
                             "KV cache"):
        WorkerNode(WorkerConfig(node_id="x", model="ssd-small-test",
                                gen_kv_block_size=16), engine=eng())
    with pytest.raises(RuntimeError,
                       match="requires gen_scheduler=continuous"):
        WorkerNode(WorkerConfig(node_id="x", model="ssd-small-test",
                                gen_scheduler="batch"), engine=eng())
    with pytest.raises(RuntimeError,
                       match="--spec-k requires a kv_paged-family "
                             "model"):
        WorkerNode(WorkerConfig(node_id="x", model="ssd-small-test",
                                gen_continuous_spec_k=2), engine=eng())
    gspec = create_model("gpt2-small-test")
    with pytest.raises(RuntimeError,
                       match="--state-rows applies to state_slab"):
        WorkerNode(WorkerConfig(node_id="y", model="gpt2-small-test",
                                gen_state_rows=8),
                   engine=InferenceEngine(
                       gspec, gspec.init(jax.random.PRNGKey(0)),
                       dtype="float32"))


# -- StateSlabPool discipline ------------------------------------------------

def test_slab_pool_invariants():
    pool = StateSlabPool(2, 8, 4)
    assert pool.rows_free == 3  # row 0 is the null row
    with pytest.raises(ValueError):
        StateSlabPool(2, 8, 1)
    ids = [pool.alloc_row() for _ in range(3)]
    assert 0 not in ids and len(set(ids)) == 3
    with pytest.raises(PoolExhausted):
        pool.alloc_row()
    pool.release_row(ids[0])
    assert pool.rows_free == 1
    pool.release_row(0)  # null row release is a no-op
    assert pool.refcount(0) == 1
    st = pool.stats()
    assert st["rows_total"] == 3
    assert "not block-addressable" in st["prefix_sharing"]
    assert st["bytes_per_row"] == 2 * 8 * 4


def test_slab_chain_round_trip_bit_exact():
    pool = StateSlabPool(2, 8, 4)
    rid = pool.alloc_row()
    flat = np.arange(16, dtype=np.float32).reshape(2, 8) * 0.37
    pool.slab = pool.slab.at[:, rid].set(jnp.asarray(flat))
    chain = pool.export_row_chain(rid)
    assert chain["family"] == "state_slab" and len(chain["blocks"]) == 1
    assert StateSlabPool.verify_chain(chain)
    other = StateSlabPool(2, 8, 4)
    assert other.chain_compatible(chain) is None
    rid2 = other.alloc_row()
    other.import_row_chain(chain, rid2)
    assert np.array_equal(np.asarray(other.slab[:, rid2]), flat)


def test_slab_chain_refusals_before_allocation():
    pool = StateSlabPool(2, 8, 4)
    rid = pool.alloc_row()
    chain = pool.export_row_chain(rid)
    # Geometry mismatches named per field.
    assert "state_dim" in StateSlabPool(2, 9, 4).chain_compatible(chain)
    assert "n_layers" in StateSlabPool(3, 8, 4).chain_compatible(chain)
    # Structural refusals.
    assert "exactly one pseudo-block" in pool.chain_compatible(
        dict(chain, blocks=[]))
    assert "payload" in pool.chain_compatible(
        dict(chain, blocks=[{"v": "aa"}]))
    truncated = dict(chain, blocks=[{"k": chain["blocks"][0]["k"][:8]}])
    assert "bytes" in pool.chain_compatible(truncated)
    # Checksum corruption is False, never a raise.
    assert not StateSlabPool.verify_chain(dict(chain, checksum=1))
    assert not StateSlabPool.verify_chain({"blocks": "garbage",
                                           "checksum": 0})


# -- scheduler e2e -----------------------------------------------------------

def test_greedy_and_seeded_streams(spec, params):
    gen = _gen(spec, params)
    try:
        a = gen.generate([[5, 9, 3], [7, 2]], max_new_tokens=12)
        b = gen.generate([[5, 9, 3], [7, 2]], max_new_tokens=12)
        assert a == b  # deterministic run-to-run
        s1 = gen.generate([[5, 9, 3]], max_new_tokens=10,
                          temperature=0.9, seed=42)
        s2 = gen.generate([[5, 9, 3]], max_new_tokens=10,
                          temperature=0.9, seed=42)
        s3 = gen.generate([[5, 9, 3]], max_new_tokens=10,
                          temperature=0.9, seed=43)
        assert s1 == s2 and s1 != s3
        st = gen.stats()["state_pool"]
        assert st["rows_free"] == st["rows_total"]  # zero slab leaks
    finally:
        gen.stop()


def test_streams_byte_identical_across_token_budgets(spec, params):
    """Greedy SSD streams byte-identical however the token budget cuts
    a prompt into chunks: eight tokens a tick (the lane's chunk) or six
    over all rows (plus a seeded stream — the fold_in(seed, position)
    rule is family-portable)."""
    prompts = [[5, 9, 3, 17, 44, 2, 8, 11, 23], [7, 2], [1] * 12]
    gen = _gen(spec, params)
    try:
        whole = gen.generate(prompts, max_new_tokens=14)
        seeded_whole = gen.generate([prompts[0]], max_new_tokens=10,
                                    temperature=0.8, seed=9)
    finally:
        gen.stop()
    genm = _gen(spec, params, mixed_token_budget=6)
    try:
        mixed = genm.generate(prompts, max_new_tokens=14)
        seeded_mx = genm.generate([prompts[0]], max_new_tokens=10,
                                  temperature=0.8, seed=9)
        assert mixed == whole
        assert seeded_mx == seeded_whole
        m = genm.stats()["mixed"]
        assert m["ticks"] == m["dispatches"]  # one dispatch per tick
        st = genm.stats()["state_pool"]
        assert st["rows_free"] == st["rows_total"]
    finally:
        genm.stop()


def test_replay_resume_byte_identical(spec, params):
    """Crash-replay identity: re-prefilling (prompt ⧺ emitted) through
    the recurrence continues the stream byte-identically — the PR 6
    journal resume needs nothing family-specific."""
    gen = _gen(spec, params)
    try:
        full = gen.generate([[5, 9, 3]], max_new_tokens=20)[0]
        for cut in (1, 7, 13):
            resume = gen.generate([[5, 9, 3] + full[:cut]],
                                  max_new_tokens=len(full) - cut)[0]
            assert resume == full[cut:], cut
    finally:
        gen.stop()


def test_penalty_and_stop_controls(spec, params):
    gen = _gen(spec, params)
    try:
        plain = gen.generate([[5, 9, 3]], max_new_tokens=12)[0]
        pen = gen.generate([[5, 9, 3]], max_new_tokens=12,
                           repetition_penalty=3.0)[0]
        assert plain != pen  # controls variant engaged and effective
        # Stop on the greedy stream's own 4th token: the stream truncates
        # BEFORE that token's FIRST occurrence — index 3 only when the
        # token does not repeat earlier, which depends on the installed
        # JAX's random init; derive the cut from the stream itself.
        cut = plain.index(plain[3])
        assert cut > 0, plain  # a stop at index 0 would test nothing
        stopped = gen.generate([[5, 9, 3]], max_new_tokens=12,
                               stop_tokens=[plain[3]])[0]
        assert stopped == plain[:cut]
    finally:
        gen.stop()


@pytest.mark.parametrize("prompt_len", [2, 20])
def test_deferred_admission_under_row_exhaustion(spec, params, prompt_len):
    """state_rows binds concurrency: with both usable rows OCCUPIED by
    long streams, two late submissions must PARK (pending_admissions >
    0), then admit as rows free — never fail, never hang (pins the
    from_pending retry gate covering the slab family), and the pool
    accounts for every row after. The same two rows are resident
    whether a stream's prompt is 2 tokens or 20 (of max_seq 64): a row
    costs the same bytes at any sequence length, where a paged row's
    blocks grow."""
    gen = _gen(spec, params, state_rows=3)  # 2 usable + null
    try:
        bytes_per_row = gen.stats()["state_pool"]["bytes_per_row"]
        long_futs = [gen.submit([9, i] * (prompt_len // 2),
                                max_new_tokens=40)
                     for i in range(2)]
        deadline = time.monotonic() + 60
        while (gen.stats()["active"] < 2
               and time.monotonic() < deadline):
            time.sleep(0.002)
        assert gen.stats()["active"] == 2
        late_futs = [gen.submit([3 + i, 7], max_new_tokens=10)
                     for i in range(2)]
        saw_pending = False
        while any(not f.done() for f in long_futs + late_futs):
            st = gen.stats()["state_pool"]
            saw_pending |= st["pending_admissions"] > 0
            time.sleep(0.001)
        assert saw_pending  # the late pair provably parked
        assert all(len(f.result(1)) == 40 for f in long_futs)
        assert all(len(f.result(1)) == 10 for f in late_futs)
        st = gen.stats()["state_pool"]
        assert st["rows_total"] == 2
        assert st["rows_free"] == 2
        assert st["rows_admitted"] == st["rows_released"] == 4
        assert st["bytes_per_row"] == bytes_per_row
    finally:
        gen.stop()


def test_deadline_cancel_releases_slab_row(spec, params):
    from tpu_engine.utils.deadline import Deadline, DeadlineExceeded

    gen = _gen(spec, params)
    try:
        fut = gen.submit([5, 9, 3], max_new_tokens=40,
                         deadline=Deadline.after_ms(40))
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=60)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st = gen.stats()["state_pool"]
            if st["rows_free"] == st["rows_total"]:
                break
            time.sleep(0.05)
        assert st["rows_free"] == st["rows_total"]
    finally:
        gen.stop()


def test_scheduler_migration_splice_identity(spec, params):
    """Export a live SSD row mid-stream, adopt it on a second lane: the
    spliced stream is byte-identical to an uninterrupted run (greedy
    AND seeded), with zero re-prefill and zero leaks on both pools."""
    a = _gen(spec, params)
    b = _gen(spec, params)
    try:
        for kw, tag in (({}, "m0"),
                        ({"temperature": 0.9, "seed": 17}, "m1")):
            control = a.generate([[5, 9, 3, 11]], max_new_tokens=18,
                                 **kw)[0]
            q = queue.Queue()
            a.submit([5, 9, 3, 11], max_new_tokens=18, stream=q,
                     tag=tag, **kw)
            got = []
            while len(got) < 5:
                item = q.get(timeout=60)
                assert item is not None
                got += item
            snap = a.export_row(tag)
            assert snap["ok"], snap
            while True:
                item = q.get(timeout=10)
                if item is None:
                    break
                got += item
            q2 = queue.Queue()
            fut = b.submit_import(snap, stream=q2)
            while True:
                item = q2.get(timeout=60)
                if item is None:
                    break
                got += item
            assert got == control
            assert fut.result(timeout=10) == control
        for g in (a, b):
            st = g.stats()["state_pool"]
            assert st["rows_free"] == st["rows_total"]
        assert a.stats()["migration"]["exported_rows"] == 2
        assert b.stats()["migration"]["imported_rows"] == 2
    finally:
        a.stop()
        b.stop()


def test_export_mid_prompt_is_refused_as_mid_prefill(spec, params):
    """A slab row's state is whole only at its prompt's end: an export
    command that meets the row between two of its prompt's chunks is
    refused by name (a replay re-prefills what an import would ship),
    counted, and the row goes on undisturbed."""
    from concurrent.futures import Future

    gen = _gen(spec, params, mixed_token_budget=8)
    try:
        prompt = list(range(1, 25))          # three chunks of eight
        want = gen.generate([prompt], max_new_tokens=6)[0]
        real = gen._slab_mixed_exe
        fut, sent = Future(), []

        def commanding(width, controls):
            if width > 1 and not sent:       # the first chunk's tick
                sent.append(True)
                gen._migrate_q.put(("mover", fut, {}))
            return real(width, controls)

        gen._slab_mixed_exe = commanding
        moving = gen.submit(prompt, max_new_tokens=6, tag="mover")
        assert fut.result(timeout=60) == {"ok": False,
                                          "reason": "row is mid-prefill"}
        assert moving.result(timeout=60) == want
        assert gen.stats()["migration"]["export_refused"] == 1
        st = gen.stats()["state_pool"]
        assert st["rows_free"] == st["rows_total"]
    finally:
        gen.stop()


def test_import_refusals_resolve_retryable(spec, params):
    from tpu_engine.runtime.scheduler import ImportRefused

    a = _gen(spec, params)
    b = _gen(spec, params)
    try:
        q = queue.Queue()
        a.submit([5, 9, 3], max_new_tokens=16, stream=q, tag="r0")
        got = []
        while len(got) < 4:
            item = q.get(timeout=60)
            assert item is not None
            got += item
        snap = a.export_row("r0")
        assert snap["ok"]
        free0 = b.stats()["state_pool"]["rows_free"]
        bad = dict(snap, chain=dict(snap["chain"], checksum=777))
        with pytest.raises(ImportRefused):
            b.submit_import(bad).result(timeout=30)
        geom = dict(snap, chain=dict(snap["chain"], state_dim=99))
        with pytest.raises(ImportRefused):
            b.submit_import(geom).result(timeout=30)
        # Refusals happen BEFORE any allocation: rows_free pinned.
        assert b.stats()["state_pool"]["rows_free"] == free0
        assert b.stats()["migration"]["import_rejected"] == 2
    finally:
        a.stop()
        b.stop()


# -- observability -----------------------------------------------------------

def test_state_pool_gated_additive(spec, params):
    gen = _gen(spec, params)
    try:
        st = gen.stats()
        assert "state_pool" in st and "kv_pool" not in st
        assert st["state_pool"]["state_dim"] == ssd_state_dim(spec.config)
    finally:
        gen.stop()
    # A kv_paged lane's stats carry NO state_pool key (defaults-off
    # bytes identical for the existing family).
    gatt = ContinuousGenerator("gpt2-small-test", n_slots=2, step_chunk=2,
                               kv_block_size=16)
    try:
        assert "state_pool" not in gatt.stats()
    finally:
        gatt.stop()


@pytest.mark.slow
def test_worker_serves_ssd_end_to_end(spec, params):
    from tpu_engine.runtime.engine import InferenceEngine
    from tpu_engine.serving.worker import WorkerNode
    from tpu_engine.utils.config import WorkerConfig
    from tpu_engine.utils.metrics import render_prometheus

    w = WorkerNode(WorkerConfig(node_id="s0", model="ssd-small-test",
                                gen_step_chunk=2, gen_prefill_chunk=8,
                                gen_state_rows=6),
                   engine=InferenceEngine(spec, params, dtype="float32"))
    try:
        out = w.handle_generate({"request_id": "r1",
                                 "prompt_tokens": [5, 9, 3],
                                 "max_new_tokens": 8})
        assert len(out["tokens"]) == 8
        out2 = w.handle_generate({"request_id": "r2",
                                  "prompt_tokens": [5, 9, 3],
                                  "max_new_tokens": 8})
        assert out2["tokens"] == out["tokens"]
        h = w.get_health()
        sp = h["generator"]["state_pool"]
        assert sp["rows_total"] == 5
        assert "kv_pool" not in h["generator"]
        body = render_prometheus([h]).decode()
        assert "tpu_engine_state_rows_total" in body
        assert "tpu_engine_state_bytes_per_row" in body
    finally:
        w.stop()


@pytest.mark.slow
def test_handoff_hold_and_export_slab(spec, params):
    """Disagg composition: a handoff-submitted SSD row parks after
    prefill, exports via wait_prefill, and the snapshot adopts on a
    decode lane byte-identically (the steady-state hop, family-ported
    for free through the shared wire format)."""
    a = _gen(spec, params)
    b = _gen(spec, params)
    try:
        control = a.generate([[4, 8, 2, 6]], max_new_tokens=12)[0]
        q = queue.Queue()
        a.submit([4, 8, 2, 6], max_new_tokens=12, stream=q, tag="h0",
                 handoff=True, handoff_park_s=30.0)
        snap = a.export_row("h0", timeout_s=30.0, wait_prefill=True)
        assert snap["ok"], snap
        got = []
        while True:
            item = q.get(timeout=10)
            if item is None:
                break
            got += item
        assert got == control[:len(got)] and len(got) >= 1
        q2 = queue.Queue()
        fut = b.submit_import(snap, stream=q2)
        while True:
            item = q2.get(timeout=60)
            if item is None:
                break
            got += item
        assert got == control and fut.result(timeout=10) == control
        assert a.stats()["handoff"]["holds"] == 1
        for g in (a, b):
            st = g.stats()["state_pool"]
            assert st["rows_free"] == st["rows_total"]
    finally:
        a.stop()
        b.stop()


@pytest.mark.slow
def test_crash_recover_keeps_serving(spec, params):
    """A device-step failure on a slab lane recovers ON the decode
    thread (the _recover path): the in-flight row fails retryable with
    its emitted count, the pool rebuilds clean (post-recover
    invariants), and fresh streams serve byte-identically."""
    gen = _gen(spec, params)
    try:
        before = gen.generate([[5, 9, 3]], max_new_tokens=8)[0]
        real = gen._slab_mixed_exe

        def failing(width, controls):
            gen._slab_mixed_exe = real

            def exe(*a, **k):
                raise RuntimeError("injected device failure")
            return exe

        gen._slab_mixed_exe = failing
        fut = gen.submit([5, 9, 3], max_new_tokens=30)
        with pytest.raises(RuntimeError, match="device-step failure"):
            fut.result(timeout=60)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st = gen.stats()["state_pool"]
            if st["rows_free"] == st["rows_total"]:
                break
            time.sleep(0.05)
        assert st["rows_free"] == st["rows_total"]
        assert gen.stats().get("recover_invariant_violations", 0) == 0
        after = gen.generate([[5, 9, 3]], max_new_tokens=8)[0]
        assert after == before
        assert gen.stats()["failures"] == 1
    finally:
        gen.stop()
