"""The Nemotron-H family (models/nemotron_h.py) on the served path: a layer
is ONE mixer of three kinds by a pattern string, and a row owns a Mamba-2
state in the M layers, a paged K/V chain (GQA at sixteen query heads a KV
head in the cell, nothing rotated) in the * layers, and nothing in the E
layers, whose routed experts are two matrices in a LATENT with relu^2
between them. `nemotron_h_small` (the cell's eleven layers `MEMEMEM*EME`, 4
query heads over 1 KV head of 8 lanes, 8 SSM heads of 4 lanes in 2 groups
over a state of 16 lanes, a latent of 16 lanes, 4 of 16 experts held, top
6) against the plain reference benchmarks/references/nemotron_h.py, on
logits; the four chips' shares summed; the two pools' bookkeeping. What the
family asks of shared code is held in tests/test_nemotron_h_ops.py."""

import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.models import nemotron_h as nh
from tpu_engine.models.nemotron_h import (
    NemotronHConfig,
    nemotron_h_apply,
    nemotron_h_step_rows_ragged,
)
from tpu_engine.models.registry import (
    FAMILY_CAPABILITIES,
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.ops import latent_attention as la
from tpu_engine.ops import paged_attention as pa
from tpu_engine.ops import ssd
from tpu_engine.ops.attention import KVCache
from tpu_engine.runtime.scheduler import ContinuousGenerator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16
LANE = dict(n_slots=4, dtype="float32", kv_block_size=BS,
            prefill_chunk=16, prefix_sharing=False)


@pytest.fixture(scope="module")
def spec():
    _ensure_builtin_models_imported()
    return create_model("nemotron_h_small")


@pytest.fixture(scope="module")
def params(spec):
    return jax.jit(spec.init)(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def reference():
    """benchmarks/references/nemotron_h.py and the test configuration's
    `reference` block as the harness hands it over."""
    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    path = os.path.join(bench, "references", "nemotron_h.py")
    module_spec = importlib.util.spec_from_file_location(
        "nemotron_h_reference_under_test", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    with open(os.path.join(ROOT, "tests", "benchmarks", "data", "configs",
                           "nemotron-h-small-test.json")) as f:
        sizes = json.load(f)["reference"]
    return module, sizes


def _sizes(sizes, **more):
    return tuple(sorted(dict(sizes, **more).items()))


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, n)]


# -- registry, configuration, the pattern ------------------------------------------

def test_family_capabilities_and_the_pattern_s_three_kinds(spec):
    cfg = spec.config
    assert spec.state_family == "kv_and_state" and cfg.recurrence == "ssd"
    assert spec.capabilities == FAMILY_CAPABILITIES["kv_and_state"]
    for absent in ("prefix_sharing", "kv_host_tier", "kv_quantize",
                   "spec_decode", "tensor_parallel", "migration", "handoff",
                   "two_path"):
        assert not spec.supports(absent)
    assert cfg.pattern == "MEMEMEM*EME" and spec.held == cfg.held == (0, 4)
    # Layer l's index among the layers of ITS kind: two pools of different
    # depths and the rows of the step's per-expert counts.
    assert cfg.pool_layer == (0, 0, 1, 1, 2, 2, 3, 0, 3, 4, 4)
    assert (cfg.n_linear_layers, cfg.n_moe_layers, cfg.n_full_layers) == (
        5, 5, 1)
    assert [k.n_layers for k in cfg.kv_block_kinds] == [1]
    assert cfg.kv_block_kinds[0].kv_lanes == (8, 8)
    # S and the conv tail, 3 x (32 + 2 x 2 x 16) = 288 numbers as 8 x 36.
    assert cfg.state_row_shapes == ((8, 4, 16), (8, 36))


def test_the_published_geometry_is_the_default():
    _ensure_builtin_models_imported()
    spec = create_model("nemotron_h")
    cfg = spec.config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        source = next(row for row in map(json.loads, f) if row["name"]
                      == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")["config"]
    assert cfg.pattern == source["hybrid_override_pattern"]
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (88, 4096, 131072)
    assert (cfg.n_linear_layers, cfg.n_moe_layers, cfg.n_full_layers) == (
        40, 40, 8)
    assert (cfg.n_heads, cfg.kv_heads, cfg.d_head) == (32, 2, 128)
    assert (cfg.lin_heads, cfg.ssm_head_dim, cfg.d_state, cfg.n_groups,
            cfg.conv_width, cfg.d_ssm, cfg.conv_lanes) == (
        128, 64, 128, 8, 4, 8192, 10240)
    assert (cfg.d_latent, cfg.d_ff_expert, cfg.d_ff_shared, cfg.n_routed,
            cfg.top_k, cfg.routed_scale, cfg.held) == (
        1024, 2688, 5376, 512, 22, 5.0, (0, 512))
    shapes = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 120.6e9 < count < 120.8e9             # the name's 120 B
    # A row's state: 128 x 64 x 128 and 3 x 10,240 float32 an M layer.
    assert cfg.state_row_shapes == ((128, 64, 128), (8, 3840))
    assert sum(int(np.prod(s)) for s in cfg.state_row_shapes) * 4 == 4317184


@pytest.mark.parametrize("change, message", [
    (dict(pattern="MEMEMEM*E-E"), "'-'.*dense feed-forward.*not built"),
    (dict(pattern="MEMEMEM*EMX"), "'X' is no layer kind"),
    (dict(pattern="MEMEMEM*EM"), "one character a layer"),
    (dict(pattern="MEMEMEMEEME"), r"needs a M and a \*"),
    (dict(pattern="*E*E*E**E*E"), r"needs a M and a \*"),
    (dict(held=(14, 4)), "is no share of 16 experts"),
    (dict(held=(0, 0)), "is no share of 16 experts"),
    (dict(lin_heads=7), "no whole groups"),
])
def test_a_pattern_or_a_share_that_cannot_be_served_is_refused(spec, change,
                                                               message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(spec.config, **change)


def test_a_cut_keeps_the_pattern_s_first_layers():
    _ensure_builtin_models_imported()
    cfg = create_model("nemotron_h_small", n_layers=9).config
    assert cfg.pattern == "MEMEMEM*E"
    assert isinstance(cfg, NemotronHConfig)
    # The dense kind is refused through the factory too, by its character.
    with pytest.raises(ValueError, match="'-'"):
        create_model("nemotron_h_small", n_layers=3, pattern="M-*")


# -- the model against the plain reference ----------------------------------------

def test_the_forward_equals_the_plain_reference(spec, params, reference):
    module, sizes = reference
    tokens = jnp.asarray(_prompt(0, 70), jnp.int32)
    want = module.forward(params, tokens, _sizes(sizes))
    with jax.default_matmul_precision("highest"):
        got = nemotron_h_apply(params, tokens[None], spec.config,
                               dtype=jnp.float32)[0]
    assert float(want.std()) > 0.5
    np.testing.assert_allclose(got, want, atol=5e-5)
    assert len(set(np.asarray(got).argmax(-1).tolist())) > 40


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_each_kind_of_layer_equals_the_reference_s(spec, params, reference,
                                                   kind):
    """One mixer of each kind on the same normed rows: the program's (the
    chunked form; attention over the sequence; the grouped product over the
    latent rows) against the reference's (the scan; the mask; every held
    expert over every token)."""
    module, sizes = reference
    cfg, sizes = spec.config, dict(sizes)
    layer = cfg.pattern.index(kind)
    bp = params["layers"][layer]
    u = jax.random.normal(jax.random.PRNGKey(7), (64, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        if kind == "M":
            got = nh._ssm_whole_row(bp["ssm"], u, cfg, jnp.float32,
                                    nh._ssm_inputs, nh._ssm_output)
            want = module._mamba(bp["ssm"], u, sizes)
        elif kind == "*":
            q, k, v = nh._attn_inputs(bp["attn"], u, cfg, jnp.float32)
            o = nh.dot_product_attention(q[None], k[None], v[None],
                                         causal=True)[0]
            got = nh._attn_output(bp["attn"], o, jnp.float32)
            # 64 tokens: one block of the reference's queries is 256.
            want = module._attention(
                bp["attn"], jnp.pad(u, ((0, 192), (0, 0))), sizes)[:64]
        else:
            got, rows = nh._latent_moe(bp["mlp"], u, jnp.ones(64, bool), cfg,
                                       jnp.float32, cfg.held, None)
            want = module._latent_moe(bp["mlp"], u, sizes)
            assert int(rows[:4].sum()) > 0 and int(rows[4:].sum()) == 0
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_four_shares_add_up_to_the_uncut_layer(spec, reference):
    """Four chips share an expert layer: each holds 4 of the 16 routed
    experts and the router, the latent projections and the shared expert
    whole. The four shares' routed parts, with what every chip computes
    alike (the shared expert; the up-projection's zero bias) counted once,
    sum to the reference's layer over all 16 experts."""
    module, sizes = reference
    _ensure_builtin_models_imported()
    whole = create_model("nemotron_h_small", held_count=0)
    cfg = whole.config
    assert cfg.held == (0, 16)
    layer = cfg.pattern.index("E")
    mp = jax.jit(whole.init)(jax.random.PRNGKey(3))["layers"][layer]["mlp"]
    u = jax.random.normal(jax.random.PRNGKey(8), (48, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = module._latent_moe(mp, u, dict(sizes))
        alike = module._dense(mp["shared"]["proj"], module._act(
            module._dense(mp["shared"]["up"], u, {}), {}), {})
        total, taken = 0.0, 0
        for first in (0, 4, 8, 12):
            share = dict(mp, experts={k: v[first:first + 4]
                                      for k, v in mp["experts"].items()})
            y, rows = nh._latent_moe(share, u, jnp.ones(48, bool), cfg,
                                     jnp.float32, (first, 4), None)
            assert int(rows.sum()) == int(rows[first:first + 4].sum()) > 0
            total, taken = total + (y - alike), taken + int(rows.sum())
            # One share alone is not the layer.
            assert float(jnp.abs(y - want).max()) > 0.05
    assert taken == 48 * cfg.top_k               # every pair on some chip
    np.testing.assert_allclose(total + alike, want, atol=3e-5)


def _pools(cfg, rows, blocks, state_dtype=jnp.float32):
    shape = (cfg.n_full_layers, blocks, BS, cfg.kv_heads * cfg.d_head)
    return (KVCache(jnp.zeros(shape), jnp.zeros(shape)),
            tuple(jnp.zeros((cfg.n_linear_layers, rows) + s, state_dtype)
                  for s in cfg.state_row_shapes))


def _serve_in_chunks(spec, params, chunks, state_dtype=jnp.float32):
    """Two rows of different lengths in the same ticks through
    `nemotron_h_step_rows_ragged`: row 0 prefills `chunks` and then decodes;
    row 2 prefills 23 tokens and decodes beside it, so a tick runs the
    chunked form and the one-step form together and routes both rows'
    pairs in one list. Row 1 is a free slot on the null state row. Returns
    ({row: (tokens, logits)}, caches, the rows the experts took)."""
    cfg = spec.config
    n_prompt, n_new = sum(chunks), 6
    seqs = {0: _prompt(1, n_prompt + n_new), 2: _prompt(2, 23 + 12)}
    plans = {0: list(chunks) + [1] * n_new, 2: [16, 7] + [1] * 12}
    caches = _pools(cfg, rows=4, blocks=17, state_dtype=state_dtype)
    table = np.zeros((3, 8), np.int32)
    table[0], table[2] = np.arange(1, 9), np.arange(9, 17)
    tables = (jnp.asarray(table), jnp.asarray([3, 0, 1], jnp.int32))

    def through(fn):
        """`fn` on a pool of `state_dtype`: what it reads is what the pool
        holds, what it writes is rounded to it."""
        def call(*args):
            ins, (pool, *where) = args[:5], args[5:]
            y, new = fn(*ins, pool.astype(jnp.float32), *where)
            return y, new.astype(pool.dtype)
        return call

    step = jax.jit(lambda tokens, caches, pos0, qlen:
                   nemotron_h_step_rows_ragged(
                       params, tokens, caches, tables, pos0, qlen, cfg,
                       dtype=jnp.float32, max_tokens=36,
                       step_fn=through(ssd.ssd_step_rows),
                       chunk_fn=through(ssd.ssd_chunk_row)))
    pos, got, taken = {0: 0, 2: 0}, {0: [], 2: []}, 0
    with jax.default_matmul_precision("highest"):
        while any(plans.values()):
            tokens = np.zeros((3, 16), np.int32)
            pos0, qlen = np.zeros(3, np.int32), np.zeros(3, np.int32)
            for r, plan in plans.items():
                if plan:
                    n = plan.pop(0)
                    tokens[r, :n] = seqs[r][pos[r]:pos[r] + n]
                    pos0[r], qlen[r] = pos[r], n
            logits, caches, rows = step(jnp.asarray(tokens), caches,
                                        jnp.asarray(pos0), jnp.asarray(qlen))
            assert rows.shape == (cfg.n_moe_layers, cfg.n_routed)
            taken = taken + np.asarray(rows)
            for r in pos:
                got[r].append(np.asarray(logits[r, :qlen[r]]))
                pos[r] += int(qlen[r])
    return ({r: (seqs[r], np.concatenate(got[r])) for r in seqs}, caches,
            taken)


@pytest.mark.parametrize("chunks", [(16, 16, 16, 2), (7, 16, 16, 11),
                                    (16, 1, 16, 16, 1)])
def test_chunked_prefill_then_decode_equals_the_reference_on_logits(
        spec, params, reference, chunks):
    """At least three chunks, so a chunk starts from the state and the conv
    tail the last one left in each of the five M layers and reads K and V
    the earlier ones wrote in the * layer, then decode steps through both
    pools, the E layers between them, on logits within 1e-4."""
    module, sizes = reference
    served, caches, taken = _serve_in_chunks(spec, params, chunks)
    for seq, got in served.values():
        want = module.forward(params, jnp.asarray(seq, jnp.int32),
                              _sizes(sizes))
        np.testing.assert_allclose(got, want, atol=1e-4)
    # The free slot's null row took nothing; only held experts took rows.
    assert all(float(jnp.abs(x[:, 0]).max()) == 0.0 for x in caches[1])
    assert caches[0].k.shape[0] == 1 and caches[1][0].shape[0] == 5
    assert taken[:, :4].sum() > 0 and taken[:, 4:].sum() == 0


def test_a_bfloat16_state_fails_the_float32_comparison(spec, params,
                                                       reference):
    """The same ticks over a state pool kept in bfloat16 (rounded after
    every chunk and every step) miss the reference by far more than the
    1e-4 the float32 pool keeps: what `correct`'s limits on the chip may
    not tell (bfloat16 weights move a logit by more) is held here."""
    module, sizes = reference
    served, _, _ = _serve_in_chunks(spec, params, (16, 16, 16, 2),
                                    state_dtype=jnp.bfloat16)
    seq, got = served[0]
    want = module.forward(params, jnp.asarray(seq, jnp.int32), _sizes(sizes))
    assert float(np.abs(got - np.asarray(want)).max()) > 1e-3


def test_the_pair_list_is_gathered_from_the_latent_rows(spec, params):
    """The step of three rows in 16 slots, traced: every E layer's two
    grouped products are (pairs, d_ff_expert) and (pairs, d_latent), the
    pair list `max_tokens` x top_k long, and nothing of pairs x d_model is
    ever made: the gather and the scatter-add move the latent's lanes. The
    * layer makes the short and the tall call; the M layers none."""
    cfg = spec.config
    asked = []

    def attn_fn(q, *rest):
        asked.append(q.shape)
        return pa.ragged_paged_attention_reference(q, *rest)

    tables = (jnp.zeros((3, 8), jnp.int32), jnp.zeros(3, jnp.int32))
    jaxpr = jax.make_jaxpr(
        lambda tokens, caches, pos0, qlen: nemotron_h_step_rows_ragged(
            params, tokens, caches, tables, pos0, qlen, cfg,
            dtype=jnp.float32, max_tokens=20, attn_fn=attn_fn,
            sample_slot=jnp.zeros(3, jnp.int32)))(
        jnp.zeros((3, 16), jnp.int32), _pools(cfg, rows=4, blocks=17),
        jnp.zeros(3, jnp.int32), jnp.ones(3, jnp.int32))
    pairs = 20 * cfg.top_k
    shapes = [tuple(v.aval.shape) for eqn in jaxpr.jaxpr.eqns
              for v in eqn.outvars]
    products = [tuple(e.outvars[0].aval.shape) for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "ragged_dot_general"]
    assert products == [(pairs, cfg.d_ff_expert),
                        (pairs, cfg.d_latent)] * cfg.n_moe_layers
    assert (pairs, cfg.d_latent) in shapes
    assert (pairs, cfg.d_model) not in shapes
    heads = (cfg.n_heads, cfg.d_head)
    assert len(asked) == 2 and asked[0] == (3, 1) + heads
    assert asked[1][1:] == (la.tall_slots(16, 4),) + heads


# -- the served path ---------------------------------------------------------------

def test_the_mixed_tick_serves_it_from_both_pools_and_counts(spec, params,
                                                             reference):
    from tpu_engine.utils.tracing import SpanRecorder

    module, sizes = reference
    gen = ContinuousGenerator(spec, params=params, **LANE)
    gen.tracer, gen.trace_node = SpanRecorder(capacity=4096), "lane"
    prompts = [_prompt(5, 50), _prompt(6, 23), _prompt(7, 37)]
    try:
        pools = gen._pool, gen._spool
        # Two pools of different depths: one * layer, five M layers.
        assert pools[0].cfg.n_layers == 1 and pools[1].n_layers == 5
        assert [x.shape for x in pools[1].slab] == [
            (5, 5, 8, 4, 16), (5, 5, 8, 36)]
        futures = [gen.submit(p, max_new_tokens=12) for p in prompts]
        served = [f.result(timeout=300) for f in futures]
        stats = gen.stats()
    finally:
        gen.stop()
    for prompt, tokens in zip(prompts, served):
        want = module.forward(params,
                              jnp.asarray(prompt + tokens[:-1], jnp.int32),
                              _sizes(sizes))[len(prompt) - 1:]
        gap = want.max(-1) - want[jnp.arange(len(tokens)), jnp.asarray(tokens)]
        assert float((gap / want.std(-1)).max()) < 0.05
    state, pool, routed = (stats["state_pool"], stats["kv_pool"],
                           stats["moe"])
    assert state["rows_total"] == 4 and state["rows_peak"] == 3
    assert state["rows_held"] == 0 and state["rows_free"] == 4
    assert state["bytes_per_row"] == 5 * (8 * 4 * 16 + 288) * 4
    assert pool["blocks_free"] == pool["blocks_total"]
    assert pool["kv_bytes_held"] == pool["state_bytes_held"] == 0
    assert pool["block_lanes"] == [8, 8]
    spans = [s["attrs"] for s in gen.tracer.snapshot()
             if s["op"] == "mixed_step"]
    mixed = stats["mixed"]
    fed = mixed["prefill_tokens"] + mixed["decode_tokens"]
    assert sum(s["ssd_chunk_tokens"] + s["ssd_step_rows"]
               for s in spans) == fed
    assert any(s["ssd_chunk_tokens"] and s["ssd_step_rows"] for s in spans)
    assert all(s["ctx_tokens_full"] == s["ctx_tokens"] for s in spans)
    # A chunk of at most 16 tokens at G = 4 is one tall tile of 32 slots.
    assert all(s["attn_tiles_short"] == s["ssd_step_rows"]
               and s["attn_tiles_tall"] == s["ssd_chunk_rows"] for s in spans)
    assert max(s["state_rows_held"] for s in spans) == 3
    assert not any(k.startswith(("gdn_", "kda_")) for s in spans for k in s)
    # Every fed token routes top_k pairs in each of the five E layers; the
    # quarter routed to the four held experts formed rows here.
    assert routed["assignments"] == fed * 6 * 5
    assert routed["assignments"] == sum(s["moe_assignments"] for s in spans)
    assert routed["assignments_held"] == sum(
        s["moe_assignments_held"] for s in spans)
    assert routed["experts_touched"] == sum(
        s["moe_experts_touched"] for s in spans)
    assert 0.1 < routed["assignments_held"] / routed["assignments"] < 0.45
    by_expert = np.asarray(routed["rows_by_expert"])
    assert by_expert.shape == (5, 16)
    assert by_expert[:, :4].sum() == routed["assignments_held"]
    assert by_expert[:, 4:].sum() == 0


def test_what_the_family_refuses_at_start_up_stays_refused(spec, params):
    for flag, value in (("prefix_sharing", True), ("kv_quantize", "int8"),
                        ("spec_k", 2)):
        with pytest.raises(ValueError, match="does not declare"):
            ContinuousGenerator(spec, params=params,
                                **{**LANE, flag: value})
    with pytest.raises(ValueError, match="mixed tick over the block pool"):
        ContinuousGenerator(spec, params=params,
                            **{**LANE, "kv_block_size": 0})
