"""Every op of a tick carries the part of the step it belongs to, and a
tick's program is named for its width (PR 55, `utils/tracing.py`
`STEP_PARTS` / `step_part` / `tick_name`): each family's lane at toy sizes,
its mixed step LOWERED (not compiled) at both widths, and the scope paths in
the lowered text's debug info held to the parts the family should open, and
to no other."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.runtime.scheduler import ContinuousGenerator
from tpu_engine.utils import tracing

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmarks"))
from bench_paths import BENCH  # noqa: E402,F401  (benchmarks/ on the path)

from lib.xplane_scopes import part_of  # noqa: E402

CHUNK = 16
ATTN = {"attn/qkv", "attn/write", "attn/read", "attn/out"}
EVERY_STEP = {"embed", "plan", "head", "sample"} | ATTN
MIXER = {"mixer/in", "mixer/step", "mixer/out"}   # + mixer/chunk at a width
MOE = {"moe/route", "moe/experts", "moe/shared"}
# family -> (lane options, the parts its step opens beside EVERY_STEP,
#            the run a generating row feeds)
FAMILIES = {
    "gpt2-small-test": ({}, {"mlp"}, 1),
    "moonlight-small-test": ({}, {"mlp"} | MOE, 1),
    "laguna-small-test": ({"prefix_sharing": False}, {"mlp"} | MOE, 1),
    "olmo_hybrid_small": ({"prefix_sharing": False}, {"mlp"} | MIXER, 1),
    "kimi_linear_small": ({"prefix_sharing": False},
                          {"mlp"} | MIXER | MOE, 1),
    "falcon_h1_small": ({"prefix_sharing": False}, {"mlp"} | MIXER, 1),
    # a layer is ONE mixer: no dense feed-forward anywhere
    "nemotron_h_small": ({"prefix_sharing": False}, MIXER | MOE, 1),
    # every layer routes, none has a shared expert; a block is revealed
    "sdar-small-test": ({"prefix_sharing": False},
                        {"moe/route", "moe/experts", "sample/reveal"}, 4),
    # one layer body under two scans: the parts are opened once
    "ouro-small-test": ({"prefix_sharing": False}, {"mlp"}, 1),
}


def _tick_args(gen, width):
    """What `_tick_mixed` hands the compiled step, with an empty block."""
    pools = (gen._pool.caches,)
    if gen._windowed:
        pools = ((gen._pool.caches, gen._wpool.caches),)
    if gen._hybrid:
        pools = ((gen._pool.caches, gen._spool.slab),)
    block = jnp.zeros((gen._tables.shape[0],
                       gen._tick_block(width, False).cols), jnp.int32)
    return (gen._step_params, *pools, block, gen._prev_nxt, gen._prev_done)


@pytest.fixture(scope="module")
def lowered():
    """family, chunk? -> (module name, scope paths of the lowered step),
    a lane a family, stopped at the module's end."""
    _ensure_builtin_models_imported()
    lanes, texts = {}, {}

    def get(family, chunk):
        options, _, run = FAMILIES[family]
        if family not in lanes:
            lanes[family] = ContinuousGenerator(
                create_model(family), n_slots=4, dtype="float32",
                kv_block_size=16, prefill_chunk=CHUNK, **options)
        if (family, chunk) not in texts:
            gen = lanes[family]
            width = CHUNK if chunk else run
            text = gen._mixed_step_exe(width, False).lower(
                *_tick_args(gen, width)).as_text(debug_info=True)
            module, = re.findall(r"module @(\S+)", text)
            texts[family, chunk] = (module,
                                    set(re.findall(r'loc\("([^"]+)"', text)))
        return texts[family, chunk]

    yield get
    for gen in lanes.values():
        gen.stop()


@pytest.mark.parametrize("chunk", [False, True], ids=["narrow", "chunk"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_family_s_step_opens_its_parts_and_no_other(lowered, family, chunk):
    _, own, run = FAMILIES[family]
    module, paths = lowered(family, chunk)
    want = EVERY_STEP | own
    if chunk and own & MIXER:
        want = want | {"mixer/chunk"}
    found = {part_of(path) for path in paths} - {None}
    assert found == want, (sorted(found - want), sorted(want - found))
    # The program says its width, as the tick's span does: a narrow tick
    # reads width 1 whatever run its rows feed.
    assert module == "jit_" + tracing.tick_name(CHUNK if chunk else 1, run)
    # ... and every op's path starts from it.
    scoped = [p for p in paths if part_of(p) and p.startswith("jit(")]
    assert scoped and all(
        p.startswith(f"jit({module[len('jit_'):]})/") for p in scoped)


def test_a_scope_inside_the_layer_scan_keeps_its_path(lowered):
    """gpt2's layers are a `lax.scan`: its body is a function of its own
    in the lowered text, and the parts opened inside it are there."""
    _, paths = lowered("gpt2-small-test", True)
    inside = {part_of(p) for p in paths if not p.startswith("jit(")}
    assert ATTN | {"mlp"} <= inside


def test_the_sampler_s_three_bodies_lie_under_sample(lowered):
    _, paths = lowered("gpt2-small-test", False)
    branches = {p for p in paths if "/cond/branch_" in p}
    assert branches and all(part_of(p) == "sample" for p in branches)


@pytest.mark.parametrize("name", ["attn", "moe", "mixer", "attn/reed",
                                  "reveal", ""])
def test_step_part_refuses_a_name_outside_the_vocabulary(name):
    with pytest.raises(ValueError):
        tracing.step_part(name)


def test_a_scope_changes_no_number():
    """A part is metadata: the same function with and without its scopes
    lowers to the same operations and returns the same bits."""
    def plain(x, w):
        return jnp.tanh(x @ w).sum(-1)

    def scoped(x, w):
        with tracing.step_part("mlp"):
            y = jnp.tanh(x @ w)
        with tracing.step_part("head"):
            return y.sum(-1)

    x = jax.random.normal(jax.random.PRNGKey(0), (8, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    assert (jax.jit(plain)(x, w) == jax.jit(scoped)(x, w)).all()
    texts = [jax.jit(f).lower(x, w).as_text() for f in (plain, scoped)]
    assert texts[0].replace("jit_plain", "jit_scoped") == texts[1]


@pytest.mark.parametrize("args, name", [
    ((1,), "tick_w1"), ((256,), "tick_w256"), ((1, 4), "tick_w1_r4"),
    ((256, 4), "tick_w256_r4"), ((5, 1, "spec"), "spec_w5"),
])
def test_tick_names(args, name):
    assert tracing.tick_name(*args) == name


def test_the_speculative_step_is_named_for_its_window():
    """`--spec-k` lanes compile another program for the same step: its name
    says `spec` and the window's width."""
    gen = ContinuousGenerator(create_model("gpt2-small-test"), n_slots=4,
                              dtype="float32", kv_block_size=16,
                              prefill_chunk=CHUNK, spec_k=2)
    try:
        exe = gen._spec_step_exe(3, False)
        assert exe.__name__ == "spec_w3"
    finally:
        gen.stop()


def test_the_listed_second_half_s_gathers_and_write_back_lie_under_its_parts(
        lowered):
    """A chunk tick's second half runs over the tick's token list (PRs 59
    and 63, `models.transformer.second_half_slots`): the gather of the
    residual's rows is an op of `attn/out` beside `wo`'s product and its
    add (that of the read's output one of `attn/read`, before it is
    converted), the one write-back an op of `mlp`, so `step.attn_busy`
    and `step.ffn_busy` read their whole parts and nothing of either
    lands under no part; a width-1 tick has neither."""
    def ops(chunk, part):
        _, paths = lowered("gpt2-small-test", chunk)
        return {p.rsplit("/", 1)[-1] for p in paths if part_of(p) == part}

    assert "gather" in ops(True, "attn/out")
    assert "scatter" in ops(True, "mlp")
    assert "scatter" not in ops(True, "attn/out")
    assert not {"gather", "scatter"} & (ops(False, "mlp")
                                        | ops(False, "attn/out"))
    # The scatters inside the layer scan: the pool's and the residual's.
    _, paths = lowered("gpt2-small-test", True)
    assert {part_of(p) for p in paths if p.endswith("/scatter")
            and not p.startswith("jit(")} == {"attn/write", "mlp"}


@pytest.mark.parametrize("chunk", [False, True], ids=["narrow", "chunk"])
def test_attn_out_names_wo_s_product_and_its_add(lowered, chunk):
    """Over the list as over every slot, `attn/out` holds one product,
    `wo`'s, and the add of its result to the residual."""
    _, paths = lowered("gpt2-small-test", chunk)
    under = [p.rsplit("/", 1)[-1] for p in paths
             if part_of(p) == "attn/out"]
    assert under.count("dot_general") == 1
    assert "add" in under
