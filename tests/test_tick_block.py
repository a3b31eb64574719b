"""The mixed tick's control block (`runtime/scheduler.py` `TickBlock`,
`_tick_mixed`): everything the host hands the compiled step per row goes
to the device as ONE array a tick.

Contracts under test, on CPU lanes:
- `pack` then `unpack` gives every field back with the type, shape and
  bits it went in with, in every variant of the layout (the `controls`
  fields, a second table, the state rows), traced and not;
- the block is made fresh every tick: what a tick in flight reads is never
  refilled;
- `stats()["mixed"]["form_transfers"]` counts the host→device arrays a
  tick's form makes: at most three a tick on a plain, a `controls`, a
  windowed and a hybrid lane, decode ticks and chunk ticks alike, while
  the two widths a variant compile as before.

That the served tokens are the in-order lane's and the two-path
scheduler's, whatever the rows ask for at once, is
tests/test_tick_overlap.py's first contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tick_pipeline import mixed_counters, serve, wait_idle
from tpu_engine.models.registry import (
    _ensure_builtin_models_imported,
    create_model,
)
from tpu_engine.runtime.scheduler import ContinuousGenerator, TickBlock
from tpu_engine.utils.sampling import MAX_STOP_TOKENS

_ensure_builtin_models_imported()

B = 5
LAYOUTS = {
    "decode": dict(width=1, table_widths=[6]),
    "chunk": dict(width=16, table_widths=[6]),
    "controls": dict(width=1, table_widths=[6], controls=True),
    "windowed": dict(width=16, table_widths=[6, 4]),
    "hybrid": dict(width=1, table_widths=[6], state_rows=True),
    "everything": dict(width=16, table_widths=[6, 4], state_rows=True,
                       controls=True),
}


def _fields(width, table_widths, state_rows=False, controls=False):
    """A tick's inputs with values a careless cast would lose."""
    rng = np.random.default_rng(47)
    fields = {
        "pos0": np.array([0, 1, 95, 2**31 - 1, 7], np.int32),
        "qlen": np.array([0, 1, 16, 1, 3], np.int32),
        "sample_slot": np.array([0, 0, 15, 0, 2], np.int32),
        "fold_pos": np.array([1, 2, 96, 4, 10], np.int32),
        "seeds": np.array([0, -1, 11, -2**31, 2**31 - 1], np.int32),
        "topks": np.array([0, 40, 1, 50257, 0], np.int32),
        "eos_vec": np.array([-1, 0, 50256, -1, 3], np.int32),
        "active": np.array([False, True, True, False, True]),
        "done": np.array([True, False, False, True, False]),
        "from_prev": np.array([False, True, False, False, True]),
        "temps": np.array([0.0, 0.7, 1e-6, -0.0, np.inf], np.float32),
        "topps": np.array([1.0, 0.95, 0.9, np.float32(1) - 2**-24, 0.5],
                          np.float32),
        "minps": np.array([0.0, 1e-6, 0.05, np.float32(1e-45), np.nan],
                          np.float32),
        "tokens": rng.integers(0, 50257, (B, width)).astype(np.int32),
    }
    if state_rows:
        fields["state_rows"] = np.array([0, 3, 1, 0, 5], np.int32)
    if controls:
        fields["pens"] = np.array([1.0, 1.2, 1.4, 0.7, 1.0], np.float32)
        fields["stops"] = np.full((B, MAX_STOP_TOKENS), -1, np.int32)
        fields["stops"][1, :2] = (17, 50256)
    tables = [rng.integers(0, 1537, (B, n)).astype(np.int32)
              for n in table_widths]
    return tables, fields


def _same_bits(got, want):
    got = np.asarray(got)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("traced", [False, True], ids=["eager", "jitted"])
@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_pack_then_unpack_gives_every_field_back_bit_for_bit(name, traced):
    layout = TickBlock(**LAYOUTS[name])
    tables, fields = _fields(**LAYOUTS[name])
    block = layout.pack(tables, **fields)
    assert block.dtype == np.int32 and block.shape == (B, layout.cols)
    assert layout.cols == sum(f.reshape(B, -1).shape[1] for f in
                              (*fields.values(), *tables))
    unpack = jax.jit(layout.unpack) if traced else layout.unpack
    got = unpack(jnp.asarray(block))
    assert set(got) == set(fields) | {"tables"}
    for key, want in fields.items():
        _same_bits(got[key], want)
    assert len(got["tables"]) == len(tables)
    for g, want in zip(got["tables"], tables):
        _same_bits(g, want)


def test_a_block_is_made_fresh_and_holds_copies():
    """The loop changes `_done`, `_seeds`, the tables while the step may
    still read the block: a later change reaches no block already made,
    and two packs share no memory."""
    layout = TickBlock(**LAYOUTS["controls"])
    tables, fields = _fields(**LAYOUTS["controls"])
    first = layout.pack(tables, **fields)
    kept = first.copy()
    fields["done"][:] = True
    fields["seeds"][:] = 9
    fields["temps"][:] = 0.25
    tables[0][:] = 0
    second = layout.pack(tables, **fields)
    assert not np.shares_memory(first, second)
    assert (first == kept).all() and (second != kept).any()


def test_a_field_the_layout_does_not_name_is_refused():
    layout = TickBlock(**LAYOUTS["decode"])
    tables, fields = _fields(**LAYOUTS["decode"])
    with pytest.raises(ValueError, match="pens"):
        layout.pack(tables, **fields, pens=np.ones((B,), np.float32))
    del fields["eos_vec"]
    with pytest.raises(ValueError, match="eos_vec"):
        layout.pack(tables, **fields)


# -- the count, on lanes of each kind ------------------------------------------

LANE = dict(dtype="float32", n_slots=4, kv_block_size=16, prefill_chunk=16,
            prefix_sharing=False)
LANES = {
    "plain": ("gpt2-small-test", {}),
    "controls": ("gpt2-small-test", dict(repetition_penalty=1.3,
                                         stop_tokens=[3])),
    "windowed": ("laguna-small-test", {}),
    "hybrid": ("olmo_hybrid_small", {}),
}


def _prompt(seed, n):
    return [(seed * 31 + j * 7) % 90 + 1 for j in range(n)]


@pytest.mark.parametrize("kind", sorted(LANES))
def test_a_tick_s_form_makes_at_most_three_transfers(kind):
    """Decode ticks and chunk ticks (a prompt of three chunks beside a
    decoding row), counted where the arrays are made; both widths ran,
    and nothing compiled past the two programs a variant."""
    model, controls = LANES[kind]
    spec = create_model(model)
    gen = ContinuousGenerator(spec, params=spec.init(jax.random.PRNGKey(0)),
                              **LANE)
    try:
        assert gen._windowed == (kind == "windowed")
        assert gen._hybrid == (kind == "hybrid")
        requests = [dict(prompt=_prompt(1, 40), max_new_tokens=6, **controls),
                    dict(prompt=_prompt(2, 5), max_new_tokens=12, **controls)]
        serve(gen, requests)
        wait_idle(gen)
        warm, programs = mixed_counters(gen), gen.stats()["compile"]["count"]
        assert 0 < warm["form_transfers"] <= 3 * warm["ticks"]
        calls = []
        real = gen._mixed_step_exe
        gen._mixed_step_exe = lambda width, variant: (
            calls.append((width, variant)) or real(width, variant))
        serve(gen, requests)
        wait_idle(gen)
        after = mixed_counters(gen)
        ticks = after["ticks"] - warm["ticks"]
        assert ticks == len(calls) and after["dispatches"] == after["ticks"]
        assert {w for w, _ in calls} == {1, 16}
        assert {v for _, v in calls} == {bool(controls)}
        assert ticks <= (after["form_transfers"]
                         - warm["form_transfers"]) <= 3 * ticks
        assert gen.stats()["compile"]["count"] == programs
    finally:
        gen.stop()
