"""Every plain reference in benchmarks/references/ against the program's own
float32 forward pass at the small test sizes, `check_served` telling a right
served path from one with a dropped term, and the rules a reference file
keeps: no import from the program, and no dialect's name in the harness."""

import ast
import glob
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH, DATA  # noqa: E402

from lib import reference  # noqa: E402

REFERENCES = os.path.join(BENCH, "references")
DIALECTS = sorted(os.path.basename(p)[:-3]
                  for p in glob.glob(os.path.join(REFERENCES, "[!_]*.py")))


def _forward(dialect, directory=REFERENCES):
    spec = importlib.util.spec_from_file_location(
        "reference_under_test_" + dialect.replace("-", "_"),
        os.path.join(directory, dialect + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.forward


def _transformer_program(spec, params):
    import jax.numpy as jnp

    from tpu_engine.models.transformer import transformer_apply

    def program(tokens):
        return np.asarray(transformer_apply(
            params, jnp.asarray(tokens)[None], spec.config,
            dtype=jnp.float32)[0])
    return program


def _slab_program(spec, params):
    """The recurrence the lane serves: one `ssd_step_rows` a token."""
    import jax
    import jax.numpy as jnp

    from tpu_engine.models.ssd import ssd_init_states, ssd_step_rows

    step = jax.jit(lambda tok, st: ssd_step_rows(params, tok, st,
                                                 spec.config))

    def program(tokens):
        states, rows = ssd_init_states(spec.config, 1), []
        for t in tokens:
            logits, states = step(jnp.asarray([t], jnp.int32), states)
            rows.append(np.asarray(logits[0]))
        return np.stack(rows)
    return program


# Reference file -> (the test configuration whose `reference` block gives its
# sizes, the program's float32 forward, a parameter the reference must miss
# when it is zeroed). A reference that arrives later brings a test file of
# its own, test_benchmark_reference_<name>.py
# (test_every_reference_file_has_a_parity_test).
CASES = {
    "gpt2": ("gpt2-small-test", _transformer_program,
             ("blocks", "mlp", "proj", "kernel")),
    "mistral": ("llama-small-test", _transformer_program,
                ("blocks", "mlp", "proj", "kernel")),
    "mamba2": ("ssd-small-test", _slab_program, ("blocks", "D")),
}
HERE_PINNED = sorted(set(DIALECTS) & set(CASES))


def _setup(dialect):
    import jax

    from tpu_engine.models import registry

    name, program_of, dropped = CASES[dialect]
    with open(os.path.join(DATA, "configs", name + ".json")) as f:
        config = json.load(f)
    registry._ensure_builtin_models_imported()
    spec = registry.create_model(config["factory"], **config["kwargs"])
    params = spec.init(jax.random.PRNGKey(3))
    return (config, spec, params, program_of(spec, params),
            _forward(dialect), dropped)


def test_every_reference_file_has_a_parity_test():
    assert {"gpt2", "mistral", "mamba2"} <= set(DIALECTS)
    tests = "".join(open(p).read() for p in glob.glob(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "test_benchmark_reference*.py")))
    for dialect in DIALECTS:
        assert f'"{dialect}"' in tests, dialect


@pytest.mark.parametrize("dialect", HERE_PINNED)
def test_reference_logits_equal_the_program_s_in_float32(dialect):
    """40 tokens: more than one prefill chunk of the test lanes (16). The
    tolerance is 2e-4 of the largest logit: both sides are float32 and
    differ by the order of their sums alone (measured here: 5e-6 for the
    recurrence over 40 steps, 1e-6 for the transformers); a dropped term
    moves logits by their own size."""
    import jax.numpy as jnp

    config, spec, params, program, forward, _ = _setup(dialect)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, spec.config.vocab, size=40).astype(np.int32)
    ours = np.asarray(forward(params, jnp.asarray(tokens),
                              reference.sizes_of(config["reference"])))
    theirs = program(tokens)
    assert ours.shape == theirs.shape == (40, spec.config.vocab)
    assert ours.dtype == np.float32
    assert np.abs(ours - theirs).max() < 2e-4 * np.abs(theirs).max()


@pytest.mark.parametrize("dialect", HERE_PINNED)
def test_check_served_accepts_greedy_tokens_and_refuses_a_dropped_term(
        dialect):
    import jax

    config, spec, params, program, forward, dropped = _setup(dialect)
    rng = np.random.default_rng(1)
    samples = []
    for length in (5, 19):
        prompt = [int(t) for t in rng.integers(0, spec.config.vocab, length)]
        seq = list(prompt)
        for _ in range(6):                       # greedy, one token at a time
            seq.append(int(program(np.asarray(seq, np.int32))[-1].argmax()))
        samples.append((prompt, seq[length:]))
    ok, details = reference.check_served(forward, params, config["reference"],
                                         samples, 0.05, 0.9, pad_to=32)
    assert ok, details
    assert details["exact_share"] == 1.0 and details["positions"] == 12
    # The same tokens against a reference that misses one term: the MLP's
    # output, or the recurrent mixer's D * x skip.
    broken = jax.tree.map(lambda x: x, params)
    leaf = broken
    for key in dropped[:-1]:
        leaf = leaf[key]
    leaf[dropped[-1]] = leaf[dropped[-1]] * 0.0
    ok, details = reference.check_served(forward, broken, config["reference"],
                                         samples, 0.05, 0.9, pad_to=32)
    assert not ok, details


def test_an_overlong_sample_is_an_error():
    config, _, params, _, forward, _ = _setup("gpt2")
    with pytest.raises(ValueError):
        reference.served_gaps(forward, params,
                              reference.sizes_of(config["reference"]),
                              list(range(40)), [1, 2], pad_to=32)


def _imports(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            found.add((node.module or "").split(".")[0])
    return found


@pytest.mark.parametrize("name", sorted(
    os.path.basename(p) for p in glob.glob(os.path.join(REFERENCES, "*.py"))))
def test_a_reference_file_imports_nothing_of_the_program(name):
    imported = _imports(os.path.join(REFERENCES, name))
    assert imported <= {"jax", "numpy", "references"}, imported
    if not name.startswith("_"):
        assert callable(_forward(name[:-3]))


@pytest.mark.parametrize("path", ["run.py", "lib/reference.py", "lib/sut.py",
                                  "lib/roofline.py"])
def test_the_harness_names_no_dialect(path):
    with open(os.path.join(BENCH, path)) as f:
        text = f.read().lower()
    for dialect in DIALECTS + ["llama", "mamba", "ssd"]:
        assert dialect not in text, f"{path} names {dialect!r}"
    assert "tpu_engine" not in _imports(os.path.join(BENCH,
                                                     "lib/reference.py"))


def test_a_test_s_own_reference_is_found_beside_its_benchmark_file():
    """tests/benchmarks/data/references/slab-test.py: the dialect of the
    rehearsal's recurrent cell, a name benchmarks/references/ does not
    have."""
    assert "slab-test" not in DIALECTS
    own = _forward("slab-test", os.path.join(DATA, "references"))
    assert own.__module__ == "references.mamba2"
