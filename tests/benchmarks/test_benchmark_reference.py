"""The plain reference against the program's own forward pass at the small
test sizes, for both dialects, and `check_served` telling a right served
path from one with a dropped term."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import DATA  # noqa: E402

from lib import reference  # noqa: E402

CONFIGS = ["gpt2-small-test", "llama-small-test"]


def _setup(name):
    import jax
    import jax.numpy as jnp

    from tpu_engine.models import registry
    from tpu_engine.models.transformer import transformer_apply

    with open(os.path.join(DATA, "configs", name + ".json")) as f:
        config = json.load(f)
    registry._ensure_builtin_models_imported()
    spec = registry.create_model(config["factory"], **config["kwargs"])
    params = spec.init(jax.random.PRNGKey(3))

    def program(tokens):
        return np.asarray(transformer_apply(
            params, jnp.asarray(tokens)[None], spec.config,
            dtype=jnp.float32)[0])

    return config, spec, params, program


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_logits_equal_the_program_s_in_float32(name):
    import jax.numpy as jnp

    config, spec, params, program = _setup(name)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, spec.config.vocab, size=40).astype(np.int32)
    ours = np.asarray(reference.forward(
        params, jnp.asarray(tokens), reference.sizes_of(config["reference"])))
    theirs = program(tokens)
    assert ours.shape == theirs.shape == (40, spec.config.vocab)
    assert np.abs(ours - theirs).max() < 2e-4 * np.abs(theirs).max()


@pytest.mark.parametrize("name", CONFIGS)
def test_check_served_accepts_greedy_tokens_and_refuses_a_dropped_term(name):
    import jax

    config, spec, params, program = _setup(name)
    rng = np.random.default_rng(1)
    samples = []
    for length in (5, 19):
        prompt = [int(t) for t in rng.integers(0, spec.config.vocab, length)]
        seq = list(prompt)
        for _ in range(6):                       # greedy, one token at a time
            seq.append(int(program(np.asarray(seq, np.int32))[-1].argmax()))
        samples.append((prompt, seq[length:]))
    ok, details = reference.check_served(params, config["reference"],
                                         samples, 0.05, 0.9, pad_to=32)
    assert ok, details
    assert details["exact_share"] == 1.0 and details["positions"] == 12
    # The same tokens against a reference whose MLP output is dropped.
    broken = jax.tree.map(lambda x: x, params)
    broken["blocks"]["mlp"]["proj"]["kernel"] = \
        broken["blocks"]["mlp"]["proj"]["kernel"] * 0.0
    ok, details = reference.check_served(broken, config["reference"],
                                         samples, 0.05, 0.9, pad_to=32)
    assert not ok, details


def test_unknown_dialect_and_overlong_sample_are_errors():
    with pytest.raises(ValueError):
        reference.sizes_of({"dialect": "bert"})
    config, _, params, _ = _setup("gpt2-small-test")
    with pytest.raises(ValueError):
        reference.served_gaps(params, reference.sizes_of(config["reference"]),
                              list(range(40)), [1, 2], pad_to=32)
