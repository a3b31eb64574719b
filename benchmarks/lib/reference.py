"""What `correct` is decided by: the served tokens against a plain reference.

This file is of no architecture. The forward pass a configuration is
compared with is a file of its own, named by the configuration's
`reference.dialect`: benchmarks/references/<dialect>.py (for the tests' own
cells, references/<dialect>.py beside their benchmark file), loaded by
run.py the way it loads a per-layer reader. A new architecture brings its
reference as a new file; nothing here, and nothing in run.py, is edited.

The contract of a reference file:

  forward(params, tokens, sizes) -> (T, vocab) float32 logits
      params  the server's own parameter tree (float32, layers stacked on a
              leading axis), read and not copied; where a deployment is
              sharded, one chip's share of it may be what is handed over,
              and the file says so
      tokens  (T,) int32, one whole sequence from position 0
      sizes   the configuration file's `reference` block as a hashable tuple
              of (key, value) pairs (`sizes_of`): what the arrays' shapes do
              not say (head counts, eps, theta, state width)
  - straightforward jax.numpy in float32 under
    jax.default_matmul_precision("highest"): no kernels, no cache, no
    batching
  - no import from tpu_engine: the program under test is not its own judge
    (shared plain pieces live in references/_plain.py)
  - for long contexts it may compute only the last few hundred query
    positions, in blocks, against the whole context: `served_gaps` reads
    the generated positions alone

`check_served` decides `correct`: the tokens the server generated greedily
are teacher-forced through ONE full forward pass of the reference, and at
every generated position the served token's reference logit must lie within
a tolerance of the reference's largest. Logits and not tokens: with random
weights the two largest logits are often closer than bf16 rounding.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def _jitted(forward):
    """One compiled program a reference file and padded length."""
    return jax.jit(forward, static_argnums=(2,))


def sizes_of(reference):
    """The configuration file's `reference` block as a hashable tuple."""
    return tuple(sorted(reference.items()))


def served_gaps(forward, params, sizes, prompt, generated, pad_to):
    """Teacher-force `generated` after `prompt` through `forward`.
    Returns, per generated position, (reference's largest logit - the
    served token's reference logit) and the logits' standard deviation
    there. Sequences are right-padded to `pad_to` so every sample of a
    configuration runs the same compiled program; a causal model keeps
    padding from reaching the positions read."""
    seq = list(prompt) + list(generated[:-1])
    if len(seq) > pad_to:
        raise ValueError(f"sample of {len(seq)} tokens exceeds the "
                         f"reference's padded length {pad_to}")
    tokens = np.zeros((pad_to,), np.int32)
    tokens[: len(seq)] = seq
    logits = _jitted(forward)(params, jnp.asarray(tokens), sizes)
    at = np.asarray(logits[len(prompt) - 1: len(seq)])   # (n_generated, V)
    served = at[np.arange(len(generated)), np.asarray(generated)]
    return at.max(-1) - served, at.std(-1)


def check_served(forward, params, reference, samples, tolerance,
                 min_exact_share, pad_to):
    """`forward`: a reference file's; `reference`: the configuration's block;
    samples: [(prompt, generated)]. The served path is correct when, at
    every generated position, the served token's reference logit is within
    `tolerance` standard deviations (of that position's logits) of the
    reference's largest, and at least `min_exact_share` of the served
    tokens ARE the reference's arg-max. Returns (ok, details)."""
    sizes = sizes_of(reference)
    worst, exact, total = 0.0, 0, 0
    for prompt, generated in samples:
        gaps, stds = served_gaps(forward, params, sizes, prompt, generated,
                                 pad_to)
        worst = max(worst, float((gaps / stds).max()))
        exact += int((gaps == 0.0).sum())
        total += len(generated)
    share = exact / max(1, total)
    ok = bool(np.isfinite(worst) and worst <= tolerance
              and share >= min_exact_share)
    return ok, {"worst_gap_in_logit_std": worst, "exact_share": share,
                "positions": total, "tolerance": tolerance,
                "min_exact_share": min_exact_share}
