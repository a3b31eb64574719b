"""Checkpoint / resume — the persistence subsystem the reference lacks.

The reference is stateless: its only persistent artifact is the ONNX file
read at startup (``/root/reference/src/inference_engine.cpp:31``); cache and
metrics die with the process (SURVEY.md §5 "checkpoint/resume: absent").
The TPU-native equivalents:

- **Model weights**: orbax checkpoints of param pytrees. A worker's
  ``model_path`` (the reference's positional arg / $MODEL_PATH,
  ``worker_node.cpp:154-168``) now points at a checkpoint directory instead
  of an .onnx file — same launch lines, real weights.
- **Training resume**: full ``TrainState`` (params + optimizer state +
  step) round-trips, so fine-tuning continues exactly where it stopped.
- **Compiled executables**: ``enable_compilation_cache`` persists XLA
  compilations to disk — the analogue of the reference paying its graph
  compile once per session load; restarted servers skip recompiles.

Checkpoints are sharding-aware: restored leaves can be placed onto a mesh
via `restore_args`-free device_put (callers re-apply their NamedShardings;
orbax stores the host view).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import numpy as np


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.StandardCheckpointer()


def save_params(path: str, params: Any, overwrite: bool = False) -> str:
    """Save a param pytree to `path` (created; must not already exist
    unless `overwrite` — orbax replaces the old checkpoint atomically, so
    a crash mid-save cannot lose both)."""
    path = os.path.abspath(path)
    ckptr = _checkpointer()
    host = jax.tree.map(np.asarray, params)
    ckptr.save(path, host, force=overwrite)
    ckptr.wait_until_finished()
    return path


def load_params(path: str, like: Optional[Any] = None) -> Any:
    """Restore a param pytree. `like` (same-structure pytree of arrays)
    restores with matching dtypes/shapes validated."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    ckptr = _checkpointer()
    if like is not None:
        target = jax.tree.map(
            lambda l: ocp.utils.to_shape_dtype_struct(l)
            if hasattr(ocp.utils, "to_shape_dtype_struct")
            else jax.ShapeDtypeStruct(l.shape, l.dtype), like)
        return ckptr.restore(path, target)
    return ckptr.restore(path)


def save_train_state(path: str, state, overwrite: bool = False) -> str:
    """Save a training.TrainState (params + opt_state + step).
    `overwrite` replaces an existing checkpoint (atomic in orbax)."""
    from tpu_engine.training.train import TrainState

    assert isinstance(state, TrainState)
    path = os.path.abspath(path)
    host = jax.tree.map(np.asarray, {
        "params": state.params,
        "opt_state": state.opt_state,
        "step": state.step,
    })
    ckptr = _checkpointer()
    ckptr.save(path, host, force=overwrite)
    ckptr.wait_until_finished()
    return path


def load_train_state(path: str, like) -> Any:
    """Restore a TrainState; `like` provides the pytree structure (e.g. a
    freshly-initialized state) so opt_state's nested containers rebuild."""
    from tpu_engine.training.train import TrainState

    import orbax.checkpoint as ocp  # noqa: F401  (backend registration)

    path = os.path.abspath(path)
    ckptr = _checkpointer()
    target = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(np.shape(l), np.asarray(l).dtype), {
            "params": like.params,
            "opt_state": like.opt_state,
            "step": like.step,
        })
    got = ckptr.restore(path, target)
    return TrainState(params=got["params"], opt_state=got["opt_state"],
                      step=got["step"])


def enable_compilation_cache() -> str:
    """Persist XLA compilations across process restarts (the reference
    pays its graph compile every session load; we pay once per checkout).
    Called once per entry point, before the first compile.

    The directory is placed from OUTSIDE: where `JAX_COMPILATION_CACHE_DIR`
    is set, JAX itself reads it and this function names no directory at
    all; unset, it is `<checkout>/.jax_cache` — a fixed path, because the
    path is part of what makes a later launch find the entries — and the
    variable is exported so child processes land on the same rule's first
    branch. Returns the directory in use."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Cache every compile, including fast ones — serving restarts replay the
    # same small executables, and a relaunch must add nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
