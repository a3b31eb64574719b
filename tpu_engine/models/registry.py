"""Model registry: name → factory for the serving zoo.

The reference serves one opaque ONNX graph per worker
(``/root/reference/src/inference_engine.cpp:31``); here models are JAX
programs registered by name, selected per worker via config
(``WorkerConfig.model``). Each factory returns a ``ModelSpec`` — everything
the engine needs to stage the model to XLA: an ``apply`` function, parameter
init, and the flat input/output contract that keeps the reference's
wire format (flat float vectors, pad/truncate) intact.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

# Serving-capability flags per state family (VirtualFlow framing: the
# registry, not the serving machinery, declares what a model family can
# do — the scheduler and worker fence mismatches LOUDLY instead of
# silently degrading). "kv_paged": autoregressive state is a growing KV
# chain in the block pool; "state_slab": a fixed-size recurrent state
# slab (O(1) per stream — SSD/Mamba family); "stateless": no
# autoregressive state at all — score/infer/embed requests admit as
# SINGLE-TICK rows in the continuous scheduler's shared slot pool
# (DESIGN.md "Unified stateless serving"), so the family has no
# generation lane but is a first-class scheduler citizen, not a side
# path. "kv_latent": a kv_paged chain whose blocks hold ONE latent vector
# and one shared rope key a token and layer (models.moonlight) and whose
# FFN routes experts inside the tick. It is served by the mixed tick
# alone; what the latent pool cannot do yet is ABSENT here and refused
# at start-up (runtime.scheduler): int8 KV scales are per KV head,
# `--tp` shards H_kv (here 1), the host tier and the chain wire format
# (migration, handoff, prefix fetch) carry a K and a V of equal width,
# speculative verify and the dense per-slot cache have no latent read.
# "kv_windowed": a kv_paged chain whose sliding-window layers keep only
# the blocks their window still sees (models.laguna, models.smallthinker):
# a row holds blocks of two kinds, and the window kind's are given back as
# the row's position passes them. Served by the mixed tick alone. A freed
# block can serve no prefix hit, be demoted to no host tier and ride no
# chain, so prefix sharing, the host tier, migration and handoff are
# ABSENT, with int8 (the window read takes no scales), `--tp` and
# speculative verify.
# "kv_and_state": a row holds a chain over the block pool, which holds the
# layers that attend, AND one row of a state pool (the recurrent mixers'
# fixed-size state and conv tail), admitted, parked and released as one:
# blocks for some layers and a state row for the others
# (models.olmo_hybrid, models.kimi_linear), with layers that own neither
# between them (models.nemotron_h: a layer is one mixer, and an expert
# layer keeps no state), or both in EVERY layer, read from the same normed
# rows and summed (models.falcon_h1). WHAT a block
# holds is the model's to state (`cfg.kv_lanes`, through
# `cfg.kv_block_kinds`) and no part of the family: K and V a head
# (models.olmo_hybrid) or a latent and its shared key lanes
# (models.kimi_linear), under the same admission, parking and release.
# Served by the mixed tick alone. A recurrent state is not
# block-addressable, cannot be rolled back past a rejected draft and rides
# no chain, so prefix sharing, the host tier, int8 payloads, speculative
# verify, `--tp`, migration and handoff are ABSENT, whatever the blocks
# hold.
# "kv_block_decode": a kv_paged chain whose generating rows step a RUN of
# `block_length` tokens a tick under a block-causal mask and reveal them
# over several passes (models.sdar; `ModelSpec.block_decode`). Served by
# the mixed tick alone. A row's last block is rewritten by every pass until
# its commit, and what a pass yields is no single token a row, so
# speculative verify, int8 payloads (a rewritten slot would be requantized
# a pass), the host tier, prefix sharing, `--tp`, migration and handoff
# (a chain carries no block in denoising) are ABSENT.
# "kv_looped": a kv_paged chain whose pool is DEEPER than the model's
# weights: the layers are applied `ModelSpec.passes` times a token with one
# set of weights and a block holds a plane a (pass, layer)
# (models.ouro; the depth is the model's `kv_block_kinds[0]`). Served by
# the mixed tick alone, through the model's own step. A block's planes
# depend on the tokens up to its end alone, so prefix sharing holds as for
# any chain; the own step takes no int8 scales and no verify window, its
# scan is run a chip whole, and a chain of that depth rides no wire yet
# (the format would carry it; it is not tested), so int8 payloads, the
# host tier, speculative verify, `--tp`, migration and handoff are ABSENT.
FAMILY_CAPABILITIES: Dict[str, Tuple[str, ...]] = {
    # "two_path": the dense per-slot cache (`kv_block_size` 0) and its
    # prefill-thread / chunk-loop stepping. Every other lane that
    # generates holds a pool or a slab and steps by the ragged tick.
    "kv_paged": ("generate", "two_path", "spec_decode",
                 "paged_kv", "prefix_sharing", "kv_quantize",
                 "kv_host_tier", "migration", "handoff",
                 "tensor_parallel", "oneshot_rows"),
    "state_slab": ("generate", "migration", "handoff", "oneshot_rows"),
    "stateless": ("oneshot_rows",),
    "kv_latent": ("generate", "paged_kv", "prefix_sharing",
                  "oneshot_rows"),
    "kv_windowed": ("generate", "paged_kv", "oneshot_rows"),
    "kv_and_state": ("generate", "paged_kv", "oneshot_rows"),
    "kv_block_decode": ("generate", "paged_kv", "oneshot_rows"),
    "kv_looped": ("generate", "paged_kv", "prefix_sharing", "oneshot_rows"),
}

# -- tensor-parallel partition rules ------------------------------------------
#
# The registry — not the serving machinery — declares how a model's
# params shard over the `model` mesh axis (the FAMILY_CAPABILITIES
# pattern, promoted from training.shard_params_tp's rank heuristic):
# every ModelSpec carries a ``tp_rule`` naming an entry here, and
# consumers (the continuous scheduler's --tp path, the worker startup
# fence) resolve it through ``tp_shardings`` / ``tp_unshardable_reason``
# instead of re-deriving placement per call site. An unshardable family
# (e.g. mamba2's depthwise conv tail + fused state slab) declares
# ``unshardable:<reason>`` and gets a LOUD pinned RuntimeError at
# resolution — never a silent mis-shard.
#
# A rule is a list of (regex over the '/'-joined param path, spec tail)
# pairs, first match wins (SNIPPETS.md [2]'s match_partition_rules
# idiom). The tail is RIGHT-ALIGNED onto the leaf's shape — stacked
# per-layer trees carry a leading (L, ...) axis the tail never names —
# and "model" marks the sharded dim (replaced by the mesh axis name).

# The transformer families' Megatron-style placement: QKV and the MLP
# up-projections shard their heads/features OUTPUT dim (column
# parallel), the attention output and MLP down-projections their heads/
# features INPUT dim (row parallel — XLA inserts the psum on ICI), the
# LM head its vocab dim; norms and embeddings replicate. The catch-all
# REPLICATES unmatched leaves (always correct, never silently
# mis-sharded — MoE expert banks currently ride replicated).
_TRANSFORMER_TP_RULES: List[Tuple[str, Tuple[Optional[str], ...]]] = [
    (r"attn/w[qkv]/kernel$", (None, "model")),
    (r"attn/w[qkv]/bias$", ("model",)),
    (r"attn/wo/kernel$", ("model", None)),
    (r"mlp/(fc|gate|up)/kernel$", (None, "model")),
    (r"mlp/(fc|gate|up)/bias$", ("model",)),
    (r"mlp/proj/kernel$", ("model", None)),
    (r"head/kernel$", (None, "model")),
    (r"head/bias$", ("model",)),
    (r".*", ()),
]


def _leaf_path_name(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def _refuse_quantized(params) -> None:
    """Weight-quantized trees refuse TP loudly (shard_params_tp's
    documented contract): int8 kernel_q + per-channel scale leaves would
    shard along mismatched axes or silently replicate."""
    from tpu_engine.ops.quant import tree_is_quantized

    if tree_is_quantized(params):
        raise RuntimeError(
            "tensor-parallel sharding cannot place a weight-quantized "
            "param tree (ops.quant kernel_q/wi_q leaves): the TP "
            "partition rules target full-precision kernels. Use int8 "
            "weight quantization OR tensor parallelism per deployment, "
            "not both.")


def _match_rules_shardings(rules, params, mesh, axis: str):
    """(regex, tail) rules + a param tree -> NamedSharding tree. A tail
    dim that does not divide over the mesh axis replicates that leaf
    (never a shape error at placement time — small biases on a wide
    mesh just stay whole)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    msize = mesh.shape[axis]

    def spec_for(path, leaf):
        name = _leaf_path_name(path)
        shape = getattr(leaf, "shape", ())
        nd = len(shape)
        for pat, tail in rules:
            if re.search(pat, name):
                tail = tuple(axis if t == "model" else t for t in tail)
                if nd < len(tail):
                    return NamedSharding(mesh, P())
                spec = (None,) * (nd - len(tail)) + tail
                for dim, t in enumerate(spec):
                    if t is not None and shape[dim] % msize:
                        return NamedSharding(mesh, P())
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_for, params)


def _transformer_tp_rule(params, mesh, axis: str = "model"):
    _refuse_quantized(params)
    return _match_rules_shardings(_TRANSFORMER_TP_RULES, params, mesh,
                                  axis)


def _dense_output_tp_rule(params, mesh, axis: str = "model"):
    """The promoted rank heuristic (training.shard_params_tp): 2-D+
    kernels shard their output-feature (last) dim, divisible 1-D leaves
    shard too, everything else replicates. The generic rule for models
    without a named layout (mlp, resnet, onnx graphs)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    _refuse_quantized(params)
    msize = mesh.shape[axis]

    def spec_for(leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 2 and shape[-1] % msize == 0:
            return P(*([None] * (len(shape) - 1)), axis)
        if len(shape) == 1 and shape[0] % msize == 0 and shape[0] > 1:
            return P(axis)
        return P()

    return jax.tree.map(lambda l: NamedSharding(mesh, spec_for(l)),
                        params)


# name -> callable(params, mesh, axis) -> tree of NamedShardings.
TP_RULES: Dict[str, Callable] = {
    "transformer": _transformer_tp_rule,
    "dense_output": _dense_output_tp_rule,
}


def tp_unshardable_reason(spec) -> Optional[str]:
    """The declared reason this model cannot tensor-parallel shard, or
    None when its rule resolves. Consumers fence HERE (worker startup,
    scheduler construction) so a --tp misconfiguration is one loud
    RuntimeError naming the layer, never a silent mis-shard."""
    # Bare stand-in specs without a declaration (test fakes) default to
    # the transformer layout — the same derivation rule the scheduler
    # applies to their state family.
    rule = getattr(spec, "tp_rule", "") or "transformer"
    if rule.startswith("unshardable"):
        _, _, reason = rule.partition(":")
        return reason.strip() or "model declares itself unshardable"
    if rule not in TP_RULES:
        return f"unknown TP partition rule {rule!r}"
    return None


def tp_shardings(spec, params, mesh, axis: str = "model"):
    """Resolve ``spec.tp_rule`` and place ``params`` — the ONE entry
    point every TP consumer uses. Raises RuntimeError (pinned message)
    for unshardable or unknown rules."""
    reason = tp_unshardable_reason(spec)
    if reason is not None:
        raise RuntimeError(
            f"model '{getattr(spec, 'name', '?')}' cannot be "
            f"tensor-parallel sharded: {reason}")
    rule = getattr(spec, "tp_rule", "") or "transformer"
    return TP_RULES[rule](params, mesh, axis)


class BlockDecode(NamedTuple):
    """What a model that generates by denoising blocks declares
    (`ModelSpec.block_decode`): a generating row's step is a run of
    `block_length` tokens; a position still masked reads `mask_id` at the
    embedding; a denoise pass reveals `tokens_per_pass` of them by the
    rule `reveal` ("sequential", "low_confidence_static" or
    "low_confidence_dynamic", the last with its confidence
    `threshold`)."""
    block_length: int
    mask_id: int
    tokens_per_pass: int
    reveal: str
    threshold: float


@dataclasses.dataclass
class ModelSpec:
    name: str
    apply: Callable          # (params, batch_input) -> batch_output
    init: Callable           # (rng) -> params
    input_shape: Tuple[int, ...]   # per-sample shape the model consumes
    output_shape: Tuple[int, ...]  # per-sample output shape
    config: Optional[object] = None  # architecture config (e.g. TransformerConfig)
    # Serving-state family ("" = derive from the config below): which
    # autoregressive-state machinery the continuous scheduler must build
    # for this model. Every registered model carries a declaration.
    state_family: str = ""
    # Serving-capability flags ("" sentinel tuple = derive from the
    # family table above). Consumers fence on these, never on isinstance.
    capabilities: Tuple[str, ...] = ()
    # Tensor-parallel partition rule ("" = derive): names a TP_RULES
    # entry, or "unshardable:<reason>" for families with no heads axis
    # to split (the mamba2 depthwise conv tail / fused state slab).
    # Resolved through tp_shardings / tp_unshardable_reason — consumers
    # fence on the declaration, never on isinstance.
    tp_rule: str = ""
    # (params, dtype) -> the tree a lane's compiled steps read instead of
    # `params` (runtime.scheduler builds it once, when the lane gets its
    # weights). None: the steps read `params` itself — every family whose
    # step has no per-tick cast to save, or casts what it must itself.
    step_weights: Optional[Callable] = None
    # The family's own step of the mixed tick, where it is not
    # `models.transformer.transformer_step_rows_ragged`: (params, tokens,
    # caches, tables, pos0, qlen, cfg, *, dtype, sample_slot, held,
    # max_tokens) -> (logits, caches, rows (expert layers, experts): the
    # rows each expert took), run over the tick's tokens
    # (models.moonlight, models.laguna). `held` = (first, count): the
    # routed experts this lane's weights hold (None: all of them).
    ragged_step: Optional[Callable] = None
    held: Optional[Tuple[int, int]] = None
    # A model whose generating rows denoise a block of tokens over
    # several ticks instead of sampling one a tick (models.sdar): the
    # scheduler reads this declaration, never the model. None: one token
    # a row a tick.
    block_decode: Optional[BlockDecode] = None
    # How many times the model applies its layers to a token, with one set
    # of weights (models.ouro): its pool holds a plane a (pass, layer),
    # `passes` times as deep as its weights, and a tick runs `passes` x
    # layers layer applications. 1: a layer is applied once.
    passes: int = 1

    def __post_init__(self):
        if not self.state_family:
            # A config may declare its family (SSDConfig does); causal
            # transformer configs default to the paged-KV family; models
            # without a generation-capable config are stateless.
            fam = getattr(self.config, "serving_state_family", None)
            if fam is None and getattr(self.config, "causal", False):
                fam = "kv_paged"
            self.state_family = fam or "stateless"
        if self.state_family not in FAMILY_CAPABILITIES:
            raise ValueError(
                f"model '{self.name}' declares unknown state family "
                f"{self.state_family!r}; known: "
                f"{sorted(FAMILY_CAPABILITIES)}")
        if not self.tp_rule:
            # A config may declare its rule (SSDConfig pins
            # "unshardable:..."); causal transformer configs get the
            # Megatron-style named layout; everything else the promoted
            # rank heuristic.
            rule = getattr(self.config, "tp_partition_rule", None)
            if rule is None:
                if self.state_family in ("kv_paged", "kv_latent",
                                         "kv_windowed", "kv_and_state",
                                         "kv_block_decode", "kv_looped"):
                    rule = "transformer"
                elif self.state_family == "state_slab":
                    # Defensive default for undeclared recurrent models:
                    # refusal beats a heuristic mis-shard.
                    rule = ("unshardable: recurrent state_slab models "
                            "declare no shardable heads axis")
                else:
                    rule = "dense_output"
            self.tp_rule = rule
        if not self.capabilities:
            caps = FAMILY_CAPABILITIES[self.state_family]
            if self.tp_rule.startswith("unshardable"):
                caps = tuple(c for c in caps if c != "tensor_parallel")
            self.capabilities = caps

    def supports(self, flag: str) -> bool:
        return flag in self.capabilities

    @property
    def input_size(self) -> int:
        n = 1
        for d in self.input_shape:
            n *= d
        return n

    @property
    def output_size(self) -> int:
        n = 1
        for d in self.output_shape:
            n *= d
        return n


def causal_lm_spec(name: str, cfg, seq_len: int, init, apply,
                   **declared) -> ModelSpec:
    """The spec of a causal language model from its `init(rng, cfg)` and
    its full-sequence forward `apply(params, tokens (B, S) int32, cfg, *,
    dtype) -> (B, S, vocab)`. The spec's own `apply` is the one-shot
    `/infer` wire contract, written once: x (B, seq_len) float token ids
    (the engine pads with zeros) -> (B, vocab) logits of the last real
    position, a pad id 0 after the first token read as padding.
    `declared`: the fields of `ModelSpec` the family states
    (`ragged_step`, `held`, `block_decode`, `passes`, `tp_rule`, ...)."""
    def wire_apply(params, x, dtype=None):
        import jax.numpy as jnp

        tokens = jnp.clip(x.astype(jnp.int32), 0, cfg.vocab - 1)
        last = jnp.max(jnp.where(tokens > 0, jnp.arange(seq_len)[None, :],
                                 0), axis=1)  # 0 if a single token
        logits = apply(params, tokens, cfg,
                       dtype=jnp.bfloat16 if dtype is None else dtype)
        return jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0]

    return ModelSpec(name=name, apply=wire_apply,
                     init=lambda rng: init(rng, cfg),
                     input_shape=(seq_len,), output_shape=(cfg.vocab,),
                     config=cfg,  # the generation service reads it
                     **declared)


_REGISTRY: Dict[str, Callable[..., ModelSpec]] = {}


def register(name: str):
    def deco(factory: Callable[..., ModelSpec]):
        _REGISTRY[name] = factory
        return factory
    return deco


def create_model(name: str, **kwargs) -> ModelSpec:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model '{name}'; available: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


def available_models():
    return sorted(_REGISTRY)


def _ensure_builtin_models_imported():
    # Import side-effect registration; kept lazy so `tpu_engine.core` users
    # never pay the JAX import. Optional families import only when their
    # module file exists — a present-but-broken module must raise, not be
    # silently dropped from the registry.
    import importlib
    import importlib.util

    from tpu_engine.models import mlp, resnet  # noqa: F401

    for optional in ("bert", "gpt2", "llama", "yolo", "ssd", "moonlight",
                     "laguna", "olmo_hybrid", "kimi_linear", "falcon_h1",
                     "nemotron_h", "sdar", "ouro", "lfm2", "granite_hybrid",
                     "smallthinker"):
        if importlib.util.find_spec(f"tpu_engine.models.{optional}") is not None:
            importlib.import_module(f"tpu_engine.models.{optional}")
