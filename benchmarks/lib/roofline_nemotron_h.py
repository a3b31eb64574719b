"""The operations and bytes that the kernels of a model whose layers are ONE
mixer each (a Mamba-2 recurrence, grouped-query attention at sixteen query
heads a KV head, or routed experts of two matrices in a LATENT narrower than
the model) cannot avoid: what `kernel.ssd64_step_roofline`,
`kernel.ssd64_chunk_roofline`, `kernel.gqa16_attn_roofline` and
`kernel.moe_latent_roofline` divide. `lib/roofline.py` has the rules, the
attention counts and `floor_seconds`; `lib/roofline_moe_mla.py` the seconds
of a kernel with a name of its own and the ticks wholly inside the slice;
`lib/roofline_falcon_h1.py` what a token and a state of the Mamba-2
recurrence cost and what tells a lane with state rows beside K/V blocks;
this file reads every size from THIS model's configuration file, counts the
layers of each kind from its pattern, and edits nothing there. Pure
functions of sizes, pinned by hand-computed cases.

Count only what no implementation could avoid (`lib/roofline.py`). An M
layer: the state, P x N float32 a head, read and written ONCE a row and
layer in a tick; a token's x and dt a head and its B and C a group in, its
read out, once. The * layers: a row's whole context of K and V once a layer
(`ctx_tokens_full`, 2 KV heads), the newest queries' FLOPs over 32 heads. An
E layer: a touched expert's TWO matrices, `d_latent` x `d_ff_expert` each,
once however many row tiles re-read them, and two matrix-vector products a
held (token, expert) pair; the latent projections, the router and the shared
expert are dense products of the rest of the step. So a share reads low and
never over 100 %.

**Whose seconds.** `ssd_step`, `ssd_chunk` (`tpu_engine/ops/ssd.py`) and
`_paged_call` are Pallas calls with names of their own in a trace; the
grouped product is the Mosaic kernel XLA makes of `jax.lax.ragged_dot`
(`ragged-dot`). The traffic is greedy, so no tick's sampler sorts: every
operation named `sort` is an expert layer's, the router's top 22 of 512 and
the pair list's.
"""

from lib import roofline, roofline_falcon_h1, roofline_moe_mla
from lib.roofline import DTYPE_BYTES
from lib.roofline_falcon_h1 import (  # noqa: F401
    CHUNK,
    PAGED,
    STEP,
    busy_share,
    holds_ssd,
    ssd_ticks,
)

EXPERTS, SORT = "ragged-dot", "sort"


def sizes(config):
    """What the counts need, from a configuration file's dict: the
    factory's keyword arguments as run and the lane's type. `layers`: the
    layers of each kind among the first `n_layers` characters of the
    pattern. `mamba` is the recurrence's part in the names
    `lib/roofline_falcon_h1.py` counts by (its `layers` the M layers)."""
    kwargs = config["kwargs"]
    pattern = kwargs["pattern"][:int(kwargs["n_layers"])]
    element = DTYPE_BYTES[config["serving"]["dtype"]]
    return {"layers": {kind: pattern.count(kind) for kind in "ME*"},
            "heads": int(kwargs["n_heads"]),
            "kv_heads": int(kwargs["n_kv_heads"]),
            "head_dim": int(kwargs["head_dim"]),
            "d_latent": int(kwargs["d_latent"]),
            "d_expert": int(kwargs["d_ff_expert"]),
            "held": (int(kwargs["held_first"]),
                     int(kwargs["held_count"]) or int(kwargs["n_experts"])),
            "bytes_per_element": element,
            "mamba": {"layers": pattern.count("M"),
                      "ssm_heads": int(kwargs["ssm_heads"]),
                      "ssm_head_dim": int(kwargs["ssm_head_dim"]),
                      "d_state": int(kwargs["d_state"]),
                      "groups": int(kwargs["n_groups"])}}


def states_a_latent(run):
    """Whether the run's configuration is of this family: its experts read
    a latent (`d_latent`). On any other, a reader here returns None."""
    return "d_latent" in run["config"].get("kwargs", {})


def expert_bytes(experts_touched, size):
    """Bytes of expert weights read: the up and the down matrix of every
    (layer, expert) that took at least one row, once."""
    return (experts_touched * 2 * size["d_latent"] * size["d_expert"]
            * size["bytes_per_element"])


def expert_flops(assignments, size):
    """A held (token, expert) pair is two matrix-vector products of
    d_latent x d_expert."""
    return assignments * 2 * 2 * size["d_latent"] * size["d_expert"]


def recurrence_roofline(run, kernel):
    """`roofline_falcon_h1.recurrence_roofline` over the M layers alone:
    the floor seconds of what the `mixed_step` spans of the ticks wholly
    inside the traced slice say went through one form of the recurrence
    (`ssd_chunk_tokens` and `ssd_chunk_rows`, or `ssd_step_rows`), against
    the self seconds of the calls named `kernel` there. None where the run
    has no trace, no peaks or no such counter."""
    ticks = roofline_moe_mla.whole_ticks(run)
    if kernel == CHUNK:
        rows = sum(a.get("ssd_chunk_rows", 0) for a in ticks)
        tokens = sum(a.get("ssd_chunk_tokens", 0) for a in ticks)
    else:
        rows = tokens = sum(a.get("ssd_step_rows", 0) for a in ticks)
    seconds = roofline_moe_mla.kernel_seconds(run, kernel)
    if (not seconds or not tokens or not run["peaks"]
            or not states_a_latent(run)):
        return None
    mamba = sizes(run["config"])["mamba"]
    floor_s = roofline.floor_seconds(
        roofline_falcon_h1.recurrence_bytes(rows, tokens, mamba),
        roofline_falcon_h1.recurrence_flops(tokens, mamba), run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds


def attention_roofline(run):
    """Percent of its roofline that the paged reads at sixteen query heads a
    KV head reach: the floor seconds of the keys and values
    `ctx_tokens_full` counts (the * layers, 2 KV heads) and of the newest
    queries' FLOPs (32 heads), over this family's ticks wholly inside the
    traced slice, against the calls' self seconds there."""
    tokens = sum(a.get("ctx_tokens_full", 0) for a in ssd_ticks(run))
    seconds = roofline_moe_mla.kernel_seconds(run, PAGED)
    if (not seconds or not tokens or not run["peaks"]
            or not states_a_latent(run)):
        return None
    size = sizes(run["config"])
    layers = size["layers"]["*"]
    floor_s = roofline.floor_seconds(
        roofline.attention_bytes(tokens, layers, size["kv_heads"],
                                 size["head_dim"],
                                 size["bytes_per_element"]),
        roofline.attention_flops(tokens, layers, size["heads"],
                                 size["head_dim"]),
        run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds


def experts_roofline(run):
    """Percent of its roofline that the grouped product of the HELD experts
    reaches: touched experts' two matrices once and the held assignments'
    FLOPs (`moe_experts_touched`, `moe_assignments_held`), over the ticks
    wholly inside the traced slice, against the product's self seconds
    there."""
    ticks = roofline_moe_mla.whole_ticks(run)
    touched = sum(a.get("moe_experts_touched", 0) for a in ticks)
    held = sum(a.get("moe_assignments_held", 0) for a in ticks)
    seconds = roofline_moe_mla.kernel_seconds(run, EXPERTS)
    if (not seconds or not held or not run["peaks"]
            or not states_a_latent(run)):
        return None
    size = sizes(run["config"])
    floor_s = roofline.floor_seconds(expert_bytes(touched, size),
                                     expert_flops(held, size), run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds


def latent_moe_lanes(run):
    """The lanes' (`stats_before`, `stats_after`) pairs of
    `stats()["moe"]` that count held assignments, on a run whose
    configuration states a latent for its experts (`d_latent`); [] on any
    other program or configuration."""
    if not states_a_latent(run):
        return []
    return [(run["stats_before"][node]["moe"], after["moe"])
            for node, after in run["stats_after"].items()
            if "assignments_held" in after.get("moe", {})
            and "moe" in run["stats_before"].get(node, {})]
