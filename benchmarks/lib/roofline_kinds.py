"""A KIND of kernel's share of the busy time and of its roofline, whatever
the family: what `kernel.paged_attn_*`, `kernel.moe_experts_*`,
`kernel.state_step_*` and `kernel.state_chunk_*` return. A roofline
takes the work from the `mixed_step` spans of the ticks that ran
WHOLLY inside the traced slice, every size from `sizes(run["config"])`
(`lib/roofline_sizes.py`) and the count from the library that has it for
that kind of sizes (`lib/roofline.py` has the rules: only what no
implementation could avoid, so a share reads low and never over 100 %).
None where the run has no trace, no peaks, no such kernel or no such
counter. Over several lanes: the work of all lanes' ticks over the number
of device planes, against the kernel's seconds a plane
(lib/xplane_reduce.py averages).
"""

from lib import (
    roofline,
    roofline_falcon_h1,
    roofline_gated_delta,
    roofline_moe_mla,
)
from lib.roofline_sizes import sizes

# The library that counts a recurrence of each kind.
RECURRENCE_COUNTS = {"gdn": roofline_gated_delta, "kda": roofline_gated_delta,
                     "ssd": roofline_falcon_h1}


def busy_share(run, part, form="kernel"):
    """Percent of the device's busy time in the kernel that `part` of the
    configuration's sizes names (a recurrence names two: `form` is `step`
    or `chunk`); None where the configuration states no such part or the
    trace holds no such operation."""
    size = sizes(run["config"])[part]
    return roofline_moe_mla.busy_share(run, size[form]) if size else None


def _share(run, kernel, n_bytes, flops):
    """Percent of the roofline: the floor seconds of the work over the
    planes, against the self seconds of the calls named `kernel`."""
    seconds = roofline_moe_mla.kernel_seconds(run, kernel)
    if not seconds or not run["peaks"]:
        return None
    floor_s = roofline.floor_seconds(n_bytes, flops, run["peaks"])
    return 100.0 * floor_s / run["trace"]["planes"] / seconds


def context_tokens(attrs):
    """The context a tick's attending layers read (`pos0 + q_len` summed
    over the rows fed), under the name the lane notes it by: a lane whose
    rows also own a state says what its attending layers alone read
    (`ctx_tokens_latent` where the pool holds a latent, `ctx_tokens_full`
    where K and V, as a block-decoding lane does); every lane's tick
    carries `ctx_tokens`."""
    for key in ("ctx_tokens_latent", "ctx_tokens_full", "ctx_tokens"):
        if key in attrs:
            return attrs[key]
    return 0


def attention_roofline(run):
    """The paged read: every context token's lanes in the pool once a
    layer (K and V of the KV heads, or a latent and its rope key), and the
    (query, key) pairs' FLOPs over the query heads. Pairs: `attn_pairs`
    where the span counts them (a block-causal mask), else a row's newest
    query against its context, exact in a width-1 tick; a chunk's queries
    before its last are not counted (the span does not say which rows hold
    chunks), an under-count of FLOPs alone."""
    size = sizes(run["config"])["attention"]
    ticks = roofline_moe_mla.whole_ticks(run)
    tokens = sum(context_tokens(a) for a in ticks)
    if not size or not tokens:
        return None
    pairs = sum(a.get("attn_pairs", context_tokens(a)) for a in ticks)
    if "latent" in size:
        n_bytes = roofline_moe_mla.latent_bytes(
            tokens, size["layers"], size["latent"], size["rope"],
            size["bytes_per_element"])
        flops = roofline_moe_mla.latent_flops(
            pairs, size["layers"], size["heads"], size["latent"],
            size["rope"])
    else:
        n_bytes = roofline.attention_bytes(
            tokens, size["layers"], size["kv_heads"], size["head_dim"],
            size["bytes_per_element"])
        flops = roofline.attention_flops(pairs, size["layers"],
                                         size["heads"], size["head_dim"])
    return _share(run, size["kernel"], n_bytes, flops)


def experts_roofline(run):
    """The grouped expert product: a touched expert's matrices once
    (`moe_experts_touched`: (layer, expert) pairs that took a row) and the
    FLOPs of the (token, expert) pairs that formed a row HERE:
    `moe_assignments_held` on a lane that holds a share of the experts and
    says so, else `moe_assignments`, every pair routed."""
    size = sizes(run["config"])["experts"]
    ticks = roofline_moe_mla.whole_ticks(run)
    touched = sum(a.get("moe_experts_touched", 0) for a in ticks)
    pairs = sum(a.get("moe_assignments_held", a.get("moe_assignments", 0))
                for a in ticks)
    if not size or not pairs:
        return None
    return _share(
        run, size["kernel"],
        roofline_moe_mla.expert_bytes(touched, size["rows"], size["cols"],
                                      size["bytes_per_element"],
                                      size["matrices"]),
        roofline_moe_mla.expert_flops(pairs, size["rows"], size["cols"],
                                      size["matrices"]))


def recurrence_roofline(run, form):
    """One form of the recurrence, `step` or `chunk`: what the spans say
    went through it under the kernel's name (`<kind>_chunk_tokens` and
    `<kind>_chunk_rows`, or `<kind>_step_rows`: a row and a token each)."""
    size = sizes(run["config"])["recurrence"]
    if not size:
        return None
    ticks = roofline_moe_mla.whole_ticks(run)
    if form == "chunk":
        rows = sum(a.get(size["chunk"] + "_rows", 0) for a in ticks)
        tokens = sum(a.get(size["chunk"] + "_tokens", 0) for a in ticks)
    else:
        rows = tokens = sum(a.get(size["step"] + "_rows", 0) for a in ticks)
    if not tokens:
        return None
    counts = RECURRENCE_COUNTS[size["kind"]]
    return _share(run, size[form],
                  counts.recurrence_bytes(rows, tokens, size),
                  counts.recurrence_flops(tokens, size))
