"""Every size the merged readers count with, from ONE function of a
configuration file's dict (`run["config"]`): `sizes(config)`.

A reader of a KIND of kernel (the paged read, the grouped expert product,
the state step, the state chunk) is written once and listed for every cell
whose step runs that kind. What differs between the cells is sizes, and
they are all here. The next family edits nothing here: where its file states
its sizes in the words below, its cell is appended to the merged lists its
lane feeds; a part whose words `sizes` does not know reads None, and for
that kernel the family brings a reader and a count file of its own, as for
any mechanism no merged reader names (`lib/roofline.py` has the rules).

`sizes` branches on what a configuration file STATES, the keyword arguments
its factory is called with: `kv_lora_rank` (the cache holds a latent),
`block_length` (rows decode by blocks), `layer_types` / `pattern` /
`kda_layers` (which layers are of which kind), `ut_steps` (a cache plane a
pass and layer), `d_latent` (an expert is two matrices in a latent),
`held_count` (a lane holds a share of the experts), `lin_key_dim` /
`kda_layers` / `ssm_heads` (which recurrence). Never on a cell's or a
configuration's name.

A configuration whose layers attend in TWO classes (`window`: a window
read beside the full read) states no single attention read: its part reads
None here and its two reads have readers of their own
(`lib/roofline_laguna.py`).
"""

from lib.roofline import DTYPE_BYTES

# What a trace calls each kernel: the Pallas call behind every paged read
# is named after `_paged_call` (tpu_engine/ops/paged_attention.py), the
# read under a block-causal mask `block_mask_read` there, the absorbed
# latent read `mla_latent_read` (ops/latent_attention.py); the grouped
# product is the Mosaic kernel XLA makes of `jax.lax.ragged_dot`; a
# recurrence's two Pallas calls are `<kind>_step` and `<kind>_chunk`
# (ops/gated_delta.py, ops/ssd.py).
PAGED, LATENT, BLOCK_READ, EXPERTS = ("paged", "mla_latent",
                                      "block_mask_read", "ragged-dot")
ATTENDING = ("full_attention", "attention")
RECURRENT = ("linear_attention", "mamba")


def _layers(kwargs):
    """(layers that attend, layers with a recurrence) among the layers as
    run, however the configuration states their kinds."""
    if "layer_types" in kwargs:
        kinds = kwargs["layer_types"]
        kinds = kinds[:int(kwargs.get("n_layers", len(kinds)))]
        return (sum(k in ATTENDING for k in kinds),
                sum(k in RECURRENT for k in kinds))
    n = int(kwargs.get("n_layers", 0))
    if "pattern" in kwargs:
        pattern = kwargs["pattern"][:n]
        return pattern.count("*"), pattern.count("M")
    if "kda_layers" in kwargs:          # 1-based, of the source's depth
        kda = sum(1 for layer in kwargs["kda_layers"] if layer <= n)
        return n - kda, kda
    # Every layer attends; a cache plane a (pass, layer) where the stack is
    # looped; every layer has the recurrence too where one is stated.
    return (n * int(kwargs.get("ut_steps", 1)),
            n if "ssm_heads" in kwargs else 0)


def _attention(kwargs, serving, layers):
    if "window" in kwargs or not layers or "n_heads" not in kwargs:
        return None
    heads = int(kwargs["n_heads"])
    size = {"layers": layers, "heads": heads,
            "bytes_per_element": DTYPE_BYTES[
                serving.get("gen_kv_quantize") or serving["dtype"]]}
    if "kv_lora_rank" in kwargs:
        # A token and layer: one latent and its shared rope key lanes.
        size.update(kernel=LATENT, latent=int(kwargs["kv_lora_rank"]),
                    rope=int(kwargs["qk_rope"]))
        size["lanes"] = size["latent"] + size["rope"]
        return size
    size.update(
        kernel=BLOCK_READ if "block_length" in kwargs else PAGED,
        kv_heads=int(kwargs.get("n_kv_heads", heads)),
        head_dim=int(kwargs.get("head_dim", kwargs.get(
            "d_head", int(kwargs["d_model"]) // heads))))
    size["lanes"] = 2 * size["kv_heads"] * size["head_dim"]    # K and V
    return size


def _experts(kwargs, element):
    if "n_experts" not in kwargs or "d_ff_expert" not in kwargs:
        return None
    latent = "d_latent" in kwargs
    return {"kernel": EXPERTS,
            # up and down in a latent, or gate, up and down in the model
            "matrices": 2 if latent else 3,
            "rows": int(kwargs["d_latent" if latent else "d_model"]),
            "cols": int(kwargs["d_ff_expert"]),
            "held": (int(kwargs.get("held_first", 0)),
                     int(kwargs.get("held_count", 0))
                     or int(kwargs["n_experts"])),
            "bytes_per_element": element}


def _recurrence(kwargs, layers):
    if not layers:
        return None
    if "ssm_heads" in kwargs:           # Mamba-2: P x N a head, B, C a group
        size = {"kind": "ssd", "heads": int(kwargs["ssm_heads"]),
                "state": (int(kwargs["ssm_head_dim"]),
                          int(kwargs["d_state"])),
                "groups": int(kwargs["n_groups"])}
    elif "kda_layers" in kwargs:        # the delta rule, a gate a channel
        dim = int(kwargs["lin_head_dim"])
        size = {"kind": "kda", "heads": int(kwargs["lin_heads"]),
                "state": (dim, dim), "gate_lanes": dim}
    elif "lin_key_dim" in kwargs:       # the delta rule, a gate a head
        size = {"kind": "gdn", "heads": int(kwargs["lin_heads"]),
                "state": (int(kwargs["lin_value_dim"]),
                          int(kwargs["lin_key_dim"])), "gate_lanes": 0}
    else:                               # a recurrence with no word here
        return None
    size.update(layers=layers, step=size["kind"] + "_step",
                chunk=size["kind"] + "_chunk")
    return size


def sizes(config):
    """What the merged readers count with: `attention` {the read's kernel
    in a trace, layers that attend (planes, under a loop), query heads, the
    lanes a token and layer takes in the pool, and what makes them: KV
    heads x head size twice, or latent + rope}; `experts` {the product's
    kernel, matrices an expert, rows x cols of each, bytes an element, the
    (first, count) of them a lane holds}; `recurrence` {kind, layers,
    heads, the state's shape a head (float32), what else a token brings
    (`groups` of B and C, or `gate_lanes` a head), the step's and the
    chunk's kernel}. A part the configuration does not state, or states in
    words this function does not know, is None: every part, where it states
    no keyword argument at all (a test's factory defaults, a run object
    without a configuration)."""
    kwargs = (config or {}).get("kwargs")
    if not kwargs:
        return {"attention": None, "experts": None, "recurrence": None}
    serving = config["serving"]
    attending, recurrent = _layers(kwargs)
    return {"attention": _attention(kwargs, serving, attending),
            "experts": _experts(kwargs, DTYPE_BYTES[serving["dtype"]]),
            "recurrence": _recurrence(kwargs, recurrent)}
