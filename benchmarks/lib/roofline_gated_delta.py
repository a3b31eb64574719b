"""The operations and bytes that a DELTA-RULE recurrence (gated by a number
a head, `gdn`, or by a number a channel, `kda`) cannot avoid: what
`kernel.state_step_roofline` and `kernel.state_chunk_roofline` divide for a
configuration whose `sizes(config)["recurrence"]` is of either kind
(`lib/roofline_sizes.py`; `lib/roofline.py` has the rules and
`floor_seconds`). Pure functions of that part of the sizes, pinned by
hand-computed cases.

Count only what no implementation could avoid. A token of the recurrence,
a head: S k, the rank-one write and S q, 2 x d_v x d_k operations each,
however the chunked form arranges them (its intra-chunk products and its
triangular solve are its own choice). The state, d_v x d_k float32 a head,
is read and written ONCE a row and layer in a tick: a decode row's step
cannot do with less, a chunk's run of tokens need not do more. A token's q,
k, v (and, gated by channel, its d_k gates) come in and its read goes out
once, float32. So a share reads low and never over 100 %.

**Whose seconds.** Both forms of the recurrence are Pallas calls with a
name of their own in a trace (`tpu_engine/ops/gated_delta.py`: `gdn_chunk`
and `kda_chunk`, the triangular solves and the pass over a run's
sub-chunks; `gdn_step` and `kda_step`, a decode row's step). What XLA does
around a call (the conv, the norms, the low-rank gates, what a sub-chunk
needs before the state is touched) counts as the rest of the step.
"""

STATE_BYTES = 4          # the state, and what goes in and out of it: float32


def state_bytes(size):
    """A row's recurrent state, one layer: float32, `state` a head (d_v x
    d_k under the delta rule, P x N under Mamba-2)."""
    return size["heads"] * size["state"][0] * size["state"][1] * STATE_BYTES


def recurrence_flops(tokens, size):
    """S k, the rank-one write and S q, every layer and head."""
    return (tokens * size["layers"] * size["heads"] * 3 * 2
            * size["state"][0] * size["state"][1])


def recurrence_bytes(rows, tokens, size):
    """The state read and written once a row and layer, and each token's
    q, k, v (and its gates a channel, where it has them) in and its read
    out."""
    value_dim, key_dim = size["state"]
    lanes = size["heads"] * (2 * (key_dim + value_dim) + size["gate_lanes"])
    return size["layers"] * (rows * 2 * state_bytes(size)
                             + tokens * lanes * STATE_BYTES)
