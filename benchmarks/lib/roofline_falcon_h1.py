"""The operations and bytes that a Mamba-2 (`ssd`) recurrence cannot avoid:
what `kernel.state_step_roofline` and `kernel.state_chunk_roofline` divide
for a configuration whose `sizes(config)["recurrence"]` is of that kind
(`lib/roofline_sizes.py`; `lib/roofline.py` has the rules and
`floor_seconds`; `lib/roofline_gated_delta.py` what a state of a given
shape weighs). Pure functions of that part of the sizes, pinned by
hand-computed cases.

Count only what no implementation could avoid. A token of the recurrence, a
head: the rank-one write dt x (outer) B into the state and the read S C, a
multiply-add over P x N each, 2 x 2 x P x N operations, however the chunked
form arranges them (its intra-chunk products are its own choice; the decay's
multiply is left out, an under-count). The state, P x N float32 a head, is
read and written ONCE a row and layer in a tick: a decode row's step cannot
do with less, a chunk's run of tokens need not do more. A token's x and dt
a head and its B and C a group come in, and its read goes out, once,
float32. So a share reads low and never over 100 %.

**Whose seconds.** Both forms of the recurrence are Pallas calls with a name
of their own in a trace (`tpu_engine/ops/ssd.py`: `ssd_step`, a decode row's
step; `ssd_chunk`, the pass over a run's sub-chunks). What XLA does around a
call (the conv, the gated norm, what a sub-chunk needs before the state is
touched) counts as the rest of the step.
"""

from lib.roofline_gated_delta import STATE_BYTES, state_bytes


def recurrence_flops(tokens, size):
    """The rank-one write and the read S C, every layer and head."""
    return (tokens * size["layers"] * size["heads"] * 2 * 2
            * size["state"][0] * size["state"][1])


def recurrence_bytes(rows, tokens, size):
    """The state read and written once a row and layer, and each token's x
    and dt a head and B and C a group in and its read out."""
    head_dim, d_state = size["state"]
    lanes = (size["heads"] * (2 * head_dim + 1)
             + 2 * size["groups"] * d_state)
    return size["layers"] * (rows * 2 * state_bytes(size)
                             + tokens * lanes * STATE_BYTES)
