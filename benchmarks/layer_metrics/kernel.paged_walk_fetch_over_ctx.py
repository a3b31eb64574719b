"""Tokens the paged read's DMAs fetch over the tokens its rows hold:
`walk_tokens_fetched` over `ctx_tokens`, summed over the `mixed_step`
spans that carry both (`tpu_engine/ops/paged_attention.py` `walk_counts`,
counted by the scheduler where it forms a tick). The floor is 1.0: every
context token's K and V once a layer, what `kernel.paged_attn_roofline`
counts as the kernel's bytes. Above it: a tile fetches whole blocks of 16
columns (~1.04 in a decode tick of a few hundred columns a row), and a
chunk of two tall tiles walks its context twice. Before PR 48 a tile
fetched whole GROUPS of 8 or 16 blocks, 1.36 in batch by the same
reckoning; that program counts nothing and reads nothing here, as do a
family whose step is its own and a window without a tick. Layer: kernels.
Moves tokens_per_s."""

from lib.metrics import lane_spans


def compute(run):
    fetched = ctx = 0
    for span in lane_spans(run, "mixed_step"):
        attrs = span["attrs"]
        if "walk_tokens_fetched" in attrs and attrs.get("ctx_tokens"):
            fetched += attrs["walk_tokens_fetched"]
            ctx += attrs["ctx_tokens"]
    return fetched / ctx if ctx else None
