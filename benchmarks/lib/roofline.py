"""The operations and bytes a kernel cannot avoid, and the time a chip
needs for them at its peaks: what a `<kernel>_roofline` reader divides by
the kernel's measured seconds.

Kept with the benchmark, so that no later PR changes what a kernel is held
against. Each function is a pure function of sizes (tests pin them by
hand-computed cases); the sizes come from the configuration file the run
object carries (`run["config"]`, through `lib/roofline_sizes.py` `sizes`)
and from what a span carries.

Count only work no implementation could avoid. Padding, a second pass over
the same bytes, a layout copy: all avoidable, none counted. Where a span
does not carry a term, leave the term out: a share read too low says "look
here"; one read above 100 % is an impossible reading and the check refuses
it.
"""

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def attention_bytes(ctx_tokens, layers, kv_heads, head_dim,
                    bytes_per_element):
    """Bytes of K and V that attention over `ctx_tokens` context tokens
    (summed over the rows of a step) reads from the cache, every layer:
    each token's key and its value once. Queries, outputs and the new
    tokens' writes are left out (a thousandth of it at a long context)."""
    return ctx_tokens * layers * 2 * kv_heads * head_dim * bytes_per_element


def attention_flops(pairs, layers, heads, head_dim):
    """Floating-point operations of attention over `pairs` (query, key)
    pairs, every layer and query head: one multiply-add over head_dim for
    the score and one for the weighted value, 2 x 2 x head_dim a pair. A
    decode row at context n attends n pairs; a causal chunk of c queries
    after w tokens, c * w + c * (c + 1) / 2."""
    return pairs * layers * heads * head_dim * 4


def floor_seconds(n_bytes, flops, peaks):
    """The least time one chip needs: the slower of moving the bytes at
    the memory's peak and doing the operations at the bf16 peak
    (lib/peaks.json's entry for the device)."""
    return max(n_bytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])
