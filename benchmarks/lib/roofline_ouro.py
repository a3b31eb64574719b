"""The bytes and operations that the step of a LOOPED model (one stack of
layers applied `ut_steps` times over one set of weights, a cache plane a
(pass, layer)) cannot avoid: what `step.loop_decode_hbm_roofline` divides
(`lib/roofline.py` has the rules; `lib/roofline_moe_mla.py` the ticks wholly
inside the slice; the paged read's own roofline is
`kernel.paged_attn_roofline`'s, `lib/roofline_kinds.py`, which counts a plane
as a layer). The counts are pure functions of sizes, pinned by hand-computed
cases.

Count only what no implementation could avoid.

- The paged read, a tick: every key and value of every fed row's context
  (`ctx_tokens` on the tick's `mixed_step` span), ONCE a plane, `kv_planes`
  planes (the span's own attr: absent on a lane whose model is not looped,
  and every reader here then reads nothing): 2 x heads x head_dim x
  bytes an element a (token, plane), 8,192 B at 16 heads of 128 lanes in
  bfloat16. Queries, outputs and the new tokens' writes are left out.
- A decode tick (width 1), the whole step: the layers' weights once a PASS
  (`ut_steps` x layers x a layer's matrices: no batch of 8 rows amortises
  them, and one pass cannot keep 4.93 GB on the chip for the next), the
  planes' keys and values as above, the head's matrix once. Norm scales,
  the embedding rows and the rows' own activations are left out.

Under-counted throughout, so a share reads low and never over 100 %.
"""

from lib import roofline_moe_mla
from lib.metrics import percentile
from lib.roofline import DTYPE_BYTES


def sizes(config):
    """What the counts need, from a configuration file's dict: the
    factory's keyword arguments as run and the lane's type."""
    kwargs = config["kwargs"]
    return {"layers": int(kwargs["n_layers"]),
            "passes": int(kwargs["ut_steps"]),
            "heads": int(kwargs["n_heads"]),
            "head_dim": int(kwargs["head_dim"]),
            "d_model": int(kwargs["d_model"]),
            "d_ff": int(kwargs["d_ff"]),
            "vocab": int(kwargs["vocab"]),
            "bytes_per_element": DTYPE_BYTES[config["serving"]["dtype"]]}


def plane_token_bytes(size):
    """A token's key and value in ONE plane."""
    return 2 * size["heads"] * size["head_dim"] * size["bytes_per_element"]


def layer_bytes(size):
    """One layer's matrices: Wq, Wk, Wv, Wo and the SwiGLU's three."""
    lanes = size["heads"] * size["head_dim"]
    return ((4 * size["d_model"] * lanes + 3 * size["d_model"] * size["d_ff"])
            * size["bytes_per_element"])


def head_bytes(size):
    return size["d_model"] * size["vocab"] * size["bytes_per_element"]


def read_bytes(ctx_tokens, planes, size):
    """Keys and values a tick's reads move: each context token once a
    plane."""
    return ctx_tokens * planes * plane_token_bytes(size)


def decode_tick_bytes(ctx_tokens, passes, planes, size):
    """What a decode-only tick must move (module docstring)."""
    return (passes * size["layers"] * layer_bytes(size)
            + read_bytes(ctx_tokens, planes, size) + head_bytes(size))


def looped_ticks(run):
    """The attrs of the ticks wholly inside the traced slice that ran a
    looped model's step (`kv_planes` on the span)."""
    return [a for a in roofline_moe_mla.whole_ticks(run) if "kv_planes" in a]


def decode_hbm_roofline(run, run_ms):
    """Percent of the memory's peak a decode tick reaches: the median, over
    the slice's whole width-1 ticks, of the seconds their bytes need at the
    peak, against `run_ms`, the median run of the width-1 program on the
    device (`step.decode_run_ms`'s number)."""
    ticks = [a for a in looped_ticks(run) if a.get("width") == 1]
    if not ticks or not run_ms or not run["peaks"]:
        return None
    size = sizes(run["config"])
    floors = [decode_tick_bytes(a.get("ctx_tokens", 0), a["ut_steps"],
                                a["kv_planes"], size)
              / run["peaks"]["hbm_bytes_per_s"] for a in ticks]
    return 100.0 * percentile(floors, 50) * 1e3 / run_ms
