"""Indices a layer's pool write scatters over the tokens the tick holds:
`write_slots` over `write_tokens`, summed over the `mixed_step` spans wider
than a slot that carry both (`tpu_engine/runtime/scheduler.py`
`_tick_formed`, counted where a uniform lane forms a tick;
`tpu_engine/models/transformer.py` `pool_write_slots` says what the step
scatters). XLA's scatter on a v5e pays by the index (~85 to 150 ns each,
PERF.md section 6, PR 52), not by the byte, and an index past a row's new
tokens writes padding into the null block. The floor is 1.0: an index a
token. Since PR 52 a chunk tick's write takes the step's token list, token
budget + rows long: 276 over ~260 in docqa, 288 over a prompt or two in
batch, 288 over one short prompt in chat. Before it the write took every
slot of the step, rows x 256: 8192 or 4096 indices a layer whatever the
tick held; that program counts nothing and reads nothing here, as do a
family whose step is its own (its list is the step) and a window without
a chunk tick. A decode tick's write is a row an index in either program
and is left out. Layer: step function. Moves tokens_per_s."""

from lib.metrics import lane_spans


def compute(run):
    slots = tokens = 0
    for span in lane_spans(run, "mixed_step"):
        attrs = span["attrs"]
        if (attrs.get("width", 1) > 1 and "write_slots" in attrs
                and attrs.get("write_tokens")):
            slots += attrs["write_slots"]
            tokens += attrs["write_tokens"]
    return slots / tokens if tokens else None
