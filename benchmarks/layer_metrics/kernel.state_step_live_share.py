"""Rows that take a step of the recurrence over the rows the step call's
grid spans, in percent: 100 x the sum of `*_step_rows` over the sum of
`*_step_slots` on the `mixed_step` spans that carry both
(`tpu_engine/runtime/scheduler.py` `_note_state_work`, under the kernel's
name: `gdn_`, `kda_` or `ssd_`). A slot is dead for the step when it is
free, when its prompt waits for the token budget or when it is the tick's
chunk row. Since PR 54 a dead row costs the call an empty grid step and no
byte (`tpu_engine/ops/gated_delta.py` `step_at`); before it a dead row's
steps copied the null row's state in and out, so the call cost its SLOTS
and the step's roofline, which counts live rows, read this share of what
the kernel did: agents' `kernel.ssd64_step_roofline` 46 % at 63 % live
beside converse's `kernel.ssd_step_roofline` 79 % at 96 %. A program that
notes no slots (the parent's) reads nothing here. Layer: kernels. Moves
tokens_per_s."""

from lib.metrics import lane_spans


def compute(run):
    rows = slots = 0
    for span in lane_spans(run, "mixed_step"):
        attrs = span["attrs"]
        for name, value in attrs.items():
            if name.endswith("_step_slots") and value:
                rows += attrs.get(name[:-len("slots")] + "rows", 0)
                slots += value
    return 100.0 * rows / slots if slots else None
