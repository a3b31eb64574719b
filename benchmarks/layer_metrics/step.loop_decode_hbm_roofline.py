"""A looped model's decode tick against the memory's peak, in percent: the
bytes a width-1 tick cannot avoid moving, at 819 GB/s, over the tick
program's median run on the device (`step.decode_run_ms`'s number: the
`tick_w1` runs on the trace's modules line). Layer: step function. Moves
tokens_per_s.

The bytes (lib/roofline_ouro.py), from the spans of the width-1 ticks
wholly inside the slice, their median:

  weights  `ut_steps` (4) x 48 layers x a layer's seven matrices: the same
           4.93 GB streamed once a PASS, whatever the batch
  planes   `ctx_tokens` x `kv_planes` (192) x 8,192 B
  head     2048 x 49,152 x 2 B, once

Norm scales, embedding rows and activations are left out: the share reads
low and never high. This is the cell's own number: the tick is bound by the
layer loop's weight reads, which no batching amortises."""

from lib.roofline_ouro import decode_hbm_roofline
from lib.xplane_scopes import run_ms


def compute(run):
    return decode_hbm_roofline(
        run, run_ms(run, lambda kind, width: kind == "tick" and width == 1))
