"""The chunked form of the recurrence against its roofline, in percent: the
time one chip needs at its peaks for the work the kernel could not avoid,
over its measured self seconds in the traced slice (the ops
`kernel.state_chunk_busy` sums). Layer: kernels. Moves tokens_per_s.

The work, from the `mixed_step` spans of the ticks that ran WHOLLY inside
the slice (`<kind>_chunk_rows`, `<kind>_chunk_tokens`) and the sizes of
`run["config"]` (lib/roofline_sizes.py, lib/roofline_kinds.py; the count of
a delta rule in lib/roofline_gated_delta.py, of Mamba-2 in
lib/roofline_falcon_h1.py):

  bytes   chunk rows x layers with the recurrence x 2 x the state (read and
          written ONCE a row, not once a token), and every token's inputs
          and its read, float32
  FLOPs   tokens x layers x heads x (3, delta rule; 2, Mamba-2) x 2 x the
          state's elements: the recurrence itself, however the chunked form
          arranges it (its intra-chunk products are its own choice)

Under-counted throughout, so the share reads low and never high."""

from lib.roofline_kinds import recurrence_roofline


def compute(run):
    return recurrence_roofline(run, "chunk")
