"""Output tokens a generating row of a block-decoding lane finishes a tick:
the window difference of the lanes' `mixed.decode_tokens` (output tokens,
counted as their block is finished) over their run passes
(`denoise_passes` + `commit_passes`, a row-tick each). An autoregressive row
reads 1.0; `block_length` tokens over `denoising_steps` + 1 passes read 0.8
at 4 and 4. Layer: scheduler tick. Moves tokens_per_s."""

from lib.roofline_sdar import counted, passes


def compute(run):
    tokens = ran = 0
    for before, after in counted(run, "mixed", "blocks_finished"):
        tokens += after["decode_tokens"] - before["decode_tokens"]
        ran += passes(before, after)
    return tokens / ran if ran else None
