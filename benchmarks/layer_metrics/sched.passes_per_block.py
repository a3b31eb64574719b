"""Forwards a block of a block-decoding lane costs: the window difference of
the lanes' `stats()["mixed"]` run passes (`denoise_passes` +
`commit_passes`: a generating row's tick, each a run of `block_length`
tokens) over `blocks_finished` (blocks whose last denoise pass landed).
`denoising_steps` + 1 where every block commits; a row's last block does
not (its K and V would be read by nobody), so 64 blocks a request read
4.98 at 4 steps. Layer: scheduler tick. Moves tokens_per_s."""

from lib.roofline_sdar import counted, passes


def compute(run):
    ran = blocks = 0
    for before, after in counted(run, "mixed", "blocks_finished"):
        ran += passes(before, after)
        blocks += after["blocks_finished"] - before["blocks_finished"]
    return ran / blocks if blocks else None
