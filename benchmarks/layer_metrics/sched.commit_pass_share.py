"""Share of a block-decoding lane's run passes that are COMMIT passes (the
forward that stores a finished block's K and V and reveals nothing), in
percent: `commit_rows` over `commit_rows` + `denoise_rows` of the window's
`mixed_step` spans. 1 in `denoising_steps` + 1 where every block commits.
What a commit fused into the next block's first pass would take out.
Layer: scheduler tick. Moves tokens_per_s."""

from lib.metrics import lane_spans


def compute(run):
    attrs = [s["attrs"] for s in lane_spans(run, "mixed_step")
             if "commit_rows" in s["attrs"]]
    commits = sum(a["commit_rows"] for a in attrs)
    ran = commits + sum(a["denoise_rows"] for a in attrs)
    return 100.0 * commits / ran if ran else None
