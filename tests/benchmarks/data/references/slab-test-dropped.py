"""The control of the rehearsal's recurrent cell: mamba2's equations with
one term dropped, the mixer's D * x skip. A served path that is right must
read `correct` false against it."""

from references import mamba2


def forward(params, tokens, sizes):
    blocks = dict(params["blocks"], D=params["blocks"]["D"] * 0.0)
    return mamba2.forward(dict(params, blocks=blocks), tokens, sizes)
