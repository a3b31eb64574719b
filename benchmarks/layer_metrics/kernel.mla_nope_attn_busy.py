"""Share of the device's busy time spent in the latent-attention (MLA)
Pallas kernel of a model that rotates nothing and whose other layers are
recurrent (32 heads over one latent: `ops/latent_attention.py`, operations
named `mla_latent_read`), in percent, over the union of all operation
intervals: `kernel.mla_attn_busy`'s operations, for the cell that metric's
list does not name. Layer: kernels. Moves tokens_per_s."""

from lib.roofline_kimi_linear import LATENT, busy_share


def compute(run):
    return busy_share(run, LATENT)
