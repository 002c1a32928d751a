"""Hand-tiled Pallas TPU kernels for the hot ops (SURVEY §7's "pallas
for the rest" tier); XLA-composed fallbacks everywhere else."""

from tpuserver.ops.flash import (  # noqa: F401
    decode_attention,
    flash_attention,
    latent_decode_attention,
    paged_decode_attention,
)
