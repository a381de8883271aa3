from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import (grouped_decode_ref,
                                                latent_decode_ref)

__all__ = ["decode_attention", "grouped_decode_ref", "latent_decode_ref"]
