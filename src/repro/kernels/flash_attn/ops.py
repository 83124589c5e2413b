"""Public wrapper for the flash-attention kernel."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.flash_attn import kernel as K


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: Optional[int] = None,
                    bq: Optional[int] = None, bk: Optional[int] = None
                    ) -> jax.Array:
    """(B, H, S, D) attention with VMEM-tiled online softmax.

    Block sizes are clamped to the sequence length so smoke-scale shapes
    run through the same kernel body.
    """
    s = q.shape[2]
    bq = min(bq or K.DEFAULT_BQ, s)
    bk = min(bk or K.DEFAULT_BK, s)
    return K.flash_attention(q, k, v, causal=causal, window=window,
                             bq=bq, bk=bk)
