"""Public wrappers for the 1-bit compression kernels.

The kernels compile to Mosaic when lowered for a TPU and run through the
Pallas interpreter when lowered for the CPU (``repro.kernels.on_platform``).
The wrappers keep the wire format identical to ``repro.core.compression``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.onebit import kernel as K


def compress(x: jax.Array, block_size: int = K.DEFAULT_BLOCK
             ) -> Tuple[jax.Array, jax.Array]:
    """(d,) f32 -> (packed (d/8,) u8, scales (d/block,) f32)."""
    packed, scales, _ = K.ef_compress_fused(x, jnp.zeros_like(x), block_size)
    return packed, scales


def decompress(packed: jax.Array, scales: jax.Array,
               block_size: int = K.DEFAULT_BLOCK) -> jax.Array:
    return K.decompress(packed, scales, block_size)


def ef_compress_fused(x: jax.Array, err: jax.Array,
                      block_size: int = K.DEFAULT_BLOCK
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused (compress(x+err), new_err) — the EF hot path."""
    return K.ef_compress_fused(x, err, block_size)
