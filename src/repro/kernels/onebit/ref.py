"""Pure-jnp oracle for the 1-bit EF-compression kernels.

Wire format (shared with ``repro.core.compression``):
  * ``packed``: uint8 bitmap, bit j of byte i is ``sign(x[8i+j]) >= 0``;
  * ``scales``: one float32 per ``block_size`` elements, ``mean(|x|)`` over
    the block (the l2-optimal scalar for sign quantization).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

def compress(x: jax.Array, block_size: int) -> Tuple[jax.Array, jax.Array]:
    """(d,) f32 -> ((d/8,) u8, (d/block,) f32)."""
    assert x.ndim == 1 and x.shape[0] % block_size == 0
    bits = (x >= 0).astype(jnp.uint8).reshape(-1, 8)
    pow2 = 2 ** jnp.arange(8, dtype=jnp.uint8)
    packed = jnp.sum(bits * pow2, axis=1, dtype=jnp.uint8)
    scales = jnp.mean(jnp.abs(x.reshape(-1, block_size)), axis=1)
    return packed, scales


def decompress(packed: jax.Array, scales: jax.Array,
               block_size: int) -> jax.Array:
    """((d/8,) u8, (d/block,) f32) -> (d,) f32."""
    bits = (packed[:, None] >> jnp.arange(8, dtype=jnp.uint8)) & jnp.uint8(1)
    signs = (bits.astype(jnp.float32) * 2.0 - 1.0).reshape(-1, block_size)
    return (signs * scales[:, None]).reshape(-1)


def ef_compress_fused(x: jax.Array, err: jax.Array, block_size: int
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused hot path: buf = x + err; compress(buf); new_err = buf - deco.

    Returns (packed, scales, new_err). One logical pass over the data —
    this is the op DeepSpeed ships custom CUDA for.
    """
    buf = x + err
    packed, scales = compress(buf, block_size)
    new_err = buf - decompress(packed, scales, block_size)
    return packed, scales, new_err
