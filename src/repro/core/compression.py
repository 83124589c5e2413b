"""Error-compensated 1-bit compression (the paper's C_omega operator).

The wire format is real: signs are packed 8-per-uint8 and one float32 scale
is kept per block, so a compressed tensor of ``d`` float32 elements costs
``d/8 + 4*d/block_size`` bytes on the wire (~1.03 bits/element at the
default block size) instead of ``4*d``.

Error feedback invariant (exact in floating point, by construction):

    compressed_value + error == input        (elementwise)

because ``error = input - decompress(compress(input))``.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

DEFAULT_BLOCK = 4096  # elements per scale block


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Configuration for the 1-bit compressor.

    kind:
      "onebit"   — sign + per-block mean-|x| scale (the paper's C_omega)
      "identity" — no-op compressor (used for the paper's "1-bit Adam
                   (32-bits)" ablation and for exactness tests)
    """

    kind: str = "onebit"
    block_size: int = DEFAULT_BLOCK
    use_kernel: bool = False  # route through the Pallas kernel wrapper

    def __post_init__(self):
        assert self.kind in ("onebit", "identity"), self.kind
        assert self.block_size % 8 == 0, "block_size must pack into bytes"


def padded_length(d: int, n_chunks: int, block_size: int = DEFAULT_BLOCK) -> int:
    """Smallest length >= d divisible by n_chunks * block_size."""
    q = n_chunks * block_size
    return ((d + q - 1) // q) * q


def _lane_bytes() -> jax.Array:
    """(128, 16) 0/1 bf16: lane l of a 128-element row is a bit of byte l // 8."""
    return (jnp.arange(128)[:, None] // 8
            == jnp.arange(16)[None, :]).astype(jnp.bfloat16)


def pack_signs(x: jax.Array) -> jax.Array:
    """(d,) float -> (d/8,) uint8 bitmap; bit j of byte i = sign(x[8i+j]) >= 0.

    The vector is viewed as rows of 128 elements, each row packing into
    16 bytes: a lane's sign bit is weighted by ``2**(lane % 8)`` and a 0/1
    matmul with :func:`_lane_bytes` sums every 8 consecutive lanes into
    their byte.  No intermediate has a minor dimension of 8, which a TPU
    pads to 128 lanes (16x the bytes).  The operands are powers of two and
    0/1 in bf16 with f32 accumulation, so the bytes are exact.
    """
    d = x.shape[0]
    pos = jnp.pad(x >= 0, (0, (-d) % 128)).reshape(-1, 128)
    weight = jnp.left_shift(1, jnp.arange(128) % 8).astype(jnp.bfloat16)
    bits = jnp.where(pos, weight, jnp.bfloat16(0))
    byte = jnp.dot(bits, _lane_bytes(), preferred_element_type=jnp.float32)
    return byte.astype(jnp.uint8).reshape(-1)[:d // 8]


def unpack_signs(packed: jax.Array) -> jax.Array:
    """(d/8,) uint8 -> (d,) float32 in {-1, +1}; the mirror of
    :func:`pack_signs`: the transposed matmul copies each byte onto its 8
    lanes (one nonzero term per output, so bf16 is exact) and lane ``l``
    keeps bit ``l % 8``."""
    n = packed.shape[0]
    rows = jnp.pad(packed, (0, (-n) % 16)).reshape(-1, 16)
    byte = jnp.dot(rows.astype(jnp.bfloat16), _lane_bytes().T,
                   preferred_element_type=jnp.bfloat16)
    bit = jnp.right_shift(byte.astype(jnp.int32), jnp.arange(128) % 8) & 1
    return (bit.astype(jnp.float32) * 2.0 - 1.0).reshape(-1)[:8 * n]


def _row_width(block_size: int) -> int:
    """Elements per row of the view the scales are taken on: 128 lanes
    when the block allows.  A flat vector viewed as rows of 128 is its own
    TPU layout, while a ``(d/block, block)`` view is a relayout copy of
    the whole vector."""
    return math.gcd(block_size, 128)


def compress_onebit(x: jax.Array, block_size: int = DEFAULT_BLOCK,
                    use_kernel: bool = False) -> Tuple[jax.Array, jax.Array]:
    """1-bit compress a flat float32 vector.

    Returns (packed uint8 of shape (d/8,), scales float32 of shape (d/block,)).
    Scale per block is mean(|x|) — the l2-optimal scalar for sign
    quantization (argmin_s ||x - s*sign(x)||^2 = mean|x|).
    """
    assert x.ndim == 1 and x.shape[0] % block_size == 0, (x.shape, block_size)
    if use_kernel:
        from repro.kernels.onebit import ops as _kops
        return _kops.compress(x, block_size=block_size)
    w = _row_width(block_size)
    row_sums = jnp.sum(jnp.abs(x).reshape(-1, w), axis=1)
    scales = jnp.sum(row_sums.reshape(-1, block_size // w), axis=1) / block_size
    return pack_signs(x), scales


def decompress_onebit(packed: jax.Array, scales: jax.Array,
                      block_size: int = DEFAULT_BLOCK,
                      use_kernel: bool = False) -> jax.Array:
    """Inverse of compress_onebit: (d/8,) uint8 + (d/block,) f32 -> (d,) f32."""
    if use_kernel:
        from repro.kernels.onebit import ops as _kops
        return _kops.decompress(packed, scales, block_size=block_size)
    return _scaled(unpack_signs(packed), scales, block_size)


def _scaled(signs: jax.Array, scales: jax.Array, block_size: int
            ) -> jax.Array:
    """``signs`` in {-1, +1} (or any per-element factor) times the scale
    of each element's block, on the 128-lane row view."""
    w = _row_width(block_size)
    row_scales = jnp.repeat(scales, block_size // w)
    return (signs.reshape(-1, w) * row_scales[:, None]).reshape(-1)


def onebit_residual(x: jax.Array, scales: jax.Array,
                    block_size: int = DEFAULT_BLOCK) -> jax.Array:
    """``x - decompress_onebit(compress_onebit(x))``, bitwise, without
    the round trip through the bitmap: the decompressed value of an
    element is its block's scale with the sign bit ``x >= 0``."""
    return x - _scaled(jnp.where(x >= 0, 1.0, -1.0), scales, block_size)


def ef_compress(x: jax.Array, err: jax.Array, cfg: CompressionConfig
                ) -> Tuple[Tuple[jax.Array, jax.Array], jax.Array]:
    """Error-feedback compress: compress(x + err) and the new error.

    Returns ((packed, scales), new_err) for kind="onebit";
    for kind="identity" the "packed" entry is the raw buffer and scales is a
    size-0 placeholder, with new_err == 0.
    """
    buf = x + err
    if cfg.kind == "identity":
        return (buf, jnp.zeros((0,), jnp.float32)), jnp.zeros_like(buf)
    packed, scales = compress_onebit(buf, cfg.block_size, cfg.use_kernel)
    return (packed, scales), onebit_residual(buf, scales, cfg.block_size)


def ef_decompress(payload: Tuple[jax.Array, jax.Array],
                  cfg: CompressionConfig) -> jax.Array:
    packed, scales = payload
    if cfg.kind == "identity":
        return packed
    return decompress_onebit(packed, scales, cfg.block_size, cfg.use_kernel)


def wire_bytes(d: int, cfg: CompressionConfig) -> int:
    """Bytes on the wire for a d-element float32 payload under cfg."""
    if cfg.kind == "identity":
        return 4 * d
    return d // 8 + 4 * (d // cfg.block_size)


@partial(jax.jit, static_argnames=("block_size",))
def compression_error_norm(x: jax.Array, block_size: int = DEFAULT_BLOCK) -> jax.Array:
    """||x - decompress(compress(x))|| — diagnostic for Assumption 1's eps."""
    packed, scales = compress_onebit(x, block_size)
    return jnp.linalg.norm(x - decompress_onebit(packed, scales, block_size))
