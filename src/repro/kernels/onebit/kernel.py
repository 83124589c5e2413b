"""Pallas TPU kernels for error-feedback 1-bit compression.

The compression hot path is memory-bound: per element we read x and err,
emit one *bit* + a shared scale, and write the new error. Unfused (as in
``ref.py``) this is ~6 HBM passes over the data (read x, read err, write
buf, read buf twice, write err, write deco...). The fused kernel below does
it in a single pass: each grid step keeps a group of scale blocks of
x/err resident in VMEM, computes the block scales with on-chip
reductions, packs the sign bitmap, and writes (packed, scale, new_err) —
2 f32 reads + 1 f32 write + ~1/32 f32 of compressed output per element.

TPU adaptation notes (vs DeepSpeed's CUDA kernel):
  * the flat vector is viewed as rows of 128 lanes, ``(d/128, 128)``, and
    the bitmap as rows of 128 bytes, ``(d/1024, 128)``.  Under the chip's
    (8, 128) tiling both views are the flat vector's own bytes, so no
    relayout copy is made around the kernel.  A grid step holds
    ``rows`` of them (whole scale blocks; a partial last step is masked
    by Pallas);
  * a scale block is ``block/128`` consecutive rows.  Loading every
    ``block/128``-th row (a strided sublane load) gives one row of each
    block in the step, so the scales, and the residual written back with
    the matching strided store, need no reshape for any block size;
  * there is no TPU analogue of a warp ballot, and a ``(n, 8)`` reshape
    would pad its minor 8 to 128 lanes.  Row ``8k + r`` packs into bytes
    ``16r .. 16r + 15`` of bitmap row ``k``: each lane's sign bit is
    weighted by ``2**(lane % 8)``, and one 0/1 matmul on the MXU per
    ``r`` sums every 8 consecutive lanes into its byte (``_spread``).
    Unpacking is the transposed matmul and a per-lane shift.  The
    operands are 0/1 and powers of two in bf16 with f32 accumulation, so
    both are exact, and the wire format is bit for bit the pure-jnp
    path's: bit j of byte i is ``sign(x[8i+j]) >= 0``;
  * scalars stay in f32; the bitmap is uint8 on the wire.

Both kernels run compiled when lowered for a TPU and through the Pallas
interpreter when lowered for the CPU (``repro.kernels.on_platform``),
where the tests check them against ``ref.py``.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import on_platform

DEFAULT_BLOCK = 4096
LANES = 128
STEP_ROWS = 1024      # rows of 128 lanes per grid step (512 KiB per f32 operand)


def _spread() -> jax.Array:
    """(8 * 128, 128) 0/1 bf16: rows ``128r .. 128r + 127`` map lane l of
    row ``8k + r`` onto byte ``16r + l // 8`` of bitmap row ``k``."""
    shape = (8 * LANES, LANES)
    src = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (dst == (src // LANES) * 16 + (src % LANES) // 8).astype(
        jnp.bfloat16)


def _lane_bit() -> jax.Array:
    """(1, 128) int32 ``lane % 8`` — the bit a lane holds in its byte."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) % 8


def _strided(n_rows: int, start: int, stride: int):
    return pl.ds(start, n_rows // stride, stride=stride)


def _ef_compress_kernel(per_block, spread_ref, x_ref, err_ref, packed_ref,
                        scale_ref, new_err_ref):
    """One grid step = ``rows`` rows of 128 lanes, whole scale blocks."""
    rows = x_ref.shape[0]

    def buf(start, stride):
        idx = (_strided(rows, start, stride), slice(None))
        return x_ref[idx] + err_ref[idx]

    # block scales: row s of every block, summed over s, then over lanes
    acc = jnp.abs(buf(0, per_block))
    for s in range(1, per_block):
        acc = acc + jnp.abs(buf(s, per_block))
    scale = jnp.sum(acc, axis=1, keepdims=True) / (per_block * LANES)
    scale_ref[...] = scale

    weight = jnp.left_shift(1, _lane_bit()).astype(jnp.float32)
    byte = None
    for r in range(8):
        bits = jnp.where(buf(r, 8) >= 0.0, weight, 0.0).astype(jnp.bfloat16)
        part = jnp.dot(bits, spread_ref[r * LANES:(r + 1) * LANES, :],
                       preferred_element_type=jnp.float32)
        byte = part if byte is None else byte + part
    packed_ref[...] = byte.astype(jnp.int32).astype(jnp.uint8)

    # last: new_err aliases err, and compiled for a TPU a load of err after
    # these stores can see the new values (on a v5e a bitmap packed after
    # them was that of x + new_err); the interpreter does not show this
    for s in range(per_block):
        b = buf(s, per_block)
        new_err_ref[_strided(rows, s, per_block), :] = (
            b - jnp.where(b >= 0.0, scale, -scale))       # exact EF residual


def _decompress_kernel(per_block, spread_ref, packed_ref, scale_ref,
                       out_ref):
    rows = out_ref.shape[0]
    packed = packed_ref[...].astype(jnp.int32).astype(jnp.bfloat16)
    shift = _lane_bit()
    for r in range(8):
        byte = jax.lax.dot_general(
            packed, spread_ref[r * LANES:(r + 1) * LANES, :],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        bit = jnp.right_shift(byte.astype(jnp.int32), shift) & 1
        out_ref[_strided(rows, r, 8), :] = bit.astype(jnp.float32) * 2.0 - 1.0
    scale = scale_ref[...]
    for s in range(per_block):
        idx = (_strided(rows, s, per_block), slice(None))
        out_ref[idx] = out_ref[idx] * scale


def _step_rows(n_rows: int, per_block: int) -> int:
    """Rows per grid step: whole scale blocks, 8-aligned scale rows and
    32-aligned bitmap rows (uint8 tiles), or everything when it is less."""
    unit = math.lcm(8 * per_block, 8 * 32)
    rows = max(unit, STEP_ROWS // unit * unit)
    return n_rows if n_rows <= rows else rows


def _padded(d: int, block_size: int) -> int:
    unit = math.lcm(block_size, 8 * LANES)
    return -(-d // unit) * unit


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def _ef_compress_call(interpret: bool, x: jax.Array, err: jax.Array,
                      block_size: int):
    per_block = block_size // LANES
    n_rows = x.shape[0] // LANES
    rows = _step_rows(n_rows, per_block)
    vec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_ef_compress_kernel, per_block),
        grid=(pl.cdiv(n_rows, rows),),
        in_specs=[pl.BlockSpec((8 * LANES, LANES), lambda i: (0, 0)),
                  vec, vec],
        out_specs=[
            pl.BlockSpec((rows // 8, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows // per_block, 1), lambda i: (i, 0)),
            vec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows // 8, LANES), jnp.uint8),
            jax.ShapeDtypeStruct((n_rows // per_block, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_rows, LANES), jnp.float32),
        ],
        input_output_aliases={2: 2},                  # err -> new_err
        interpret=interpret,
    )(_spread(), x.reshape(n_rows, LANES), err.reshape(n_rows, LANES))


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def _decompress_call(interpret: bool, packed: jax.Array, scales: jax.Array,
                     block_size: int):
    per_block = block_size // LANES
    n_rows = packed.shape[0] * 8 // LANES
    rows = _step_rows(n_rows, per_block)
    return pl.pallas_call(
        functools.partial(_decompress_kernel, per_block),
        grid=(pl.cdiv(n_rows, rows),),
        in_specs=[
            pl.BlockSpec((8 * LANES, LANES), lambda i: (0, 0)),
            pl.BlockSpec((rows // 8, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows // per_block, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, LANES), jnp.float32),
        interpret=interpret,
    )(_spread(), packed.reshape(n_rows // 8, LANES),
      scales.reshape(n_rows // per_block, 1))


def _check_block(block_size: int) -> None:
    assert block_size % LANES == 0, (
        f"block_size {block_size} must be a multiple of {LANES} lanes")


def ef_compress_fused(x: jax.Array, err: jax.Array,
                      block_size: int = DEFAULT_BLOCK
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused EF-compress. x, err: (d,) f32 with d % block_size == 0.

    Returns (packed (d/8,) u8, scales (d/block,) f32, new_err (d,) f32).
    A length that does not fill whole bitmap rows is padded with zero
    blocks, whose outputs are cut off again.
    """
    _check_block(block_size)
    d = x.shape[0]
    assert d % block_size == 0, (d, block_size)
    pad = _padded(d, block_size) - d
    if pad:
        x, err = (jnp.pad(a, (0, pad)) for a in (x, err))
    packed, scales, new_err = on_platform(
        functools.partial(_ef_compress_call, block_size=block_size), x, err)
    return (packed.reshape(-1)[:d // 8], scales.reshape(-1)[:d // block_size],
            new_err.reshape(-1)[:d])


def decompress(packed: jax.Array, scales: jax.Array,
               block_size: int = DEFAULT_BLOCK) -> jax.Array:
    """(d/8,) u8 + (d/block,) f32 -> (d,) f32."""
    _check_block(block_size)
    d = packed.shape[0] * 8
    pad = _padded(d, block_size) - d
    if pad:
        packed = jnp.pad(packed, (0, pad // 8))
        scales = jnp.pad(scales, (0, pad // block_size))
    out = on_platform(
        functools.partial(_decompress_call, block_size=block_size),
        packed, scales)
    return out.reshape(-1)[:d]
