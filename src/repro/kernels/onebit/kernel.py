"""Pallas TPU kernels for error-feedback 1-bit compression.

The compression hot path is memory-bound: per element we read x and err,
emit one *bit* + a shared scale, and write the new error. Unfused (as in
``ref.py``) this is ~6 HBM passes over the data (read x, read err, write
buf, read buf twice, write err, write deco...). The fused kernel below does
it in a single pass: each grid step keeps a group of scale blocks of
x/err resident in VMEM, computes the block scales with on-chip
reductions, packs the sign bitmap, and writes (packed, scale, new_err) —
2 f32 reads + 1 f32 write + ~1/32 f32 of compressed output per element.

The server step of the flat schedule (``ef_server_fused``) is the same
sweep over a rank's chunk: it unpacks the ``n`` chunks the rank
received, averages them and adds the server error in VMEM, then
compresses.  It reads the server error once and writes it once, where
XLA splits "block sum, then scale the same block" into a reduce and a
consumer fusion and sweeps the chunk ~8 times.

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
  * scalars stay in f32; the bitmap is uint8 on the wire;
  * the server kernel takes the chunks an all_to_all received as rows
    of 128 bytes, the all_to_all's own view.  The one chunk of a group
    of one comes straight from the worker's compress: from the jnp pack
    (``core.compression``), whose ``(d/128, 16)`` bytes XLA lays out as
    ``(16, d/128)``, the kernel takes and gives the bitmap in that view,
    byte ``j`` of row ``i`` at ``[j, i]``, so no relayout is made
    around it (a u8 relayout of BERT-Large's bitmap also took the TPU
    compiler minutes).  A row's 16 bytes are one matmul with a 0/1
    ``(16, 128)`` matrix: over lanes to pack, over the 16 bytes to
    unpack.

The kernels run compiled when lowered for a TPU and through the Pallas
interpreter when lowered for the CPU (``repro.kernels.on_platform``),
where the tests check them against ``ref.py``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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

    packed_ref[...] = _pack(spread_ref, buf)

    # last: new_err aliases err, and compiled for a TPU a load of err after
    # these stores can see the new values (on a v5e a bitmap packed after
    # them was that of x + new_err); the interpreter does not show this
    for s in range(per_block):
        b = buf(s, per_block)
        new_err_ref[_strided(rows, s, per_block), :] = (
            b - jnp.where(b >= 0.0, scale, -scale))       # exact EF residual


def _pack(spread_ref, buf):
    """The ``(rows/8, 128)`` bitmap rows of the rows ``buf(start, 8)``
    gives."""
    weight = jnp.left_shift(1, _lane_bit()).astype(jnp.float32)
    byte = None
    for r in range(8):
        bits = jnp.where(buf(r, 8) >= 0.0, weight, 0.0).astype(jnp.bfloat16)
        part = jnp.dot(bits, spread_ref[r * LANES:(r + 1) * LANES, :],
                       preferred_element_type=jnp.float32)
        byte = part if byte is None else byte + part
    return byte.astype(jnp.int32).astype(jnp.uint8)


def _unpack(spread_ref, packed, out_ref):
    """The signs of ``(rows/8, 128)`` bitmap rows, +-1, into ``out_ref``."""
    rows = out_ref.shape[0]
    packed = packed.astype(jnp.int32).astype(jnp.bfloat16)
    shift = _lane_bit()
    for r in range(8):
        byte = jax.lax.dot_general(
            packed, spread_ref[r * LANES:(r + 1) * LANES, :],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        bit = jnp.right_shift(byte.astype(jnp.int32), shift) & 1
        out_ref[_strided(rows, r, 8), :] = bit.astype(jnp.float32) * 2.0 - 1.0


def _decompress_kernel(per_block, spread_ref, packed_ref, scale_ref,
                       out_ref):
    rows = out_ref.shape[0]
    _unpack(spread_ref, packed_ref[...], out_ref)
    scale = scale_ref[...]
    for s in range(per_block):
        idx = (_strided(rows, s, per_block), slice(None))
        out_ref[idx] = out_ref[idx] * scale


def _byte_lanes() -> jax.Array:
    """(16, 128) 0/1 bf16: lane l of a row is a bit of byte ``l // 8``."""
    shape = (16, LANES)
    byte = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (byte == lane // 8).astype(jnp.bfloat16)


def _server_kernel(per_block, mean, lane_dense, mat_ref, packed_ref,
                   scale_ref, err_ref, packed_out, scale_out, new_err_ref,
                   sign_ref, buf_ref):
    """The server step over ``rows`` rows of its chunk: the n received
    chunks decompressed and summed in order (then divided by n for a
    mean), plus the server error, in ``buf_ref``; then the scales, signs
    and residual of that buffer.  The bitmaps are rows of 128 bytes
    (``lane_dense``, ``mat_ref`` is ``_spread``) or ``(16, rows)``
    (``mat_ref`` is ``_byte_lanes``).

    A scale block is ``per_block`` consecutive rows, taken whole: with
    the strided loads the other kernels use to reach one row of every
    block, this kernel took 1.6x (one chunk) to 2.7x (four) as long on
    a v5e.  The scales come and go as ``(blocks, 128)``, replicated
    across lanes: Mosaic broadcasts a row across sublanes, but not a
    ``(1, 1)`` across both."""
    n = packed_ref.shape[0]
    rows = err_ref.shape[0]
    shift = _lane_bit()

    def block(b):
        start = b * per_block
        if per_block % 8 == 0:
            start = pl.multiple_of(start, 8)
        return pl.ds(start, per_block), slice(None)

    def each_block(body):              # unrolled where the blocks are few
        blocks = rows // per_block
        jax.lax.fori_loop(0, blocks, lambda b, c: body(b) or c, None,
                          unroll=blocks <= 32)

    for i in range(n):
        if lane_dense:
            _unpack(mat_ref, packed_ref[i], sign_ref)
        else:
            byte = jax.lax.dot_general(
                packed_ref[i].astype(jnp.int32).astype(jnp.bfloat16),
                mat_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            bit = jnp.right_shift(byte.astype(jnp.int32), shift) & 1
            sign_ref[...] = bit.astype(jnp.float32) * 2.0 - 1.0

        def combine(b, i=i):
            v = sign_ref[block(b)] * scale_ref[i, pl.ds(b, 1), :]
            if i:
                v = buf_ref[block(b)] + v
            if i == n - 1:
                if mean:
                    v = v / n
                v = v + err_ref[block(b)]
                total = jnp.sum(jnp.sum(jnp.abs(v), axis=0, keepdims=True),
                                axis=1, keepdims=True)
                scale_out[pl.ds(b, 1), :] = jnp.broadcast_to(
                    total / (per_block * LANES), (1, LANES))
            buf_ref[block(b)] = v
        each_block(combine)

    if lane_dense:
        packed_out[...] = _pack(
            mat_ref, lambda start, stride: buf_ref[
                _strided(rows, start, stride), :])
    else:
        weight = jnp.left_shift(1, shift).astype(jnp.float32)
        bits = jnp.where(buf_ref[...] >= 0.0, weight, 0.0).astype(
            jnp.bfloat16)
        byte = jax.lax.dot_general(mat_ref[...], bits,
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        packed_out[...] = byte.astype(jnp.int32).astype(jnp.uint8)

    # last, as in _ef_compress_kernel: new_err aliases err
    def residual(b):
        v = buf_ref[block(b)]
        scale = scale_out[pl.ds(b, 1), :]
        new_err_ref[block(b)] = v - jnp.where(v >= 0.0, scale, -scale)

    each_block(residual)


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


@functools.partial(jax.jit,
                   static_argnames=("block_size", "mean", "byte_rows",
                                    "interpret"))
def _ef_server_call(interpret: bool, packed: jax.Array, scales: jax.Array,
                    err: jax.Array, block_size: int, mean: bool,
                    byte_rows: bool):
    per_block = block_size // LANES
    n = packed.shape[0]
    n_rows = err.shape[0] // LANES
    rows = _step_rows(n_rows, per_block)       # a multiple of 128 lanes
    lane_dense = byte_rows and n_rows % 8 == 0
    if lane_dense:
        mat = _spread()
        packed = packed.reshape(n, n_rows // 8, LANES)
        bitmap, step, at = (n_rows // 8, LANES), (rows // 8, LANES), 0
    else:
        mat = _byte_lanes()
        packed = jnp.swapaxes(packed.reshape(n, n_rows, 16), 1, 2)
        bitmap, step, at = (16, n_rows), (16, rows), 1

    def grid_step(i):              # the bitmap block of grid step i
        return (i, 0) if at == 0 else (0, i)
    vec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    packed, scales, new_err = pl.pallas_call(
        functools.partial(_server_kernel, per_block, mean, lane_dense),
        grid=(pl.cdiv(n_rows, rows),),
        in_specs=[pl.BlockSpec(mat.shape, lambda i: (0, 0)),
                  pl.BlockSpec((n,) + step, lambda i: (0,) + grid_step(i)),
                  pl.BlockSpec((n, rows // per_block, LANES),
                               lambda i: (0, i, 0)),
                  vec],
        out_specs=[pl.BlockSpec(step, grid_step),
                   pl.BlockSpec((rows // per_block, LANES),
                                lambda i: (i, 0)),
                   vec],
        out_shape=[
            jax.ShapeDtypeStruct(bitmap, jnp.uint8),
            jax.ShapeDtypeStruct((n_rows // per_block, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_rows, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32)] * 2,
        input_output_aliases={3: 2},                  # err -> new_err
        interpret=interpret,
        name="onebit_server",
    )(mat, packed,
      jnp.broadcast_to(scales[..., None], scales.shape + (LANES,)),
      err.reshape(n_rows, LANES))
    if not lane_dense:
        packed = packed.T
    return packed.reshape(-1), scales[:, 0], new_err.reshape(-1)


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


def ef_server_fused(packed: jax.Array, scales: jax.Array, err: jax.Array,
                    block_size: int = DEFAULT_BLOCK, combine: str = "mean",
                    byte_rows: Optional[bool] = None,
                    cpu=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The flat schedule's server step in one sweep: the ``n`` received
    chunks ``packed`` (n, c/8) u8 and ``scales`` (n, c/block) f32 are
    decompressed and combined (``"mean"`` or ``"sum"``), the server error
    ``err`` (c,) f32 is added, and the buffer is compressed again.

    ``byte_rows`` says where the bitmaps come from: rows of 128 bytes,
    as an all_to_all (``n > 1``, the default) and the Pallas codec give
    them, or the jnp pack's own layout (``core.compression``).  The
    kernel takes and gives that view, so no relayout of the bitmap is
    made around it.

    Returns (packed (c/8,) u8, scales (c/block,) f32, new_err (c,) f32),
    the wire format of :func:`ef_compress_fused`.  Lowered for the CPU it
    runs ``cpu(packed, scales, err)`` where that is given, else the
    interpreter.
    """
    _check_block(block_size)
    assert combine in ("mean", "sum"), combine
    assert err.shape[0] % block_size == 0, (err.shape, block_size)
    if byte_rows is None:
        byte_rows = packed.shape[0] > 1
    return on_platform(
        functools.partial(_ef_server_call, block_size=block_size,
                          mean=combine == "mean", byte_rows=byte_rows),
        packed, scales, err, cpu=cpu)
