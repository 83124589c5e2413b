"""Pallas TPU kernel: fused elementwise Adam (BertAdam) update.

The warmup-phase optimizer is pure elementwise work over four same-shaped
f32 vectors (x, m, v, g). Unfused, XLA often materializes the m/v
intermediates to HBM (6 reads + 5 writes per element); the fused kernel
streams each tile through VMEM once: 4 reads + 3 writes — a ~1.6x cut on
the memory-bound optimizer step.

Tiling: the flat vectors are viewed as rows of 128 lanes, which under the
chip's (8, 128) tiling is the vector's own layout (no relayout copy), and
a 1-D grid walks ``STEP_ROWS`` rows at a time (512 KiB per operand, 7
operands double-buffered = 7 MiB of VMEM); a partial last step is masked
by Pallas.  The new x, m and v overwrite the old ones in place, so a
step that donates them holds each once.  ``lr`` is a scalar in SMEM, so
the schedule can vary it per step without recompiling.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import on_platform

LANES = 128
STEP_ROWS = 1024


def _adam_kernel(b1: float, b2: float, eps: float, wd: float,
                 lr_ref, x_ref, m_ref, v_ref, g_ref,
                 nx_ref, nm_ref, nv_ref):
    g = g_ref[...]
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    upd = m / (jnp.sqrt(v) + eps)
    x = x_ref[...]
    if wd:
        upd = upd + wd * x
    nx_ref[...] = x - lr_ref[0] * upd
    nm_ref[...] = m
    nv_ref[...] = v


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps",
                                             "weight_decay", "interpret"))
def _adam_call(interpret: bool, x, m, v, g, lr, b1: float, b2: float,
               eps: float, weight_decay: float):
    n_rows = x.shape[0] // LANES
    rows = min(n_rows, STEP_ROWS)
    vec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_adam_kernel, b1, b2, eps, weight_decay),
        grid=(pl.cdiv(n_rows, rows),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [vec] * 4,
        out_specs=[vec] * 3,
        out_shape=[jax.ShapeDtypeStruct((n_rows, LANES), jnp.float32)] * 3,
        input_output_aliases={1: 0, 2: 1, 3: 2},    # x, m, v in place
        interpret=interpret,
    )(lr.reshape(1), *(a.reshape(n_rows, LANES) for a in (x, m, v, g)))
    return tuple(o.reshape(-1) for o in out)


def adam_step(x: jax.Array, m: jax.Array, v: jax.Array, g: jax.Array,
              lr: jax.Array, b1: float = 0.9, b2: float = 0.999,
              eps: float = 1e-8, weight_decay: float = 0.0
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused BertAdam step on flat (d,) f32 vectors, d % 128 == 0."""
    assert x.shape[0] % LANES == 0, x.shape
    lr = jnp.asarray(lr, jnp.float32)
    return on_platform(
        functools.partial(_adam_call, b1=b1, b2=b2, eps=eps,
                          weight_decay=weight_decay), x, m, v, g, lr)
