"""Pallas TPU flash-attention forward kernel.

Grid: (batch*heads, S_q / BQ). Each grid step holds one (BQ, D) query
block in VMEM and loops over (BK, D) key/value blocks with the online
softmax recurrence (running max m, normalizer l, weighted accumulator o)
kept in f32 VREGs — the score matrix never materializes beyond a
(BQ, BK) tile, so HBM traffic is O(S*D) instead of O(S^2).

TPU adaptation (vs the CUDA flash-attention):
  * block sizes default to (BQ, BK) = (256, 256) with D up to 128 —
    (256, 128) operands feed the 128x128 MXU with full lanes; the
    (BQ, BK) f32 score tile is 256 KiB of VMEM;
  * the kv loop is a ``lax.fori_loop`` inside the kernel body (sequential
    per grid step, pipelined across grid steps by the Pallas runtime); each
    iteration slices its (BK, D) block from the K/V refs, and the running
    max and normalizer are (BQ, 1) columns;
  * causal masking prunes whole kv blocks past the diagonal by clamping
    the loop bound (no wasted MXU work right of the diagonal);
  * optional sliding window adds the left bound.

Compiled when lowered for a TPU; tested in interpret mode on the CPU
against ``ref.sdpa`` (``repro.kernels.on_platform``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import on_platform

DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30


def _flash_kernel(causal: bool, window: Optional[int], bk: int, s_kv: int,
                  q_ref, k_ref, v_ref, o_ref):
    bq, d = q_ref.shape[1], q_ref.shape[2]
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) / (d ** 0.5)       # (BQ, D)

    q_start = qi * bq
    # causal: kv blocks strictly right of the diagonal contribute nothing
    if causal:
        n_kv = jnp.minimum((q_start + bq + bk - 1) // bk, s_kv // bk)
    else:
        n_kv = s_kv // bk
    if window is not None:
        k0 = jnp.maximum((q_start - window) // bk, 0)
    else:
        k0 = 0

    def body(j, carry):
        m_prev, l_prev, o_prev = carry
        kv = pl.ds(pl.multiple_of(j * bk, bk), bk)
        k = k_ref[0, kv, :].astype(jnp.float32)
        v = v_ref[0, kv, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask = mask & (cols <= rows)
        if window is not None:
            mask = mask & (cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        o_new = o_prev * corr + jnp.dot(p, v,
                                        preferred_element_type=jnp.float32)
        return m_new, l_new, o_new

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    o0 = jnp.zeros((bq, d), jnp.float32)
    m, l, o = jax.lax.fori_loop(k0, n_kv, body, (m0, l0, o0))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq",
                                             "bk", "interpret"))
def _flash_call(interpret: bool, q: jax.Array, k: jax.Array, v: jax.Array,
                causal: bool, window: Optional[int], bq: int, bk: int):
    b, h, s, d = q.shape
    qf, kf, vf = (a.reshape(b * h, s, d) for a in (q, k, v))
    out = pl.pallas_call(
        functools.partial(_flash_kernel, causal, window, bk, s),
        grid=(b * h, s // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: Optional[int] = None,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK) -> jax.Array:
    """q/k/v: (B, H, S, D) -> (B, H, S, D). S % bq == S % bk == 0."""
    s = q.shape[2]
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    return on_platform(
        functools.partial(_flash_call, causal=causal, window=window, bq=bq,
                          bk=bk), q, k, v)
