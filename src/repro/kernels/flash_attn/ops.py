"""Differentiable flash attention: one ``jax.custom_vjp`` op whose rules
run the Pallas kernels when lowered for a TPU, and on the CPU a jnp rule
built on ``ref.scores`` with the same residuals.

The forward saves (q, k, v, lse) and, where the kernels tile the keys,
o in f32 for the backward's di; the backward recomputes the
probabilities from lse (the kernels) or from q and k (the CPU rule,
through ``jax.vjp`` of its forward).  The platform is chosen inside each
rule, so both platforms save the same residuals and no S x S array is a
residual on either.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import on_platform
from repro.kernels.flash_attn import kernel as K
from repro.kernels.flash_attn import ref


def supports(seq: int, heads: int, head_dim: int) -> bool:
    """Whether (B, seq, heads, head_dim) attention fits the kernels:
    whole 128-row blocks, and heads that tile the 128 lanes."""
    return seq % K.LANES == 0 and K.head_group(heads, head_dim) is not None


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: Optional[int] = None
                    ) -> jax.Array:
    """q/k/v (B, S, H, D) -> (B, S, H, D), differentiable.

    The kernels take q and k heads-major, (B, H, D, S), and v packed,
    (B, S, H * D) (``kernel``'s docstring says why), in the blocks of
    ``kernel.blocks``.
    """
    b, s, h, d = q.shape
    assert supports(s, h, d), (
        f"flash_attention: sequence {s} or {h} heads of {d} do not fit "
        f"the kernel's {K.LANES}-row, {K.LANES}-lane blocks")
    heads_major = lambda a: a.transpose(0, 2, 3, 1)
    o = _attention(heads_major(q), heads_major(k), v.reshape(b, s, h * d),
                   causal, window)
    return o.reshape(b, s, h, d)


def _fwd_rule(q, k, v, causal: bool, window: Optional[int]):
    """The CPU rule: the kernels' forward, o, lse and o32, from the full
    score matrix (``ref.scores``), in the kernels' layouts."""
    b, h, d, s = q.shape
    sc = ref.scores(q.swapaxes(2, 3), k.swapaxes(2, 3), causal, window)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", (p / l).astype(v.dtype),
                   v.reshape(b, s, h, d), preferred_element_type=jnp.float32)
    lse = (m + jnp.log(l)).swapaxes(2, 3)                 # (B, H, 1, S)
    o = o.reshape(v.shape)
    o32 = o if K.blocks(s)[1] < s else None
    return o.astype(v.dtype), lse, o32


def _fwd(q, k, v, causal, window):
    bq, bk = K.blocks(q.shape[3])
    o, lse, o32 = on_platform(
        lambda interpret, *a: K.fwd(*a, causal=causal, window=window,
                                    bq=bq, bk=bk, interpret=interpret),
        q, k, v, cpu=lambda *a: _fwd_rule(*a, causal, window))
    return o, (q, k, v, lse, o32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention(q, k, v, causal, window):
    return _fwd(q, k, v, causal, window)[0]


def _bwd(causal, window, res, do) -> Tuple[jax.Array, ...]:
    q, k, v, lse, o32 = res
    b, h, d, s = q.shape
    bq, bk = K.blocks(s)

    def kernel(interpret, q, k, v, lse, do, *o32):
        di = None
        if o32:            # kv blocks; one kv block forms di in the kernel
            di = jnp.sum((o32[0] * do.astype(jnp.float32)
                          ).reshape(b, s, h, d), axis=-1)
            di = di.transpose(0, 2, 1)[:, :, None, :]      # (B, H, 1, S)
        return K.bwd(q, k, v, do, lse, di, causal=causal, window=window,
                     bq=bq, bk=bk, interpret=interpret)

    def rule(q, k, v, lse, do, *o32):
        return jax.vjp(lambda *a: _fwd_rule(*a, causal, window)[0],
                       q, k, v)[1](do)

    extra = () if o32 is None else (o32,)
    return on_platform(kernel, q, k, v, lse, do, *extra, cpu=rule)


_attention.defvjp(_fwd, _bwd)
