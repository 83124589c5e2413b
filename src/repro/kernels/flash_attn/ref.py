"""Pure-jnp oracle for the flash-attention op and its kernels: the
forward here, its gradients through ``jax.vjp`` in the tests."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def scores(q: jax.Array, k: jax.Array, causal: bool = True,
           window: Optional[int] = None) -> jax.Array:
    """q/k: (B, H, S, D) -> the scaled f32 scores (B, H, Sq, Sk), NEG_INF
    where the causal or sliding-window mask hides a key."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (d ** 0.5)
    sq, sk = q.shape[2], k.shape[2]
    qi = jnp.arange(sq)[:, None]
    kj = jnp.arange(sk)[None, :]
    m = (kj <= qi) if causal else jnp.ones((sq, sk), bool)
    if window is not None:
        m = m & (kj > qi - window)
    return jnp.where(m[None, None], s, NEG_INF)


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
         window: Optional[int] = None) -> jax.Array:
    """q/k/v: (B, H, S, D) -> (B, H, S, D). f32 softmax, same-dtype out."""
    w = jax.nn.softmax(scores(q, k, causal, window), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w,
                      v.astype(jnp.float32)).astype(q.dtype)
