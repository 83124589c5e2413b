"""Public wrapper for the fused Adam kernel with automatic padding."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.fused_adam import kernel as K


def adam_step(x: jax.Array, m: jax.Array, v: jax.Array, g: jax.Array,
              lr, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
              weight_decay: float = 0.0
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused BertAdam step on flat f32 vectors; pads to whole lane rows."""
    d = x.shape[0]
    pad = (-d) % K.LANES
    if pad:
        x, m, v, g = (jnp.pad(a, (0, pad)) for a in (x, m, v, g))
    nx, nm, nv = K.adam_step(x, m, v, g, lr, b1, b2, eps, weight_decay)
    if pad:
        nx, nm, nv = nx[:d], nm[:d], nv[:d]
    return nx, nm, nv
