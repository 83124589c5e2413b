"""Pallas kernels for the paper's compute hot spots: ``onebit`` (fused
error-feedback 1-bit compression), ``fused_adam`` (the warmup-stage Adam
update) and ``flash_attn`` (attention, forward and backward, for
training and prefill).

Each kernel compiles to Mosaic when lowered for a TPU.  Lowered for the
CPU, ``onebit`` and ``fused_adam`` run through the Pallas interpreter,
where the tests check them against their ``ref.py``; ``flash_attn`` runs
a jnp rule there, and its tests call the interpreter directly.
:func:`on_platform` makes that choice at lowering time; nothing here
asks for a backend when it is imported.
"""
from __future__ import annotations

import functools

import jax


def on_platform(call, *args, cpu=None):
    """``call(interpret, *args)``: compiled when lowered for a TPU, through
    the Pallas interpreter when lowered for the CPU, or ``cpu(*args)``
    there where it is given.  Lowering for any other platform is an
    error, and a TPU program never holds the interpreter."""
    return jax.lax.platform_dependent(
        *args, cpu=cpu or functools.partial(call, True),
        tpu=functools.partial(call, False))
