"""Pallas kernels for the paper's compute hot spots: ``onebit`` (fused
error-feedback 1-bit compression), ``fused_adam`` (the warmup-stage Adam
update) and ``flash_attn`` (attention forward).

Each kernel compiles to Mosaic when lowered for a TPU and runs through the
Pallas interpreter when lowered for the CPU, where the tests check it
against its ``ref.py``.  :func:`on_platform` makes that choice at lowering
time; nothing here asks for a backend when it is imported.
"""
from __future__ import annotations

import functools

import jax


def on_platform(call, *args):
    """``call(interpret, *args)``: compiled when lowered for a TPU, through
    the Pallas interpreter when lowered for the CPU.  Lowering for any
    other platform is an error, and a TPU program never holds the
    interpreter."""
    return jax.lax.platform_dependent(
        *args, cpu=functools.partial(call, True),
        tpu=functools.partial(call, False))
