"""Production mesh construction.

TPU v5e target: one pod = 256 chips as a (16, 16) ("data", "model") mesh;
multi-pod = 2 pods = 512 chips as (2, 16, 16) ("pod", "data", "model").
The model axis stays within a pod (ICI); the pod axis crosses DCI — the
hierarchical compressed allreduce (beyond-paper) exploits exactly that.

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (device count is locked at first use).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types (sharding propagated by the
    compiler inside the step's ``shard_map``)."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


# hardware constants: single-sourced from repro.perf.device (the TPU v5e
# preset) — re-exported here only for the legacy names; new code should
# take a DeviceSpec
from repro.perf.device import (HBM_BW, HBM_BYTES, ICI_BW,  # noqa: E402,F401
                               PEAK_FLOPS_BF16)
