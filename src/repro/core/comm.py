"""Collectives for the compressed-optimizer family: the paper's
``compressed_allreduce``, generalised over pluggable compressors and
lowered through the :mod:`repro.plan` IR.

All functions here are meant to be called *inside* a ``shard_map`` body.
``axis_names`` is the tuple of mesh axes forming the data-parallel
super-axis (e.g. ``("data",)`` single-pod, ``("pod", "data")`` multi-pod).

This module contains NO inline schedule bodies: every exchange — the
paper's Fig. 3 flat schedule, the beyond-paper hierarchical two-level
schedule, and the uncompressed warmup mean — is built as a declarative
:class:`~repro.plan.ir.CommPlan` (``repro.plan.schedules``) and lowered
by the generic executor (``repro.plan.executor``).  The SAME plan
objects are priced by the α-β cost model (``repro.plan.cost``) and
validated byte-for-byte against the compiled HLO in
``benchmarks/comm_volume.py --check-plans``, so predicted and executed
wire traffic cannot drift apart.

The flat schedule is the paper's Figure 3, mapped onto TPU-native
collectives:

  1. worker EF-compress of the local momentum        (Alg. 1 line 7)
  2. ``all_to_all`` of the packed payload chunks     (Fig. 3a — MPI_Alltoall)
  3. local average of the received chunks            (Fig. 3b)
  4. server EF-compress of the averaged chunk        (Alg. 1 line 10)
  5. ``all_gather`` of the packed result             (Fig. 3c — MPI_Allgather)

Each rank plays "server" for its own chunk, exactly as in the paper.
The schedule never inspects the payload: a compressor hands back a tuple
of element-ordered wire arrays (see ``repro.optim.compressors``) whose
declared ``wire_specs`` annotate the plan ops, so the bytes that cross
the interconnect are the compressor's real wire format.

``cfg`` may be a :class:`repro.optim.compressors.Compressor` or a legacy
:class:`repro.core.compression.CompressionConfig` (adapted on the fly).
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.obs import trace as obs
from repro.plan import executor as _exec
from repro.plan import schedules as _sched

AxisNames = Tuple[str, ...]
Errs = Dict[str, jax.Array]


def _concat(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(tuple(parts))


def flat_dim(x) -> int:
    """Flat element count of an exchange value: a ``(d,)`` vector or a
    tuple of per-bucket parts (``--overlap-bwd``) summing to ``d``."""
    if isinstance(x, (tuple, list)):
        return int(sum(p.shape[0] for p in x))
    return int(x.shape[0])


def _execute(plan, comp, value, errs, n_buckets: int, n_total: int):
    """Lower a plan serially, or — for ``n_buckets > 1`` — through the
    bucketed pipelined executor (``repro.pipeline``): the plan is split
    into block-aligned per-bucket stages issued in wavefront order so
    XLA can overlap one bucket's cross-pod leg with the next bucket's
    compress + intra-pod work.  ``n_buckets`` clamps to the alignment
    unit count; 1 is byte-for-byte the serial executor.

    ``value`` may arrive as a tuple of per-bucket parts (backward
    overlap): when the parts line up with the bucketer's sizes they are
    handed to the pipelined executor unconcatenated — each bucket then
    depends only on its own gradient fragments, not on a whole-vector
    ravel — and issued in ready (reversed-bucket) order.  Any mismatch
    (serial path, clamped bucket count) concatenates first, which is
    bitwise the same exchange."""
    parts = value if isinstance(value, (tuple, list)) else None
    if n_buckets <= 1:
        if parts is not None:
            value = _concat(parts)
        return _exec.execute_plan(plan, comp, value, errs)
    from repro.pipeline import (Bucketer, execute_pipelined,  # no cycle
                                lower_to_pipelined)
    # comp.block_size is required: bucket alignment to compressor blocks
    # is what makes per-bucket compression bitwise the serial schedule
    bucketer = Bucketer.for_exchange(plan.d, n_total, comp.block_size,
                                     n_buckets)
    pplan = lower_to_pipelined(plan, comp, bucketer)
    if parts is not None:
        sizes = tuple(p.shape[0] for p in parts)
        value = (tuple(parts)
                 if sizes == tuple(bp.size for bp in pplan.buckets)
                 else _concat(parts))
    return execute_pipelined(pplan, comp, value, errs)


def _as_compressor(cfg):
    if hasattr(cfg, "ef_compress") and hasattr(cfg, "decompress"):
        return cfg
    from repro.optim.compressors import as_compressor  # lazy: no cycle
    return as_compressor(cfg)


def axis_size(axis_names: Sequence[str]) -> int:
    if not axis_names:
        return 1
    return jax.lax.psum(1, tuple(axis_names))


def allreduce_mean(x: jax.Array, axis_names: Sequence[str]) -> jax.Array:
    """Uncompressed baseline: mean over the dp super-axis (vanilla Adam).

    Flat (1-D) vectors — the optimizer exchange — lower through the plan
    IR so the warmup hop is costable like every other schedule; other
    shapes (scalars/metrics) take the plain pmean."""
    axes = tuple(axis_names)
    if not axes:
        return x
    with obs.layer_scope("exchange", "allreduce"):
        if x.ndim != 1:
            return jax.lax.pmean(x, axes)
        plan = _sched.allreduce_schedule(x.shape[0], axis_size(axes), axes)
        out, _ = _exec.execute_plan(plan, None, x)
    return out


def compressed_allreduce(
    x: jax.Array,
    worker_err: jax.Array,
    server_err: jax.Array,
    axis_names: Sequence[str],
    cfg,
    n_buckets: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Error-compensated compressed allreduce (Alg. 1 lines 7-11 / Fig. 3).

    Args:
      x:          (D,) float32 local value (momentum), D % (n*block) == 0.
      worker_err: (D,) float32 per-worker compression error (delta^(i)).
      server_err: (D/n,) float32 this rank's server-chunk error (delta-bar).
      axis_names: dp mesh axes.
      cfg:        a Compressor or legacy CompressionConfig.
      n_buckets:  >1 = bucketed pipelined execution (repro.pipeline);
                  bitwise the serial schedule.

    Returns (averaged (D,) replicated over dp, new worker_err, new server_err).
    """
    out, errs = compressed_exchange(
        x, {"worker": worker_err, "server": server_err}, axis_names, (),
        cfg, n_buckets=n_buckets)
    return out, errs["worker"], errs["server"]


def compressed_allreduce_hierarchical(
    x: jax.Array,
    errs: Errs,
    inner_axes: Sequence[str],
    outer_axes: Sequence[str],
    cfg,
    n_buckets: int = 1,
) -> Tuple[jax.Array, Errs]:
    """Beyond-paper: two-level compressed allreduce (intra-pod then
    cross-pod), with the cross-pod hop at SERVER-CHUNK granularity.

    Stage 1a runs the paper's worker compress + all_to_all + average over
    the fast intra-pod ``inner_axes`` (ICI), leaving each rank holding its
    (D/n_inner,) server chunk.  Stage 2 re-reduces THAT CHUNK over the
    slow cross-pod ``outer_axes`` (DCI) — both legs carry the compressed
    wire format, and because only chunk-sized payloads cross the DCI the
    per-pod cross-pod bytes shrink by ~n_inner× versus the flat schedule
    (measured in benchmarks/comm_volume.py).  Stage 1b then
    server-EF-compresses the pod-mean chunk and all_gathers it within the
    pod (ICI, cheap).

    ``errs`` is the error-feedback slot dict keyed by plan slot name
    (``repro.state`` declares the backing state slots): ``worker`` (D,)
    and ``server`` (D/n_inner,) always; for SPARSE compressors the
    cross-pod legs each carry their own EF loop — ``outer``
    (D/n_inner,) on the all_to_all and ``outer_ag``
    (D/(n_inner*n_outer),) on the all_gather.  Dense compressors run the
    outer stage EF-free (their residual is O(eps/n_pods) and does not
    accumulate); extra keys pass through untouched, so callers hand in
    every EF slot they hold and write back whatever returns.

    ``n_buckets > 1`` pipelines the whole two-level schedule over
    block-aligned buckets (``repro.pipeline``): bucket *i*'s cross-pod
    legs overlap bucket *i+1*'s intra-pod work, bitwise the serial
    schedule for every compressor.

    Returns ``(out, new_errs)``.
    """
    return compressed_exchange(x, errs, inner_axes, outer_axes, cfg,
                               n_buckets=n_buckets)


def compressed_exchange(
    x,
    errs: Errs,
    dp_axes: Sequence[str],
    pod_axes: Sequence[str],
    cfg,
    n_buckets: int = 1,
) -> Tuple[jax.Array, Errs]:
    """THE compressed optimizer exchange: flat schedule over ``dp_axes``
    when ``pod_axes`` is empty, hierarchical two-level otherwise.  Takes
    and returns the full EF slot dict (extra keys untouched).

    ``x`` is the ``(d,)`` flat value, or — under backward overlap — a
    tuple of per-bucket parts in bucket (= element) order, which keeps
    per-bucket data dependencies intact through to the pipelined
    executor.  The result is always one ``(d,)`` vector."""
    comp = _as_compressor(cfg)
    axes_in = tuple(dp_axes)
    axes_out = tuple(pod_axes)
    d = flat_dim(x)
    with obs.layer_scope("exchange", comp.name):
        n_in = axis_size(axes_in)
        if not axes_out:
            assert d % n_in == 0, (d, n_in)
            plan = _sched.flat_schedule(comp, d, n_in, axes_in)
            return _execute(plan, comp, x, errs, n_buckets, n_in)
        outer_ef = _sched.needs_outer_ef(comp)
        assert not outer_ef or ("outer" in errs and "outer_ag" in errs), \
            ("hierarchical topology needs a dense (or lossless) "
             "compressor, or the outer/outer_ag EF slots: un-compensated "
             "cross-pod legs would permanently drop the sparse residual "
             f"of {type(comp).__name__}")
        n_out = axis_size(axes_out)
        plan = _sched.hier_schedule(comp, d, n_in, n_out, axes_in,
                                    axes_out, outer_ef=outer_ef)
        return _execute(plan, comp, x, errs, n_buckets, n_in * n_out)
