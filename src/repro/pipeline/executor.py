"""Pipelined executor — run a :class:`~repro.pipeline.ir.PipelinedPlan`
inside a ``shard_map`` body, overlapping cross-pod legs with intra-pod
work.

``execute_pipelined`` slices the rank's flat value and every EF buffer
into per-bucket views (static offsets from the bucketer — see
``PipelinedPlan.slot_strides``), then issues the (bucket x stage) grid
in *wavefront order*: at tick ``t`` it emits stage ``s`` of bucket
``t - s`` for every live stage, so bucket *i*'s cross-pod collective is
traced beside bucket *i+1*'s compress + intra-pod collective with NO
data dependency between them.  That independence is the whole trick:
XLA's latency-hiding scheduler turns independent collectives into
async start/done pairs and runs the DCI transfer of one bucket under
the ICI traffic and (de)compress compute of the next — double-buffered
because at any tick at most one bucket occupies each stream.

The schedule is UNROLLED, not a ``lax.scan``: a scan body is one
program XLA schedules per-iteration, so a cross-pod collective inside
iteration *i* could never overlap an intra-pod collective of iteration
*i+1* — exactly the overlap we are after.  Unrolling costs trace size
(n_buckets x ops, buckets are single digits) and buys the scheduler a
flat dependency DAG.  Bucket sizes need not be uniform, which the
remainder-handling size policy exploits.

Numerics: per-bucket execution is BITWISE identical to the serial
executor — for EVERY topology x compressor combination — whenever
buckets are block-aligned (``Bucketer`` enforces it): per-block
compression cannot see bucket boundaries that coincide with block
boundaries, the per-rank chunk means reduce the same operands in the
same order, and every EF slot is consumed and produced by one op for
the elements the executing rank serves, so the per-element error-
feedback arithmetic never depends on the bucket partition
(tests/test_distributed.py::TestPipelinedParity pins all combos over
chained exchanges).

EF slot layout: a chunk-sized slot (``server``/``outer``/``outer_ag``)
holds this rank's residuals ordered by global element index WITHIN the
rank's served set; per-bucket views are contiguous slices computed
from the bucket structure (the strides above), not a stored format the
buffer owns.  Which elements a rank serves does depend on the bucket
partition, so checkpoints store these slots in the bucket-count-
independent canonical (serial) keying and scatter them into the
resuming run's partition (``repro.state.layout`` — the same
``ef_element_map`` describes both views), making saved state portable
across ``--pipeline off/N/M``.

The compressor's ``use_kernel`` flag routes each bucket's compress /
EF / decompress through the fused Pallas kernels (``kernels/onebit``)
instead of the jnp chain; the wire format is bit-for-bit identical
(tests/test_perf.py pins sign-bitmap parity per bucket, uneven buckets
included), so kernel choice never affects what the collectives move —
only the compute stream the cost model prices
(``repro.plan.cost.pipeline_breakdown``, via the per-bucket
ComputeSpec annotations ``lower_to_pipelined`` attaches).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.plan.executor import Errs, execute_op, op_after

from repro.pipeline.ir import PipelinedPlan


def scoped_op_names(pplan: PipelinedPlan) -> Tuple[str, ...]:
    """The span names one ``execute_pipelined`` run emits (tracing on),
    in wavefront issue order — one ``obs::<plan>::b<bucket>.s<stage>::
    <Kind>~<tier>`` per grid point, the expected coverage set a
    measured-profile fold (:mod:`repro.obs.profile`) is held against."""
    from repro.obs.trace import span_name
    return tuple(
        span_name(pplan.name, s,
                  pplan.buckets[b].plan.ops[s].kind,
                  pplan.buckets[b].plan.ops[s].tier, bucket=b)
        for b, s in pplan.issue_order())


def execute_pipelined(pplan: PipelinedPlan, comp, value,
                      errs: Optional[Errs] = None,
                      order: Optional[Tuple[int, ...]] = None
                      ) -> Tuple[jax.Array, Errs]:
    """Run ``pplan`` on this rank's ``value``; returns (result, new errs).

    Same contract as :func:`repro.plan.executor.execute_plan`: ``errs``
    must contain the keys in ``pplan.err_slots`` (full-size buffers;
    extra keys pass through untouched).

    ``value`` is either the rank's flat ``(d,)`` vector (sliced into
    per-bucket views here — every bucket then depends on the WHOLE
    vector, the "grads done" barrier) or a tuple of per-bucket parts
    matching the bucket sizes.  Parts are consumed as-is: bucket ``b``'s
    first stage depends only on part ``b``, so when the parts are built
    from per-leaf gradient fragments (``repro.train.step``) XLA's
    scheduler may start a bucket's compress+exchange while backward is
    still producing OTHER buckets' gradients.  In parts mode the grid
    is issued in ready order — ``order`` defaults to reversed bucket
    index, backprop's production order (trailing layers first) — which
    changes trace order only, never bucket contents; results stay
    bitwise identical (concatenation is by bucket index either way).
    """
    errs = dict(errs or {})
    missing = [s for s in pplan.err_slots if s not in errs]
    assert not missing, f"plan {pplan.name!r} needs EF slots {missing}"
    strides = pplan.slot_strides()

    parts = value if isinstance(value, (tuple, list)) else None
    if parts is not None:
        assert len(parts) == pplan.n_buckets, (
            len(parts), pplan.n_buckets)
        for bp, part in zip(pplan.buckets, parts):
            assert part.shape == (bp.size,), (part.shape, bp.size)
        if order is None:
            order = tuple(reversed(range(pplan.n_buckets)))
    else:
        assert value.shape == (pplan.d,), (value.shape, pplan.d)

    vals = []
    bucket_errs = []
    for b, bp in enumerate(pplan.buckets):
        vals.append(parts[b] if parts is not None
                    else jax.lax.slice(value, (bp.offset,),
                                       (bp.offset + bp.size,)))
        be = {}
        for slot, f in strides.items():
            lo, hi = bp.offset // f, (bp.offset + bp.size) // f
            be[slot] = jax.lax.slice(errs[slot], (lo,), (hi,))
        bucket_errs.append(be)

    # wavefront issue: stage s of bucket t-s at tick t — ops of one tick
    # are mutually independent, the overlap surface for the scheduler
    for b, s in pplan.issue_order(order):
        ops = pplan.buckets[b].plan.ops
        vals[b], bucket_errs[b] = execute_op(ops[s], comp, vals[b],
                                             bucket_errs[b],
                                             plan_name=pplan.name,
                                             stage=s, bucket=b,
                                             next_op=op_after(ops, s))

    out = vals[0] if pplan.n_buckets == 1 else jnp.concatenate(vals)
    new_errs = dict(errs)
    for slot in strides:
        parts = [be[slot] for be in bucket_errs]
        new_errs[slot] = parts[0] if len(parts) == 1 \
            else jnp.concatenate(parts)
    return out, new_errs
