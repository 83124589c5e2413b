"""Lower a :class:`~repro.plan.ir.CommPlan` to real JAX collectives.

``execute_plan`` is meant to be called *inside* a ``shard_map`` body, on
per-rank flat f32 vectors.  It walks the plan op by op, carrying

  * ``value`` — the current represented f32 vector (its length follows
    the plan's ``d_in``/``d_out`` chain), and
  * ``errs``  — a dict of error-feedback buffers keyed by slot name
    (``plan.err_slots`` lists the required keys).

Compression points are implicit in the ops: an op with ``err_slot`` does
an error-compensated ``comp.ef_compress`` (consuming and replacing that
slot); an op without one does a plain ``comp.compress``; ``AllReduce`` /
``ReduceScatter`` / ``Broadcast`` move the raw f32 value.

The executor asserts, at trace time, that the arrays the compressor
actually hands it match the op's declared ``payload`` WireSpecs — the
same annotations the cost model prices — so a plan can never move bytes
the coster didn't see (``comm_volume.py --check-plans`` closes the loop
against the compiled HLO).

Numerics are bit-for-bit the pre-IR inline schedules of
``repro.core.comm``: chunk exchange is ``all_to_all`` per payload leaf +
vmapped decompress + ``jnp.mean``; gather is tiled ``all_gather`` per
leaf + decompress (see tests/test_distributed.py parity tests).  In the
flat schedule the received chunks reach the server's all_gather still
compressed (:class:`Received`), and the compressor's
``server_ef_compress`` combines and re-compresses them: the same
decompress, mean and ``ef_compress`` by default, one Pallas sweep for
1-bit lowered for a TPU.

When trace spans are enabled (``repro.obs.trace.set_tracing``), every
op lowers inside a ``jax.named_scope`` carrying its
``obs::<plan>::[b<bucket>.]s<stage>::<Kind>~<tier>`` span name, so a
profiler trace attributes device time to the same (bucket, stage,
stream) grid the cost model prices.  Scopes are HLO *metadata* only —
the compiled collectives are identical on and off (pinned by
tests/test_obs.py) — and a shared nullcontext when disabled.  Always
on, and as metadata only too, each rank's work as a server (the average
of the chunks it received, the compression of what it sends on) lies
under the ``obs::exchange::server`` layer scope (``server_scope``).
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import trace as obs
from repro.obs.trace import op_scope
from repro.plan.ir import (AllGather, AllReduce, AllToAll, Broadcast,
                           CollectiveOp, CommPlan, ReduceScatter)

Errs = Dict[str, jax.Array]


def _check_payload(op: CollectiveOp, payload) -> None:
    got = tuple((jnp.asarray(p).dtype.name, tuple(p.shape)) for p in payload)
    want = tuple((w.dtype, w.shape) for w in op.payload)
    assert got == want, (
        f"{op.kind}: compressor payload {got} != plan annotation {want} — "
        "the compressor's wire_specs() and compress() disagree")


def _compress(op: CollectiveOp, comp, value: jax.Array, errs: Errs
              ) -> Tuple[Tuple[jax.Array, ...], Errs]:
    if op.err_slot is not None:
        payload, new_err = comp.ef_compress(value, errs[op.err_slot])
        errs = dict(errs)
        errs[op.err_slot] = new_err
    else:
        payload = comp.compress(value)
    _check_payload(op, payload)
    return payload, errs


def chunk_all_to_all(p: jax.Array, n: int, axes) -> jax.Array:
    """Exchange the ``n`` equal chunks of a flat payload leaf over
    ``axes``; returns the ``(n, chunk)`` received chunks.

    Each chunk travels as rows of up to 128 lanes, ``(n, chunk/L, L)``:
    the same bytes, but the TPU compiler takes minutes over a 2-D
    ``uint8`` all_to_all at BERT-Large length and under a second over
    this one."""
    chunk = p.shape[0] // n
    lanes = math.gcd(chunk, 128)
    recv = jax.lax.all_to_all(p.reshape(n, chunk // lanes, lanes), axes,
                              split_axis=0, concat_axis=0, tiled=False)
    return recv.reshape(n, chunk)


def server_scope():
    """The ``obs::exchange::server`` layer scope: a rank's work as the
    server of its chunk -- the average of the chunks it received and
    the compression (with its error feedback) of what it sends on.  The
    collectives themselves stay outside it."""
    return obs.layer_scope("exchange", "server")


class Received(NamedTuple):
    """The chunks an all_to_all received, left compressed (each leaf
    ``(n, ...)``) for the server step that follows it."""
    payload: Tuple[jax.Array, ...]
    combine: str


def _feeds_server(op: CollectiveOp, next_op: Optional[CollectiveOp]) -> bool:
    """True for the flat schedule's all_to_all, whose received chunks go
    straight to the server all_gather over the same axes: the compressor
    then combines and re-compresses them in one ``server_ef_compress``."""
    return (isinstance(op, AllToAll) and bool(op.axes)
            and isinstance(next_op, AllGather)
            and next_op.err_slot == "server" and next_op.axes == op.axes)


def _exec_all_to_all(op: AllToAll, comp, value, errs, to_server=False):
    payload, errs = _compress(op, comp, value, errs)
    if op.axes:
        recv = tuple(chunk_all_to_all(p, op.n, op.axes) for p in payload)
        if to_server:
            return Received(recv, op.combine), errs
        with server_scope():
            value = comp.combine_received(recv, op.combine)
    else:
        # degenerate single-group: the compress/decompress roundtrip still
        # runs so single-device numerics match the distributed path
        with server_scope():
            value = comp.decompress(payload)
    return value, errs


def _exec_all_gather(op: AllGather, comp, value, errs):
    with server_scope():
        if isinstance(value, Received):
            payload, new_err = comp.server_ef_compress(
                value.payload, errs[op.err_slot], value.combine)
            _check_payload(op, payload)
            errs = {**errs, op.err_slot: new_err}
        else:
            payload, errs = _compress(op, comp, value, errs)
    if op.axes:
        out = tuple(jax.lax.all_gather(p, op.axes, tiled=op.tiled)
                    for p in payload)
        value = comp.decompress(out)
    else:
        value = comp.decompress(payload)
    return value, errs


def _exec_all_reduce(op: AllReduce, comp, value, errs):
    if op.axes:
        value = (jax.lax.pmean(value, op.axes) if op.reduce == "mean"
                 else jax.lax.psum(value, op.axes))
    return value, errs


def _exec_reduce_scatter(op: ReduceScatter, comp, value, errs):
    if op.axes:
        value = jax.lax.psum_scatter(value, op.axes, scatter_dimension=0,
                                     tiled=True)
        if op.reduce == "mean":
            value = value / op.n
    return value, errs


def _exec_broadcast(op: Broadcast, comp, value, errs):
    if op.axes:
        mine = jax.lax.axis_index(op.axes) == op.root
        value = jax.lax.psum(jnp.where(mine, value, jnp.zeros_like(value)),
                             op.axes)
    return value, errs


_EXEC = {
    AllToAll: _exec_all_to_all,
    AllGather: _exec_all_gather,
    AllReduce: _exec_all_reduce,
    ReduceScatter: _exec_reduce_scatter,
    Broadcast: _exec_broadcast,
}

# every op kind this executor can lower — each one is wrapped in an
# op_scope whose span name the profile joiner (repro.obs.profile) must
# parse back to its grid cell; tests/test_profile.py pins the coverage
# so no collective can become silently unattributable
SCOPED_KINDS = tuple(sorted(cls.__name__ for cls in _EXEC))


def scoped_op_names(plan: CommPlan) -> Tuple[str, ...]:
    """The span names one serial ``execute_plan`` run emits (tracing
    on) — the expected coverage set for a measured-profile fold."""
    from repro.obs.trace import span_name
    return tuple(span_name(plan.name, s, op.kind, op.tier)
                 for s, op in enumerate(plan.ops))


def execute_op(op: CollectiveOp, comp, value, errs: Errs,
               plan_name: str = "plan", stage: int = 0,
               bucket: Optional[int] = None,
               next_op: Optional[CollectiveOp] = None) -> Tuple[object, Errs]:
    """Lower ONE collective op (the public entry the pipelined executor
    in :mod:`repro.pipeline.executor` steps through in wavefront order).
    ``next_op`` is the op that consumes the result: where it is the
    flat schedule's server all_gather, the result is the
    :class:`Received` chunks, which only that op takes.
    ``plan_name``/``stage``/``bucket`` only label the op's trace span
    when tracing is on — they never change the lowering."""
    with op_scope(plan_name, stage, op, bucket):
        if _feeds_server(op, next_op):
            return _exec_all_to_all(op, comp, value, errs, to_server=True)
        return _EXEC[type(op)](op, comp, value, errs)


def execute_plan(plan: CommPlan, comp, value: jax.Array,
                 errs: Optional[Errs] = None
                 ) -> Tuple[jax.Array, Errs]:
    """Run ``plan`` on this rank's ``value``; returns (result, new errs).

    ``errs`` must contain exactly the keys in ``plan.err_slots`` (extra
    keys pass through untouched).
    """
    errs = dict(errs or {})
    missing = [s for s in plan.err_slots if s not in errs]
    assert not missing, f"plan {plan.name!r} needs EF slots {missing}"
    assert value.shape == (plan.d,), (value.shape, plan.d)
    for stage, op in enumerate(plan.ops):
        value, errs = execute_op(op, comp, value, errs, plan.name, stage,
                                 next_op=op_after(plan.ops, stage))
    return value, errs


def op_after(ops, stage: int) -> Optional[CollectiveOp]:
    return ops[stage + 1] if stage + 1 < len(ops) else None
