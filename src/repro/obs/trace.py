"""Trace spans: layer scopes, executor op scopes, host wall-clock spans,
and the collective-signature helper that keeps them honest.

Three kinds of span, because JAX separates trace time from run time:

  * **Layer scopes** (:func:`layer_scope`) — ``obs::<layer>::<part>``
    ``jax.named_scope`` annotations around each layer's work in the
    train step: ``model::{embed,norm,attention,mlp,moe,ssm,head}``,
    ``optimizer::{flatten,momentum,update,stats,unflatten}`` and
    ``exchange::{<compressor>,allreduce,gather_replica}``.  Always on: a
    name stack entry is HLO metadata only, so the timed and the traced
    program are one program (tests/test_obs.py pins the compiled HLO,
    metadata stripped, equal with and without them).  Autodiff keeps the scope in the ``op_name``
    of the backward (``transpose(jvp(...))``) and of the recomputed
    forward (``.../rematted_computation/...``), so a device trace tells
    forward, backward and recomputation of each layer apart.

  * **Op scopes** (:func:`op_scope`) — ``jax.named_scope`` annotations
    the plan/pipelined executors wrap around every collective op at
    TRACE time.  They attach the span name (see :func:`span_name`) to
    the emitted HLO as metadata, so a ``jax.profiler`` device trace
    attributes each timed kernel to its (plan, bucket, stage, stream)
    grid point — the same three-stream schedule
    ``repro.plan.cost.pipeline_breakdown`` prices.  Names are metadata
    ONLY: enabling tracing must not change the compiled collectives
    (:func:`collective_signature` extracts the comparable op set;
    tests/test_obs.py pins on-vs-off equality).  Scopes are off by
    default and a shared ``nullcontext`` when disabled — zero cost.

  * **Host spans** (:class:`Tracer`) — wall-clock timed regions of the
    driver (a training-step window, a checkpoint save, a drift probe),
    emitted as ``span`` events to a telemetry sink and bracketed with
    ``jax.profiler.TraceAnnotation`` so they also show up on the host
    track of a profiler trace.  NOTE: a span around an async-dispatched
    jitted call measures dispatch, not device time — drivers that want
    honest step timing span a WINDOW that ends at a host sync (e.g. the
    batched metric fetch) and record ``n`` steps per window.

Span naming convention (documented in README "Observability")::

    obs::<layer>::<part>                           layer scope
    obs::<plan>::s<stage>::<Kind>~<tier>          serial executor
    obs::<plan>::b<bucket>.s<stage>::<Kind>~<tier> pipelined executor

A grid scope nests inside its ``obs::exchange::<compressor>`` scope:
``.../obs::exchange::onebit/obs::flat/onebit::s1::AllToAll~intra/...``.

e.g. ``obs::hier_onebit::b2.s1::AllToAll~cross`` = bucket 2's cross-pod
all_to_all leg.  The tier separator is ``~`` because ``@`` is reserved
by JAX's name stack (it marks transform annotations like ``vmap@...``)
and everything from ``@`` on is SILENTLY DROPPED when the scope is
lowered to HLO ``op_name`` metadata — the one place the name must
survive for :mod:`repro.obs.profile` to join a device trace back onto
the grid.  ``repro.obs.profile.SCOPE_RE`` accepts both separators so
pre-rename logs still parse.
"""
from __future__ import annotations

import contextlib
import re
import time
from typing import List, Optional, Tuple

_NULL = contextlib.nullcontext()
_ENABLED = False


def set_tracing(on: bool) -> None:
    """Globally enable/disable executor op scopes (process-wide; the
    driver flips it once per run — steps must be re-traced to pick up a
    change, which drivers do by building fresh jitted steps)."""
    global _ENABLED
    _ENABLED = bool(on)


def tracing_enabled() -> bool:
    return _ENABLED


@contextlib.contextmanager
def tracing(on: bool = True):
    """Scoped :func:`set_tracing` (tests use this)."""
    prev = _ENABLED
    set_tracing(on)
    try:
        yield
    finally:
        set_tracing(prev)


def span_name(plan_name: str, stage: int, kind: str, tier: str,
              bucket: Optional[int] = None) -> str:
    b = f"b{bucket}." if bucket is not None else ""
    return f"obs::{plan_name}::{b}s{stage}::{kind}~{tier}"


def layer_scope(layer: str, part: str):
    """The ``obs::<layer>::<part>`` named scope around one layer's work;
    the names hold no ``/``, ``@`` or ``jvp(``."""
    import jax
    return jax.named_scope(f"obs::{layer}::{part}")


def op_scope(plan_name: str, stage: int, op, bucket: Optional[int] = None):
    """Context manager naming one collective op's trace region; the
    shared nullcontext when tracing is disabled (no allocation, no
    overhead on the default path)."""
    if not _ENABLED:
        return _NULL
    import jax
    return jax.named_scope(span_name(plan_name, stage, op.kind, op.tier,
                                     bucket))


class Tracer:
    """Host-side wall-clock spans, recorded and (optionally) emitted as
    ``span`` events to a telemetry sink.

    Spans nest (the tracer keeps a depth stack, recorded as ``depth``
    on each span, with monotonic ``t_mono0``/``t_mono1`` endpoints —
    so sibling spans provably never overlap and nesting is well-formed,
    pinned by tests/test_properties.py).  A body that RAISES still ends
    its span: the record carries ``ok: false`` and a ``warning`` event
    marks the abnormal close — an exception mid-window must not lose
    the span or silently skew dur/n."""

    def __init__(self, sink=None):
        self.sink = sink
        self.spans: List[dict] = []
        self._depth = 0

    @contextlib.contextmanager
    def span(self, name: str, stream: str = "host", **attrs):
        """Time a region; ``attrs`` ride on the span event (``step``,
        ``n``, ``op_kind``, ...)."""
        import jax
        t0 = time.perf_counter()
        wall0 = time.time()
        depth = self._depth
        self._depth = depth + 1
        exc: Optional[BaseException] = None
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        except BaseException as e:
            exc = e
            raise
        finally:
            self._depth = depth
            t1 = time.perf_counter()
            rec = {"name": name, "stream": stream, "t_start": wall0,
                   "dur": t1 - t0, "ok": exc is None, "depth": depth,
                   "t_mono0": t0, "t_mono1": t1, **attrs}
            self.spans.append(rec)
            if self.sink is not None:
                self.sink.emit("span", **rec)
                if exc is not None:
                    self.sink.emit("warning", what="span.abort",
                                   detail=f"span {name!r} closed by "
                                          f"{type(exc).__name__}")


# --------------------------------------------------------------------------
# HLO collective signature (the telemetry-neutrality check)
# --------------------------------------------------------------------------

# the collective op mnemonics XLA emits (superset of what programs here
# produce; matches repro.analysis.roofline._COLLECTIVES)
_COLLECTIVE_RE = re.compile(
    r"\b((?:all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?)\b")


def collective_signature(hlo_text: str) -> Tuple[Tuple[str, str], ...]:
    """The compiled program's collective ops as a sorted tuple of
    ``(opcode, result shape)`` pairs — everything that determines WHAT
    the program communicates, nothing of the metadata/names that
    tracing annotations add.  Two lowerings with equal signatures move
    identical collective traffic; ``tests/test_obs.py`` pins that
    enabling telemetry/tracing leaves the signature unchanged."""
    sig = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        opcode = m.group(1).replace("-start", "")
        shape = line.split("=", 1)[0].strip()
        # the lhs reads like  "%all-to-all.1 = u8[4,128]{1,0}" in HLO or
        # "%0 : tensor<4x128xui8>" in StableHLO; keep the dtype/shape
        # token on the RHS instead, which both dialects place after "=";
        # layout annotations ("{1,0}") are stripped — they don't change
        # what is communicated, only how it's tiled in memory
        rhs = re.sub(r"\{[0-9,]*\}", "",
                     line.split("=", 1)[1].strip())
        shape_m = re.match(r"[(]?([a-z0-9]+\[[0-9,]*\]"
                           r"(?:, ?[a-z0-9]+\[[0-9,]*\])*)", rhs)
        sig.append((opcode, shape_m.group(1) if shape_m else rhs[:40]))
    return tuple(sorted(sig))
