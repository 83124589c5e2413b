"""Cost-model drift monitor: predicted vs measured collective times.

The α-β model of :mod:`repro.plan.cost` drives every ``--topology auto``
/ ``--pipeline auto`` decision, but its numbers are either presets or a
one-off ``comm_sweep.py`` calibration — nothing checks them against the
fabric a run actually lands on.  :class:`DriftMonitor` closes that
loop online:

  1. feed it measured per-op samples — ``observe(kind, tier, n,
     payload_bytes, seconds)`` — from wherever they come: the
     :func:`probe_plan` helper (times each collective of a resolved
     plan in isolation, comm_sweep-style), profiler spans, or an
     external log;
  2. every sample is priced by the SAME formula the tuner uses
     (:func:`repro.plan.cost.op_time_kind`) against the run's
     :class:`~repro.plan.cost.ClusterSpec`, giving a per-sample
     residual ratio;
  3. ``report()`` aggregates per (op kind, tier) and flags drift where
     the mean measured/predicted ratio leaves ``[1/(1+threshold),
     1+threshold]`` with at least ``min_samples`` samples;
  4. when anything drifts, ``recalibrate()`` least-squares refits
     (op_overhead, α/β per tier) from the accumulated samples — using
     the coefficient rows of :func:`repro.plan.cost.op_coeffs_kind`, so
     fit and pricing cannot disagree — and ``emit_recalibration(path)``
     writes it in exactly the JSON ``ClusterSpec.from_measured``
     consumes.  A drifted run hands the next run its correction.

The fit needs at least two collective kinds with different
latency/bandwidth coefficient ratios per tier to separate α from the
shared launch overhead (same reasoning as ``benchmarks/comm_sweep.py``);
with fewer, ``recalibrate`` still returns a clamped best-effort fit.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.plan.cost import ClusterSpec, op_coeffs_kind, op_time_kind

_KINDS = ("AllToAll", "AllGather", "AllReduce", "ReduceScatter",
          "Broadcast")


@dataclasses.dataclass(frozen=True)
class DriftSample:
    """One measured collective: what moved, where, and how long."""

    op_kind: str
    tier: str
    n: int
    payload_bytes: float
    seconds: float


def fit_linkspecs(samples: Sequence[DriftSample]) -> Dict[str, object]:
    """Joint lstsq fit of (op_overhead, α/β per tier) from measured
    samples — the drift-side twin of ``comm_sweep.fit_cluster``, built
    on the cost model's own coefficient rows so the fitted spec
    reproduces the samples through ``op_time`` by construction.
    Negative solutions (noise) clamp to tiny positive values."""
    assert samples, "fit_linkspecs needs at least one sample"
    tiers = sorted({s.tier for s in samples})
    cols = 1 + 2 * len(tiers)
    rows, ts = [], []
    for s in samples:
        ov, al, ib = op_coeffs_kind(s.op_kind, s.n, s.payload_bytes)
        row = [ov] + [0.0] * (cols - 1)
        j = 1 + 2 * tiers.index(s.tier)
        row[j], row[j + 1] = al, ib
        rows.append(row)
        ts.append(s.seconds)
    x, *_ = np.linalg.lstsq(np.asarray(rows), np.asarray(ts), rcond=None)
    out: Dict[str, object] = {"op_overhead": float(max(x[0], 1e-9)),
                              "tiers": {}}
    for i, tier in enumerate(tiers):
        alpha = float(max(x[1 + 2 * i], 1e-9))
        inv_b = float(max(x[2 + 2 * i], 1e-15))
        out["tiers"][tier] = {"latency": alpha, "bandwidth": 1.0 / inv_b}
    return out


class DriftMonitor:
    """Accumulate measured op times; compare against ``spec``'s α-β
    predictions; emit a recalibration when they diverge."""

    def __init__(self, spec: ClusterSpec, threshold: float = 0.25,
                 min_samples: int = 3):
        assert threshold > 0.0, threshold
        self.spec = spec
        self.threshold = float(threshold)
        self.min_samples = max(int(min_samples), 1)
        self.samples: List[DriftSample] = []

    # --- feeding ----------------------------------------------------------
    def observe(self, op_kind: str, tier: str, n: int,
                payload_bytes: float, seconds: float) -> dict:
        """Record one measured collective; returns its residual record
        ``{t_measured, t_predicted, ratio}``."""
        assert op_kind in _KINDS, op_kind
        s = DriftSample(op_kind, tier, int(n), float(payload_bytes),
                        float(seconds))
        self.samples.append(s)
        pred = self._predict(s)
        return {"t_measured": s.seconds, "t_predicted": pred,
                "ratio": s.seconds / pred if pred > 0 else float("inf")}

    def observe_op(self, op, seconds: float) -> dict:
        """Record a measured :class:`~repro.plan.ir.CollectiveOp`."""
        return self.observe(op.kind, op.tier, op.n, op.payload_bytes,
                            seconds)

    def _predict(self, s: DriftSample) -> float:
        return op_time_kind(s.op_kind, s.tier, s.n, s.payload_bytes,
                            self.spec)

    # --- verdicts ---------------------------------------------------------
    def report(self) -> List[dict]:
        """Per-(op kind, tier) aggregation: mean measured/predicted and
        the drift verdict (see class docstring for the rule)."""
        groups: Dict[Tuple[str, str], List[DriftSample]] = {}
        for s in self.samples:
            groups.setdefault((s.op_kind, s.tier), []).append(s)
        out = []
        lo, hi = 1.0 / (1.0 + self.threshold), 1.0 + self.threshold
        for (kind, tier), ss in sorted(groups.items()):
            meas = float(np.mean([s.seconds for s in ss]))
            pred = float(np.mean([self._predict(s) for s in ss]))
            ratio = meas / pred if pred > 0 else float("inf")
            out.append({
                "op_kind": kind, "tier": tier, "n_samples": len(ss),
                "t_measured": meas, "t_predicted": pred, "ratio": ratio,
                "drifting": (len(ss) >= self.min_samples
                             and not lo <= ratio <= hi),
                "threshold": self.threshold,
            })
        return out

    @property
    def drifting(self) -> List[Tuple[str, str]]:
        """(op kind, tier) pairs currently over the drift threshold."""
        return [(r["op_kind"], r["tier"]) for r in self.report()
                if r["drifting"]]

    # --- recalibration ----------------------------------------------------
    def recalibrate(self) -> Dict[str, object]:
        """Refit α/β from the accumulated samples, in the
        ``ClusterSpec.from_measured`` JSON layout (``comm_sweep``'s
        format: ``intra``/``cross``/``op_overhead``/pod split)."""
        fit = fit_linkspecs(self.samples)
        tiers = fit["tiers"]
        return {
            "name": f"drift-recal({self.spec.name})",
            "intra": tiers.get("intra") or tiers.get("cross"),
            "cross": tiers.get("cross") if "intra" in tiers else None,
            "op_overhead": fit["op_overhead"],
            "n_inner": self.spec.n_inner, "n_outer": self.spec.n_outer,
            "samples": [dataclasses.asdict(s) for s in self.samples],
        }

    def emit_recalibration(self, path: str) -> Dict[str, object]:
        """Write the recalibration JSON; round-trips through
        ``ClusterSpec.from_measured(path)``."""
        out = self.recalibrate()
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
        return out

    def events(self, emit_recal_path: Optional[str] = None) -> List[dict]:
        """The monitor's state as telemetry event field-dicts: one
        ``drift`` record per (kind, tier), plus a ``recalibration``
        record when anything drifts (written to ``emit_recal_path``
        when given)."""
        report = self.report()
        out = [("drift", r) for r in report]
        if any(r["drifting"] for r in report):
            recal = (self.emit_recalibration(emit_recal_path)
                     if emit_recal_path else self.recalibrate())
            fields = {k: recal[k] for k in ("op_overhead", "intra",
                                            "cross", "n_inner", "n_outer")
                      if recal.get(k) is not None}
            if emit_recal_path:
                fields["path"] = emit_recal_path
            fields["reason"] = ", ".join(
                f"{r['op_kind']}@{r['tier']} x{r['ratio']:.2f}"
                for r in report if r["drifting"])
            out.append(("recalibration", fields))
        return out


# --------------------------------------------------------------------------
# live probe: time a resolved plan's collectives on the real mesh
# --------------------------------------------------------------------------

def probe_plan(plan, mesh, iters: int = 4,
               repeats: int = 3) -> List[DriftSample]:
    """Time each collective op of ``plan`` in isolation on ``mesh`` —
    the live sample source for :class:`DriftMonitor` (comm_sweep-style:
    best-of-``iters`` wall clock around a blocking jitted shard_map of
    just that op's wire leg, moving the op's DECLARED payload).
    Each op is measured ``repeats`` times (independent best-of-``iters``
    samples), so one probe pass satisfies the monitor's default
    ``min_samples`` gate and a genuinely drifted fabric triggers the
    recalibration instead of being discarded as one-off noise.

    Degenerate ops (``n <= 1`` or no axes) move no bytes and are
    skipped, so a single-device run probes nothing and the monitor
    simply reports no samples.  Forced-host CPU meshes exercise the
    machinery; only real fabrics yield meaningful α/β.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.plan.executor import chunk_all_to_all
    from repro.plan.ir import (AllGather, AllReduce, AllToAll, Broadcast,
                               ReduceScatter)

    samples: List[DriftSample] = []
    for op in plan.ops:
        if op.n <= 1 or not op.axes:
            continue
        payloads = tuple(jnp.zeros(w.shape, dtype=w.dtype)
                         for w in op.payload)

        def body(o=op):
            outs = []
            for p in (tuple(jnp.zeros(w.shape, dtype=w.dtype)
                            for w in o.payload)):
                if isinstance(o, AllToAll):
                    r = chunk_all_to_all(p, o.n, o.axes)
                elif isinstance(o, AllGather):
                    r = jax.lax.all_gather(p, o.axes, tiled=o.tiled)
                elif isinstance(o, AllReduce):
                    r = jax.lax.psum(p.astype(jnp.float32), o.axes)
                elif isinstance(o, ReduceScatter):
                    r = jax.lax.psum_scatter(p.astype(jnp.float32),
                                             o.axes, scatter_dimension=0,
                                             tiled=True)
                elif isinstance(o, Broadcast):
                    mine = jax.lax.axis_index(o.axes) == o.root
                    q = p.astype(jnp.float32)
                    r = jax.lax.psum(jnp.where(mine, q,
                                               jnp.zeros_like(q)), o.axes)
                else:   # pragma: no cover — IR kinds are exactly the above
                    raise TypeError(type(o).__name__)
                outs.append(jnp.sum(r.astype(jnp.float32)))
            # replicate the scalar so an out_spec of P() is honest
            return jax.lax.pmean(jnp.stack(outs).sum(),
                                 tuple(mesh.axis_names))

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(),
                                   out_specs=P(), check_vma=False))
        jax.block_until_ready(fn())          # compile outside the clock
        for _ in range(max(repeats, 1)):
            best = float("inf")
            for _ in range(max(iters, 1)):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                best = min(best, time.perf_counter() - t0)
            samples.append(DriftSample(op.kind, op.tier, op.n,
                                       float(op.payload_bytes), best))
        del payloads
    return samples
