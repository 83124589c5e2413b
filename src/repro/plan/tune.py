"""Cluster auto-tuner: pick the cheapest valid collective schedule.

``autotune`` enumerates (topology x compressor x block_size x
n_buckets x use_kernel) for a given :class:`~repro.plan.cost
.ClusterSpec` + flat model dimension, prices every candidate with the
α-β model (pipelined pricing when ``n_buckets > 1``), and returns the
cheapest VALID plan.  With ``price_compute=True`` (the default) each
candidate's compress/EF/decompress compute is HBM-rooflined against
``spec.device`` (``repro.perf``) and folded into the price — serially
for unpipelined plans, as a third overlappable stream for pipelined
ones — which is what lets the ``use_kernel`` axis (jnp vs fused
Pallas; identical wire bytes, different passes and launches) change a
decision at all, and lets a compute-bound device veto bucket counts
whose extra kernel launches cost more than the overlap buys.
Validity is structural, not heuristic:

  * ``hier`` needs a real pod split (``spec.n_outer > 1``); when it runs
    a sparse compressor it gets the ``outer`` EF slot (one extra
    (d/n_inner,) f32 buffer per rank, reported on the candidate);
  * the flat dimension is re-padded per block size
    (``padded_length(d, n_total, block)``), so candidates with different
    block sizes are priced on the vector they would actually move;
  * ``n_buckets`` clamps to the alignment-unit count (the ``Bucketer``
    policy) — a clamped candidate is priced at its EFFECTIVE bucket
    count, never at a fictional one.

Optimizer-state memory is priced from the DECLARED slot registry
(``repro.state``): every candidate carries ``state_bytes_per_rank`` —
the per-rank bytes of the optimizer's :class:`~repro.state.SlotSpec`
extents materialised for the candidate's (topology, layout) — and a
``layouts`` axis with ``max_state_bytes_per_rank`` lets the tuner trade
the paper's replicated layout against ZeRO-1 sharding when the
replicated state does not fit: no hand-derived size formula anywhere,
the same declarations that build the state price it.

Update frequency is a second objective axis (0/1 Adam, 2202.06009): a
``sync_interval`` of k means the optimizer exchanges once every k
steps, so the AVERAGE per-step cost is ``t_exchange / k`` (and
``hlo_bytes / k`` bytes).  With ``sync_intervals`` the tuner enumerates
that axis too, under an optional per-step comm budget
(``max_bytes_per_step`` / ``max_t_per_step``): selection prefers the
SMALLEST interval whose cheapest plan fits the budget — i.e. it buys
back update frequency with schedule/compressor savings and skips syncs
only when no plan fits otherwise.  Without a budget every interval is
valid and the most frequent (best-converging) schedule wins, priced
per step.

``launch.train --topology auto`` / ``--pipeline auto`` use this with
the compressor/block pinned by the recipe; benchmarks and tests sweep
the full product.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

from repro.core.compression import padded_length
from repro.plan import schedules
from repro.plan.cost import (ClusterSpec, cross_pod_bytes,
                             pipelined_plan_time, plan_compute_time,
                             plan_time)
from repro.plan.ir import CommPlan

TOPOLOGIES = ("flat", "hier")


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One priced point of the (topology x compressor x block x buckets
    x use_kernel x sync interval) grid."""

    topology: str
    compressor: str
    block_size: int
    plan: Optional[CommPlan]
    t_exchange: float            # priced seconds per sync exchange
    hlo_bytes: float             # per-device collective bytes (HLO conv.)
    dci_bytes_per_pod: int       # bytes/pod over the cross tier
    d_padded: int
    outer_ef: bool = False       # plan carries the outer EF slot
    valid: bool = True
    why: str = ""                # reason when invalid
    n_buckets: int = 1           # EFFECTIVE pipeline bucket count
    sync_interval: int = 1       # steps between exchanges (0/1 Adam)
    use_kernel: bool = False     # fused Pallas compress path priced
    t_compute: float = 0.0       # compute share of t_exchange (roofline
    #                              busy seconds; 0 when not priced)
    layout: str = "replicated"   # optimizer-state layout priced
    state_bytes_per_rank: int = 0  # per-rank state bytes from the slot
    #                                registry extents (repro.state)
    wire_watermark_bytes: float = 0.0  # peak concurrent wire/staging
    #                                    bytes (live watermark over the
    #                                    pipelined schedule's intervals)
    peak_bytes_per_rank: float = 0.0   # state + watermark + the caller's
    #                                    fixed bytes (params/grads/acts);
    #                                    filled by autotune's budget pass
    overlap_bwd: bool = False    # ready-order backward overlap priced:
    #                              t_exchange is then the EXPOSED seconds
    #                              beyond backward (four-stream t_total
    #                              minus t_bwd), comparable head-to-head
    #                              with the after-backward candidates
    t_bwd: float = 0.0           # backward seconds the overlap hid under
    ready_times: Tuple[float, ...] = ()  # per-bucket predicted ready
    #                                      seconds (the bwd stream's
    #                                      schedule; plan telemetry
    #                                      carries these)

    @property
    def t_step_avg(self) -> float:
        """Average exchange seconds per TRAINING step."""
        return self.t_exchange / max(self.sync_interval, 1)

    @property
    def bytes_per_step(self) -> float:
        """Average per-device collective bytes per training step."""
        return self.hlo_bytes / max(self.sync_interval, 1)

    def summary(self) -> Dict[str, object]:
        return {"topology": self.topology, "compressor": self.compressor,
                "block_size": self.block_size, "valid": self.valid,
                "n_buckets": self.n_buckets,
                "sync_interval": self.sync_interval,
                "use_kernel": self.use_kernel,
                "t_exchange_s": self.t_exchange,
                "t_compute_s": self.t_compute,
                "t_step_avg_s": self.t_step_avg,
                "layout": self.layout,
                "state_bytes_per_rank": self.state_bytes_per_rank,
                "wire_watermark_bytes": self.wire_watermark_bytes,
                "peak_bytes_per_rank": self.peak_bytes_per_rank,
                "hlo_bytes": self.hlo_bytes,
                "bytes_per_step": self.bytes_per_step,
                "dci_bytes_per_pod": self.dci_bytes_per_pod,
                "outer_ef": self.outer_ef,
                "overlap_bwd": self.overlap_bwd,
                "t_bwd_s": self.t_bwd,
                "why": self.why}


@dataclasses.dataclass(frozen=True)
class TuneResult:
    best: Candidate
    table: Tuple[Candidate, ...]   # every enumerated candidate, priced

    def summary(self) -> Dict[str, object]:
        return {"best": self.best.summary(),
                "table": [c.summary() for c in self.table]}


def _axes_for(spec: ClusterSpec, topology: str):
    """Representative axis names for offline plan construction (the cost
    model only needs group sizes; real axis names are bound by the
    caller that executes the plan)."""
    if topology == "hier":
        return ("data",), ("pod",)
    return (("pod", "data") if spec.n_outer > 1 else ("data",)), ()


def _invalid(topology, compressor, block_size, d, why,
             n_buckets=1, sync_interval=1, use_kernel=False,
             layout="replicated") -> Candidate:
    # record the REQUESTED bucket count so the table/CI artifact shows
    # every enumerated grid point, not one collapsed row
    return Candidate(topology, compressor, block_size, None,
                     float("inf"), 0.0, 0, d, valid=False, why=why,
                     n_buckets=n_buckets, sync_interval=sync_interval,
                     use_kernel=use_kernel, layout=layout)


def layout_state_bytes(spec: ClusterSpec, d_pad: int, topology: str,
                       layout: str, comp=None) -> int:
    """Per-rank optimizer-state bytes, read off the DECLARED slot
    extents (repro.state) — zero1's dp-sharded ``v``/master chunks and
    hier's inner-sized EF chunks price themselves, and the cross-pod EF
    slots count where ``comp`` (default: 1-bit) needs them on hier."""
    from repro.optim.base import TwoStageOptimizer  # lazy: no cycle
    from repro.state import StateLayout, state_bytes
    n_srv = spec.n_inner if topology == "hier" else spec.n_total
    ctx = StateLayout(d=d_pad, n_dp=spec.n_total, n_srv=n_srv,
                      n_outer=spec.n_outer if topology == "hier" else 1)
    opt = (TwoStageOptimizer() if comp is None
           else TwoStageOptimizer(compressor=comp))
    return state_bytes(opt.state_slots(layout, topology), ctx)


def build_candidate(spec: ClusterSpec, d: int, topology: str,
                    compressor: str, block_size: int,
                    compressor_kwargs: Optional[dict] = None,
                    n_buckets: int = 1,
                    sync_interval: int = 1,
                    use_kernel: bool = False,
                    price_compute: bool = True,
                    layout: str = "replicated",
                    overlap_bwd: bool = False,
                    t_bwd: float = 0.0,
                    ready_times_fn=None) -> Candidate:
    """Price one (topology, compressor, block_size, n_buckets,
    use_kernel, overlap_bwd) point.

    ``price_compute`` folds the compressor's declared compute
    (``repro.perf``) into the price: serially for ``n_buckets == 1``
    (the serial executor has no stream to hide it in), via the
    three-stream list schedule otherwise.  ``use_kernel`` prices (and,
    when the plan is executed, runs) the fused Pallas compress path —
    identical wire bytes, fewer HBM passes and launches; compressors
    without a kernel path yield an invalid candidate.

    ``overlap_bwd`` prices ready-order backward overlap through the
    FOUR-stream breakdown: per-bucket ready times come from
    ``ready_times_fn(offsets, d_pad)`` (the caller's
    ``analysis.model_math.bwd_ready_times`` closure, exact per-layer
    bwd FLOPs) or, absent one, a linear sweep of ``t_bwd`` seconds
    over the flat vector (uniform-layer approximation).  The
    candidate's ``t_exchange`` is then the EXPOSED time beyond
    backward — four-stream ``t_total`` minus the backward time — so
    overlap and after-backward candidates price the same quantity:
    seconds the exchange ADDS to a step.  Needs ``n_buckets > 1``
    (one bucket has no production order to exploit)."""
    from repro.optim.compressors import (compressor_has_kernel,
                                         get_compressor)  # lazy: no cycle
    kw = dict(compressor_kwargs or {})
    kw["block_size"] = block_size
    if use_kernel:
        try:
            if not compressor_has_kernel(compressor):
                return _invalid(topology, compressor, block_size, d,
                                "no fused kernel path", n_buckets,
                                sync_interval, use_kernel)
        except KeyError as e:
            return _invalid(topology, compressor, block_size, d, str(e),
                            n_buckets, sync_interval, use_kernel)
        kw["use_kernel"] = True
    try:
        comp = get_compressor(compressor, **kw)
    except (AssertionError, TypeError, KeyError) as e:
        return _invalid(topology, compressor, block_size, d, str(e),
                        n_buckets, sync_interval, use_kernel)
    d_pad = padded_length(d, spec.n_total, block_size)
    if topology == "hier":
        if spec.n_outer <= 1:
            return _invalid(topology, compressor, block_size, d_pad,
                            "hier needs n_outer > 1", n_buckets,
                            sync_interval, use_kernel)
        inner_axes, outer_axes = _axes_for(spec, topology)
        outer_ef = schedules.needs_outer_ef(comp)
        plan = schedules.hier_schedule(comp, d_pad, spec.n_inner,
                                       spec.n_outer, inner_axes, outer_axes,
                                       outer_ef=outer_ef)
    else:
        axes, _ = _axes_for(spec, topology)
        tier = "intra" if spec.n_outer <= 1 else "cross"
        plan = schedules.flat_schedule(comp, d_pad, spec.n_total, axes,
                                       tier=tier)
        outer_ef = False
    if overlap_bwd and n_buckets <= 1:
        return _invalid(topology, compressor, block_size, d_pad,
                        "overlap-bwd needs a pipelined exchange "
                        "(n_buckets > 1)", n_buckets, sync_interval,
                        use_kernel, layout)
    ready = None
    t_bwd_eff = 0.0
    if n_buckets > 1:
        from repro.pipeline import Bucketer, lower_to_pipelined
        from repro.plan.cost import (bucket_staging_bytes,
                                     pipeline_breakdown, wire_watermark)
        bk = Bucketer.for_exchange(d_pad, spec.n_total, block_size,
                                   n_buckets)
        pplan = lower_to_pipelined(plan, comp, bk)
        if overlap_bwd:
            offs = tuple(bp.offset for bp in pplan.buckets)
            if ready_times_fn is not None:
                ready = [max(float(r), 0.0)
                         for r in ready_times_fn(offs, d_pad)]
            else:
                ready = [float(t_bwd) * (d_pad - o) / d_pad
                         for o in offs]
            t_bwd_eff = max(ready) if ready else 0.0
        bd = pipeline_breakdown(pplan, spec,
                                include_compute=price_compute,
                                ready=ready)
        # overlap candidates pay only what the bwd stream fails to
        # hide; after-backward candidates pay the whole exchange
        t_ex = bd["t_total"] - t_bwd_eff
        t_comp = float(bd["busy"].get("compute", 0.0))
        eff_buckets = bk.n_buckets
        watermark = wire_watermark(bd["intervals"],
                                   bucket_staging_bytes(pplan))
    else:
        t_comp = (plan_compute_time(plan, comp, spec)
                  if price_compute else 0.0)
        t_ex = plan_time(plan, spec) + t_comp
        eff_buckets = 1
        watermark = float(sum(op.payload_bytes for op in plan.ops))
    return Candidate(topology, compressor, block_size, plan,
                     t_ex, plan.hlo_bytes(),
                     cross_pod_bytes(plan, spec), d_pad,
                     outer_ef=outer_ef, n_buckets=eff_buckets,
                     sync_interval=max(sync_interval, 1),
                     use_kernel=use_kernel, t_compute=t_comp,
                     layout=layout,
                     state_bytes_per_rank=layout_state_bytes(
                         spec, d_pad, topology, layout, comp),
                     wire_watermark_bytes=watermark,
                     overlap_bwd=bool(overlap_bwd),
                     t_bwd=t_bwd_eff,
                     ready_times=tuple(ready) if ready else ())


def enumerate_candidates(spec: ClusterSpec, d: int,
                         compressors: Optional[Sequence[str]] = None,
                         block_sizes: Sequence[int] = (1024, 4096, 16384),
                         topologies: Sequence[str] = TOPOLOGIES,
                         compressor_kwargs: Optional[dict] = None,
                         n_buckets_options: Sequence[int] = (1,),
                         sync_intervals: Sequence[int] = (1,),
                         use_kernel_options: Sequence[bool] = (False,),
                         price_compute: bool = True,
                         layouts: Sequence[str] = ("replicated",),
                         overlap_bwd_options: Sequence[bool] = (False,),
                         t_bwd: float = 0.0,
                         ready_times_fn=None
                         ) -> Tuple[Candidate, ...]:
    from repro.optim.compressors import get_compressor, list_compressors
    names = list(compressors) if compressors else list_compressors()

    def comp(name, block):
        return get_compressor(name, **dict(compressor_kwargs or {},
                                           block_size=block))
    out = []
    for topo in topologies:
        assert topo in TOPOLOGIES, topo
        for name in names:
            for block in block_sizes:
                for nb in n_buckets_options:
                    for uk in use_kernel_options:
                        for ob in overlap_bwd_options:
                            if ob and nb <= 1:
                                continue   # nothing to ready-order
                            # build/price the plan ONCE; the sync
                            # interval only rescales the derived
                            # per-step figures, and the layout only
                            # swaps the slot-registry state bytes —
                            # neither re-lowers the plan
                            base = build_candidate(
                                spec, d, topo, name, block,
                                compressor_kwargs, n_buckets=nb,
                                use_kernel=uk,
                                price_compute=price_compute,
                                layout=layouts[0],
                                overlap_bwd=ob, t_bwd=t_bwd,
                                ready_times_fn=ready_times_fn)
                            for lay in layouts:
                                c = base if lay == layouts[0] else \
                                    dataclasses.replace(
                                        base, layout=lay,
                                        state_bytes_per_rank=(
                                            layout_state_bytes(
                                                spec, base.d_padded,
                                                topo, lay, comp(name,
                                                                block))
                                            if base.valid else 0))
                                out.extend(dataclasses.replace(
                                    c, sync_interval=max(si, 1))
                                    for si in sync_intervals)
    return tuple(out)


def _dedupe(cands: Tuple[Candidate, ...]) -> Tuple[Candidate, ...]:
    """Clamped bucket counts collapse onto the same effective candidate;
    keep the first of each (topology, comp, block, buckets, kernel,
    interval, overlap)."""
    seen, out = set(), []
    for c in cands:
        key = (c.topology, c.compressor, c.block_size, c.n_buckets,
               c.sync_interval, c.use_kernel, c.layout, c.overlap_bwd,
               c.valid)
        if key in seen:
            continue
        seen.add(key)
        out.append(c)
    return tuple(out)


def autotune(spec: ClusterSpec, d: int,
             compressors: Optional[Sequence[str]] = None,
             block_sizes: Sequence[int] = (1024, 4096, 16384),
             topologies: Sequence[str] = TOPOLOGIES,
             compressor_kwargs: Optional[dict] = None,
             n_buckets_options: Sequence[int] = (1,),
             sync_intervals: Sequence[int] = (1,),
             use_kernel_options: Sequence[bool] = (False,),
             price_compute: bool = True,
             max_bytes_per_step: Optional[float] = None,
             max_t_per_step: Optional[float] = None,
             layouts: Sequence[str] = ("replicated",),
             max_state_bytes_per_rank: Optional[int] = None,
             hbm_capacity: Optional[float] = None,
             fixed_bytes_per_rank: float = 0.0,
             overlap_bwd_options: Sequence[bool] = (False,),
             t_bwd: float = 0.0,
             ready_times_fn=None) -> TuneResult:
    """Cheapest valid plan on ``spec`` for a ``d``-element exchange.

    Selection order: smallest ``sync_interval`` first (update frequency
    is convergence — only give it up when the budget forces it), then
    average per-step exchange time, then fewer buckets (less fill/drain
    exposure and trace size), then ``flat`` before ``hier`` (fewer
    stages, no outer EF state), then the larger block size (fewer scale
    bytes), then the jnp path before the Pallas kernel (only take on
    kernel surface when it pays), then the replicated (paper) state
    layout before zero1 (shard state only when memory forces it).
    ``max_bytes_per_step`` / ``max_t_per_step`` mark over-budget
    candidates invalid (``why="over comm budget"``);
    ``max_state_bytes_per_rank`` does the same against the slot-registry
    state bytes (``why="over state-memory budget"``).

    ``hbm_capacity`` is the capacity-aware generalisation: every
    candidate's ``peak_bytes_per_rank`` is filled with
    ``state_bytes_per_rank + wire_watermark_bytes +
    fixed_bytes_per_rank`` (the caller supplies params/grads/activation
    bytes — layout-independent — via ``fixed_bytes_per_rank``), and
    candidates whose peak exceeds the capacity are marked invalid
    (``why="over hbm capacity"``).  The explicit
    ``max_state_bytes_per_rank`` override is kept and still applies
    when stricter.

    ``price_compute=False`` reverts to link-only pricing — the pre-
    ``repro.perf`` objective, kept so decision diffs are testable (and
    for fabrics whose compute genuinely runs elsewhere).  Link-only
    pricing cannot distinguish ``use_kernel`` candidates (identical
    wire bytes): the tie-break then always keeps the jnp path.

    ``overlap_bwd_options`` adds the backward-overlap axis: overlap
    candidates are priced with the four-stream schedule (per-bucket
    ready times from ``ready_times_fn(offsets, d_pad)`` or the linear
    ``t_bwd`` ramp) and charged only the exchange time EXPOSED beyond
    the backward pass, so they compete head-to-head with after-backward
    candidates.  Ties prefer overlap off (simpler trace).
    """
    table = _dedupe(enumerate_candidates(
        spec, d, compressors, block_sizes, topologies, compressor_kwargs,
        n_buckets_options, sync_intervals, use_kernel_options,
        price_compute, layouts, overlap_bwd_options, t_bwd,
        ready_times_fn))
    if (max_bytes_per_step is not None or max_t_per_step is not None
            or max_state_bytes_per_rank is not None
            or hbm_capacity is not None):
        budgeted = []
        for c in table:
            peak = (c.state_bytes_per_rank + c.wire_watermark_bytes
                    + float(fixed_bytes_per_rank))
            over = c.valid and (
                (max_bytes_per_step is not None
                 and c.bytes_per_step > max_bytes_per_step)
                or (max_t_per_step is not None
                    and c.t_step_avg > max_t_per_step))
            over_state = c.valid and (
                max_state_bytes_per_rank is not None
                and c.state_bytes_per_rank > max_state_bytes_per_rank)
            over_hbm = c.valid and (
                hbm_capacity is not None and peak > hbm_capacity)
            budgeted.append(dataclasses.replace(
                c, peak_bytes_per_rank=peak,
                valid=(c.valid and not over and not over_state
                       and not over_hbm),
                why=c.why or ("over comm budget" if over
                              else "over state-memory budget"
                              if over_state
                              else "over hbm capacity"
                              if over_hbm else "")))
        table = tuple(budgeted)
    valid = [c for c in table if c.valid]
    assert valid, f"no valid plan for {spec.name} (d={d})"
    from repro.optim.base import LAYOUTS as _LAYOUTS  # lazy: no cycle
    best = min(valid, key=lambda c: (c.sync_interval, c.t_step_avg,
                                     c.n_buckets,
                                     TOPOLOGIES.index(c.topology),
                                     -c.block_size, c.use_kernel,
                                     c.overlap_bwd,
                                     _LAYOUTS.index(c.layout)))
    return TuneResult(best=best, table=table)
