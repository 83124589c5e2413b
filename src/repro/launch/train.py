"""Training driver: two-stage compressed optimizers with auto-warmup,
checkpointing, and LR schedule. Runs on whatever devices exist (CPU smoke
-> TPU pod).

The optimizer, compressor, and warmup→compression switch policy are all
selected by name: either a registered recipe (``--recipe``, see
``repro.configs.base.list_optim_recipes``) or explicit ``--optimizer`` /
``--compressor`` registry names. The driver owns only host-side policy —
which stage to run, and (for 0/1 Adam) whether this step synchronises —
and picks the matching jitted step from a small cache.

Usage (CPU-scale example — see examples/ for ready-made invocations):
  PYTHONPATH=src python -m repro.launch.train --arch bert-base-smoke \\
      --steps 200 --batch 8 --seq 128 --mesh 1x1 --lr 1e-3 --warmup-steps 40
  PYTHONPATH=src python -m repro.launch.train --recipe onebit_lamb ...
  PYTHONPATH=src python -m repro.launch.train --recipe zerone_adam_local ...

``--telemetry DIR`` turns on structured run telemetry (repro.obs):
typed JSONL events (step metrics via a BUFFERED device→host path,
stage/sync transitions, per-tier plan bytes, warnings), executor trace
spans, and — with ``--drift-probe`` — the predicted-vs-measured
cost-model drift monitor.  Fold the log with
``python -m repro.obs.report DIR/telemetry.jsonl``.  The layer is
zero-cost when off (NullSink + disabled tracing + async metric parking).

``--audit on`` (with ``--telemetry``) turns on the per-segment
compression-fidelity & frozen-variance audit (:mod:`repro.obs.audit`):
every ``--audit-every``-th compression-stage step additionally runs a
SEPARATE jitted probe on the same batch — shadow variance EMA vs the
frozen ``v`` per segment, cosine/sign fidelity of the compressed
momentum, EF-residual mass — emitting ``fidelity`` events plus host
``health`` verdicts (variance drift, EF blow-up, non-finite stats,
loss spikes).  The probe never touches the train step's compiled
program: audit on vs off is telemetry-neutral (same collective
signature, bitwise losses; pinned in tests/test_audit.py).

``--memory on`` (with ``--telemetry``) turns on the per-rank HBM
ledger (:mod:`repro.obs.mem`): a predicted ``memory`` event at start
(params/grads from the model math, optimizer slots via the SlotSpec
registry, the wire live-watermark, an activation estimate, against the
``--device`` capacity), one live sample per log window
(``device.memory_stats()`` or host RSS) feeding ``mem_headroom`` /
``mem_growth`` health verdicts, and a post-run compiled-program
attribution (``memory_analysis()`` temp+output mapped onto the ledger
categories with an explicit residual) — plus ``DIR/memory_ledger.json``
and ``mem_*`` perf-ledger cells when ``--profile`` runs.  Host-side
only: the train step's compiled program is untouched (neutrality
pinned in tests/test_mem.py).

``--profile DIR`` captures a ``jax.profiler`` trace of the last
``--profile-steps`` steady-state steps and folds it back onto the plan
grid (:mod:`repro.obs.profile`): every executor collective attributed
to its (plan, bucket, stage, kind, tier) cell via the ``op_scope`` name
grammar, a measured-vs-predicted overlap audit against
``pipeline_breakdown``'s intervals, a ``profile`` telemetry event, and
a ``BENCH_<name>.json`` perf-ledger record (``--bench`` names it) the
CI ``perf-ledger`` job gates on via ``results/bench_compare.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import (SHAPES, get_config, get_optim_recipe, list_archs,
                           list_optim_recipes)
from repro.configs.base import InputShape
from repro.data import SyntheticStream
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.obs import (AUDIT_MODES, FiniteGuard, HealthMonitor,
                       MEMORY_MODES, MetricBuffer, Tracer, as_sink,
                       make_audit_probe, set_tracing)
from repro.optim import WarmupSwitch, list_compressors, list_optimizers
from repro.perf import resolve_device
from repro.state import load_train_state, save_train_state
from repro.train.step import (TrainStepConfig, _flat_dim, init_train_state,
                              make_train_step, mesh_axes, pod_split,
                              state_layout_ctx, train_slots,
                              train_state_specs)

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else the
    fixed ``<repo>/.jax_cache`` — a fixed path, so a later run finds it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def bwd_ready_fn(cfg, batch: int, seq: int, device, tp: int = 1):
    """Closure ``(bucket_offsets, d_pad) -> per-bucket ready times``
    from the analytic reverse sweep (``analysis.model_math``), plus the
    total backward seconds — the (ready_times_fn, t_bwd) pair the
    tuner's four-stream pricing and the plan telemetry both use."""
    from repro.analysis.model_math import bwd_ready_times, bwd_total_time
    shape = InputShape("custom", seq, batch, "train")

    def fn(offsets, d_pad):
        return bwd_ready_times(offsets, d_pad, cfg, shape, device, tp)

    return fn, bwd_total_time(cfg, shape, device, tp)


def resolve_schedule(topology: str, pipeline, cluster: str, cfg, mesh,
                     compressor: str, block_size: int,
                     compressor_kwargs=None, verbose: bool = True,
                     use_kernel="off", device: str = "tpu-v5e",
                     overlap_bwd="off", batch: int = 8, seq: int = 128):
    """Resolve the ``"auto"`` axes of the collective schedule with ONE
    joint ``repro.plan.autotune`` search; returns ``(topology,
    n_buckets, use_kernel, overlap_bwd)``.

    The mesh fixes the pod split (leading "pod" axis = n_outer); the
    ``cluster`` preset fixes the link speeds; the ``device`` preset (or
    a ``kernel_sweep.py``-measured spec) fixes the compute roofline the
    three-stream coster prices; the recipe's compressor and block size
    are pinned.  Topology, bucket count, the jnp-vs-Pallas kernel
    choice and backward overlap are tuned TOGETHER when "auto" —
    tuning topology on serial plans and then buckets with the topology
    pinned can miss the joint optimum (e.g. a pipelined hier beating
    serial flat on a uniform fabric), the kernel choice only matters
    through the compute stream the joint search prices, and ready-order
    overlap changes which bucket count pays (more buckets = earlier
    first issue).  Explicit values pass through (``pipeline``: "off" ->
    1, N -> N; ``use_kernel``/``overlap_bwd``: "off"/"on") and pin
    their axis of the search.  Overlap candidates are priced with the
    four-stream schedule on the analytic backward ready times for
    (``batch``, ``seq``) and charged only the exchange time exposed
    beyond the backward pass.
    """
    pipe_auto = pipeline == "auto"
    topo_auto = topology == "auto"
    kern_auto = use_kernel == "auto"
    ob_auto = overlap_bwd == "auto"
    n_buckets = 1
    if not pipe_auto and pipeline not in (None, "off"):
        n_buckets = int(pipeline)
        assert n_buckets >= 1, pipeline
    kernels = use_kernel in ("on", True)
    overlap = overlap_bwd in ("on", True)
    if not topo_auto and not pipe_auto and not kern_auto and not ob_auto:
        return topology, n_buckets, kernels, overlap and n_buckets > 1
    from repro.optim import compressor_has_kernel
    from repro.plan import autotune, get_cluster
    dp_axes, dp_sizes, tp = mesh_axes(mesh)
    _, _, n_inner, n_outer = pod_split(dp_axes, dp_sizes)
    spec = get_cluster(cluster, n_inner=n_inner, n_outer=n_outer,
                       device=device)
    d = _flat_dim(cfg, tp, max(n_inner * n_outer, 1), block_size)
    if topo_auto:
        topos = ("flat", "hier") if n_outer > 1 else ("flat",)
    else:
        # a forced "hier" on a single-pod mesh degrades to flat in the
        # step; price what will actually run
        topos = (topology if (topology != "hier" or n_outer > 1)
                 else "flat",)
    if kern_auto:
        kernel_opts = ((False, True) if compressor_has_kernel(compressor)
                       else (False,))
    else:
        kernel_opts = (kernels,)
    # forced-on still enumerates False so a pinned serial pipeline
    # (overlap needs buckets) keeps a valid candidate to price
    overlap_opts = (False, True) if (ob_auto or overlap) else (False,)
    ready_fn, t_bwd = bwd_ready_fn(cfg, batch, seq, spec.device, tp)
    res = autotune(spec, d, compressors=[compressor],
                   block_sizes=[block_size], topologies=topos,
                   compressor_kwargs=compressor_kwargs,
                   n_buckets_options=(1, 2, 4, 8) if pipe_auto
                   else (n_buckets,),
                   use_kernel_options=kernel_opts,
                   overlap_bwd_options=overlap_opts,
                   t_bwd=t_bwd, ready_times_fn=ready_fn)
    best = res.best
    if verbose:
        print(f"[auto-schedule] cluster={spec.name} "
              f"({n_outer} pod(s) x {n_inner} dp, "
              f"device={spec.device.name}): picked "
              f"{best.topology!r} x {best.n_buckets} bucket(s), "
              f"kernels={'pallas' if best.use_kernel else 'jnp'}, "
              f"overlap-bwd={'on' if best.overlap_bwd else 'off'} "
              f"(t_exchange {best.t_exchange*1e3:.3f} ms, compute "
              f"{best.t_compute*1e3:.3f} ms, "
              f"DCI {best.dci_bytes_per_pod} B/pod)")
        for c in res.table:
            if c.valid:
                print(f"    {c.topology:5s} buckets={c.n_buckets} "
                      f"kernels={'pallas' if c.use_kernel else 'jnp':6s} "
                      f"overlap={'on' if c.overlap_bwd else 'off':3s} "
                      f"t={c.t_exchange*1e3:.3f} ms "
                      f"(compute {c.t_compute*1e3:.3f}) "
                      f"dci={c.dci_bytes_per_pod}")
    out_nb = best.n_buckets if pipe_auto else n_buckets
    out_ob = best.overlap_bwd if ob_auto else overlap
    return (best.topology if topo_auto else topology,
            out_nb, best.use_kernel if kern_auto else kernels,
            out_ob and out_nb > 1)


def resolve_topology(topology: str, cluster: str, cfg, mesh,
                     compressor: str, block_size: int,
                     compressor_kwargs=None, verbose: bool = True) -> str:
    """``topology="auto"`` with serial execution (see resolve_schedule)."""
    return resolve_schedule(topology, "off", cluster, cfg, mesh,
                            compressor, block_size, compressor_kwargs,
                            verbose)[0]


def resolve_pipeline(pipeline, topology: str, cluster: str, cfg, mesh,
                     compressor: str, block_size: int,
                     compressor_kwargs=None, verbose: bool = True) -> int:
    """``pipeline="auto"`` with the topology pinned (see
    resolve_schedule)."""
    return resolve_schedule(topology, pipeline, cluster, cfg, mesh,
                            compressor, block_size, compressor_kwargs,
                            verbose)[1]


def resolve_kernels(use_kernel, topology: str, cluster: str, cfg, mesh,
                    compressor: str, block_size: int,
                    compressor_kwargs=None, verbose: bool = True,
                    device: str = "tpu-v5e") -> bool:
    """``--kernels auto`` with topology/pipeline pinned (see
    resolve_schedule): let the repro.perf compute model decide whether
    the fused Pallas compress path pays on this (cluster, device)."""
    return resolve_schedule(topology, "off", cluster, cfg, mesh,
                            compressor, block_size, compressor_kwargs,
                            verbose, use_kernel=use_kernel,
                            device=device)[2]


def lr_schedule(step: int, base_lr: float, lr_warmup: int,
                decay: float = 0.99, decay_every: int = 520) -> float:
    """The paper's BERT schedule: linear warmup then step decay."""
    if step < lr_warmup:
        return base_lr * (step + 1) / max(lr_warmup, 1)
    return base_lr * (decay ** ((step - lr_warmup) // decay_every))


def run_plans(optim, cfg, mesh, topology: str, block_size: int):
    """The (warmup, compressed) CommPlans THIS run executes — the same
    constructions ``repro.core.comm`` lowers inside the step, rebuilt
    host-side so telemetry can account their per-tier bytes and the
    drift probe can time their ops without retracing the step."""
    from repro.plan import (allreduce_schedule, flat_schedule,
                            hier_schedule, needs_outer_ef)
    dp_axes, dp_sizes, tp = mesh_axes(mesh)
    n_dp = 1
    for s in dp_sizes:
        n_dp *= s
    n_dp = max(n_dp, 1)
    inner_axes, outer_axes, n_inner, n_outer = pod_split(dp_axes, dp_sizes)
    d = _flat_dim(cfg, tp, n_dp, block_size)
    comp = optim.compressor
    warm = allreduce_schedule(d, n_dp, dp_axes,
                              tier="cross" if n_outer > 1 else "intra")
    if topology == "hier" and len(dp_axes) > 1:
        comp_plan = hier_schedule(comp, d, n_inner, n_outer, inner_axes,
                                  outer_axes,
                                  outer_ef=needs_outer_ef(comp))
    else:
        comp_plan = flat_schedule(comp, d, n_dp, dp_axes)
    return warm, comp_plan


def plan_ready_times(cfg, plan_d: int, n_dp: int, block_size: int,
                     n_buckets: int, device, batch: int, seq: int,
                     tp: int = 1):
    """Per-bucket predicted backward ready times for THIS run's bucket
    partition (``None`` unless actually bucketed) — the list the plan
    telemetry, the memory ledger and the profile fold all share so
    predicted schedules agree everywhere."""
    if n_buckets <= 1:
        return None, 0.0
    from repro.pipeline import Bucketer
    ready_fn, t_bwd = bwd_ready_fn(cfg, batch, seq, device, tp)
    bk = Bucketer.for_exchange(plan_d, max(n_dp, 1), block_size,
                               n_buckets)
    offs = []
    off = 0
    for sz in bk.sizes:
        offs.append(off)
        off += sz
    return [float(r) for r in ready_fn(tuple(offs), plan_d)], t_bwd


def emit_plan_telemetry(sink, tracer, optim, cfg, mesh, topology: str,
                        n_buckets: int, block_size: int, cluster: str,
                        device: str, drift_probe: bool = False,
                        telemetry_dir: Optional[str] = None,
                        overlap_bwd: bool = False, batch: int = 8,
                        seq: int = 128) -> None:
    """Emit the run's ``plan`` events (per-tier HLO bytes + predicted
    α-β times of the executed CommPlans — under ``overlap_bwd`` also
    the per-bucket backward ready times the four-stream schedule is
    held to) and, with ``drift_probe``, time each compressed-exchange
    collective in isolation on the real mesh and run the
    predicted-vs-measured drift monitor over the samples — writing a
    ``ClusterSpec.from_measured`` recalibration JSON into the telemetry
    dir when drift exceeds the threshold."""
    from repro.plan import cross_pod_bytes, get_cluster, plan_time
    dp_axes, dp_sizes, tp = mesh_axes(mesh)
    _, _, n_inner, n_outer = pod_split(dp_axes, dp_sizes)
    spec = get_cluster(cluster, n_inner=n_inner, n_outer=n_outer,
                       device=device)
    warm, comp_plan = run_plans(optim, cfg, mesh, topology, block_size)
    for stage, p, nb in (("warmup", warm, 1),
                         ("compressed", comp_plan, n_buckets)):
        extra = {}
        if overlap_bwd and stage == "compressed":
            ready, t_bwd = plan_ready_times(
                cfg, p.d, n_inner * n_outer, block_size, nb,
                spec.device, batch, seq, tp)
            if ready is not None:
                extra = {"overlap_bwd": True, "t_bwd": float(t_bwd),
                         "ready_times": ready}
        sink.emit("plan", name=p.name, stage=stage, d=p.d,
                  intra_hlo_bytes=float(p.hlo_bytes("intra")),
                  cross_hlo_bytes=float(p.hlo_bytes("cross")),
                  n_buckets=nb,
                  wire_send_bytes=float(p.wire_send_bytes()),
                  dci_bytes_per_pod=float(cross_pod_bytes(p, spec)),
                  t_predicted=float(plan_time(p, spec)), **extra)
    if not drift_probe:
        return
    from repro.obs import DriftMonitor, probe_plan
    mon = DriftMonitor(spec)
    with tracer.span("drift.probe"):
        samples = probe_plan(comp_plan, mesh)
    for s in samples:
        mon.observe(s.op_kind, s.tier, s.n, s.payload_bytes, s.seconds)
        sink.emit("span", name=f"probe::{s.op_kind}@{s.tier}",
                  stream=s.tier, dur=s.seconds, op_kind=s.op_kind,
                  tier=s.tier, payload_bytes=s.payload_bytes)
    recal_path = (os.path.join(telemetry_dir, "recalibration.json")
                  if telemetry_dir else None)
    for etype, fields in mon.events(emit_recal_path=recal_path):
        sink.emit(etype, **fields)
    for pair in mon.drifting:
        print(f"[drift] {pair[0]}@{pair[1]} outside the cost model's "
              f"{mon.threshold:.0%} band"
              + (f" — recalibration written to {recal_path}"
                 if recal_path else ""))


def ready_order_rows(fold_intervals, predicted_intervals, ready):
    """The measured-vs-predicted ready-order table: one row per bucket
    with its predicted backward ready time and the first collective
    start on each side — did the run really issue buckets in ready
    order, and did they start when the four-stream schedule said they
    could?"""
    def first_starts(intervals):
        first = {}
        for iv in intervals:
            b = iv.get("bucket")
            if b is None or iv.get("phase") == "bwd":
                continue
            t = float(iv["t_start"])
            if b not in first or t < first[b]:
                first[b] = t
        return first
    meas, pred = first_starts(fold_intervals), \
        first_starts(predicted_intervals)
    rows = []
    for b in sorted(set(meas) | set(pred)):
        rows.append({"bucket": int(b),
                     "ready_predicted": (float(ready[b])
                                         if ready and b < len(ready)
                                         else 0.0),
                     "first_start_predicted": pred.get(b, 0.0),
                     "first_start_measured": meas.get(b, 0.0)})
    return rows


def fold_profile_window(profile_dir: str, hlo_texts, n_steps: int,
                        optim, cfg, mesh, topology: str, n_buckets: int,
                        block_size: int, cluster: str, device: str,
                        stage: str = "compressed",
                        overlap_bwd: bool = False, batch: int = 8,
                        seq: int = 128):
    """Fold the captured profiler trace onto the plan grid and build
    the ``profile`` event fields (:func:`repro.obs.profile.attribution`)
    — measured cells joined via the compiled-HLO op_name bridge, the
    overlap audit diffed against the predicted ``pipeline_breakdown``
    intervals of THIS run's lowered exchange (the FOUR-stream schedule
    when ``overlap_bwd``: per-bucket backward ready times gate the
    prediction exactly as they gate the executed issue order), and
    bytes/step from the executed plan's HLO accounting.  Under overlap
    the fields also carry the per-bucket ``ready_order`` table."""
    from repro.obs import profile as prof
    from repro.pipeline import Bucketer, lower_to_pipelined
    from repro.plan import get_cluster, pipeline_breakdown
    dp_axes, dp_sizes, tp = mesh_axes(mesh)
    _, _, n_inner, n_outer = pod_split(dp_axes, dp_sizes)
    spec = get_cluster(cluster, n_inner=n_inner, n_outer=n_outer,
                       device=device)
    warm, comp_plan = run_plans(optim, cfg, mesh, topology, block_size)
    plan = comp_plan if stage == "compressed" else warm
    comp = optim.compressor if stage == "compressed" else None
    nb = n_buckets if stage == "compressed" else 1
    bucketer = Bucketer.for_exchange(plan.d, max(n_inner * n_outer, 1),
                                     block_size, nb)
    ready = None
    if overlap_bwd and stage == "compressed":
        ready, _ = plan_ready_times(cfg, plan.d, n_inner * n_outer,
                                    block_size, bucketer.n_buckets,
                                    spec.device, batch, seq, tp)
    predicted = pipeline_breakdown(
        lower_to_pipelined(plan, comp, bucketer), spec, ready=ready)
    fold = prof.fold_profile(profile_dir, hlo_texts)
    fields = prof.attribution(fold, n_steps=n_steps, predicted=predicted,
                              bytes_per_step=float(plan.hlo_bytes()),
                              source="launch.train")
    if ready is not None:
        fields["ready_order"] = ready_order_rows(
            fold["intervals"], predicted["intervals"], ready)
    return fields


def build_memory_ledger(optim, cfg, mesh, topology: str, n_buckets: int,
                        block_size: int, cluster: str, device: str,
                        layout: str, batch: int, seq: int,
                        overlap_bwd: bool = False):
    """The predicted per-rank :class:`~repro.obs.mem.MemoryLedger` of
    THIS run: the same host-side plan/spec reconstruction the plan
    telemetry uses, priced against the ``--device`` preset's capacity.
    Under ``overlap_bwd`` the wire watermark is taken over the
    four-stream (ready-gated) schedule."""
    from repro.obs.mem import capacity_of, predict_ledger
    from repro.plan import get_cluster
    dp_axes, dp_sizes, tp = mesh_axes(mesh)
    _, _, n_inner, n_outer = pod_split(dp_axes, dp_sizes)
    spec = get_cluster(cluster, n_inner=n_inner, n_outer=n_outer,
                       device=device)
    _, comp_plan = run_plans(optim, cfg, mesh, topology, block_size)
    ready = None
    if overlap_bwd:
        ready, _ = plan_ready_times(cfg, comp_plan.d, n_inner * n_outer,
                                    block_size, n_buckets, spec.device,
                                    batch, seq, tp)
    return predict_ledger(
        cfg, mesh, optim=optim, layout=layout, topology=topology,
        block=block_size, n_buckets=n_buckets, batch_global=batch,
        seq=seq, plan=comp_plan, spec=spec,
        capacity_bytes=capacity_of(spec.device), ready=ready)


def emit_memory_attribution(steps_fns, sample_args, sink, ledger,
                            telemetry_dir: Optional[str] = None):
    """Post-run measured side of the ledger: one ``memory`` event
    (``kind="compiled"``) per executed step program — temp+output bytes
    attributed onto the predicted categories with an explicit residual
    — plus ``memory_ledger.json`` in the telemetry dir.  Returns the
    largest program's :class:`~repro.obs.mem.CompiledMemory` (the
    ``mem_compiled_*`` perf-ledger cells)."""
    from repro.obs.mem import attribution_event_fields, compiled_memory
    params, opt, batch_data, lr = sample_args
    biggest, dump = None, []
    for (stage, sync), fn in steps_fns.items():
        name = f"{stage}{'' if sync else '_local'}"
        cm = compiled_memory(
            fn.build(batch_data).lower(params, opt, batch_data, lr)
            .compile(), program=name)
        if cm is None:
            continue
        fields = attribution_event_fields(ledger, cm)
        sink.emit("memory", **fields)
        dump.append(fields)
        if biggest is None or cm.per_device_bytes > biggest.per_device_bytes:
            biggest = cm
    if telemetry_dir:
        path = os.path.join(telemetry_dir, "memory_ledger.json")
        with open(path, "w") as f:
            json.dump({"predicted": ledger.summary(),
                       "compiled": dump}, f, indent=2)
    return biggest


def emit_profile_ledger(profile_dir: str, steps_fns, sample_args, sink,
                        optim, cfg, mesh, topology: str, n_buckets: int,
                        block_size: int, cluster: str, device: str,
                        n_steps: int, stage: str, bench: Optional[str],
                        arch: str, mesh_shape, use_kernel: bool,
                        extra_metrics: Optional[dict] = None,
                        overlap_bwd: bool = False, batch: int = 8,
                        seq: int = 128) -> dict:
    """Post-run profile pipeline: compiled-HLO texts of every executed
    step (the op_name bridge the trace join needs), the grid fold +
    attribution (``fold_profile_window``), a ``profile`` telemetry
    event, and the ``BENCH_<name>.json`` perf-ledger record."""
    from repro.obs.bench import bench_record, write_ledger
    params, opt, batch_data, lr = sample_args
    hlo_texts = []
    for fn in steps_fns.values():
        hlo_texts.append(fn.build(batch_data)
                         .lower(params, opt, batch_data, lr)
                         .compile().as_text())
    fields = fold_profile_window(profile_dir, hlo_texts, n_steps, optim,
                                 cfg, mesh, topology, n_buckets,
                                 block_size, cluster, device,
                                 stage=stage, overlap_bwd=overlap_bwd,
                                 batch=batch, seq=seq)
    sink.emit("profile", **fields)
    metrics = {k: float(fields[k]) for k in
               ("s_per_step", "comm_fraction", "overlap_efficiency",
                "exposed_comm_s", "roofline_fraction", "t_window",
                "t_attributed", "t_residual", "bytes_per_step")
               if k in fields}
    metrics["n_cells"] = int(fields["n_cells"])
    if fields.get("t_window"):
        metrics["attributed_fraction"] = (fields["t_attributed"]
                                          / fields["t_window"])
    if extra_metrics:
        metrics.update({k: float(v) for k, v in extra_metrics.items()})
    name = bench or "train"
    rec = bench_record(name, config=arch,
                       mesh=[int(s) for s in mesh_shape],
                       pipeline=int(n_buckets), kernels=bool(use_kernel),
                       metrics=metrics)
    ledger_path = os.path.join(profile_dir, f"BENCH_{name}.json")
    write_ledger(ledger_path, [rec],
                 meta={"source": "launch.train", "cluster": cluster,
                       "device": device, "arch": arch, "stage": stage})
    print(f"profile: {fields['n_cells']} grid cells, "
          f"{fields['t_attributed']:.3f}s attributed + "
          f"{fields['t_residual']:.3f}s residual of "
          f"{fields['t_window']:.3f}s window "
          f"({n_steps} steps); ledger -> {ledger_path}")
    return fields


def run(arch: str, steps: int, batch: int, seq: int, mesh_shape,
        base_lr: float = 1e-3, lr_warmup: int = 100,
        warmup_steps: Optional[int] = None, block_size: int = 4096,
        auto_warmup: bool = False, seed: int = 0, log_every: int = 10,
        ckpt: Optional[str] = None, resume: Optional[str] = None,
        stage_override: Optional[str] = None, log_file: Optional[str] = None,
        recipe: str = "onebit_adam", optimizer: Optional[str] = None,
        compressor: Optional[str] = None, topology: Optional[str] = None,
        cluster: str = "ethernet-10g", pipeline=None, kernels=None,
        overlap_bwd: str = "off",
        device: Optional[str] = None, telemetry: Optional[str] = None,
        drift_probe: bool = False, profile: Optional[str] = None,
        profile_steps: int = 4, bench: Optional[str] = None,
        audit: str = "off", audit_every: int = 10,
        memory: str = "off", programs: Optional[dict] = None):
    """Train ``arch`` for ``steps`` steps; returns ``(params, opt,
    history)``.  ``programs``, when given, is filled with the jitted step
    programs the run used, keyed ``(stage, sync)`` (their ``build`` and
    ``lower`` give the compiled HLO)."""
    assert audit in AUDIT_MODES, audit
    assert memory in MEMORY_MODES, memory
    device = resolve_device(device)
    cfg = get_config(arch)
    axes = ("data", "model")[:len(mesh_shape)] if len(mesh_shape) <= 2 else \
        ("pod", "data", "model")
    mesh = make_mesh(mesh_shape, axes)
    dp_axes, dp_sizes, tp = mesh_axes(mesh)
    n_dp = 1
    for s in dp_sizes:
        n_dp *= s

    shape = InputShape("custom", seq, batch, "train")
    stream = SyntheticStream(cfg, shape, seed=seed)

    # --- resolve the recipe -> TrainStepConfig -----------------------------
    spec = get_optim_recipe(recipe)
    if optimizer:
        spec = dataclasses.replace(spec, optimizer=optimizer)
    if compressor:
        spec = dataclasses.replace(spec, compressor=compressor)
    spec = dataclasses.replace(spec, block_size=block_size)
    if topology is None:
        topology = spec.topology
    if stage_override == "compressed_hier":
        topology, stage_override = "hier", "compressed"
    if pipeline is None:
        pipeline = spec.pipeline
    if kernels is None:
        kernels = spec.use_kernel
    topology, n_buckets, use_kernel, overlap_on = resolve_schedule(
        topology, pipeline, cluster, cfg, mesh, spec.compressor,
        spec.block_size, spec.compressor_kwargs, use_kernel=kernels,
        device=device, overlap_bwd=overlap_bwd, batch=batch, seq=seq)
    def effective_buckets(nb: int) -> int:
        """The bucket count the executor will actually use on THIS run's
        padded flat dimension (Bucketer clamps to the alignment-unit
        count) — the quantity that fixes the EF-slot layout."""
        from repro.pipeline import Bucketer
        return Bucketer.for_exchange(
            _flat_dim(cfg, tp, max(n_dp, 1), block_size), max(n_dp, 1),
            spec.block_size, nb).n_buckets

    if n_buckets > 1:
        # store/compare the EFFECTIVE (clamped) count: an explicit
        # --pipeline N above the alignment-unit count clamps inside the
        # executor anyway
        n_buckets = effective_buckets(n_buckets)
    base_tsc = TrainStepConfig(
        optimizer=spec.optimizer, compressor=spec.compressor,
        block_size=spec.block_size, opt_kwargs=spec.optimizer_kwargs,
        comp_kwargs=spec.compressor_kwargs, topology=topology,
        pipeline=n_buckets, use_kernel=bool(use_kernel),
        overlap_bwd=bool(overlap_on))
    optim = base_tsc.build_optimizer()
    layout = "local" if optim.may_skip_sync else "replicated"
    base_tsc = dataclasses.replace(base_tsc, layout=layout)

    def place(tree, specs, put=jax.device_put):
        """Lay a state tree out as the step expects it, so it is spread
        over the mesh before the first step and not left on device 0."""
        return jax.tree.map(
            lambda p, a: put(a, NamedSharding(mesh, p)),
            specs, tree, is_leaf=lambda x: isinstance(x, P))

    param_specs = T.param_specs(cfg, base_tsc.model_axis, tp)
    opt_specs = train_state_specs(mesh, base_tsc.model_axis, layout, optim,
                                  topology)
    key = jax.random.PRNGKey(seed)
    params = T.init_params(cfg, key, tp=tp)
    # the zero optimizer state is made in place on every device: built
    # whole on device 0 first, its per-rank slots (dp copies of the flat
    # model) overfill that device at bert-large width and dp 4
    opt = place(init_train_state(cfg, mesh, block=block_size, layout=layout,
                                 topology=topology, optimizer=optim,
                                 abstract=True), opt_specs,
                lambda sd, sharding: jnp.zeros(sd.shape, sd.dtype,
                                               device=sharding))
    # the slot-registry context every checkpoint conversion derives from:
    # EF slots are SAVED in the canonical (serial) global-element keying
    # and scattered into this run's bucket partition on load, so
    # checkpoints are portable across --pipeline off/N/M by construction
    slots = train_slots(mesh, base_tsc.model_axis, layout, topology, optim)
    state_ctx = state_layout_ctx(cfg, mesh, block=spec.block_size,
                                 topology=topology)
    start_step = 0
    if resume:
        # slot-diff-driven migration (repro.state.checkpoint): slots the
        # archive predates resume from their zeros template, named from
        # the registry; bucket-keyed EF slots re-key to this run's
        # bucket partition
        (params, opt), start_step = load_train_state(
            resume, params, opt, slots=slots, ctx=state_ctx,
            n_buckets=n_buckets, block=spec.block_size)
        print(f"resumed from {resume} at step {start_step}")
    params, opt = place(params, param_specs), place(opt, opt_specs)

    # each step donates params and optimizer state: without it the
    # program holds both twice, as inputs and as outputs
    steps_fns = {} if programs is None else programs

    def get_step(stage: str, sync: bool = True):
        key = (stage, sync)
        if key not in steps_fns:
            steps_fns[key] = make_train_step(
                cfg, mesh,
                dataclasses.replace(base_tsc, stage=stage, sync=sync))
        return steps_fns[key]

    # manual T_w when given (and not auto); otherwise the paper's Sec. 7.1
    # variance-ratio rule
    manual = warmup_steps is not None and not auto_warmup \
        and spec.switch_mode != "auto"
    switch = WarmupSwitch(
        mode="steps" if manual else "auto",
        warmup_steps=warmup_steps if warmup_steps is not None else 0,
        b2=optim.b2, threshold=spec.var_freeze_threshold,
        lr_warmup_steps=lr_warmup)

    # --- telemetry (repro.obs; every piece a no-op when --telemetry is
    # off: NullSink swallows events, tracing stays disabled, and the
    # metric buffer only ever parks async device arrays) ------------------
    sink = as_sink(telemetry)
    tracer = Tracer(sink)
    # --profile needs the op_scope names in the compiled HLO even when
    # --telemetry is off (scopes are metadata-only; neutrality is pinned)
    set_tracing(sink.enabled or profile is not None)
    if sink.enabled:
        sink.emit("run_meta", optimizer=spec.optimizer,
                  compressor=spec.compressor, topology=topology,
                  n_buckets=n_buckets, arch=arch, layout=layout,
                  use_kernel=bool(use_kernel),
                  overlap_bwd=bool(overlap_on),
                  mesh=[int(s) for s in mesh_shape], steps=steps,
                  block_size=spec.block_size, cluster=cluster,
                  device=device, seed=seed, recipe=recipe,
                  audit=audit, audit_every=int(audit_every),
                  source="launch.train")
        emit_plan_telemetry(sink, tracer, optim, cfg, mesh, topology,
                            n_buckets, spec.block_size, cluster, device,
                            drift_probe=drift_probe,
                            telemetry_dir=telemetry,
                            overlap_bwd=bool(overlap_on), batch=batch,
                            seq=seq)

    # --- per-rank HBM ledger (repro.obs.mem; host-side only — the train
    # step's compiled program is untouched) -------------------------------
    memory_on = memory == "on" and sink.enabled
    mem_ledger, mem_sampler = None, None
    if memory_on:
        from repro.obs.mem import LiveSampler
        mem_ledger = build_memory_ledger(
            optim, cfg, mesh, topology, n_buckets, spec.block_size,
            cluster, device, layout, batch, seq,
            overlap_bwd=bool(overlap_on))
        sink.emit("memory", **mem_ledger.event_fields())
        mem_sampler = LiveSampler()

    def on_warning(wstep: int, detail: str) -> None:
        print(f"[warn] step {wstep}: {detail}")
        sink.emit("warning", what="non-finite v_l1", step=wstep,
                  detail=detail)

    def on_bad_stat(wstep: int, key: str, value: float) -> None:
        print(f"[warn] step {wstep}: non-finite {key} ({value}) dropped "
              f"from the step record")
        sink.emit("warning", what=f"non-finite {key}", step=wstep,
                  detail=f"{key}={value} rejected by FiniteGuard")

    was_compressed = False
    prev_sync = True
    comp_step = 0  # compression-stage step index (drives sync_due)
    history = []
    mbuf = MetricBuffer()
    pending = {}   # step -> (stage, sync), until the batched drain

    # --- per-segment fidelity audit (repro.obs.audit) --------------------
    audit_on = audit == "on"
    guard = FiniteGuard()          # non-finite stats: drop, count, warn
    health = HealthMonitor()
    abuf = MetricBuffer() if audit_on else None
    audit_probe = None             # built lazily at the first audited step
    shadow_v = None                # shadow variance EMA, seeded from live v
    audit_idx = 0                  # compression-stage steps seen

    def _emit_audit(s: int, fid: dict) -> None:
        """One audited step: host extrema + the fidelity event, then the
        HealthMonitor's verdicts."""
        def finite(xs):
            return [x for x in xs if math.isfinite(x)] \
                if isinstance(xs, list) else []
        drift, cos, sign = (finite(fid.get(k)) for k in
                            ("v_drift", "cos_sim", "sign_agree"))
        extra = {}
        if drift:
            extra["v_drift_max"] = max(drift)
            extra["v_drift_min"] = min(drift)
        if cos:
            extra["cos_sim_min"] = min(cos)
        if sign:
            extra["sign_agree_min"] = min(sign)
        n_seg = fid.get("cos_sim")
        n_seg = len(n_seg) if isinstance(n_seg, list) else 1
        sink.emit("fidelity", step=s, n_segments=n_seg,
                  stage="compressed", source="launch.train",
                  **fid, **extra)
        hfields, warns = health.observe(s, fid)
        sink.emit("health", **hfields)
        for w in warns:
            print(f"[health] step {s}: {w['what']} — {w['detail']}")
            sink.emit("warning", **w)

    def drain():
        """Materialise every parked step's metrics in ONE device_get and
        fold them into history + step events, in step order (non-finite
        optimizer stats are dropped + warned, not recorded); then fold
        the audited steps' fidelity stats into fidelity/health events."""
        for s, m in mbuf.drain():
            st_stage, st_sync = pending.pop(s)
            m = guard.filter(s, m, on_reject=on_bad_stat)
            rec = {"step": s, "stage": st_stage, "sync": st_sync,
                   "optimizer": optim.name, **m}
            history.append(rec)
            sink.emit("step", **rec)
            health.observe_loss(s, m.get("loss"))
        if abuf is not None:
            for s, fid in abuf.drain():
                _emit_audit(s, fid)

    t_start = time.time()
    win_t0, win_step0 = t_start, start_step
    # --profile: trace the LAST profile_steps steps (steady state —
    # warmup compiles and stage switches are behind us by then)
    prof_start = max(start_step, steps - max(profile_steps, 1)) \
        if profile else None
    prof_span = None
    try:
        for step in range(start_step, steps):
            if prof_start is not None and step == prof_start \
                    and prof_span is None:
                # drain outstanding async work so the traced window
                # holds exactly the profiled steps, then open the
                # host-span bracket the fold uses as its wall clock
                jax.block_until_ready(jax.tree_util.tree_leaves(params))
                os.makedirs(profile, exist_ok=True)
                jax.profiler.start_trace(profile,
                                         create_perfetto_trace=True)
                prof_span = tracer.span("profile.window",
                                        n=steps - prof_start, step=step)
                prof_span.__enter__()
            if stage_override:
                stage, sync = stage_override, True
            else:
                compressed = switch.compressed(step)
                if compressed and not was_compressed:
                    if switch.mode == "auto":
                        print(f"[auto-warmup] variance frozen at step "
                              f"{step} (ratio {switch.ratio:.4f})"
                              if switch.ratio is not None else
                              f"[auto-warmup] variance frozen at step "
                              f"{step}")
                    ratio = switch.ratio if switch.mode == "auto" else None
                    sink.emit("transition", step=step, kind="stage",
                              frm="warmup", to="compressed",
                              mode=switch.mode,
                              **({"ratio": float(ratio)}
                                 if ratio is not None else {}))
                    was_compressed = True
                stage = "compressed" if compressed else "warmup"
                sync = optim.sync_due(comp_step) if compressed else True
                if compressed:
                    comp_step += 1
            batch_data = stream.batch_at(step)
            lr = jnp.float32(lr_schedule(step, base_lr, lr_warmup))
            if audit_on and stage == "compressed":
                if audit_idx % max(audit_every, 1) == 0:
                    if audit_probe is None:
                        # its OWN jitted program — the train step's
                        # compiled HLO is untouched (neutrality pinned
                        # in tests/test_audit.py)
                        audit_probe = make_audit_probe(
                            cfg, mesh, dataclasses.replace(
                                base_tsc, stage="compressed"))
                        # seed the shadow EMA with its own copy: the
                        # step donates opt["v"]
                        shadow_v = jnp.copy(opt["v"])
                    # probe BEFORE the step: audits exactly the
                    # (params, state, batch) this step consumes
                    shadow_v, astats = audit_probe(params, opt,
                                                   shadow_v, batch_data)
                    abuf.push(step, astats)
                audit_idx += 1
            fresh = (stage, sync) not in steps_fns
            step_fn = get_step(stage, sync)
            t_call = time.time()
            params, opt, metrics = step_fn(params, opt, batch_data, lr)
            if fresh:
                # dispatch is asynchronous: a program's first call returns
                # once it is traced, lowered and compiled
                dt = time.time() - t_call
                name = f"{stage}{'' if sync else '_local'}"
                print(f"compiled the {name} step in {dt:.1f}s")
                sink.emit("span", name=f"compile.{name}", stream="host",
                          t_start=t_call, dur=dt, n=1, step=step)
            # park the device metrics — async dispatch, no host sync;
            # only consumers that need host floats THIS step fetch them
            # (one batched transfer), everything else waits for a drain
            mbuf.push(step, metrics)
            pending[step] = (stage, sync)
            if sync != prev_sync:
                sink.emit("transition", step=step, kind="sync",
                          frm="sync" if prev_sync else "local",
                          to="sync" if sync else "local")
                prev_sync = sync
            if switch.mode == "auto" and not stage_override:
                # the variance-ratio rule needs v_l1 on the host every
                # step: one batched fetch (vs one sync per scalar before)
                switch.observe(step, mbuf.host(step),
                               on_warning=on_warning)
            else:
                switch.observe(step, {})
            if step % log_every == 0 or step == steps - 1:
                rec = mbuf.host(step)
                dt = time.time() - t_start
                print(f"step {step:5d} "
                      f"[{stage:10s}{'' if sync else ' local'}] "
                      f"loss {rec['loss']:.4f} "
                      f"acc {rec['acc']:.3f} v_l1 {rec['v_l1']:.3e} "
                      f"({dt:.1f}s)")
                # the window span ends at the host fetch above (a real
                # sync point), so dur/n is an honest measured s/step
                now = time.time()
                sink.emit("span", name="train.window", stream="host",
                          t_start=win_t0, dur=now - win_t0,
                          n=step - win_step0 + 1, step=step)
                win_t0, win_step0 = now, step + 1
                drain()
                if mem_sampler is not None:
                    mfields = mem_sampler.sample(step)
                    if mfields:
                        sink.emit("memory", **mfields)
                        hfields, warns = health.observe_memory(
                            step, mfields["bytes_in_use"],
                            mfields.get("peak_bytes_in_use"),
                            capacity_bytes=mem_ledger.capacity_bytes)
                        sink.emit("health", **hfields)
                        for w in warns:
                            print(f"[health] step {step}: {w['what']} — "
                                  f"{w['detail']}")
                            sink.emit("warning", **w)
            if ckpt and (step + 1) % 100 == 0:
                with tracer.span("checkpoint.save", step=step):
                    save_train_state(ckpt, params, opt, step + 1,
                                     slots=slots, ctx=state_ctx,
                                     n_buckets=n_buckets,
                                     block=spec.block_size)
        drain()
        mem_extra = None
        if memory_on:
            from repro.obs.mem import mem_metrics
            biggest = emit_memory_attribution(
                steps_fns, (params, opt, batch_data, lr), sink,
                mem_ledger, telemetry_dir=telemetry)
            mem_extra = mem_metrics(
                mem_ledger, compiled=biggest,
                live_peak=mem_sampler.peak_bytes if mem_sampler else None)
        if prof_span is not None:
            # the drain above materialised the window's metrics — a real
            # host sync — so the span's wall clock is honest
            prof_span.__exit__(None, None, None)
            prof_span = None
            jax.profiler.stop_trace()
            emit_profile_ledger(
                profile, steps_fns, (params, opt, batch_data, lr),
                sink, optim, cfg, mesh, topology, n_buckets,
                spec.block_size, cluster, device,
                n_steps=steps - prof_start, stage=stage,
                bench=bench, arch=arch, mesh_shape=mesh_shape,
                use_kernel=bool(use_kernel),
                extra_metrics=mem_extra,
                overlap_bwd=bool(overlap_on), batch=batch, seq=seq)
        if ckpt:
            with tracer.span("checkpoint.save", step=steps):
                save_train_state(ckpt, params, opt, steps, slots=slots,
                                 ctx=state_ctx, n_buckets=n_buckets,
                                 block=spec.block_size)
    finally:
        if prof_span is not None:    # abnormal exit mid-window
            prof_span.__exit__(None, None, None)
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
        set_tracing(False)
        sink.close()
    if sink.enabled:
        print(f"telemetry: {sink.n_events} events -> {sink.path}")
    if audit_on and health.n_checked:
        print(f"audit: {health.n_checked} health check(s), "
              f"{health.n_failed} failed"
              + (f"; {guard.n_rejected} non-finite stat(s) dropped"
                 if guard.n_rejected else ""))
    if log_file:
        with open(log_file, "w") as f:
            json.dump(history, f)
    return params, opt, history


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="bert-base-smoke")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1",
                    help="e.g. 1x1, 4x2 (dp x tp), 2x4x2 (pod x dp x tp)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--lr-warmup", type=int, default=20)
    ap.add_argument("--warmup-steps", type=int, default=None,
                    help="compressed-optimizer warmup steps (manual T_w)")
    ap.add_argument("--auto-warmup", action="store_true",
                    help="use the variance-ratio rule to pick T_w")
    ap.add_argument("--recipe", default="onebit_adam",
                    choices=list_optim_recipes(),
                    help="named optimizer recipe (configs.base)")
    ap.add_argument("--optimizer", default=None,
                    choices=[None] + list_optimizers(),
                    help="override the recipe's optimizer")
    ap.add_argument("--compressor", default=None,
                    choices=[None] + list_compressors(),
                    help="override the recipe's compressor")
    ap.add_argument("--topology", default=None,
                    choices=[None, "flat", "hier", "auto"],
                    help="hier = two-level cross-pod compressed allreduce; "
                         "auto = repro.plan tuner picks per --cluster; "
                         "default = the recipe's topology")
    ap.add_argument("--cluster", default="ethernet-10g",
                    help="cluster preset for --topology/--pipeline auto "
                         "(repro.plan.list_clusters()), or "
                         "measured:<calibration.json> — a comm_sweep fit "
                         "or a --drift-probe recalibration")
    ap.add_argument("--pipeline", default=None,
                    help="bucketed pipelined exchange: off, auto, or a "
                         "bucket count N (>1 overlaps cross-pod legs "
                         "with intra-pod work; default = the recipe's)")
    ap.add_argument("--kernels", default=None,
                    choices=[None, "off", "on", "auto"],
                    help="fused Pallas compress path (kernels/onebit): "
                         "on/off, or auto = the repro.perf compute model "
                         "decides per --cluster/--device; default = the "
                         "recipe's")
    ap.add_argument("--overlap-bwd", default="off",
                    choices=["off", "on", "auto"],
                    help="backward-overlap exchange: feed the bucketed "
                         "pipeline per-bucket gradient parts in backprop "
                         "ready order (trailing layers first) so the "
                         "compressed exchange starts under the backward "
                         "pass; needs --pipeline > 1, bitwise identical "
                         "losses; auto = the four-stream cost model "
                         "decides per --cluster/--device")
    ap.add_argument("--device", default=None,
                    help="device preset for the compute-stream pricing "
                         "(repro.perf.list_devices()), used by "
                         "--topology/--pipeline/--kernels auto; on a TPU "
                         "it follows the chip found (naming another chip "
                         "is an error), elsewhere it defaults to tpu-v5e")
    ap.add_argument("--block-size", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", default=None)
    ap.add_argument("--stage", default=None,
                    choices=[None, "warmup", "compressed", "compressed_hier"])
    ap.add_argument("--log-file", default=None)
    ap.add_argument("--telemetry", default=None, metavar="DIR",
                    help="write structured run telemetry (repro.obs) to "
                         "DIR/telemetry.jsonl: typed step/transition/"
                         "plan/span events plus executor trace spans; "
                         "summarize with python -m repro.obs.report")
    ap.add_argument("--log-every", type=int, default=10,
                    help="print + drain buffered metrics every N steps")
    ap.add_argument("--audit", default="off", choices=["off", "on"],
                    help="per-segment compression-fidelity & frozen-"
                         "variance audit (repro.obs.audit): a separate "
                         "jitted probe every --audit-every compression-"
                         "stage steps emits fidelity events + host "
                         "health verdicts; telemetry-neutral for the "
                         "train step itself")
    ap.add_argument("--audit-every", type=int, default=10,
                    help="audit every N-th compression-stage step")
    ap.add_argument("--memory", default="off", choices=["off", "on"],
                    help="per-rank HBM ledger (repro.obs.mem): a "
                         "predicted memory event (slot registry + wire "
                         "watermark + activation estimate vs --device "
                         "capacity), live samples per log window with "
                         "mem_headroom/mem_growth health verdicts, and "
                         "post-run compiled-program attribution; "
                         "host-side only, telemetry-neutral")
    ap.add_argument("--drift-probe", action="store_true",
                    help="with --telemetry: time each compressed-"
                         "exchange collective on the real mesh before "
                         "training and run the cost-model drift monitor "
                         "(writes recalibration.json on drift)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the last "
                         "--profile-steps steps into DIR, fold it onto "
                         "the plan grid (repro.obs.profile: measured "
                         "per-(plan,bucket,stage,tier) cells + overlap "
                         "audit) and write DIR/BENCH_<name>.json")
    ap.add_argument("--profile-steps", type=int, default=4,
                    help="steady-state steps the --profile trace covers")
    ap.add_argument("--bench", default=None, metavar="NAME",
                    help="perf-ledger name for --profile "
                         "(BENCH_<NAME>.json; default: train)")
    args = ap.parse_args(argv)
    use_compile_cache()
    mesh_shape = tuple(int(x) for x in args.mesh.split("x"))
    run(args.arch, args.steps, args.batch, args.seq, mesh_shape,
        base_lr=args.lr, lr_warmup=args.lr_warmup,
        warmup_steps=args.warmup_steps, auto_warmup=args.auto_warmup,
        block_size=args.block_size, seed=args.seed, ckpt=args.ckpt,
        resume=args.resume, stage_override=args.stage,
        log_file=args.log_file, recipe=args.recipe,
        optimizer=args.optimizer, compressor=args.compressor,
        topology=args.topology, cluster=args.cluster,
        pipeline=args.pipeline, kernels=args.kernels,
        overlap_bwd=args.overlap_bwd,
        device=args.device, telemetry=args.telemetry,
        drift_probe=args.drift_probe, log_every=args.log_every,
        profile=args.profile, profile_steps=args.profile_steps,
        bench=args.bench, audit=args.audit,
        audit_every=args.audit_every, memory=args.memory)


if __name__ == "__main__":
    main()
