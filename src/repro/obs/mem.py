"""Per-rank HBM ledger: predicted memory model + measured attribution.

1-bit Adam's whole premise trades optimizer-state MEMORY (the frozen
``v``, one EF residual slot per lossy hop) for communication, and the
family variants keep adding state — yet until this module the repo
priced time and wire bytes exhaustively while memory was invisible.
This is the HBM analogue of the PR 6–8 predict→measure→gate loop for
step time; both sides per rank, itemized:

**Predicted** — :func:`predict_ledger` builds a :class:`MemoryLedger`
from the same declarations everything else derives from:

  * ``params`` / ``grads`` — exact per-model-rank parameter bytes from
    :mod:`repro.analysis.model_math` (the ``eval_shape`` leaf walk the
    flat optimizer dimension uses), plus the padded flat f32 gradient
    exchange buffer;
  * ``opt_state`` — the PR 5 ``SlotSpec`` registry priced through
    :func:`repro.state.state_bytes` for this run's (optimizer, layout,
    topology) — pinned EXACTLY against ``init_train_state`` in
    tests/test_mem.py;
  * ``wire`` — per-bucket staging buffers with a LIVE-WATERMARK over
    ``pipeline_breakdown``'s scheduled intervals
    (:func:`repro.plan.wire_watermark`): the peak concurrent buckets in
    flight, not the sum over buckets;
  * ``activations`` — the fwd+bwd live-set estimate
    (:func:`repro.analysis.model_math.activation_bytes`).

**Measured** — :func:`compiled_memory` is the ONE reader of
``compiled.memory_analysis()`` (``launch/dryrun.py`` and
``analysis/roofline.py`` route through it instead of parsing the stats
ad-hoc); :func:`attribute_compiled` maps a program's temp+output bytes
back onto the ledger categories with an explicit residual
(attributed + residual ≡ compiled total).  :class:`LiveSampler` reads
``device.memory_stats()`` (``bytes_in_use`` / ``peak_bytes_in_use``)
once per log window — host-process RSS via psutil on backends (CPU)
that expose no allocator stats.

Everything folds into the ``memory`` event kind
(:mod:`repro.obs.events`), the report's memory section + ``--diff``
rows, ``mem_*`` BENCH metrics (structural in
``results/bench_compare.py``), :meth:`HealthMonitor.observe_memory`
verdicts (``mem_headroom`` / ``mem_growth``), and the tuner's
``hbm_capacity`` constraint (:func:`repro.plan.autotune`).  Wired as
``launch.train --memory {off,on}``; pinned telemetry-NEUTRAL (identical
collective signature + bitwise losses) in tests/test_mem.py.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional, Tuple

MEMORY_MODES = ("off", "on")

# ledger categories, in report order
MEM_CATEGORIES = ("params", "grads", "opt_state", "wire", "activations")


# --------------------------------------------------------------------------
# predicted side — the MemoryLedger
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MemoryLedger:
    """Itemized per-rank HBM prediction (bytes per category)."""

    categories: Mapping[str, float]
    detail: Mapping[str, str] = dataclasses.field(default_factory=dict)
    capacity_bytes: Optional[float] = None

    @property
    def total_bytes(self) -> float:
        return float(sum(self.categories.values()))

    @property
    def headroom_frac(self) -> Optional[float]:
        """Predicted peak as a fraction of capacity (None = unknown)."""
        if not self.capacity_bytes:
            return None
        return self.total_bytes / float(self.capacity_bytes)

    def rows(self):
        """(category, bytes, fraction-of-total, note) report rows."""
        total = self.total_bytes or 1.0
        return [(name, float(b), float(b) / total,
                 self.detail.get(name, ""))
                for name, b in self.categories.items()]

    def summary(self) -> Dict[str, object]:
        out: Dict[str, object] = {
            "categories": {k: float(v) for k, v in
                           self.categories.items()},
            "total_bytes": self.total_bytes,
        }
        if self.capacity_bytes:
            out["capacity_bytes"] = float(self.capacity_bytes)
            out["headroom_frac"] = self.headroom_frac
        return out

    def event_fields(self) -> Dict[str, object]:
        """Fields of the ``memory`` event with ``kind="predicted"``."""
        fields = dict(kind="predicted", source="repro.obs.mem",
                      **self.summary())
        fields["wire_watermark_bytes"] = float(
            self.categories.get("wire", 0.0))
        fields["state_bytes_per_rank"] = float(
            self.categories.get("opt_state", 0.0))
        return fields


def staging_bytes_serial(plan) -> float:
    """Wire/staging bytes of a SERIAL plan execution: the sum of its
    ops' per-device operand payloads (consecutive stages' buffers
    coexist across the handoff — same convention as the per-bucket
    pricing in :func:`repro.plan.bucket_staging_bytes`)."""
    return float(sum(op.payload_bytes for op in plan.ops))


def wire_ledger_bytes(plan, comp=None, n_buckets: int = 1,
                      n_total: int = 1, block: int = 4096,
                      spec=None, ready=None) -> Tuple[float, str]:
    """(watermark bytes, note) of the wire category for one exchange.

    Serial runs (or when the pipelined timeline cannot be priced —
    no compressor / no ClusterSpec) fall back to the serial sum, which
    is exact for one bucket and conservative otherwise.  ``ready``
    (per-bucket backward ready times, ``--overlap-bwd``) reprices the
    timeline with the bwd producer stream: buckets then stage while
    backward still produces later ones, and the watermark is the peak
    of THAT schedule — production intervals themselves hold no staging
    (``wire_watermark`` skips them)."""
    if plan is None:
        return 0.0, "no plan"
    serial = staging_bytes_serial(plan)
    if n_buckets <= 1 or comp is None or spec is None:
        return serial, "serial staging (sum of op payloads)"
    from repro.pipeline import Bucketer, lower_to_pipelined
    from repro.plan.cost import (bucket_staging_bytes, pipeline_breakdown,
                                 wire_watermark)
    bk = Bucketer.for_exchange(plan.d, max(n_total, 1), block, n_buckets)
    pplan = lower_to_pipelined(plan, comp, bk)
    if ready is not None and len(ready) != pplan.n_buckets:
        ready = None  # bucket clamp changed the count; fall back
    bd = pipeline_breakdown(pplan, spec, ready=ready)
    per_bucket = bucket_staging_bytes(pplan)
    wm = wire_watermark(bd["intervals"], per_bucket)
    note = (f"live watermark over {pplan.n_buckets} bucket(s) "
            f"(sum {sum(per_bucket):.0f} B)")
    if ready is not None:
        note += ", bwd-overlap schedule"
    return wm, note


def predict_ledger(cfg, mesh, *, optim=None, layout: str = "replicated",
                   topology: str = "flat", block: int = 4096,
                   n_buckets: int = 1, batch_global: int = 1,
                   seq: int = 1, plan=None, spec=None,
                   capacity_bytes: Optional[float] = None,
                   param_dtype_bytes: int = 4, ready=None) -> MemoryLedger:
    """Build the predicted per-rank ledger for one training run.

    ``plan`` is the compressed-exchange :class:`~repro.plan.CommPlan`
    the run executes (``launch.train.run_plans`` rebuilds it host-side;
    None prices the wire category at zero) and ``spec`` the
    :class:`~repro.plan.ClusterSpec` whose device/links schedule the
    pipelined watermark timeline."""
    from repro.analysis.model_math import activation_bytes, param_bytes
    from repro.state import state_bytes
    from repro.train.step import mesh_axes, state_layout_ctx, train_slots
    dp_axes, dp_sizes, tp = mesh_axes(mesh)
    n_dp = 1
    for s in dp_sizes:
        n_dp *= s
    n_dp = max(n_dp, 1)
    ctx = state_layout_ctx(cfg, mesh, block=block, topology=topology)
    if optim is None:
        from repro.optim.base import TwoStageOptimizer
        optim = TwoStageOptimizer()
    slots = train_slots(mesh, layout=layout, topology=topology,
                        optimizer=optim)
    pbytes = float(param_bytes(cfg, tp, param_dtype_bytes))
    # the padded flat f32 exchange buffer IS the gradient's steady-state
    # residency; the unflattened grad tree is transient (-> activations
    # / residual)
    gbytes = float(ctx.d) * 4.0
    sbytes = float(state_bytes(slots, ctx))
    comp = getattr(optim, "compressor", None)
    wbytes, wire_note = wire_ledger_bytes(
        plan, comp, n_buckets=n_buckets, n_total=n_dp, block=block,
        spec=spec, ready=ready)
    abytes = activation_bytes(cfg, max(batch_global // n_dp, 1), seq, tp)
    cats = {"params": pbytes, "grads": gbytes, "opt_state": sbytes,
            "wire": wbytes, "activations": abytes}
    detail = {
        "params": f"{param_dtype_bytes}B x per-model-rank leaves (tp={tp})",
        "grads": f"flat f32 exchange buffer (d={ctx.d})",
        "opt_state": (f"{len(slots)} slot(s), layout={layout}, "
                      f"topology={topology}"),
        "wire": wire_note,
        "activations": (f"fwd+bwd live-set estimate "
                        f"(b={max(batch_global // n_dp, 1)}, s={seq})"),
    }
    return MemoryLedger(categories=cats, detail=detail,
                        capacity_bytes=capacity_bytes)


def capacity_of(device) -> Optional[float]:
    """Per-rank capacity bytes of a DeviceSpec or preset name (None
    when unknown — e.g. cpu-host without psutil)."""
    from repro.perf.device import as_device
    cap = as_device(device).hbm_capacity
    return float(cap) if cap else None


# --------------------------------------------------------------------------
# measured side — compiled-program attribution + live samples
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompiledMemory:
    """One jitted program's ``memory_analysis()`` stats (per device)."""

    program: str
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    alias_bytes: int

    @property
    def per_device_bytes(self) -> int:
        """Peak residency the program needs: live arguments + outputs
        (minus donated aliases) + XLA temp space."""
        return (self.argument_bytes + self.output_bytes
                - self.alias_bytes + self.temp_bytes)

    def summary(self) -> Dict[str, object]:
        return {"program": self.program,
                "argument_bytes": self.argument_bytes,
                "output_bytes": self.output_bytes,
                "temp_bytes": self.temp_bytes,
                "alias_bytes": self.alias_bytes,
                "per_device_bytes": self.per_device_bytes}

    def event_fields(self) -> Dict[str, object]:
        """Fields of the ``memory`` event with ``kind="compiled"``."""
        return {"kind": "compiled", "program": self.program,
                "argument_bytes": float(self.argument_bytes),
                "output_bytes": float(self.output_bytes),
                "temp_bytes": float(self.temp_bytes),
                "alias_bytes": float(self.alias_bytes),
                "peak_bytes": float(self.per_device_bytes),
                "source": "repro.obs.mem"}


def compiled_memory(compiled, program: str = "step"
                    ) -> Optional[CompiledMemory]:
    """THE reader of ``compiled.memory_analysis()`` — dryrun, roofline
    and the driver all come through here.  Returns None when the
    backend exposes no analysis (the callers' stats stay absent rather
    than zero)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    return CompiledMemory(
        program=program,
        argument_bytes=int(ma.argument_size_in_bytes),
        output_bytes=int(ma.output_size_in_bytes),
        temp_bytes=int(ma.temp_size_in_bytes),
        alias_bytes=int(ma.alias_size_in_bytes))


def attribute_compiled(ledger: MemoryLedger, cm: CompiledMemory,
                       metrics_bytes: float = 4096.0) -> Dict[str, object]:
    """Attribute a compiled program's temp+output bytes onto the ledger
    categories, with an explicit residual.

    The pool is ``output_bytes + temp_bytes`` — what the program
    allocates beyond its arguments.  Outputs are the new params, the
    new optimizer state and the metrics dict; temps are the gradient
    buffer, the wire staging and the activation live-set.  Categories
    claim bytes greedily up to their predicted size, clamped so
    ``attributed + residual == compiled total`` holds as an identity:
    residual is the UNEXPLAINED remainder (the acceptance pin keeps it
    under 25%), and over-prediction is reported separately as
    ``over_predicted_bytes`` instead of silently absorbing it."""
    total = float(cm.output_bytes + cm.temp_bytes)
    predicted = {
        "params": float(ledger.categories.get("params", 0.0)),
        "opt_state": float(ledger.categories.get("opt_state", 0.0)),
        "metrics": float(metrics_bytes),
        "grads": float(ledger.categories.get("grads", 0.0)),
        "wire": float(ledger.categories.get("wire", 0.0)),
        "activations": float(ledger.categories.get("activations", 0.0)),
    }
    attribution: Dict[str, float] = {}
    remaining = total
    for name, want in predicted.items():
        take = min(max(want, 0.0), remaining)
        attribution[name] = take
        remaining -= take
    attributed = total - remaining
    residual = remaining
    return {
        "program": cm.program,
        "compiled_bytes": total,
        "attribution": attribution,
        "attributed_bytes": attributed,
        "residual_bytes": residual,
        "residual_frac": residual / total if total > 0 else 0.0,
        "over_predicted_bytes": max(
            sum(predicted.values()) - total, 0.0),
    }


def attribution_event_fields(ledger: MemoryLedger, cm: CompiledMemory,
                             metrics_bytes: float = 4096.0
                             ) -> Dict[str, object]:
    """One ``memory`` event (``kind="compiled"``) carrying both the raw
    program stats and the ledger attribution."""
    att = attribute_compiled(ledger, cm, metrics_bytes=metrics_bytes)
    fields = cm.event_fields()
    fields["attribution"] = {k: float(v) for k, v in
                             att["attribution"].items()}
    fields["attributed_bytes"] = float(att["attributed_bytes"])
    fields["residual_bytes"] = float(att["residual_bytes"])
    fields["residual_frac"] = float(att["residual_frac"])
    return fields


class LiveSampler:
    """Per-log-window live memory samples.

    Prefers the device allocator's ``memory_stats()`` (``bytes_in_use``
    / ``peak_bytes_in_use`` — real HBM residency on TPU/GPU); on
    backends that expose none (CPU), falls back to the host process RSS
    via psutil and tracks the peak itself.  Every call is host-side
    only — nothing touches a compiled program, so ``--memory on`` stays
    telemetry-neutral."""

    def __init__(self, device=None):
        self._device = device
        self._peak = 0.0

    @property
    def peak_bytes(self) -> Optional[float]:
        """Largest sample seen so far (None before the first)."""
        return self._peak or None

    def _resolve(self):
        if self._device is None:
            import jax
            self._device = jax.local_devices()[0]
        return self._device

    def sample(self, step: Optional[int] = None) -> Optional[dict]:
        """Fields of one ``memory`` event (``kind="live"``), or None
        when no source is available at all."""
        dev = self._resolve()
        fields: Dict[str, object] = {"kind": "live",
                                     "source": "repro.obs.mem"}
        if step is not None:
            fields["step"] = int(step)
        stats = None
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats and "bytes_in_use" in stats:
            in_use = float(stats["bytes_in_use"])
            peak = float(stats.get("peak_bytes_in_use", in_use))
            fields["device"] = str(getattr(dev, "platform", dev))
        else:
            rss = _process_rss()
            if rss is None:
                return None
            in_use = float(rss)
            peak = max(self._peak, in_use)
            fields["device"] = "host-rss"
        self._peak = max(self._peak, peak)
        fields["bytes_in_use"] = in_use
        fields["peak_bytes_in_use"] = self._peak
        return fields


def _process_rss() -> Optional[int]:
    try:
        import psutil
        return int(psutil.Process().memory_info().rss)
    except Exception:
        return None


# --------------------------------------------------------------------------
# BENCH metrics + report rows
# --------------------------------------------------------------------------

def mem_metrics(ledger: MemoryLedger,
                compiled: Optional[CompiledMemory] = None,
                live_peak: Optional[float] = None) -> Dict[str, float]:
    """Perf-ledger cells for one run.  ``mem_*`` names are DETERMINISTIC
    byte counts (slot registry, compiled program stats, the predicted
    watermark) and gate STRUCTURALLY in ``results/bench_compare.py``;
    the live sample keeps a non-``mem_`` name (``live_bytes_peak``) so
    allocator/RSS noise stays a timing-style WARN."""
    out = {
        "mem_state_bytes": float(ledger.categories.get("opt_state", 0.0)),
        "mem_wire_watermark_bytes": float(
            ledger.categories.get("wire", 0.0)),
        "mem_predicted_total_bytes": ledger.total_bytes,
    }
    if compiled is not None:
        out["mem_compiled_temp_bytes"] = float(compiled.temp_bytes)
        out["mem_compiled_output_bytes"] = float(compiled.output_bytes)
        out["mem_compiled_argument_bytes"] = float(
            compiled.argument_bytes)
    if live_peak:
        out["live_bytes_peak"] = float(live_peak)
    return out


def format_rows(ledger: MemoryLedger,
                attributions=()) -> str:
    """Human-readable ledger rows (dryrun prints these): predicted
    categories, then per-program compiled attribution."""
    lines = ["memory ledger (per rank, predicted):"]
    for name, nbytes, frac, note in ledger.rows():
        lines.append(f"  {name:12s} {nbytes / 2 ** 20:12.2f} MiB "
                     f"({frac:6.1%})  {note}")
    cap = ledger.capacity_bytes
    lines.append(f"  {'total':12s} {ledger.total_bytes / 2 ** 20:12.2f} MiB"
                 + (f"  of {cap / 2 ** 30:.1f} GiB capacity "
                    f"({ledger.headroom_frac:.1%})" if cap else ""))
    for att in attributions:
        lines.append(
            f"  compiled [{att['program']}]: "
            f"{att['compiled_bytes'] / 2 ** 20:.2f} MiB temp+output; "
            f"attributed {att['attributed_bytes'] / 2 ** 20:.2f} MiB, "
            f"residual {att['residual_bytes'] / 2 ** 20:.2f} MiB "
            f"({att['residual_frac']:.1%})")
    return "\n".join(lines)
