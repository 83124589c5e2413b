"""Tests for repro.obs — structured run telemetry.

Pins, per ISSUE acceptance:
  * the event schema itself (required/optional typing, scalar-only extras);
  * sinks (buffered JSONL writer, zero-cost NullSink) and the batched
    MetricBuffer device→host path;
  * the non-finite v_l1 guard (VarianceMonitor rejection + WarmupSwitch
    warning callback — a NaN can neither trigger nor block the freeze);
  * layer scopes: the compiled step is the same program with and
    without its obs::<layer>::<part> scopes (metadata stripped), and
    every op it runs lies under exactly one of them, outside a short
    list of exceptions;
  * trace spans: naming, the disabled-is-nullcontext fast path, and
    TELEMETRY NEUTRALITY — with tracing on, the train step's compiled
    collective signature and the losses it produces are unchanged
    (subprocess with forced host devices, flat and hierarchical meshes);
  * the drift monitor: against a ClusterSpec with deliberately mis-set
    α/β the drifting (kind, tier) pairs are flagged and the emitted
    recalibration JSON round-trips through ClusterSpec.from_measured to
    within fit tolerance;
  * per-step telemetry overhead stays bounded (pinned, generous);
  * report folding + the end-to-end --telemetry training log.
"""
import contextlib
import json
import math
import os
import re
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.obs import events as E
from repro.obs import trace as TR
from repro.obs.drift import DriftMonitor, DriftSample, fit_linkspecs
from repro.obs.metrics import MetricBuffer, NullSink, TelemetrySink, as_sink

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = REPO_SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


# --------------------------------------------------------------------------
# event schema
# --------------------------------------------------------------------------

class TestEventSchema:
    def test_every_kind_has_a_minimal_valid_record(self):
        minimal = {
            "run_meta": dict(optimizer="onebit_adam", compressor="onebit",
                             topology="flat", n_buckets=1),
            "plan": dict(name="flat_onebit", stage="compressed", d=4096,
                         intra_hlo_bytes=1e6, cross_hlo_bytes=0.0),
            "comm": dict(t_comm=0.5, t_compute=0.2),
            "step": dict(step=3),
            "transition": dict(step=7, kind="stage", to="compressed"),
            "warning": dict(what="non-finite v_l1"),
            "span": dict(name="train.window", dur=0.25),
            "drift": dict(op_kind="AllReduce", tier="intra", n_samples=4,
                          t_measured=1e-3, t_predicted=2e-3, ratio=0.5,
                          drifting=True),
            "recalibration": dict(op_overhead=5e-6),
            "profile": dict(n_steps=4, t_window=1.0, t_attributed=0.8,
                            t_residual=0.2),
            "fidelity": dict(step=4, n_segments=3),
            "health": dict(step=4, ok=True),
            "memory": dict(kind="live", step=4, bytes_in_use=1e6),
        }
        assert sorted(minimal) == sorted(E.EVENT_SCHEMA)
        for etype, fields in minimal.items():
            rec = E.make_event(etype, **fields)
            assert rec["type"] == etype and "t" in rec
            assert E.validate_event(rec) is rec

    def test_missing_required_field_raises(self):
        with pytest.raises(ValueError, match="missing required"):
            E.make_event("transition", step=1, kind="stage")  # no "to"

    def test_wrong_required_type_raises(self):
        with pytest.raises(ValueError, match="expected int"):
            E.make_event("step", step="three")

    def test_wrong_optional_type_raises(self):
        with pytest.raises(ValueError, match="expected num"):
            E.make_event("step", step=1, loss="diverged")

    def test_bool_is_not_a_number(self):
        with pytest.raises(ValueError, match="expected num"):
            E.make_event("comm", t_comm=True, t_compute=0.1)

    def test_unknown_event_type_raises(self):
        with pytest.raises(ValueError, match="unknown event type"):
            E.make_event("metrics", step=1)

    def test_unknown_extras_must_be_scalars(self):
        rec = E.make_event("step", step=1, custom_tag="ok", custom_n=7)
        assert rec["custom_tag"] == "ok"
        with pytest.raises(ValueError, match="JSON scalars"):
            E.make_event("step", step=1, custom=[1, 2])

    def test_validate_records_reports_index(self):
        good = E.make_event("step", step=0)
        assert E.validate_records([good, good]) == 2
        with pytest.raises(ValueError, match="record 1:"):
            E.validate_records([good, {"type": "step"}])


# --------------------------------------------------------------------------
# sinks + metric buffer
# --------------------------------------------------------------------------

class TestSinks:
    def test_jsonl_roundtrip_and_buffering(self, tmp_path):
        sink = TelemetrySink(str(tmp_path), buffer_lines=3)
        sink.emit("step", step=0, loss=2.5)
        sink.emit("step", step=1, loss=2.4)
        # under buffer_lines: nothing on disk yet
        assert open(sink.path).read() == ""
        sink.emit("step", step=2, loss=2.3)
        lines = open(sink.path).read().splitlines()
        assert len(lines) == 3
        sink.emit("warning", what="x")
        sink.close()
        recs = [json.loads(l) for l in open(sink.path)]
        assert [r["type"] for r in recs] == ["step"] * 3 + ["warning"]
        assert E.validate_records(recs) == 4
        assert sink.n_events == 4

    def test_emit_validates(self, tmp_path):
        with TelemetrySink(str(tmp_path)) as sink:
            with pytest.raises(ValueError):
                sink.emit("step")    # missing required "step"
        assert open(sink.path).read() == ""

    def test_as_sink_none_is_null(self):
        sink = as_sink(None, filename="ignored.jsonl")
        assert isinstance(sink, NullSink)
        assert sink.enabled is False and sink.path is None
        with sink as s:      # context manager, emit: all no-ops
            s.emit("not even a valid type", nonsense=object())
        sink.close()

    def test_as_sink_dir_is_enabled(self, tmp_path):
        sink = as_sink(str(tmp_path), filename="x.jsonl")
        assert sink.enabled is True
        assert sink.path.endswith("x.jsonl")
        sink.close()


class TestMetricBuffer:
    def test_push_host_drain(self):
        import jax.numpy as jnp
        buf = MetricBuffer()
        for s in range(4):
            buf.push(s, {"loss": jnp.float32(2.0 - s), "v_l1": jnp.float32(s)})
        assert buf.n_pending == 4
        rec = buf.host(2)
        assert rec == {"loss": 0.0, "v_l1": 2.0}
        assert buf.host(2) is rec           # cached, no second fetch
        assert buf.n_pending == 3
        drained = buf.drain()
        assert [s for s, _ in drained] == [0, 1, 2, 3]
        assert drained[1][1]["loss"] == 1.0
        assert all(isinstance(v, float) for _, r in drained
                   for v in r.values())
        assert buf.n_pending == 0 and buf.drain() == []


# --------------------------------------------------------------------------
# non-finite v_l1 guard
# --------------------------------------------------------------------------

class TestNaNGuard:
    def _stable(self, mon, t0, n):
        """Feed n stable observations starting at step t0."""
        fired = None
        for t in range(t0, t0 + n):
            if mon.observe(t, 100.0) and fired is None:
                fired = t
        return fired

    def test_monitor_rejects_non_finite(self):
        from repro.core.variance import VarianceMonitor
        mon = VarianceMonitor(b2=0.9, threshold=0.96)   # delta = 10
        for bad in (float("nan"), float("inf"), -float("inf")):
            assert mon.observe(0, bad) is False
        assert mon.history == [] and mon.n_rejected == 3

    def test_nan_cannot_block_the_freeze(self):
        """A NaN mid-window must not poison the ratio: the rule still
        fires delta steps after stable values resume, not later."""
        from repro.core.variance import VarianceMonitor
        mon = VarianceMonitor(b2=0.9, threshold=0.96)
        self._stable(mon, 0, 5)
        assert mon.observe(5, float("nan")) is False
        fired = self._stable(mon, 6, 20)
        assert mon.freeze_step is not None
        # 11 finite observations = len > delta; NaN consumed no slot
        assert fired == 11
        assert mon.n_rejected == 1

    def test_nan_cannot_trigger_the_freeze(self):
        from repro.core.variance import VarianceMonitor
        mon = VarianceMonitor(b2=0.9, threshold=0.96)
        self._stable(mon, 0, 3)
        for t in range(3, 30):
            mon.observe(t, float("inf"))
        assert mon.freeze_step is None

    def test_switch_warns_on_non_finite(self):
        from repro.optim import WarmupSwitch
        sw = WarmupSwitch(mode="auto", b2=0.9)
        warnings = []
        sw.observe(0, {"v_l1": 10.0},
                   on_warning=lambda s, d: warnings.append((s, d)))
        assert warnings == []
        sw.observe(1, {"v_l1": float("nan")},
                   on_warning=lambda s, d: warnings.append((s, d)))
        assert len(warnings) == 1
        assert warnings[0][0] == 1 and "v_l1" in warnings[0][1]
        assert sw.monitor.n_rejected == 1

    def test_steps_mode_ignores_stats(self):
        from repro.optim import WarmupSwitch
        sw = WarmupSwitch(mode="steps", warmup_steps=3)
        assert sw.observe(0, {}) is False
        assert sw.observe(2, {}) is True


# --------------------------------------------------------------------------
# trace spans
# --------------------------------------------------------------------------

class TestTrace:
    def test_span_name_grammar(self):
        # tier separator is "~", NOT "@": JAX's name stack reserves "@"
        # for transform annotations and drops it (and the tier) from the
        # HLO op_name metadata the profile fold joins on
        assert (TR.span_name("hier_onebit", 1, "AllToAll", "cross",
                             bucket=2)
                == "obs::hier_onebit::b2.s1::AllToAll~cross")
        assert (TR.span_name("flat_onebit", 0, "AllGather", "intra")
                == "obs::flat_onebit::s0::AllGather~intra")

    def test_op_scope_disabled_is_shared_nullcontext(self):
        class Op:
            kind, tier = "AllReduce", "intra"
        assert not TR.tracing_enabled()
        c1 = TR.op_scope("p", 0, Op())
        c2 = TR.op_scope("p", 1, Op(), bucket=3)
        assert c1 is c2 is TR._NULL

    def test_op_scope_enabled_is_named_scope(self):
        class Op:
            kind, tier = "AllReduce", "intra"
        with TR.tracing(True):
            scope = TR.op_scope("p", 0, Op())
            assert scope is not TR._NULL
            with scope:
                pass
        assert not TR.tracing_enabled()

    def test_tracer_records_and_emits(self, tmp_path):
        with TelemetrySink(str(tmp_path)) as sink:
            tr = TR.Tracer(sink)
            with tr.span("train.window", step=9, n=10):
                time.sleep(0.01)
        assert len(tr.spans) == 1
        rec = tr.spans[0]
        assert rec["name"] == "train.window" and rec["dur"] >= 0.01
        assert rec["step"] == 9 and rec["n"] == 10
        logged = [json.loads(l) for l in open(sink.path)]
        assert logged[0]["type"] == "span"
        assert logged[0]["dur"] == rec["dur"]

    def test_collective_signature_parses_hlo(self):
        hlo = """
          %all-to-all.1 = u8[4,128]{1,0} all-to-all(%p), dimensions={0}
          %ag = (f32[512]{0}, u8[64]{0}) all-gather-start(%x, %y)
          %d = f32[8,8]{1,0} dot(%a, %b)
          ROOT %ar = f32[512]{0} all-reduce(%z), to_apply=%add
        """
        sig = TR.collective_signature(hlo)
        assert sig == tuple(sorted([("all-to-all", "u8[4,128]"),
                                    ("all-gather", "f32[512], u8[64]"),
                                    ("all-reduce", "f32[512]")]))
        assert TR.collective_signature("%d = f32[2] dot(%a)") == ()


# --------------------------------------------------------------------------
# layer scopes in the compiled train step
# --------------------------------------------------------------------------

_HLO_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) \(.*\{$")
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_HLO_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# a layer scope as one op_name component, bare or as the argument of the
# autodiff transform it sits directly under: "jvp(obs::model::head)"
_LAYER_SCOPE = re.compile(
    r"^(?:[A-Za-z_]+\()*obs::(?:model|optimizer|exchange)::[A-Za-z0-9_]+"
    r"\)*$")
_SERVER = "obs::exchange::server"
# instructions that execute nothing
_FREE = ("parameter", "constant", "bitcast", "get-tuple-element", "tuple")
# the named ops that lie under no layer scope: the layer scan's own loop
# (the while, its counter and condition, the per-layer slices of the
# stacked parameters and the stacking of the outputs, the remat call's
# boundary) and, at the top of the gradient computation, the zero
# initial values of the stacked outputs and the sum of the per-layer
# auxiliary losses
_UNSCOPED_OK = re.compile(
    r"/while(?:/(?:body|cond)/[^/]+|/body/closed_call(?:/remat2)?)?$"
    r"|^jit\(step\)/(?:transpose\()?jvp\(\)\)?/"
    r"(?:broadcast_in_dim|reduce_sum)$")


def _strip_metadata(hlo: str) -> str:
    """Compiled HLO text without what names and source positions add:
    each instruction's metadata and the module's stack-frame tables."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    return "\n".join(
        line for line in hlo.splitlines() if not re.match(
            r"(\d+ |FileNames|FunctionNames|FileLocations|StackFrames)",
            line))


def _executed_ops(hlo: str):
    """(opcode, op_name) of each named instruction the device runs as an
    op of its own: those of the entry computation and of the loops'
    bodies, not those inside a fusion or a reducer."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _HLO_COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.strip() == "}":
            cur = None
        elif cur is not None and _HLO_INSTR.match(line):
            cur.append(_HLO_INSTR.match(line).group(2))
    inner = set()
    for body in comps.values():
        for rest in body:
            inner.update(re.findall(r"calls=%([\w.\-]+)", rest))
            if " call(" not in rest:
                inner.update(re.findall(r"to_apply=%([\w.\-]+)", rest))
    for name, body in comps.items():
        if name in inner:
            continue
        for rest in body:
            src = _OP_NAME.search(rest)
            if src:
                yield _HLO_OPCODE.search(" " + rest).group(1), src.group(1)


@pytest.fixture(scope="module")
def step_hlo():
    """Compiled HLO of the bert-base-smoke warmup and compressed steps on
    one device, with and without the layer scopes."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as T
    from repro.train.step import (TrainStepConfig, init_train_state,
                                  make_train_step)

    cfg = get_config("bert-base-smoke")
    mesh = make_mesh((1, 1), ("data", "model"))
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0),
                                                  tp=1))
    batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32)
             for k in ("tokens", "labels")}

    def compile_step(stage):
        step = make_train_step(cfg, mesh, TrainStepConfig(
            stage=stage, block_size=512), donate=False)
        opt = init_train_state(cfg, mesh, block=512, abstract=True,
                               optimizer=step.optimizer)
        return step.build(batch).lower(params, opt, batch,
                                       jnp.float32(1e-3)).compile().as_text()

    out = {}
    for stage in ("warmup", "compressed"):
        out[stage, True] = compile_step(stage)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(TR, "layer_scope",
                       lambda layer, part: contextlib.nullcontext())
            out[stage, False] = compile_step(stage)
    return out


@pytest.mark.parametrize("stage", ["warmup", "compressed"])
class TestLayerScopes:
    def test_scopes_are_metadata_only(self, step_hlo, stage):
        scoped, bare = step_hlo[stage, True], step_hlo[stage, False]
        assert "obs::model::attention" in scoped
        assert "obs::" not in bare
        assert _strip_metadata(scoped) == _strip_metadata(bare)

    def test_every_op_under_one_layer_scope(self, step_hlo, stage):
        ops = [(code, src) for code, src in _executed_ops(
            step_hlo[stage, True]) if code not in _FREE]
        assert len(ops) > 100
        scopes = {src: [p for p in src.split("/") if _LAYER_SCOPE.match(p)]
                  for _, src in ops}
        # one layer scope each; the server's part of the exchange nests
        # in the exchange's own scope, and the innermost one counts
        stray = sorted({src for src, found in scopes.items()
                        if len(found) != 1 and found[1:] != [_SERVER]
                        and not _UNSCOPED_OK.search(src)})
        assert not stray, stray
        # every scope the step opens reaches the compiled program (inside
        # a fusion, where the fusion is named after another instruction)
        named = {re.search(r"obs::\w+::\w+", p).group(0)
                 for src in _OP_NAME.findall(step_hlo[stage, True])
                 for p in src.split("/") if _LAYER_SCOPE.match(p)}
        want = {"obs::model::embed", "obs::model::norm", "obs::model::mlp",
                "obs::model::attention", "obs::model::head",
                "obs::optimizer::flatten", "obs::optimizer::momentum",
                "obs::optimizer::update", "obs::optimizer::stats",
                "obs::optimizer::unflatten"}
        want |= ({"obs::exchange::onebit", _SERVER} if stage == "compressed"
                 else {"obs::exchange::allreduce"})
        assert want <= named, want - named
        # forward, backward and the recomputed forward of attention
        attn = [src for src in scopes if "obs::model::attention/" in src]
        assert any(s.startswith("jit(step)/jvp()/") for s in attn)
        assert any(s.startswith("jit(step)/transpose(jvp())/")
                   and "rematted_computation" not in s for s in attn)
        assert any("/rematted_computation/" in s for s in attn)


def _innermost_scope(src: str):
    found = [p for p in src.split("/") if _LAYER_SCOPE.match(p)]
    return re.search(r"obs::\w+::\w+", found[-1]).group(0) if found \
        else None


def test_dp4_collectives_under_exchange_scopes(tmp_path):
    """The compressed step over four data-parallel devices: every
    collective it runs lies under an ``obs::exchange::<part>`` scope, so
    the benchmark's exchange metrics read all of them, and each chip's
    server work (the average of the received chunks, the re-compression
    with the server's error feedback) lies under
    ``obs::exchange::server``, nested in the exchange's own scope."""
    hlo = tmp_path / "step.hlo"
    run_with_devices(f"""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as T
    from repro.train.step import (TrainStepConfig, init_train_state,
                                  make_train_step)
    cfg = get_config("bert-base-smoke")
    mesh = make_mesh((4, 1), ("data", "model"))
    params = jax.eval_shape(lambda: T.init_params(
        cfg, jax.random.PRNGKey(0), tp=1))
    batch = {{k: jax.ShapeDtypeStruct((8, 32), jnp.int32)
             for k in ("tokens", "labels")}}
    step = make_train_step(cfg, mesh, TrainStepConfig(
        stage="compressed", block_size=512), donate=False)
    opt = init_train_state(cfg, mesh, block=512, abstract=True,
                           optimizer=step.optimizer)
    text = step.build(batch).lower(params, opt, batch,
                                   jnp.float32(1e-3)).compile().as_text()
    open({str(hlo)!r}, "w").write(text)
    """, n=4)
    ops = list(_executed_ops(hlo.read_text()))
    coll = [(code, src) for code, src in ops if re.match(
        r"(all-to-all|all-gather|all-reduce|reduce-scatter|"
        r"collective-permute)", code)]
    kinds = {re.sub(r"-(start|done)$", "", code) for code, _ in coll}
    assert {"all-to-all", "all-gather", "all-reduce"} <= kinds, kinds
    outside = [(code, src) for code, src in coll if not (
        _innermost_scope(src) or "").startswith("obs::exchange::")]
    assert not outside, outside
    server = [src for _, src in ops if _innermost_scope(src) == _SERVER]
    assert server
    assert all("/obs::exchange::onebit/" in src for src in server)
    assert not any(_innermost_scope(src) == _SERVER for _, src in coll)


# --------------------------------------------------------------------------
# drift monitor
# --------------------------------------------------------------------------

def _mk_spec(name, intra, cross, n_inner, n_outer, overhead):
    from repro.plan.cost import ClusterSpec, LinkSpec
    return ClusterSpec(name=name, intra=LinkSpec(*intra),
                       cross=LinkSpec(*cross), n_inner=n_inner,
                       n_outer=n_outer, op_overhead=overhead)


def _synthetic_samples(spec):
    """Measured samples generated BY a truth spec through the cost
    model's own pricing — so a fit must recover the truth exactly."""
    out = []
    for kind in ("AllToAll", "AllGather", "AllReduce", "ReduceScatter"):
        for tier, n in (("intra", spec.n_inner), ("cross", spec.n_outer)):
            for mb in (1, 4, 16):
                from repro.plan.cost import op_time_kind
                payload = mb * 2 ** 20
                out.append(DriftSample(kind, tier, n, payload,
                                       op_time_kind(kind, tier, n, payload,
                                                    spec)))
    return out


class TestDriftMonitor:
    TRUTH = ("truth", (50e-6, 1.25e9), (500e-6, 0.125e9), 8, 4, 5e-6)
    WRONG = ("wrong", (5e-6, 200e9), (5e-6, 25e9), 8, 4, 1e-6)

    def test_pricing_matches_coeff_rows(self):
        """op_time_kind must equal the dot product of op_coeffs_kind with
        (overhead, α, 1/β) — the invariant the lstsq fit relies on."""
        from repro.plan.cost import op_coeffs_kind, op_time_kind
        spec = _mk_spec(*self.TRUTH)
        for kind in ("AllToAll", "AllGather", "AllReduce", "ReduceScatter",
                     "Broadcast"):
            for tier, n in (("intra", 8), ("cross", 4)):
                ov, ca, cb = op_coeffs_kind(kind, n, 2 ** 22)
                link = spec.link(tier)
                manual = (ov * spec.op_overhead + ca * link.latency
                          + cb / link.bandwidth)
                assert op_time_kind(kind, tier, n, 2 ** 22, spec) == \
                    pytest.approx(manual)
        assert op_time_kind("AllReduce", "intra", 1, 2 ** 22, spec) == 0.0
        with pytest.raises(KeyError):
            op_coeffs_kind("Gossip", 4, 1024)

    def test_no_drift_against_the_true_spec(self):
        spec = _mk_spec(*self.TRUTH)
        mon = DriftMonitor(spec)
        for s in _synthetic_samples(spec):
            r = mon.observe(s.op_kind, s.tier, s.n, s.payload_bytes,
                            s.seconds)
            assert r["ratio"] == pytest.approx(1.0)
        assert mon.drifting == []
        assert all(not r["drifting"] for r in mon.report())

    def test_min_samples_gate(self):
        mon = DriftMonitor(_mk_spec(*self.WRONG), min_samples=3)
        truth = _mk_spec(*self.TRUTH)
        sample = _synthetic_samples(truth)[0]
        mon.observe(sample.op_kind, sample.tier, sample.n,
                    sample.payload_bytes, sample.seconds)
        assert mon.drifting == []          # 1 < min_samples: no verdict
        for _ in range(2):
            mon.observe(sample.op_kind, sample.tier, sample.n,
                        sample.payload_bytes, sample.seconds)
        assert mon.drifting == [(sample.op_kind, sample.tier)]

    def test_misset_spec_flags_and_recalibration_roundtrips(self, tmp_path):
        """The ISSUE acceptance test: a deliberately mis-set α/β spec vs
        samples from the true fabric — every sampled (kind, tier) is
        flagged, and the emitted recalibration JSON, loaded back through
        ClusterSpec.from_measured, reprices every sample to within fit
        tolerance."""
        from repro.plan.cost import ClusterSpec, op_time_kind
        truth = _mk_spec(*self.TRUTH)
        samples = _synthetic_samples(truth)
        mon = DriftMonitor(_mk_spec(*self.WRONG), threshold=0.25)
        for s in samples:
            mon.observe(s.op_kind, s.tier, s.n, s.payload_bytes, s.seconds)
        flagged = set(mon.drifting)
        expect = {(k, t) for k in ("AllToAll", "AllGather", "AllReduce",
                                   "ReduceScatter")
                  for t in ("intra", "cross")}
        assert flagged == expect
        path = str(tmp_path / "recal.json")
        emitted = mon.emit_recalibration(path)
        assert emitted["n_inner"] == 8 and emitted["n_outer"] == 4
        recovered = ClusterSpec.from_measured(path)
        assert recovered.n_inner == 8 and recovered.n_outer == 4
        # the recovered spec must REPRICE the measured samples ~exactly
        for s in samples:
            pred = op_time_kind(s.op_kind, s.tier, s.n, s.payload_bytes,
                                recovered)
            assert pred == pytest.approx(s.seconds, rel=1e-3)
        # and a fresh monitor against it sees no drift
        mon2 = DriftMonitor(recovered)
        for s in samples:
            mon2.observe(s.op_kind, s.tier, s.n, s.payload_bytes, s.seconds)
        assert mon2.drifting == []
        # the driver-facing entry point: --cluster measured:<path>
        from repro.plan.cost import get_cluster
        via_cli = get_cluster(f"measured:{path}", n_inner=8, n_outer=4)
        assert via_cli.intra == recovered.intra
        assert via_cli.cross == recovered.cross
        with pytest.raises(KeyError, match="measured:"):
            get_cluster("no-such-preset", n_inner=8)

    def test_fit_recovers_truth_parameters(self):
        truth = _mk_spec(*self.TRUTH)
        fit = fit_linkspecs(_synthetic_samples(truth))
        assert fit["op_overhead"] == pytest.approx(5e-6, rel=1e-3)
        assert fit["tiers"]["intra"]["latency"] == pytest.approx(
            50e-6, rel=1e-3)
        assert fit["tiers"]["intra"]["bandwidth"] == pytest.approx(
            1.25e9, rel=1e-3)
        assert fit["tiers"]["cross"]["bandwidth"] == pytest.approx(
            0.125e9, rel=1e-3)

    def test_events_validate_and_carry_recalibration(self, tmp_path):
        truth = _mk_spec(*self.TRUTH)
        mon = DriftMonitor(_mk_spec(*self.WRONG))
        for s in _synthetic_samples(truth):
            mon.observe(s.op_kind, s.tier, s.n, s.payload_bytes, s.seconds)
        path = str(tmp_path / "recal.json")
        evs = mon.events(emit_recal_path=path)
        assert os.path.exists(path)
        types = [t for t, _ in evs]
        assert types.count("recalibration") == 1
        assert types.count("drift") == len(mon.report())
        for etype, fields in evs:
            E.make_event(etype, **fields)    # schema-valid as emitted
        recal = dict(evs)["recalibration"]
        assert recal["path"] == path and "AllReduce@" in recal["reason"]


# --------------------------------------------------------------------------
# telemetry neutrality + end-to-end (subprocess: forced host devices)
# --------------------------------------------------------------------------

class TestTelemetryNeutrality:
    def test_tracing_leaves_step_unchanged(self):
        """Flat (4,1) and hier (2,2,1) onebit compressed steps, tracing
        off vs on: identical compiled collective signatures AND
        bitwise-equal losses over 3 steps."""
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.data import SyntheticStream
        from repro.launch.mesh import make_mesh
        from repro.models import transformer as T
        from repro.obs.trace import collective_signature, tracing
        from repro.train.step import (TrainStepConfig, init_train_state,
                                      make_train_step)

        cfg = get_config("internlm2-1.8b").reduced()
        shape = InputShape("t", 64, 4, "train")

        def losses_and_sig(mesh, topology, trace_on):
            tsc = TrainStepConfig(stage="compressed", topology=topology)
            with tracing(trace_on):
                step = make_train_step(cfg, mesh, tsc, donate=False)
                params = T.init_params(cfg, jax.random.PRNGKey(0), tp=1)
                opt = init_train_state(cfg, mesh, topology=topology)
                stream = SyntheticStream(cfg, shape)
                batch0 = stream.batch_at(0)
                lr = jnp.float32(1e-3)
                jitted = step.build(batch0)
                sig = collective_signature(
                    jitted.lower(params, opt, batch0, lr)
                    .compile().as_text())
                losses = []
                for t in range(3):
                    params, opt, m = step(params, opt, stream.batch_at(t),
                                          lr)
                    losses.append(np.asarray(m["loss"]).tobytes())
            return sig, losses

        for mesh, topo in ((make_mesh((4, 1), ("data", "model")), "flat"),
                           (make_mesh((2, 2, 1),
                                      ("pod", "data", "model")), "hier")):
            sig_off, loss_off = losses_and_sig(mesh, topo, False)
            sig_on, loss_on = losses_and_sig(mesh, topo, True)
            assert sig_off, f"{topo}: no collectives found"
            assert sig_on == sig_off, (topo, sig_on, sig_off)
            assert loss_on == loss_off, f"{topo}: losses differ"
            print(f"{topo}: {len(sig_off)} collectives, "
                  f"3 losses bitwise-equal OK")
        """, n=4)
        assert "flat:" in out and "hier:" in out

    def test_probe_feeds_monitor_on_forced_mesh(self):
        """probe_plan on a forced-host 4-way mesh yields one sample per
        non-degenerate op and the monitor prices them (values are
        meaningless on CPU — only the plumbing is pinned)."""
        out = run_with_devices("""
        from repro.launch.mesh import make_mesh
        from repro.obs.drift import DriftMonitor, probe_plan
        from repro.optim import get_compressor
        from repro.plan.cost import get_cluster
        from repro.plan.schedules import flat_schedule

        mesh = make_mesh((4,), ("data",))
        plan = flat_schedule(get_compressor("onebit", block_size=256),
                             4096, 4, ("data",))
        samples = probe_plan(plan, mesh, iters=2, repeats=3)
        live = [op for op in plan.ops if op.n > 1 and op.axes]
        # 3 independent samples per live op: one probe pass can satisfy
        # the monitor's min_samples gate
        assert len(samples) == 3 * len(live) > 0
        mon = DriftMonitor(get_cluster("ethernet-10g", n_inner=4))
        for s in samples:
            r = mon.observe(s.op_kind, s.tier, s.n, s.payload_bytes,
                            s.seconds)
            assert r["t_measured"] > 0
        report = mon.report()
        assert all(r["n_samples"] >= 3 for r in report) and report
        print("probe OK:", len(samples), "samples")
        """, n=4)
        assert "probe OK" in out


class TestEndToEnd:
    def test_train_telemetry_log_validates(self, tmp_path):
        """launch.train --telemetry over a real (tiny) run: every record
        validates, the expected kinds are present, the report folds, and
        the no-telemetry history is unaffected."""
        from repro.launch.train import run
        from repro.obs import report as R
        tel = str(tmp_path / "tel")
        run("internlm2-1.8b-smoke", steps=8, batch=4, seq=64,
            mesh_shape=(1, 1), base_lr=2e-3, lr_warmup=3, warmup_steps=4,
            block_size=512, log_every=4, telemetry=tel)
        path = os.path.join(tel, "telemetry.jsonl")
        recs = R.load(path, validate=True)
        by_type = {}
        for r in recs:
            by_type.setdefault(r["type"], []).append(r)
        assert len(by_type["run_meta"]) == 1
        assert by_type["run_meta"][0]["optimizer"] == "onebit_adam"
        steps = by_type["step"]
        assert [r["step"] for r in steps] == list(range(8))
        assert all(math.isfinite(r["loss"]) for r in steps)
        assert {r["stage"] for r in steps} == {"warmup", "compressed"}
        trans = [r for r in by_type["transition"] if r["kind"] == "stage"]
        assert len(trans) == 1 and trans[0]["step"] == 4
        assert len(by_type["plan"]) >= 2       # warmup + compressed
        assert any(s["name"] == "train.window" for s in by_type["span"])
        summary = R.summarize(recs)
        assert summary["steps"]["switch_step"] == 4
        assert summary["steps"]["n_steps"] == 8
        text = R.format_report(summary)
        assert "train.window" in text and "switch_step" in text

    def test_report_cli(self, tmp_path):
        with TelemetrySink(str(tmp_path)) as sink:
            sink.emit("run_meta", optimizer="adam", compressor="none",
                      topology="flat", n_buckets=1)
            for s in range(3):
                sink.emit("step", step=s, loss=2.0 - s * 0.1, v_l1=1.0 + s)
        env = dict(os.environ, PYTHONPATH=REPO_SRC)
        out_json = str(tmp_path / "summary.json")
        r = subprocess.run(
            [sys.executable, "-m", "repro.obs.report", sink.path,
             "--validate", "--json", out_json],
            capture_output=True, text=True, env=env, timeout=120)
        assert r.returncode == 0, r.stderr
        assert "validated 4 records OK" in r.stdout
        summary = json.load(open(out_json))
        assert summary["n_events"] == 4
        assert summary["steps"]["n_steps"] == 3


# --------------------------------------------------------------------------
# overhead pin
# --------------------------------------------------------------------------

class TestOverheadPin:
    N = 200

    def test_disabled_path_is_free(self):
        """The off path per step: one NullSink.emit + one MetricBuffer
        park — pinned well under a millisecond per step (generous 10x
        headroom over observed; this is the 'zero-cost when disabled'
        claim)."""
        sink = NullSink()
        buf = MetricBuffer()
        metrics = {k: float(i) for i, k in enumerate(E.STEP_METRICS[:9])}
        t0 = time.perf_counter()
        for s in range(self.N):
            buf.push(s, metrics)
            sink.emit("step", step=s, **metrics)
        buf._pending.clear()
        elapsed = time.perf_counter() - t0
        assert elapsed < 0.05 * (self.N / 200), elapsed

    def test_enabled_path_is_bounded(self, tmp_path):
        """Validated emit + buffered write + batched drain: < 2 ms/step
        (observed ~20 µs; the bound only catches a pathological
        per-event flush/validate regression)."""
        import jax.numpy as jnp
        metrics = {k: jnp.float32(i)
                   for i, k in enumerate(E.STEP_METRICS[:9])}
        with TelemetrySink(str(tmp_path)) as sink:
            buf = MetricBuffer()
            t0 = time.perf_counter()
            for s in range(self.N):
                buf.push(s, metrics)
            for s, rec in buf.drain():
                sink.emit("step", step=s, **rec)
            elapsed = time.perf_counter() - t0
        assert elapsed < 2e-3 * self.N, elapsed
        assert sink.n_events == self.N
