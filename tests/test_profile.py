"""Tests for repro.obs.profile + repro.obs.bench — the measured-profile
fold and the BENCH perf ledger.

Pins, per ISSUE acceptance:
  * the op_scope grammar roundtrip: every span name either executor can
    emit (``SCOPED_KINDS`` x tiers, serial and pipelined, every schedule
    shape) parses back to its exact (plan, bucket, stage, kind, tier)
    cell — no collective can become silently unattributable;
  * the compiled-HLO bridge: scoped instructions map, fusions/``call``
    wrappers inherit their computation's scope, cross-program ambiguity
    (same module name, conflicting or absent scopes) is DROPPED into the
    residual rather than misattributed;
  * the fold: attributed + residual sums to the window by construction,
    wire vs compute stream split, window selection;
  * the overlap audit (busy/hidden/exposed per stream) on known interval
    layouts, and ``pipeline_breakdown``'s predicted intervals being
    consistent with its own busy/t_total totals;
  * the ledger: record validation, result flattening, write/load/merge
    roundtrip, and ``results/bench_compare.py``'s structural-vs-timing
    failure split;
  * end-to-end on this machine: a profiler trace of a real pipelined
    shard_map exchange folds back onto the full (bucket, stage) grid
    (subprocess with forced host devices).
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.obs import bench as B
from repro.obs import events as E
from repro.obs import profile as prof
from repro.obs import trace as TR
from repro.obs.trace import span_name

REPO = os.path.join(os.path.dirname(__file__), "..")


def run_with_devices(code: str, n: int = 4, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def load_bench_compare():
    spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(REPO, "results", "bench_compare.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# scope grammar
# --------------------------------------------------------------------------

class TestScopeGrammar:
    def test_roundtrip_serial_and_pipelined(self):
        s = prof.parse_scope(span_name("flat/onebit", 2, "AllGather",
                                       "intra"))
        assert s == {"plan": "flat/onebit", "bucket": None, "stage": 2,
                     "kind": "AllGather", "tier": "intra"}
        s = prof.parse_scope(span_name("pipe(hier/onebit+outer_ef)x4", 1,
                                       "AllToAll", "cross", bucket=3))
        assert prof.cell_key(s) == ("pipe(hier/onebit+outer_ef)x4", 3, 1,
                                    "AllToAll", "cross")

    def test_parses_inside_hlo_op_name_path(self):
        name = ("jit(step)/jit(main)/jit(shmap_body)/"
                + span_name("p", 0, "AllReduce", "intra") + "/psum")
        s = prof.parse_scope(name)
        assert prof.cell_key(s) == ("p", None, 0, "AllReduce", "intra")

    @pytest.mark.parametrize("plan,bucket", [("flat/onebit", None),
                                             ("onebit", 2)])
    def test_grid_scope_under_layer_scope(self, plan, bucket):
        # the exchange's layer scope wraps the executors' grid scopes: the
        # plan must not run back into the enclosing obs:: component
        name = ("jit(step)/obs::exchange::onebit/"
                + span_name(plan, 1, "AllToAll", "intra", bucket=bucket)
                + "/all_to_all")
        assert prof.cell_key(prof.parse_scope(name)) == (
            plan, bucket, 1, "AllToAll", "intra")
        assert prof.parse_scope(
            "jit(step)/jvp()/while/body/closed_call/obs::model::attention"
            "/dot_general") is None

    def test_legacy_at_separator_still_parses(self):
        s = prof.parse_scope("obs::hier_onebit::b2.s1::AllToAll@cross")
        assert prof.cell_key(s) == ("hier_onebit", 2, 1, "AllToAll",
                                    "cross")

    def test_non_scope_names_are_none(self):
        assert prof.parse_scope("jit(step)/psum") is None
        assert prof.parse_scope("obs::plan::smash") is None

    def test_every_executor_op_kind_parseable(self):
        """The coverage pin: every span name either executor can emit —
        all op kinds, all schedule shapes, serial and pipelined — parses
        back to its exact grid cell."""
        from repro.optim import get_compressor
        from repro.pipeline import Bucketer, lower_to_pipelined
        from repro.pipeline.executor import (scoped_op_names
                                             as pipelined_scoped)
        from repro.plan import (allreduce_schedule, flat_schedule,
                                hier_schedule)
        from repro.plan.executor import SCOPED_KINDS, scoped_op_names

        assert SCOPED_KINDS == ("AllGather", "AllReduce", "AllToAll",
                                "Broadcast", "ReduceScatter")
        comp = get_compressor("onebit", block_size=64)
        d = 8 * 64 * 4
        plans = [
            allreduce_schedule(d, 8, ("data",)),
            flat_schedule(comp, d, 8, ("data",)),
            hier_schedule(comp, d, 4, 2, ("data",), ("pod",)),
            hier_schedule(get_compressor("topk", block_size=64), d, 4, 2,
                          ("data",), ("pod",), outer_ef=True),
        ]
        for plan in plans:
            names = scoped_op_names(plan)
            assert len(names) == len(plan.ops)
            for s, name in enumerate(names):
                scope = prof.parse_scope(name)
                assert scope is not None, name
                assert scope["kind"] in SCOPED_KINDS
                assert prof.cell_key(scope) == (
                    plan.name, None, s, plan.ops[s].kind, plan.ops[s].tier)
        pp = lower_to_pipelined(plans[1], comp,
                                Bucketer.for_exchange(d, 8, 64, 3))
        cells = set()
        for name in pipelined_scoped(pp):
            scope = prof.parse_scope(name)
            assert scope is not None and scope["kind"] in SCOPED_KINDS
            cells.add((scope["bucket"], scope["stage"]))
        assert cells == {(b, s) for b in range(pp.n_buckets)
                         for s in range(pp.n_stages)}


# --------------------------------------------------------------------------
# HLO bridge
# --------------------------------------------------------------------------

def hlo(module, body):
    return f"HloModule {module}, is_scheduled=true\n\n{body}\n"


SCOPED = ('  %all-to-all.1 = u8[4,64]{1,0} all-to-all(u8[4,64]{1,0} %p.1),'
          ' metadata={op_name="jit(step)/'
          + span_name("flat/onebit", 0, "AllToAll", "intra") + '"}')


class TestHloScopeMap:
    def test_scoped_instruction_maps_both_keys(self):
        m = prof.hlo_scope_map(hlo("jit_step", "ENTRY %main () -> u8[] {\n"
                                   + SCOPED + "\n}"))
        for key in ("all-to-all.1", ("jit_step", "all-to-all.1")):
            assert prof.cell_key(m[key]) == ("flat/onebit", None, 0,
                                             "AllToAll", "intra")

    def test_call_inherits_computation_scope(self):
        body = (
            "%decomp_fusion.2 (Arg_0.9: f32[]) -> f32[] {\n"
            '  %mul.3 = f32[] multiply(f32[] %Arg_0.9, f32[] %Arg_0.9), '
            'metadata={op_name="jit(step)/'
            + span_name("flat/onebit", 1, "AllGather", "intra") + '"}\n'
            "}\n\n"
            "ENTRY %main () -> f32[] {\n"
            "  %call.7 = f32[] call(f32[] %x.1), "
            "to_apply=%decomp_fusion.2\n"
            "}")
        m = prof.hlo_scope_map(hlo("jit_step", body))
        assert prof.cell_key(m[("jit_step", "call.7")]) == (
            "flat/onebit", None, 1, "AllGather", "intra")

    def test_ambiguous_computation_scope_not_propagated(self):
        body = (
            "%f.1 (a: f32[]) -> f32[] {\n"
            '  %m.1 = f32[] multiply(f32[] %a), metadata={op_name="'
            + span_name("p", 0, "AllToAll", "intra") + '"}\n'
            '  %m.2 = f32[] multiply(f32[] %a), metadata={op_name="'
            + span_name("p", 1, "AllGather", "intra") + '"}\n'
            "}\n\nENTRY %main () -> f32[] {\n"
            "  %call.1 = f32[] call(f32[] %x), to_apply=%f.1\n}")
        m = prof.hlo_scope_map(hlo("jit_step", body))
        assert "call.1" not in m and ("jit_step", "call.1") not in m
        assert "m.1" in m and "m.2" in m

    def test_cross_program_conflict_dropped(self):
        """Two jitted steps both compile to modules named jit_step; an
        instruction name scoped differently in each — or scoped in one
        and a plain unscoped op in the other — must not be attributed
        at all (it lands in the residual, never the wrong cell)."""
        a = hlo("jit_step", "ENTRY %e () -> u8[] {\n" + SCOPED + "\n}")
        plain = ('  %all-to-all.1 = f32[4]{0} all-to-all(f32[4]{0} %g.2), '
                 'metadata={op_name="jit(step)/psum"}')
        b = hlo("jit_step", "ENTRY %e () -> u8[] {\n" + plain + "\n}")
        m = prof.hlo_scope_map([a, b])
        assert "all-to-all.1" not in m
        assert ("jit_step", "all-to-all.1") not in m
        # agreeing duplicates survive
        m2 = prof.hlo_scope_map([a, a])
        assert ("jit_step", "all-to-all.1") in m2


# --------------------------------------------------------------------------
# the fold
# --------------------------------------------------------------------------

def ev(name, ts_us, dur_us, hlo_op="", module="jit_step"):
    e = {"name": name, "ph": "X", "ts": ts_us, "dur": dur_us, "pid": 1,
         "tid": 1}
    if hlo_op:
        e["args"] = {"hlo_op": hlo_op, "hlo_module": module}
    return e


class TestFoldTrace:
    def scope_map(self):
        return prof.hlo_scope_map(hlo(
            "jit_step", "ENTRY %e () -> u8[] {\n" + SCOPED + "\n"
            '  %fusion.1 = f32[64]{0} fusion(f32[64]{0} %p.2), '
            'kind=kLoop, metadata={op_name="jit(step)/'
            + span_name("flat/onebit", 0, "AllToAll", "intra") + '"}\n}'))

    def test_wire_vs_compute_split_and_residual(self):
        events = [
            ev(prof.WINDOW_SPAN, 0, 1000),              # 1ms window
            ev("all-to-all.1", 100, 200, "all-to-all.1"),
            ev("fusion.1", 300, 100, "fusion.1"),
            ev("unrelated.1", 500, 50, "unrelated.1"),  # residual
        ]
        fold = prof.fold_trace(events, self.scope_map())
        assert fold["n_matched"] == 2 and fold["n_unattributed"] == 2
        key = ("flat/onebit", None, 0, "AllToAll", "intra")
        c = fold["cells"][key]
        assert c["n"] == 2
        assert c["t_wire"] == pytest.approx(200e-6)
        assert c["t_compute"] == pytest.approx(100e-6)
        assert c["t_total"] == pytest.approx(300e-6)
        assert fold["t_window"] == pytest.approx(1e-3)
        assert fold["t_attributed"] == pytest.approx(300e-6)
        assert fold["t_attributed"] + fold["t_residual"] == \
            pytest.approx(fold["t_window"])
        streams = {iv["stream"] for iv in fold["intervals"]}
        assert streams == {"intra", "compute"}

    def test_window_defaults_to_trace_extent(self):
        events = [ev("all-to-all.1", 1000, 500, "all-to-all.1")]
        fold = prof.fold_trace(events, self.scope_map())
        assert fold["t_window"] == pytest.approx(500e-6)
        assert fold["t_residual"] == pytest.approx(0.0)
        # intervals are normalized to window start
        assert fold["intervals"][0]["t_start"] == pytest.approx(0.0)

    def test_scope_in_event_name_fallback(self):
        events = [ev("TSL:" + span_name("p", 0, "AllReduce", "cross"),
                     0, 100)]
        fold = prof.fold_trace(events, {})
        assert ("p", None, 0, "AllReduce", "cross") in fold["cells"]


class TestIntervalAlgebra:
    def test_merge_and_length(self):
        merged = prof.merge_spans([(3, 4), (0, 1), (0.5, 2), (4, 4)])
        assert merged == [(0, 2), (3, 4)]
        assert prof.span_length(merged) == pytest.approx(3.0)

    def test_intersect_and_clip(self):
        a = [(0, 2), (3, 5)]
        assert prof.intersect_spans(a, [(1, 4)]) == [(1, 2), (3, 4)]
        assert prof.clip_spans(a, 1.5, 10) == [(1.5, 2), (3, 5)]


# --------------------------------------------------------------------------
# overlap audit + attribution
# --------------------------------------------------------------------------

def iv(stream, a, b):
    return {"stream": stream, "t_start": a, "t_end": b}


class TestOverlapAudit:
    def test_known_layout(self):
        # compute [0,10]; intra [2,6] fully hidden; cross [8,14]: 2 hidden
        audit = prof.overlap_audit([iv("compute", 0, 10), iv("intra", 2, 6),
                                    iv("cross", 8, 14)])
        assert audit["streams"]["intra"] == {"busy": 4, "hidden": 4,
                                             "exposed": 0}
        assert audit["streams"]["cross"]["hidden"] == pytest.approx(2)
        assert audit["streams"]["cross"]["exposed"] == pytest.approx(4)
        assert audit["comm_busy"] == pytest.approx(10)
        assert audit["overlap_efficiency"] == pytest.approx(6 / 10)
        # compute hidden by the comm streams it overlaps
        assert audit["streams"]["compute"]["hidden"] == pytest.approx(6)

    def test_no_comm_is_fully_efficient(self):
        audit = prof.overlap_audit([iv("compute", 0, 5)])
        assert audit["overlap_efficiency"] == 1.0
        assert audit["comm_busy"] == 0.0

    def test_audit_diff_rows(self):
        m = prof.overlap_audit([iv("intra", 0, 4)])
        p = prof.overlap_audit([iv("intra", 0, 2), iv("cross", 0, 1)])
        rows = prof.audit_diff(m, p)
        assert [r["stream"] for r in rows] == ["cross", "intra"]
        r = {r["stream"]: r for r in rows}
        assert r["intra"]["busy_measured"] == 4
        assert r["intra"]["busy_predicted"] == 2
        assert r["cross"]["busy_measured"] == 0

    def test_attribution_fields_and_event_validates(self):
        events = [ev(prof.WINDOW_SPAN, 0, 1000),
                  ev("all-to-all.1", 0, 400, "all-to-all.1")]
        fold = prof.fold_trace(events, TestFoldTrace().scope_map())
        predicted = {"intervals": [iv("intra", 0, 1e-4),
                                   iv("compute", 0, 2e-4)],
                     "busy": {"compute": 2e-4, "intra": 1e-4}}
        att = prof.attribution(fold, n_steps=2, predicted=predicted,
                               bytes_per_step=1234.0, source="test")
        assert att["s_per_step"] == pytest.approx(5e-4)
        assert att["comm_fraction"] == pytest.approx(0.4)
        assert att["t_attributed"] + att["t_residual"] == \
            pytest.approx(att["t_window"])
        assert len(att["audit_vs_predicted"]) == 2
        assert "roofline_fraction" not in att  # no measured compute
        rec = E.make_event("profile", **att)
        assert E.validate_event(rec) is rec

    def test_predicted_intervals_consistent_with_busy(self):
        """pipeline_breakdown's intervals must reproduce its own busy
        totals and fit inside t_total — the contract the measured-vs-
        predicted audit relies on."""
        from repro.optim import get_compressor
        from repro.pipeline import Bucketer, lower_to_pipelined
        from repro.plan import flat_schedule, get_cluster, \
            pipeline_breakdown
        comp = get_compressor("onebit", block_size=64)
        d, n = 8 * 64 * 6, 8
        pp = lower_to_pipelined(flat_schedule(comp, d, n, ("data",)),
                                comp, Bucketer.for_exchange(d, n, 64, 3))
        bd = pipeline_breakdown(pp, get_cluster("ethernet-10g", n))
        assert bd["intervals"], "no predicted intervals"
        by_stream = {}
        for r in bd["intervals"]:
            assert set(r) >= {"bucket", "stage", "phase", "stream",
                              "kind", "tier", "t_start", "t_end"}
            assert 0 <= r["t_start"] < r["t_end"] <= bd["t_total"] + 1e-12
            by_stream.setdefault(r["stream"], []).append(
                (r["t_start"], r["t_end"]))
        for stream, spans in by_stream.items():
            assert prof.span_length(prof.merge_spans(spans)) == \
                pytest.approx(bd["busy"][stream])
        grid = {(r["bucket"], r["stage"]) for r in bd["intervals"]
                if r["phase"] == "wire"}
        assert grid == {(b, s) for b in range(pp.n_buckets)
                        for s in range(pp.n_stages)}


# --------------------------------------------------------------------------
# BENCH ledger
# --------------------------------------------------------------------------

class TestBenchLedger:
    def test_record_roundtrip(self, tmp_path):
        rec = B.bench_record("smoke", "bert", (4, 1), 2, False,
                             {"s_per_step": 0.5}, t=123.0)
        assert E.bench_key(rec) == ("smoke", "bert", (4, 1), 2, False)
        path = str(tmp_path / "BENCH_x.json")
        B.write_ledger(path, [rec], meta={"source": "test"})
        payload = B.load_ledger(path)
        assert payload["schema"] == E.BENCH_SCHEMA
        assert payload["records"][0]["metrics"]["s_per_step"] == 0.5

    def test_invalid_records_rejected(self, tmp_path):
        with pytest.raises(ValueError):
            E.validate_bench_record({"bench": "x"})
        with pytest.raises(ValueError):
            B.bench_record("x", "c", (1,), 1, False,
                           {"bad": "string"})
        path = str(tmp_path / "bad.json")
        with open(path, "w") as f:
            json.dump({"schema": "nope", "records": []}, f)
        with pytest.raises(ValueError, match="unknown ledger schema"):
            B.load_ledger(path)

    def test_records_from_result_shapes(self):
        recs = B.records_from_result("tp", {
            "speedup": 3.3, "label": "ignored-string",
            "bw": {"a": 1.0, "b": 2.0},
            "rows": [{"network": "eth", "gpus": 8, "frac": 0.9},
                     {"network": "ib", "gpus": 8, "frac": 0.5}],
        })
        by_cfg = {r["config"]: r for r in recs}
        assert by_cfg["all"]["metrics"] == {"speedup": 3.3}
        assert by_cfg["bw"]["metrics"] == {"a": 1.0, "b": 2.0}
        assert by_cfg["rows[0]"]["metrics"]["frac"] == 0.9
        rows = B.records_from_result("cf", [
            {"network": "eth", "gpus": 64, "allreduce_frac": 0.94}])
        assert rows[0]["config"] == "eth/64"

    def test_merge_later_wins(self):
        r1 = B.bench_record("b", "c", (1,), 1, False, {"m": 1.0})
        r2 = B.bench_record("b", "c", (1,), 1, False, {"m": 2.0})
        merged = B.merge_ledgers({"records": [r1]}, {"records": [r2]})
        assert len(merged) == 1 and merged[0]["metrics"]["m"] == 2.0


class TestBenchCompare:
    def write(self, tmp_path, name, records):
        path = str(tmp_path / name)
        B.write_ledger(path, records)
        return path

    def rec(self, metrics, config="smoke"):
        return B.bench_record("train", config, (4, 1), 2, False, metrics)

    def test_identical_passes(self, tmp_path):
        bc = load_bench_compare()
        p = self.write(tmp_path, "a.json",
                       [self.rec({"s_per_step": 0.5})])
        assert bc.main([p, p]) == 0

    def test_missing_cell_and_metric_fail(self, tmp_path):
        bc = load_bench_compare()
        base = self.write(tmp_path, "b.json", [
            self.rec({"s_per_step": 0.5}),
            self.rec({"x": 1.0}, config="other")])
        cur = self.write(tmp_path, "c.json", [self.rec({"y": 2.0})])
        out = bc.compare(B.load_ledger(base), B.load_ledger(cur))
        assert len(out["failures"]) == 2  # missing cell + missing metric
        assert bc.main([base, cur]) == 1

    def test_attribution_collapse_fails_timing_only_warns(self, tmp_path):
        bc = load_bench_compare()
        base = self.write(tmp_path, "b.json", [self.rec(
            {"s_per_step": 0.5, "attributed_fraction": 0.2})])
        cur = self.write(tmp_path, "c.json", [self.rec(
            {"s_per_step": 5.0, "attributed_fraction": 0.001})])
        out = bc.compare(B.load_ledger(base), B.load_ledger(cur))
        assert len(out["failures"]) == 1
        assert "attributed_fraction" in out["failures"][0]
        assert len(out["warnings"]) == 1          # 10x slower: WARN only
        # degenerate baseline can't brick CI
        out2 = bc.compare(B.load_ledger(cur), B.load_ledger(base))
        assert not out2["failures"]

    def test_new_cells_are_notes(self, tmp_path):
        bc = load_bench_compare()
        base = self.write(tmp_path, "b.json",
                          [self.rec({"s_per_step": 0.5})])
        cur = self.write(tmp_path, "c.json", [
            self.rec({"s_per_step": 0.5, "extra": 1.0}),
            self.rec({"m": 1.0}, config="new")])
        out = bc.compare(B.load_ledger(base), B.load_ledger(cur))
        assert not out["failures"] and not out["warnings"]
        assert len(out["notes"]) == 2


# --------------------------------------------------------------------------
# Tracer abnormal close
# --------------------------------------------------------------------------

class TestTracerAbort:
    def test_raise_ends_span_with_ok_false_and_warning(self):
        from repro.obs.metrics import TelemetrySink
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            sink = TelemetrySink(d)
            tr = TR.Tracer(sink)
            with pytest.raises(RuntimeError):
                with tr.span("outer"):
                    with tr.span("inner", step=3):
                        raise RuntimeError("boom")
            sink.close()
            recs = [json.loads(line) for line in
                    open(os.path.join(d, "telemetry.jsonl"))]
        spans = [r for r in recs if r["type"] == "span"]
        warns = [r for r in recs if r["type"] == "warning"]
        assert [s["name"] for s in spans] == ["inner", "outer"]
        assert all(s["ok"] is False for s in spans)
        assert [s["depth"] for s in spans] == [1, 0]
        assert len(warns) == 2
        assert all(w["what"] == "span.abort" for w in warns)
        assert "RuntimeError" in warns[0]["detail"]
        assert tr._depth == 0  # depth restored for the next span

    def test_ok_true_on_clean_close(self):
        tr = TR.Tracer()
        with tr.span("w", n=4):
            pass
        assert tr.spans[0]["ok"] is True and tr.spans[0]["depth"] == 0


# --------------------------------------------------------------------------
# end-to-end: real trace of a pipelined exchange folds onto the grid
# --------------------------------------------------------------------------

class TestEndToEndFold:
    def test_pipelined_exchange_trace_attributes_every_collective(
            self, tmp_path):
        """Acceptance pin: profile a real 4-device pipelined shard_map
        exchange and fold the trace — every (bucket, stage) collective
        must land on its grid cell, and attributed + residual must sum
        to the window."""
        out = run_with_devices(f"""
        import glob, os
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.obs import profile as prof, set_tracing
        from repro.optim import get_compressor
        from repro.pipeline import Bucketer, lower_to_pipelined, \\
            execute_pipelined
        from repro.plan import flat_schedule

        set_tracing(True)
        n, block, nb = 4, 64, 2
        d = n * block * 4
        comp = get_compressor("onebit", block_size=block)
        plan = flat_schedule(comp, d, n, ("data",))
        pp = lower_to_pipelined(plan, comp,
                                Bucketer.for_exchange(d, n, block, nb))
        mesh = make_mesh((n,), ("data",))
        errs0 = {{slot: jnp.zeros((d // f,), jnp.float32)
                 for slot, f in pp.slot_strides().items()}}

        def body(x):
            out, _ = execute_pipelined(pp, comp, x[0], dict(errs0))
            return out[None]

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                                  in_specs=P(None, None),
                                  out_specs=P("data", None),
                                  check_vma=False))
        x = jnp.asarray(np.random.default_rng(0)
                        .normal(size=(1, d)).astype(np.float32))
        jax.block_until_ready(f(x))          # compile outside the trace
        txt = f.lower(x).compile().as_text()

        prof_dir = {str(tmp_path)!r}
        jax.profiler.start_trace(prof_dir)
        for _ in range(2):
            jax.block_until_ready(f(x))
        jax.profiler.stop_trace()

        fold = prof.fold_profile(prof_dir, [txt])
        cells = fold["cells"]
        grid = {{(k[1], k[2]) for k in cells}}
        want = {{(b, s) for b in range(pp.n_buckets)
                for s in range(pp.n_stages)}}
        assert grid == want, (grid, want)
        for k, c in cells.items():
            assert k[0] == pp.name and c["n"] > 0 and c["t_total"] > 0, k
            assert c["t_wire"] > 0, (k, c)   # the collective itself
        assert fold["t_attributed"] > 0
        assert abs(fold["t_attributed"] + fold["t_residual"]
                   - fold["t_window"]) < 1e-9
        audit = prof.overlap_audit(fold["intervals"])
        assert audit["streams"]["intra"]["busy"] > 0
        print("CELLS", len(cells), "OK")
        """)
        assert "OK" in out
