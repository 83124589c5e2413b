"""repro.pipeline subsystem tests: Bucketer size policy + edge cases,
the CommPlan -> PipelinedPlan lowering (stage/stream structure, byte
preservation), the single-device pipelined executor parity, the
pipelined α-β pricing mode (bottleneck + fill/drain), the bucket-count
and sync-interval axes of the auto-tuner, and the measured-α/β
calibration path (comm_sweep fit + ClusterSpec.from_measured)."""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import get_compressor
from repro.pipeline import (Bucketer, PipelinedPlan, execute_pipelined,
                            lower_to_pipelined)
from repro.plan import (ClusterSpec, allreduce_schedule, autotune,
                        execute_plan, flat_schedule, get_cluster,
                        hier_schedule, pipeline_breakdown,
                        pipelined_plan_time, plan_time)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))

BLOCK = 256


def rand(d, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(d,)).astype(np.float32) * scale)


class TestBucketer:
    def test_even_split(self):
        bk = Bucketer.build(d=8 * 1024, n_buckets=4, align=1024)
        assert bk.sizes == (2048,) * 4
        assert bk.offsets == (0, 2048, 4096, 6144)

    def test_remainder_goes_to_trailing_buckets(self):
        # 12 units over 5 buckets: 2,2,2,3,3 — leading buckets small so
        # the pipeline fills fast
        bk = Bucketer.build(d=12 * 64, n_buckets=5, align=64)
        assert bk.sizes == (128, 128, 128, 192, 192)
        assert sum(bk.sizes) == bk.d

    def test_one_bucket(self):
        bk = Bucketer.build(d=4096, n_buckets=1, align=512)
        assert bk.sizes == (4096,)
        assert bk.offsets == (0,)

    def test_more_buckets_than_units_clamps(self):
        bk = Bucketer.build(d=3 * 512, n_buckets=8, align=512)
        assert bk.n_buckets == 3
        assert bk.sizes == (512, 512, 512)

    def test_unaligned_d_rejected(self):
        with pytest.raises(AssertionError):
            Bucketer.build(d=1000, n_buckets=2, align=512)

    def test_for_exchange_alignment(self):
        bk = Bucketer.for_exchange(d=8 * BLOCK * 4, n_total=8,
                                   block_size=BLOCK, n_buckets=2)
        assert all(s % (8 * BLOCK) == 0 for s in bk.sizes)


class TestLowering:
    @pytest.mark.parametrize("kind", ["onebit", "identity", "topk"])
    def test_flat_structure_and_bytes(self, kind):
        comp = get_compressor(kind, block_size=BLOCK)
        d, n = 8 * BLOCK * 6, 8
        plan = flat_schedule(comp, d, n, ("data",))
        pp = lower_to_pipelined(
            plan, comp, Bucketer.for_exchange(d, n, BLOCK, 4))
        assert isinstance(pp, PipelinedPlan)
        assert pp.n_buckets == 4 and pp.n_stages == len(plan.ops)
        assert pp.streams == tuple(op.tier for op in plan.ops)
        # bucketing rearranges WHEN bytes move, never how many
        assert pp.hlo_bytes() == plan.hlo_bytes()
        assert pp.wire_send_bytes() == plan.wire_send_bytes()
        for bp in pp.buckets:
            assert bp.plan.d_out == bp.size   # per-bucket chain validates

    @pytest.mark.parametrize("kind", ["onebit", "identity", "topk"])
    def test_hier_structure_and_slots(self, kind):
        comp = get_compressor(kind, block_size=BLOCK)
        d = 8 * BLOCK * 6
        plan = hier_schedule(comp, d, 4, 2, ("data",), ("pod",),
                             outer_ef=(kind == "topk"))
        pp = lower_to_pipelined(
            plan, comp, Bucketer.for_exchange(d, 8, BLOCK, 3))
        assert pp.err_slots == plan.err_slots
        strides = pp.slot_strides()
        assert strides["worker"] == 1
        assert strides["server"] == 4          # chunk-sized: d / n_inner
        if kind == "topk":
            assert strides["outer"] == 4
            # gather-leg EF: per-element sub-chunk slot, d / (n_in*n_out)
            assert strides["outer_ag"] == 8
        # streams: cross legs sandwiched by intra legs
        assert pp.streams[0] == "intra" and pp.streams[-1] == "intra"
        assert "cross" in pp.streams

    def test_dependency_grid(self):
        comp = get_compressor("onebit", block_size=BLOCK)
        d, n = 8 * BLOCK * 4, 8
        pp = lower_to_pipelined(
            flat_schedule(comp, d, n, ("data",)), comp,
            Bucketer.for_exchange(d, n, BLOCK, 4))
        edges = set(pp.edges())
        assert (((1, 1), (1, 0))) in edges     # own previous stage
        assert (((1, 1), (0, 1))) in edges     # previous bucket, same stage
        order = list(pp.issue_order())
        assert len(order) == pp.n_buckets * pp.n_stages
        # every op issues after its dependencies
        pos = {bs: i for i, bs in enumerate(order)}
        for dst, src in edges:
            assert pos[src] < pos[dst], (src, dst)

    def test_nonlinear_payload_refuses_to_lower(self):
        from repro.plan.ir import AllReduce, CommPlan, WireSpec
        plan = CommPlan(name="odd", d=1024, ops=(
            AllReduce(axes=("data",), n=4, tier="intra",
                      payload=(WireSpec("float32", (100,)),),
                      d_in=1024),)).validate()
        comp = get_compressor("identity")
        with pytest.raises(ValueError):
            lower_to_pipelined(plan, comp,
                               Bucketer.build(1024, 2, 512))


class TestExecutorParity:
    """Single-device (degenerate axes) parity: the multi-device shard_map
    parity across (flat, hier) x (replicated, zero1) x compressors lives
    in tests/test_distributed.py::TestPipelinedParity."""

    @pytest.mark.parametrize("kind", ["onebit", "identity", "topk"])
    @pytest.mark.parametrize("n_buckets", [1, 3, 4])
    def test_degenerate_bitwise(self, kind, n_buckets):
        comp = get_compressor(kind, block_size=BLOCK)
        d = BLOCK * 12
        plan = flat_schedule(comp, d, 1, ())
        x, we, se = rand(d, 1), rand(d, 2, .1), rand(d, 3, .1)
        o1, e1 = execute_plan(plan, comp, x, {"worker": we, "server": se})
        pp = lower_to_pipelined(
            plan, comp, Bucketer.for_exchange(d, 1, BLOCK, n_buckets))
        o2, e2 = execute_pipelined(pp, comp, x,
                                   {"worker": we, "server": se})
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        for slot in ("worker", "server"):
            np.testing.assert_array_equal(np.asarray(e1[slot]),
                                          np.asarray(e2[slot]))

    def test_one_bucket_is_serial_byte_for_byte(self):
        """n_buckets=1 degenerates to the serial plan exactly — output
        AND every EF buffer, same layout."""
        comp = get_compressor("topk", block_size=BLOCK, ratio=8)
        d = BLOCK * 8
        plan = flat_schedule(comp, d, 1, ())
        x, we, se = rand(d, 5), rand(d, 6, .1), rand(d, 7, .1)
        o1, e1 = execute_plan(plan, comp, x, {"worker": we, "server": se})
        pp = lower_to_pipelined(plan, comp,
                                Bucketer.for_exchange(d, 1, BLOCK, 1))
        assert pp.n_buckets == 1
        o2, e2 = execute_pipelined(pp, comp, x,
                                   {"worker": we, "server": se})
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        for slot in e1:
            np.testing.assert_array_equal(np.asarray(e1[slot]),
                                          np.asarray(e2[slot]))

    def test_missing_slot_raises_and_extras_pass_through(self):
        comp = get_compressor("onebit", block_size=BLOCK)
        d = BLOCK * 4
        pp = lower_to_pipelined(
            flat_schedule(comp, d, 1, ()), comp,
            Bucketer.for_exchange(d, 1, BLOCK, 2))
        with pytest.raises(AssertionError):
            execute_pipelined(pp, comp, rand(d), {"worker": rand(d)})
        extra = rand(7, 9)
        _, errs = execute_pipelined(
            pp, comp, rand(d), {"worker": rand(d, 1, .1),
                                "server": rand(d, 2, .1),
                                "spare": extra})
        np.testing.assert_array_equal(np.asarray(errs["spare"]),
                                      np.asarray(extra))


class TestServerStep:
    """The flat schedule's all_to_all hands its received chunks, still
    compressed, to the server's all_gather, which runs the compressor's
    ``server_ef_compress``: serially and in each pipelined bucket alike.
    The hierarchical schedule's server gets an averaged f32 value."""

    @pytest.mark.parametrize("schedule,n_buckets,calls", [
        ("flat", 1, 1), ("flat", 3, 3), ("hier", 1, 0), ("hier", 3, 0)])
    def test_flat_server_goes_through_the_compressor(
            self, monkeypatch, schedule, n_buckets, calls):
        import jax
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P
        from repro.optim.compressors import OneBitCompressor
        seen = []
        server = OneBitCompressor.server_ef_compress

        def spy(self, recv, err, combine):
            seen.append((tuple(p.shape for p in recv), err.shape, combine))
            return server(self, recv, err, combine)
        monkeypatch.setattr(OneBitCompressor, "server_ef_compress", spy)

        comp = get_compressor("onebit", block_size=BLOCK)
        d = BLOCK * 12
        if schedule == "flat":
            mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
            plan = flat_schedule(comp, d, 1, ("data",))
        else:
            mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                        ("pod", "data"))
            plan = hier_schedule(comp, d, 1, 1, ("data",), ("pod",))
        errs = {"worker": rand(d, 2, .1), "server": rand(d, 3, .1)}

        def run(pipelined):
            def body(x, e):
                if not pipelined:
                    return execute_plan(plan, comp, x, e)
                pp = lower_to_pipelined(plan, comp, Bucketer.for_exchange(
                    d, 1, BLOCK, n_buckets))
                return execute_pipelined(pp, comp, x, e)
            return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                                         out_specs=P(), check_vma=False))(
                rand(d, 1), errs)

        o1, e1 = run(pipelined=False)
        assert len(seen) == min(calls, 1)
        seen.clear()
        o2, e2 = run(pipelined=True)
        assert len(seen) == calls
        size = d // n_buckets
        assert all(s == (((1, size // 8), (1, size // BLOCK)), (size,),
                         "mean") for s in seen)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        for slot in errs:
            np.testing.assert_array_equal(np.asarray(e1[slot]),
                                          np.asarray(e2[slot]))


class TestPipelinedCost:
    def _hier(self, d=1 << 27, block=4096):
        comp = get_compressor("onebit", block_size=block)
        return comp, hier_schedule(comp, d, 4, 2, ("data",), ("pod",))

    def test_acceptance_strictly_faster_on_ethernet10g(self):
        """Acceptance: pipelined pricing strictly below serial on the
        ethernet-10g preset with >= 2 buckets — with the compute stream
        priced (repro.perf), serial = links + compute back-to-back."""
        from repro.plan import plan_compute_time
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        comp, plan = self._hier()
        t_serial = plan_time(plan, spec) + plan_compute_time(plan, comp,
                                                             spec)
        for nb in (2, 4):
            pp = lower_to_pipelined(
                plan, comp,
                Bucketer.for_exchange(plan.d, 8, comp.block_size, nb))
            assert pipelined_plan_time(pp, spec) < t_serial, nb
            # the link-only figure still prices below link-only serial
            assert pipelined_plan_time(pp, spec, include_compute=False) \
                < plan_time(plan, spec), nb

    def test_one_bucket_prices_exactly_serial(self):
        from repro.plan import plan_compute_time
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        comp, plan = self._hier(d=1 << 20)
        pp = lower_to_pipelined(
            plan, comp, Bucketer.for_exchange(plan.d, 8, 4096, 1))
        assert pipelined_plan_time(pp, spec) == pytest.approx(
            plan_time(plan, spec) + plan_compute_time(plan, comp, spec),
            rel=1e-12)
        assert pipelined_plan_time(pp, spec, include_compute=False) == \
            pytest.approx(plan_time(plan, spec), rel=1e-12)

    def test_latency_dominated_exchange_gets_slower(self):
        """Tiny exchange on a high-latency link: bucketing only adds
        per-op launches (link AND kernel) — the model must price that,
        or the tuner would always pick max buckets."""
        from repro.plan import plan_compute_time
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        comp, plan = self._hier(d=8 * 4096 * 8)   # ~8 KiB cross legs:
        pp = lower_to_pipelined(                   # alpha=50us dominates
            plan, comp, Bucketer.for_exchange(plan.d, 8, 4096, 8))
        assert pp.n_buckets == 8
        assert pipelined_plan_time(pp, spec) > \
            plan_time(plan, spec) + plan_compute_time(plan, comp, spec)
        assert pipelined_plan_time(pp, spec, include_compute=False) > \
            plan_time(plan, spec)

    def test_breakdown_decomposition(self):
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        comp, plan = self._hier()
        pp = lower_to_pipelined(
            plan, comp, Bucketer.for_exchange(plan.d, 8, 4096, 4))
        bd = pipeline_breakdown(pp, spec)
        # the 1-bit EF compute is the honest bottleneck of this exchange
        # on a v5e — exactly what link-only pricing could not see
        assert bd["bottleneck"] == "compute"
        assert bd["t_total"] == pytest.approx(
            bd["busy"]["compute"] + bd["fill_drain"])
        assert bd["t_total"] <= bd["t_serial"]
        assert bd["saved"] == pytest.approx(bd["t_serial"] - bd["t_total"])
        # every stream's busy time lower-bounds the schedule
        assert all(bd["t_total"] >= b for b in bd["busy"].values())
        # link-only view: the wire bottleneck is the slow cross tier
        bd0 = pipeline_breakdown(pp, spec, include_compute=False)
        assert bd0["bottleneck"] == "cross"
        assert "compute" not in bd0["busy"]

    def test_uncompressed_allreduce_plan_prices_too(self):
        spec = get_cluster("ethernet-10g", n_inner=8, n_outer=1)
        plan = allreduce_schedule(1 << 20, 8, ("data",))
        pp = lower_to_pipelined(plan, None, Bucketer.build(1 << 20, 2,
                                                           1 << 19))
        assert pipelined_plan_time(pp, spec) > 0.0


class TestTunerBucketSearch:
    def test_picks_multiple_buckets_on_slow_cross(self):
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        res = autotune(spec, 1 << 27, compressors=["onebit"],
                       block_sizes=[4096], topologies=["hier"],
                       n_buckets_options=(1, 2, 4, 8))
        assert res.best.n_buckets > 1
        one = [c for c in res.table if c.n_buckets == 1 and c.valid]
        assert res.best.t_exchange < min(c.t_exchange for c in one)

    def test_keeps_serial_when_latency_dominates(self):
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        res = autotune(spec, 8 * 4096, compressors=["onebit"],
                       block_sizes=[4096], topologies=["hier"],
                       n_buckets_options=(1, 2, 4, 8))
        assert res.best.n_buckets == 1

    def test_clamped_bucket_counts_deduped(self):
        spec = get_cluster("uniform", n_inner=4, n_outer=1)
        d = 4 * 1024 * 2          # only 2 alignment units at block 1024
        res = autotune(spec, d, compressors=["onebit"],
                       block_sizes=[1024], topologies=["flat"],
                       n_buckets_options=(1, 2, 4, 8))
        effective = sorted({c.n_buckets for c in res.table if c.valid})
        assert effective == [1, 2]     # 4 and 8 clamp onto 2

    def test_sync_interval_scales_per_step_cost(self):
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        res = autotune(spec, 1 << 20, compressors=["onebit"],
                       block_sizes=[4096], topologies=["hier"],
                       sync_intervals=(4,))
        c = res.best
        assert c.sync_interval == 4
        assert c.t_step_avg == pytest.approx(c.t_exchange / 4)
        assert c.bytes_per_step == pytest.approx(c.hlo_bytes / 4)

    def test_budget_trades_update_frequency_for_volume(self):
        """ROADMAP (2202.06009): under a per-step comm budget the tuner
        gives up update frequency ONLY when no plan fits — and buys
        frequency back with a cheaper compressor when one does."""
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        d = 1 << 20
        every = autotune(spec, d, compressors=["identity"],
                         block_sizes=[4096], topologies=["hier"],
                         sync_intervals=(1, 4, 16))
        # no budget: sync every step (best convergence)
        assert every.best.sync_interval == 1
        budget = every.best.hlo_bytes / 8   # identity@1 is 8x over
        skip = autotune(spec, d, compressors=["identity"],
                        block_sizes=[4096], topologies=["hier"],
                        sync_intervals=(1, 4, 16),
                        max_bytes_per_step=budget)
        assert skip.best.sync_interval == 16   # forced to skip syncs
        # a 1-bit wire fits the same budget at every-step sync: the
        # tuner prefers it (frequency beats volume at equal budget)
        both = autotune(spec, d, compressors=["identity", "onebit"],
                        block_sizes=[4096], topologies=["hier"],
                        sync_intervals=(1, 4, 16),
                        max_bytes_per_step=budget)
        assert both.best.compressor == "onebit"
        assert both.best.sync_interval == 1
        over = [c for c in skip.table if not c.valid]
        assert any(c.why == "over comm budget" for c in over)


class TestMeasuredCalibration:
    def _synth_samples(self, spec):
        """Synthetic timings generated FROM the α-β formulas — the fit
        must recover the generating constants."""
        from comm_sweep import _coeffs
        samples = []
        for tier, link in (("intra", spec.intra), ("cross", spec.cross)):
            for nbytes in (1 << 12, 1 << 16, 1 << 20, 1 << 23):
                for op in ("allreduce", "reduce_scatter"):
                    n = 4 if tier == "intra" else 2
                    ov, al, ib = _coeffs(op, n, nbytes)
                    t = (ov * spec.op_overhead + al * link.latency
                         + ib / link.bandwidth)
                    samples.append({"tier": tier, "op": op, "n": n,
                                    "nbytes": nbytes, "seconds": t})
        return samples

    def test_fit_recovers_generating_spec(self):
        from comm_sweep import fit_cluster
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        fit = fit_cluster(self._synth_samples(spec))
        assert fit["op_overhead"] == pytest.approx(spec.op_overhead,
                                                   rel=1e-6)
        for tier, link in (("intra", spec.intra), ("cross", spec.cross)):
            assert fit["tiers"][tier]["latency"] == pytest.approx(
                link.latency, rel=1e-6)
            assert fit["tiers"][tier]["bandwidth"] == pytest.approx(
                link.bandwidth, rel=1e-6)

    def test_from_measured_roundtrip(self, tmp_path):
        from comm_sweep import fit_cluster
        src = get_cluster("tpu-dci", n_inner=8, n_outer=2)
        fit = fit_cluster(self._synth_samples(src))
        path = tmp_path / "measured.json"
        path.write_text(json.dumps({
            "name": "measured-test", "intra": fit["tiers"]["intra"],
            "cross": fit["tiers"]["cross"],
            "op_overhead": fit["op_overhead"],
            "n_inner": 8, "n_outer": 2}))
        spec = ClusterSpec.from_measured(str(path))
        assert spec.name == "measured-test"
        assert spec.n_inner == 8 and spec.n_outer == 2
        assert spec.cross.bandwidth == pytest.approx(src.cross.bandwidth,
                                                     rel=1e-6)
        # a spec priced from its own measurements reproduces the preset's
        # plan ordering
        comp = get_compressor("onebit", block_size=4096)
        plan = hier_schedule(comp, 1 << 20, 8, 2, ("data",), ("pod",))
        assert plan_time(plan, spec) == pytest.approx(
            plan_time(plan, src), rel=1e-3)
        # re-sizing for a different deployment keeps the links
        big = ClusterSpec.from_measured(str(path), n_inner=16, n_outer=4)
        assert big.n_total == 64 and big.intra == spec.intra

    def test_fit_rejects_degenerate_groups(self):
        """n=1 groups move no bytes — their α/β rows are all-zero and
        the fit would be rank-deficient garbage; fit_cluster refuses."""
        from comm_sweep import fit_cluster
        with pytest.raises(AssertionError):
            fit_cluster([{"tier": "intra", "op": "allreduce", "n": 1,
                          "nbytes": 4096, "seconds": 1e-4}])
        with pytest.raises(AssertionError):
            fit_cluster([])

    def test_sweep_run_skips_single_device(self):
        """On a 1-device mesh there is nothing to calibrate: run()
        reports a skip instead of emitting an unphysical spec."""
        import comm_sweep
        out = comm_sweep.run((1,), sizes=(4096,), verbose=False)
        assert "skipped" in out and "intra" not in out

    def test_from_measured_single_tier_falls_back_to_intra(self, tmp_path):
        path = tmp_path / "one_tier.json"
        path.write_text(json.dumps({
            "intra": {"latency": 2e-6, "bandwidth": 40e9},
            "cross": None, "op_overhead": 4e-6, "n_inner": 8}))
        spec = ClusterSpec.from_measured(str(path))
        assert spec.cross == spec.intra
        assert spec.uniform


class TestCommLayerIntegration:
    """compressed_allreduce(n_buckets=...) on the degenerate single-rank
    path (multi-rank in test_distributed.py)."""

    def test_comm_n_buckets_bitwise(self):
        from repro.core.comm import compressed_allreduce
        comp = get_compressor("onebit", block_size=BLOCK)
        d = BLOCK * 8
        x, we, se = rand(d, 1), rand(d, 2, .1), rand(d, 3, .1)
        o1, w1, s1 = compressed_allreduce(x, we, se, (), comp)
        o2, w2, s2 = compressed_allreduce(x, we, se, (), comp,
                                          n_buckets=4)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))

    def test_step_config_pipeline_field(self):
        from repro.train.step import TrainStepConfig
        assert TrainStepConfig().n_buckets == 1
        assert TrainStepConfig(pipeline=4).n_buckets == 4
        assert TrainStepConfig(pipeline="6").n_buckets == 6
        with pytest.raises(AssertionError):
            TrainStepConfig(pipeline="auto").n_buckets
        with pytest.raises(AssertionError):
            TrainStepConfig(pipeline=0).n_buckets

    def test_checkpoint_records_bucket_count(self, tmp_path):
        """A checkpoint carries the bucket count it was written with —
        the repro.state loader uses it to lift bucket-major-era archives
        to the canonical EF keying — and stays loadable by the
        metadata-unaware reader."""
        from repro.checkpoint import load_meta, load_pytree, save_pytree
        tree = {"a": jnp.arange(4.0), "b": jnp.zeros((2,))}
        p = str(tmp_path / "ck.npz")
        save_pytree(p, tree, step=7, meta={"n_buckets": 4})
        assert load_meta(p) == {"n_buckets": 4}
        restored, step = load_pytree(p, tree)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.asarray(tree["a"]))
        # legacy checkpoint (no meta): empty dict, not an error
        save_pytree(p, tree, step=1)
        assert load_meta(p) == {}

    def test_recipe_and_resolver(self):
        from repro.configs import get_optim_recipe
        from repro.launch.train import resolve_pipeline
        spec = get_optim_recipe("onebit_adam_pipelined")
        assert spec.pipeline == "auto" and spec.topology == "auto"
        assert resolve_pipeline("off", "flat", "uniform", None, None,
                                "onebit", 4096) == 1
        assert resolve_pipeline(3, "flat", "uniform", None, None,
                                "onebit", 4096) == 3
        assert resolve_pipeline("5", "flat", "uniform", None, None,
                                "onebit", 4096) == 5
