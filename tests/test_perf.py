"""repro.perf — device specs, declared kernel costs, three-stream
pricing, and the compute-aware tuner decisions.

The byte pins here are the compute analogue of the ``--check-plans``
wire-byte pins: ``Compressor.compute_specs`` / ``adam_update_cost``
declare HBM traffic that must track the kernel implementations
(``kernels/onebit``: fused EF = 2 f32 reads + 1 f32 write + wire;
``kernels/fused_adam``: 4 reads + 3 writes fused vs 6 + 5 unfused —
both counts come from those modules' docstrings, the ground truth).
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import get_compressor
from repro.perf import (ComputeSpec, DeviceSpec, adam_update_cost,
                        get_device, list_devices)
from repro.pipeline import Bucketer, lower_to_pipelined
from repro.plan import (autotune, flat_schedule, get_cluster,
                        hier_schedule, op_compute, pipeline_breakdown,
                        pipelined_plan_time, plan_compute_time, plan_time)


class TestDeviceSpec:
    def test_presets(self):
        assert {"tpu-v5e", "tpu-v4", "tpu-v5p", "cpu-host"} <= \
            set(list_devices())
        v5e = get_device("tpu-v5e")
        assert v5e.peak_flops == 197e12 and v5e.hbm_bw == 819e9
        with pytest.raises(KeyError):
            get_device("abacus")

    def test_single_source_of_hardware_peaks(self):
        """launch.mesh constants and the roofline report must READ the
        perf.device preset, not carry their own copies."""
        from repro.analysis.roofline import RooflineReport
        from repro.launch import mesh
        v5e = get_device("tpu-v5e")
        assert mesh.PEAK_FLOPS_BF16 is v5e.peak_flops
        assert mesh.HBM_BW is v5e.hbm_bw
        assert mesh.ICI_BW is v5e.ici_bw
        assert mesh.HBM_BYTES is v5e.hbm_bytes
        rep = RooflineReport(dot_flops=197e12, hbm_bytes=819e9,
                             coll_bytes=50e9, coll_by_kind={})
        assert rep.device is v5e
        assert rep.t_compute == pytest.approx(1.0)
        assert rep.t_memory == pytest.approx(1.0)
        assert rep.t_collective == pytest.approx(1.0)
        fast = RooflineReport(dot_flops=197e12, hbm_bytes=819e9,
                              coll_bytes=50e9, coll_by_kind={},
                              device=get_device("tpu-v5p"))
        assert fast.t_compute < rep.t_compute

    def test_cluster_spec_embeds_device(self):
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        assert spec.device.name == "tpu-v5e"
        assert spec.peak_flops == spec.device.peak_flops
        slow = get_cluster("ethernet-10g", n_inner=4, n_outer=2,
                           device="cpu-host")
        assert slow.hbm_bw < spec.hbm_bw

    def test_roofline_time(self):
        dev = DeviceSpec("t", peak_flops=1e12, hbm_bw=1e11,
                         kernel_overhead=1e-6)
        # memory-bound: 1e9 bytes / 1e11 B/s = 10 ms >> 1e9 flops
        assert dev.roofline_time(1e9, 1e9, kernels=2) == pytest.approx(
            1e9 / 1e11 + 2e-6)
        # compute-bound: flops term binds
        assert dev.roofline_time(1e12, 1e3) == pytest.approx(1.0)

    def test_from_measured_roundtrip(self, tmp_path):
        from benchmarks.kernel_sweep import fit_device
        truth = {"kernel_overhead": 3e-6, "hbm_bw": 5e11}
        samples = []
        for k, hb in ((1, 1e6), (1, 64e6), (6, 1e6), (6, 64e6),
                      (5, 16e6)):
            samples.append({"op": "synth", "d": 0, "kernels": k,
                            "hbm_bytes": hb,
                            "seconds": k * truth["kernel_overhead"]
                            + hb / truth["hbm_bw"]})
        fit = fit_device(samples)
        assert fit["kernel_overhead"] == pytest.approx(3e-6, rel=1e-6)
        assert fit["hbm_bw"] == pytest.approx(5e11, rel=1e-6)
        path = tmp_path / "dev.json"
        path.write_text(json.dumps({"name": "synth", **fit,
                                    "peak_flops": None}))
        spec = DeviceSpec.from_measured(str(path))
        assert spec.hbm_bw == pytest.approx(5e11, rel=1e-6)
        assert spec.kernel_overhead == pytest.approx(3e-6, rel=1e-6)
        # unobservable fields fall back to the base preset
        assert spec.peak_flops == get_device("tpu-v5e").peak_flops


class TestComputeSpecPins:
    """Closed-form HBM-byte pins per compressor — kept in lockstep with
    the kernel/ref implementations' declared traffic."""

    def test_onebit_fused_ef_matches_kernel_traffic(self):
        d, block = 1 << 16, 4096
        comp = get_compressor("onebit", block_size=block, use_kernel=True)
        w = d // 8 + 4 * (d // block)
        assert comp.wire_bytes(d) == w
        ef = comp.compute_specs(d)["ef_compress"]
        # kernels/onebit docstring: 2 f32 reads + 1 f32 write + wire, ONE
        # launch
        assert ef.hbm_bytes == 12 * d + w
        assert ef.kernels == 1
        assert comp.compute_specs(d)["compress"].hbm_bytes == 4 * d + w
        assert comp.compute_specs(d)["decompress"].hbm_bytes == w + 4 * d

    def test_onebit_jnp_chain_costs_more(self):
        d, block = 1 << 16, 4096
        jnp_c = get_compressor("onebit", block_size=block)
        ker_c = get_compressor("onebit", block_size=block, use_kernel=True)
        w = jnp_c.wire_bytes(d)
        sj, sk = jnp_c.compute_specs(d), ker_c.compute_specs(d)
        # unfused: add pass + 2-pass compress + sign-materialising
        # decompress + residual pass
        assert sj["ef_compress"].hbm_bytes == 44 * d + 2 * w
        assert sj["ef_compress"].kernels == 6
        for op in ("compress", "decompress", "ef_compress"):
            assert sk[op].hbm_bytes < sj[op].hbm_bytes, op
            assert sk[op].kernels < sj[op].kernels, op

    def test_identity_is_near_free(self):
        comp = get_compressor("identity")
        d = 1 << 16
        specs = comp.compute_specs(d)
        assert specs["compress"].hbm_bytes == 0
        assert specs["decompress"].hbm_bytes == 0
        # ef is one add pass: read x + err, write the buffer
        assert specs["ef_compress"].hbm_bytes == 12 * d
        assert specs["ef_compress"].kernels == 1

    def test_topk_declares_sort_flops_and_scatter(self):
        d, block = 1 << 16, 4096
        comp = get_compressor("topk", block_size=block)
        w = comp.wire_bytes(d)
        specs = comp.compute_specs(d)
        assert specs["compress"].hbm_bytes == 8 * d + w
        assert specs["compress"].flops == d * 12      # log2(4096)
        assert specs["decompress"].hbm_bytes == 4 * d + 2 * w
        # base EF composition: add + compress + decompress + residual
        assert specs["ef_compress"].hbm_bytes == \
            specs["compress"].hbm_bytes + specs["decompress"].hbm_bytes \
            + 24 * d

    def test_adam_fused_vs_unfused(self):
        d = 1 << 20
        fused, unfused = adam_update_cost(d, True), adam_update_cost(d,
                                                                     False)
        # kernels/fused_adam docstring: 4 reads + 3 writes fused,
        # 6 reads + 5 writes unfused
        assert fused.hbm_bytes == 4 * d * 7
        assert unfused.hbm_bytes == 4 * d * 11
        assert fused.kernels == 1 and unfused.kernels == 5
        assert fused.flops == unfused.flops

    def test_compute_spec_additive(self):
        a = ComputeSpec(1.0, 2.0, 3)
        b = ComputeSpec(10.0, 20.0, 1)
        c = a + b
        assert (c.flops, c.hbm_bytes, c.kernels) == (11.0, 22.0, 4)

    def test_op_compute_maps_executor_rules(self):
        comp = get_compressor("onebit", block_size=4096)
        d = 1 << 20
        plan = flat_schedule(comp, d, 8, ("data",))
        a2a, ag = plan.ops
        pre, post = op_compute(a2a, comp)
        assert pre == comp.compute_specs(d)["ef_compress"]
        assert post.hbm_bytes > comp.compute_specs(d)["decompress"].hbm_bytes
        pre_g, post_g = op_compute(ag, comp)
        assert pre_g == comp.compute_specs(ag.d_in)["ef_compress"]
        assert post_g == comp.compute_specs(ag.d_out)["decompress"]
        # raw collectives carry no compressor compute
        from repro.plan import allreduce_schedule
        (ar,) = allreduce_schedule(d, 8, ("data",)).ops
        assert op_compute(ar, comp) == (ComputeSpec(), ComputeSpec())


class TestThreeStreamPricing:
    def _pp(self, device="tpu-v5e", use_kernel=False, nb=4, d=1 << 24):
        comp = get_compressor("onebit", block_size=4096,
                              use_kernel=use_kernel)
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2,
                           device=device)
        plan = hier_schedule(comp, d, 4, 2, ("data",), ("pod",))
        pp = lower_to_pipelined(plan, comp,
                                Bucketer.for_exchange(d, 8, 4096, nb))
        return comp, spec, plan, pp

    def test_busy_reports_compute_stream(self):
        comp, spec, plan, pp = self._pp()
        bd = pipeline_breakdown(pp, spec)
        assert bd["busy"]["compute"] > 0
        assert set(bd["busy"]) == {"compute", "intra", "cross"}
        # the compute stream's busy time is the plan's roofline compute
        # (lowering conserves compute exactly as it conserves bytes)
        assert bd["busy"]["compute"] >= plan_compute_time(plan, comp, spec)

    def test_lowering_conserves_bytes_with_compute_annotations(self):
        comp, spec, plan, pp = self._pp()
        assert pp.hlo_bytes() == plan.hlo_bytes()
        assert pp.buckets[0].compute  # annotations attached

    def test_compute_bound_pallas_beats_jnp(self):
        """Acceptance: where the exchange is HBM/launch-bound, the fused
        kernel path prices strictly below the jnp chain (identical wire
        bytes — only the compute stream distinguishes them)."""
        _, spec, _, pp_j = self._pp(use_kernel=False)
        _, _, _, pp_k = self._pp(use_kernel=True)
        assert pipelined_plan_time(pp_k, spec) < \
            pipelined_plan_time(pp_j, spec)
        # link-only pricing cannot tell them apart
        assert pipelined_plan_time(pp_k, spec, include_compute=False) == \
            pytest.approx(pipelined_plan_time(pp_j, spec,
                                              include_compute=False))

    def test_latency_bound_serial_beats_pipelined(self):
        """Acceptance: a tiny exchange on a launch-heavy device — every
        extra bucket duplicates kernel launches, so serial wins."""
        comp, spec, plan, pp = self._pp(device="cpu-host", nb=8,
                                        d=8 * 4096 * 8)
        t_serial = plan_time(plan, spec) + plan_compute_time(plan, comp,
                                                             spec)
        assert pipelined_plan_time(pp, spec) > t_serial

    def test_monotone_in_device_spec(self):
        """Faster HBM or cheaper launches can only shrink the price."""
        comp, spec, plan, pp = self._pp()
        base = pipelined_plan_time(pp, spec)
        import dataclasses
        faster = dataclasses.replace(
            spec, device=dataclasses.replace(spec.device,
                                             hbm_bw=spec.device.hbm_bw * 4))
        slower_launch = dataclasses.replace(
            spec, device=dataclasses.replace(
                spec.device,
                kernel_overhead=spec.device.kernel_overhead * 100))
        assert pipelined_plan_time(pp, faster) < base
        assert pipelined_plan_time(pp, slower_launch) > base
        assert plan_compute_time(plan, comp, faster) < \
            plan_compute_time(plan, comp, spec)


class TestComputeAwareTuner:
    KW = dict(compressors=["onebit"], block_sizes=[4096],
              topologies=["flat"], n_buckets_options=(1, 2, 4),
              use_kernel_options=(False, True))

    def test_decision_changes_with_compute_pricing(self):
        """Acceptance pin: on (uniform fabric, tpu-v5e, 16M params) the
        link-only coster keeps the serial jnp plan (links are cheap and
        identical for both kernel paths), while three-stream costing
        picks the PIPELINED PALLAS plan — buckets hide wire legs under
        the compute stream and the fused kernel shrinks that stream."""
        spec = get_cluster("uniform", n_inner=8)
        d = 1 << 24
        link = autotune(spec, d, price_compute=False, **self.KW).best
        three = autotune(spec, d, price_compute=True, **self.KW).best
        assert (link.n_buckets, link.use_kernel) == (1, False)
        assert (three.n_buckets, three.use_kernel) == (2, True)
        assert three.t_compute > 0 and link.t_compute == 0.0

    def test_kernel_axis_invalid_without_kernel_path(self):
        spec = get_cluster("uniform", n_inner=8)
        res = autotune(spec, 1 << 20, compressors=["topk", "onebit"],
                       block_sizes=[4096], topologies=["flat"],
                       use_kernel_options=(False, True))
        topk_kernel = [c for c in res.table
                       if c.compressor == "topk" and c.use_kernel]
        assert topk_kernel and all(not c.valid for c in topk_kernel)
        assert all("kernel" in c.why for c in topk_kernel)
        onebit_kernel = [c for c in res.table
                         if c.compressor == "onebit" and c.use_kernel]
        assert onebit_kernel and all(c.valid for c in onebit_kernel)

    def test_link_only_ties_break_to_jnp(self):
        spec = get_cluster("uniform", n_inner=8)
        res = autotune(spec, 1 << 22, price_compute=False, **self.KW)
        assert not res.best.use_kernel

    def test_predict_point_charges_exchange_compute(self):
        """The scaling report must price the SAME objective the tuner
        selected on: the exchange's compress/EF compute is in t_step."""
        from repro.analysis.scaling import predict_point
        from repro.configs import get_config
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=4)
        cfg = get_config("internlm2-1.8b")
        out = predict_point(cfg, 512, 4, spec)
        assert out["t_exchange_compute"] > 0
        assert out["t_step_compressed"] == pytest.approx(
            out["t_comm_compressed"] + out["t_exchange_compute"]
            + out["t_compute"])

    def test_candidate_summary_carries_compute_fields(self):
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        res = autotune(spec, 1 << 20, compressors=["onebit"],
                       block_sizes=[4096])
        s = res.best.summary()
        assert "use_kernel" in s and "t_compute_s" in s
        assert s["t_compute_s"] > 0

    def test_resolve_kernels_auto(self):
        """launch.train --kernels auto: the compute model decides; a
        compressor without a kernel path resolves to the jnp chain."""
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.launch.train import resolve_kernels
        cfg = get_config("internlm2-1.8b-smoke")
        mesh = make_mesh((1, 1), ("data", "model"))
        on = resolve_kernels("auto", "flat", "uniform", cfg, mesh,
                             "onebit", 4096, verbose=False)
        assert on is True       # memory-bound exchange on a v5e: pallas
        off = resolve_kernels("auto", "flat", "uniform", cfg, mesh,
                              "topk", 4096, verbose=False)
        assert off is False
        assert resolve_kernels("on", "flat", "uniform", cfg, mesh,
                               "onebit", 4096, verbose=False) is True


class TestKernelWiring:
    def test_train_step_config_kernel_enabled(self):
        from repro.train.step import TrainStepConfig
        assert not TrainStepConfig().kernel_enabled
        assert TrainStepConfig(use_kernel="on").kernel_enabled
        assert TrainStepConfig(use_kernel=True).kernel_enabled
        with pytest.raises(AssertionError):
            TrainStepConfig(use_kernel="auto").kernel_enabled
        opt = TrainStepConfig(use_kernel="on").build_optimizer()
        assert opt.compressor.use_kernel
        with pytest.raises(ValueError):
            TrainStepConfig(use_kernel="on",
                            compressor="topk").build_optimizer()

    def test_optim_spec_has_kernel_axis(self):
        from repro.configs.base import OptimSpec
        assert OptimSpec().use_kernel == "off"

    def test_with_kernels_helper(self):
        from repro.optim import get_optimizer
        opt = get_optimizer("onebit_adam")
        on = opt.with_kernels(True)
        assert on.compressor.use_kernel and not opt.compressor.use_kernel
        assert on.with_kernels(True) is on
        assert on.with_kernels(False).compressor.use_kernel is False
        lamb_topk = get_optimizer("onebit_lamb", compressor="topk")
        with pytest.raises(ValueError):
            lamb_topk.with_kernels(True)
        assert lamb_topk.with_kernels(False) is lamb_topk


class TestPipelinedKernelParity:
    """Kernel-vs-jnp wire/value parity INSIDE the pipelined executor,
    with UNEVEN buckets (the satellite the tuner's use_kernel axis
    leans on: flipping the kernel flag must never change what moves)."""

    D, BLOCK = 5 * 512, 512    # 5 alignment units -> buckets (2, 3)

    def _run(self, use_kernel):
        from repro.pipeline import execute_pipelined
        comp = get_compressor("onebit", block_size=self.BLOCK,
                              use_kernel=use_kernel)
        plan = flat_schedule(comp, self.D, 1, ())   # degenerate 1-rank
        bk = Bucketer.for_exchange(self.D, 1, self.BLOCK, 2)
        assert bk.sizes == (2 * self.BLOCK, 3 * self.BLOCK)  # uneven
        pp = lower_to_pipelined(plan, comp, bk)
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(self.D,)).astype(np.float32))
        errs = {"worker": jnp.asarray(
            rng.normal(size=(self.D,)).astype(np.float32)) * 0.1,
            "server": jnp.asarray(
                rng.normal(size=(self.D,)).astype(np.float32)) * 0.1}
        out, new_errs = execute_pipelined(pp, comp, x, errs)
        return comp, bk, x, errs, out, new_errs

    def test_bitwise_wire_format_per_bucket(self):
        comp_j, bk, x, errs, _, _ = self._run(False)
        comp_k = get_compressor("onebit", block_size=self.BLOCK,
                                use_kernel=True)
        for off, size in zip(bk.offsets, bk.sizes):
            buf = x[off:off + size] + errs["worker"][off:off + size]
            pk_j, sc_j = comp_j.compress(buf)
            pk_k, sc_k = comp_k.compress(buf)
            # sign bitmap: BITWISE; scales: same math, fused reduction
            np.testing.assert_array_equal(np.asarray(pk_j),
                                          np.asarray(pk_k))
            np.testing.assert_allclose(np.asarray(sc_j), np.asarray(sc_k),
                                       rtol=1e-6)

    def test_value_and_ef_parity(self):
        _, _, _, _, out_j, errs_j = self._run(False)
        _, _, _, _, out_k, errs_k = self._run(True)
        np.testing.assert_allclose(np.asarray(out_j), np.asarray(out_k),
                                   rtol=1e-6, atol=1e-6)
        for slot in ("worker", "server"):
            np.testing.assert_allclose(np.asarray(errs_j[slot]),
                                       np.asarray(errs_k[slot]),
                                       rtol=1e-5, atol=1e-6)


class TestDeviceKinds:
    """On a TPU the pricing preset follows ``device_kind``; an unknown
    kind or a preset naming another chip is an error, not a default."""

    class _Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    def test_v5e_kind_maps_to_its_preset(self):
        from repro.perf import resolve_device
        dev = self._Dev("tpu", "TPU v5 lite")
        assert resolve_device(None, dev) == "tpu-v5e"
        assert resolve_device("tpu-v5e", dev) == "tpu-v5e"

    def test_unknown_kind_raises(self):
        from repro.perf import preset_for_kind, resolve_device
        with pytest.raises(KeyError):
            preset_for_kind("TPU v99")
        with pytest.raises(KeyError):
            resolve_device(None, self._Dev("tpu", "TPU v99"))

    def test_preset_naming_another_chip_raises(self):
        from repro.perf import resolve_device
        with pytest.raises(ValueError):
            resolve_device("tpu-v4", self._Dev("tpu", "TPU v5 lite"))

    def test_cpu_keeps_the_requested_preset(self):
        from repro.perf import resolve_device
        cpu = self._Dev("cpu", "cpu")
        assert resolve_device(None, cpu) == "tpu-v5e"
        assert resolve_device("cpu-host", cpu) == "cpu-host"
        assert resolve_device("tpu-v4", cpu) == "tpu-v4"
