"""Benchmark: communication volume of the compressed allreduce
(paper Fig. 3 / Sec. 6 / the "5x less end-to-end volume" claim) — and
the plan-vs-HLO validation gate (``--check-plans``).

Measures the bytes that actually cross the interconnect by compiling the
optimizer exchange on an 8-way mesh and parsing the collective operand
bytes out of the optimized HLO — the wire format is real for EVERY
registered compressor (packed uint8 + f32 scales for 1-bit; values +
16-bit intra-block indices for top-k), so the reduction shows up in the
compiled artifact, not in a simulation.

Since the comm layer lowers every schedule through the ``repro.plan``
IR, the same :class:`CommPlan` objects the executor ran can be priced
analytically: ``--check-plans`` asserts, for every registered
compressor x topology, that the cost model's predicted collective bytes
(``plan.hlo_bytes()``) EXACTLY equal the bytes counted in the compiled
HLO by ``repro.analysis.roofline``.  This is the invariant that keeps
the α-β cost model (and therefore ``topology="auto"``) honest — CI runs
it on every push and uploads the cost-model JSON as an artifact
(``--json``).

Cross-pod (DCI) accounting comes from ``repro.plan.cost.cross_pod_bytes``
over the same plans: the hierarchical schedule crosses the DCI at
SERVER-CHUNK granularity (chunk = d/n_inner), so its per-pod DCI bytes
shrink by ~n_inner x versus flat — the whole point of running the
paper's server stage within the pod.

``--check-plans`` also pins the PIPELINED executor (``repro.pipeline``,
``n_buckets=2``): bucketing must rearrange WHEN bytes move, never how
many, so ``PipelinedPlan.hlo_bytes()`` — the figure the pipelined cost
mode prices — is asserted against the compiled HLO of the bucketed
exchange with the same exactness as serial.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from repro.optim import get_compressor, list_compressors
from repro.plan import (cross_pod_bytes, flat_schedule, get_cluster,
                        hier_schedule, needs_outer_ef)

D = 1 << 20          # 1M params
N_FLAT = 8           # flat measurement mesh
N_INNER, N_OUTER = 4, 2   # hier measurement mesh (pods x dp)
BLOCK = 4096
PIPE_BUCKETS = 2     # bucket count for the pipelined HLO pin

_MEASURE_CODE = """
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.analysis.roofline import analyze_compiled
from repro.core.comm import (compressed_allreduce,
                             compressed_allreduce_hierarchical)
from repro.launch.mesh import make_mesh
from repro.optim import get_compressor
from repro.plan.schedules import needs_outer_ef

d, block = {d}, {block}
n, n_in, n_out = {n}, {n_in}, {n_out}
topos = {topos!r}
pipe_buckets = {pipe_buckets}
out = {{}}
for kind in {kinds!r}:
    comp = get_compressor(kind, block_size=block)

    # --- flat: n-way single-level schedule -------------------------------
    mesh = make_mesh((n,), ("data",))

    def measure_flat(key, n_buckets):
        def body(x, we, se):
            o, nw, ns = compressed_allreduce(x[0], we[0], se[0],
                                             ("data",), comp,
                                             n_buckets=n_buckets)
            return o[None], nw[None], ns[None]

        f = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("data", None),) * 3,
            out_specs=(P("data", None),) * 3, check_vma=False))
        args = (jax.ShapeDtypeStruct((n, d), jnp.float32),
                jax.ShapeDtypeStruct((n, d), jnp.float32),
                jax.ShapeDtypeStruct((n, d // n), jnp.float32))
        rep = analyze_compiled(f.lower(*args).compile())
        out[key] = {{"bytes": rep.coll_bytes,
                     "kinds": dict(rep.coll_by_kind)}}

    measure_flat(f"flat/{{kind}}", 1)
    if pipe_buckets > 1:
        measure_flat(f"pipe/flat/{{kind}}", pipe_buckets)

    # --- hier: (n_out pods) x (n_in dp) two-level schedule ----------------
    if "hier" not in topos:
        continue
    mesh2 = make_mesh((n_out, n_in), ("pod", "data"))
    outer_ef = needs_outer_ef(comp)

    def measure_hier(key, n_buckets):
        def body2(x, we, se, oe, oae):
            errs = {{"worker": we[0, 0], "server": se[0, 0]}}
            if outer_ef:
                errs["outer"] = oe[0, 0]
                errs["outer_ag"] = oae[0, 0]
            o, errs = compressed_allreduce_hierarchical(
                x[0, 0], errs, inner_axes=("data",),
                outer_axes=("pod",), cfg=comp, n_buckets=n_buckets)
            lift = lambda a: a[None, None]
            return (lift(o), lift(errs["worker"]), lift(errs["server"]),
                    lift(errs.get("outer", oe[0, 0])),
                    lift(errs.get("outer_ag", oae[0, 0])))

        f2 = jax.jit(jax.shard_map(
            body2, mesh=mesh2, in_specs=(P("pod", "data", None),) * 5,
            out_specs=(P("pod", "data", None),) * 5, check_vma=False))
        args2 = (jax.ShapeDtypeStruct((n_out, n_in, d), jnp.float32),
                 jax.ShapeDtypeStruct((n_out, n_in, d), jnp.float32),
                 jax.ShapeDtypeStruct((n_out, n_in, d // n_in),
                                      jnp.float32),
                 jax.ShapeDtypeStruct((n_out, n_in, d // n_in),
                                      jnp.float32),
                 jax.ShapeDtypeStruct((n_out, n_in, d // (n_in * n_out)),
                                      jnp.float32))
        rep2 = analyze_compiled(f2.lower(*args2).compile())
        out[key] = {{"bytes": rep2.coll_bytes,
                     "kinds": dict(rep2.coll_by_kind)}}

    measure_hier(f"hier/{{kind}}", 1)
    if pipe_buckets > 1:
        measure_hier(f"pipe/hier/{{kind}}", pipe_buckets)
print(json.dumps(out))
"""


def measured_volumes(d: int = D, n: int = N_FLAT, n_in: int = N_INNER,
                     n_out: int = N_OUTER, block: int = BLOCK, kinds=None,
                     topologies=("flat", "hier"), pipe_buckets: int = 0):
    """Compiled collective bytes per (topology, compressor), measured in
    a subprocess with forced host devices (benchmarks themselves keep
    seeing the real single device). Each requested topology is a
    separate XLA compile — ask only for what you read."""
    kinds = list(kinds or list_compressors())
    env = dict(os.environ)
    # the child means the CPU's forced host devices, never a chip the
    # parent's machine may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" + \
        str(max(n, n_in * n_out))
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c",
         _MEASURE_CODE.format(d=d, n=n, n_in=n_in, n_out=n_out,
                              block=block, kinds=kinds,
                              topos=tuple(topologies),
                              pipe_buckets=pipe_buckets)],
        capture_output=True, text=True, env=env, timeout=1800)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def predicted_plans(d: int = D, n: int = N_FLAT, n_in: int = N_INNER,
                    n_out: int = N_OUTER, block: int = BLOCK, kinds=None,
                    pipe_buckets: int = 0):
    """The SAME CommPlans the comm layer lowers, built offline — plus,
    with ``pipe_buckets > 1``, their pipelined lowerings (the very
    PipelinedPlans the bucketed executor runs)."""
    plans = {}
    for kind in (kinds or list_compressors()):
        comp = get_compressor(kind, block_size=block)
        plans[f"flat/{kind}"] = flat_schedule(comp, d, n, ("data",))
        plans[f"hier/{kind}"] = hier_schedule(
            comp, d, n_in, n_out, ("data",), ("pod",),
            outer_ef=needs_outer_ef(comp))
        if pipe_buckets > 1:
            from repro.pipeline import Bucketer, lower_to_pipelined
            for topo, n_tot in (("flat", n), ("hier", n_in * n_out)):
                bk = Bucketer.for_exchange(d, n_tot, block, pipe_buckets)
                plans[f"pipe/{topo}/{kind}"] = lower_to_pipelined(
                    plans[f"{topo}/{kind}"], comp, bk)
    return plans


def check_plans(verbose: bool = True):
    """Assert predicted plan bytes == compiled HLO bytes for every
    registered compressor x topology, serial AND pipelined. Returns the
    comparison table."""
    vols = measured_volumes(pipe_buckets=PIPE_BUCKETS)
    plans = predicted_plans(pipe_buckets=PIPE_BUCKETS)
    table = {}
    failures = []
    for key, plan in sorted(plans.items()):
        want = plan.hlo_bytes()
        got = vols[key]["bytes"]
        ok = int(want) == int(got)
        table[key] = {"predicted": int(want), "measured_hlo": int(got),
                      "match": ok, "kinds": vols[key]["kinds"]}
        if not ok:
            failures.append(key)
        if verbose:
            mark = "PASS" if ok else "FAIL"
            print(f"  [{mark}] {key:16s} predicted {int(want):>10d} "
                  f"== HLO {int(got):>10d}")
    assert not failures, \
        f"cost-model bytes drifted from compiled HLO for: {failures}"
    return table


def endtoend_volume_ratio(warmup_ratio: float, compression: float = 32.0):
    """Paper Sec. 7.1: 1 / (w + (1-w)/16) for fp16; we report the fp32
    analogue with the measured wire compression."""
    return 1.0 / (warmup_ratio + (1.0 - warmup_ratio) / compression)


def run(verbose: bool = True):
    d = D
    results = {}
    # hier numbers below come from the plans analytically; only flat
    # needs the (expensive) compiled measurement here
    vols = measured_volumes(topologies=("flat",))
    b_id = vols["flat/identity"]["bytes"]
    results["uncompressed_bytes_per_dev"] = int(b_id)
    # per-compressor: compiled bytes + the registry's analytic wire bytes
    for kind in list_compressors():
        comp = get_compressor(kind, block_size=BLOCK)
        b = vols[f"flat/{kind}"]["bytes"]
        results[f"{kind}_bytes_per_dev"] = int(b)
        results[f"{kind}_compression_x"] = round(b_id / max(b, 1), 2)
        results[f"{kind}_analytic_payload_ratio"] = round(
            4 * d / comp.wire_bytes(d), 2)
    ratio = b_id / vols["flat/onebit"]["bytes"]
    results["wire_compression_x"] = round(ratio, 2)
    # paper's end-to-end claim with BERT-Large warmup ratio 23K/152K
    w = 23_000 / 152_000
    results["paper_endtoend_volume_x_fp16"] = round(
        endtoend_volume_ratio(w, 16.0), 2)   # paper computes ~5x with 1/16
    results["our_endtoend_volume_x_fp32"] = round(
        endtoend_volume_ratio(w, ratio), 2)
    # hierarchical schedule: cross-pod (DCI) accounting from the SAME
    # plans the executor lowers, priced by repro.plan.cost
    spec = get_cluster("ethernet-10g", n_inner=N_INNER, n_outer=N_OUTER)
    plans = predicted_plans()
    for kind in list_compressors():
        comp = get_compressor(kind, block_size=BLOCK)
        hier = cross_pod_bytes(plans[f"hier/{kind}"], spec)
        flat_plan = flat_schedule(comp, d, N_INNER * N_OUTER,
                                  ("pod", "data"), tier="cross")
        flat = cross_pod_bytes(flat_plan, spec)
        results[f"hier_cross_pod_bytes_{kind}"] = hier
        results[f"flat_cross_pod_bytes_{kind}"] = flat
        results[f"hier_dci_reduction_x_{kind}"] = round(
            flat / max(hier, 1), 2)
    if verbose:
        print("== comm_volume (Fig. 3 / Sec. 6) ==")
        for k, v in results.items():
            print(f"  {k}: {v}")
        ok = ratio > 10.0
        ok_hier = results["hier_dci_reduction_x_onebit"] > N_INNER * 0.5
        print(f"  [{'PASS' if ok else 'FAIL'}] compiled wire compression "
              f"{ratio:.1f}x > 10x")
        print(f"  [{'PASS' if ok_hier else 'FAIL'}] hierarchical schedule "
              f"cuts cross-pod bytes "
              f"{results['hier_dci_reduction_x_onebit']}x")
    return results


def cost_model_report():
    """Auto-tuner tables for a few cluster presets (the CI artifact),
    including the pipelined bucket-count search and the jnp-vs-Pallas
    kernel axis the repro.perf compute stream prices."""
    from repro.plan import autotune, pipeline_breakdown
    from repro.pipeline import Bucketer, lower_to_pipelined
    report = {}
    for cluster in ("uniform", "ethernet-10g", "infiniband"):
        spec = get_cluster(cluster, n_inner=N_INNER, n_outer=N_OUTER)
        res = autotune(spec, D, block_sizes=(1024, 4096, 16384),
                       n_buckets_options=(1, 2, 4, 8),
                       use_kernel_options=(False, True))
        report[cluster] = res.summary()
    # per-bucket pipelined pricing of the hier/onebit exchange (the
    # overlap-vs-launch-latency trade the tuner searches)
    comp = get_compressor("onebit", block_size=BLOCK)
    plan = hier_schedule(comp, D, N_INNER, N_OUTER, ("data",), ("pod",))
    pipe = {}
    for cluster in ("uniform", "ethernet-10g", "infiniband"):
        spec = get_cluster(cluster, n_inner=N_INNER, n_outer=N_OUTER)
        rows = {}
        for nb in (1, 2, 4, 8):
            pplan = lower_to_pipelined(
                plan, comp,
                Bucketer.for_exchange(D, N_INNER * N_OUTER, BLOCK, nb))
            rows[nb] = pipeline_breakdown(pplan, spec)
        pipe[cluster] = rows
    report["pipelined_hier_onebit"] = pipe
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check-plans", action="store_true",
                    help="assert predicted plan bytes == compiled HLO "
                         "bytes for every compressor x topology, serial "
                         "and pipelined (n_buckets=2)")
    ap.add_argument("--json", default=None,
                    help="write results + cost-model tables to this path")
    args = ap.parse_args(argv)
    out = {}
    if args.check_plans:
        print("== plan validation (predicted vs compiled HLO bytes, "
              "serial + pipelined) ==")
        out["plan_check"] = check_plans()
        out["cost_model"] = cost_model_report()
        print("  all plans match the compiled HLO")
    else:
        out["volumes"] = run()
        out["cost_model"] = cost_model_report()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return out


if __name__ == "__main__":
    main()
