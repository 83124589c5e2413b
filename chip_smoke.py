"""Bring-up check: BERT-Large 1-bit Adam training on a TPU through the
normal entry point, ``repro.launch.train.run``.

Usage (from the repository root, on a machine with a TPU):

    python chip_smoke.py             # one chip: the jnp and Pallas main paths
    python chip_smoke.py --chips 4   # four chips: the dp-4 compressed
                                     # exchange against its 32-bit ablation

One chip, in one process:

  1. device: the first JAX device must be a TPU (no CPU fallback);
  2. main path, jnp: ``bert-large`` at published widths on a 1x1 mesh,
     recipe ``onebit_adam``, batch 32 x sequence 128 (the paper's first
     pre-training phase), 3 warmup (Adam) steps then 3 compressed (1-bit)
     steps, ``kernels="off"``; every loss finite;
  3. main path, Pallas: the same run with ``kernels="on"``; the compiled
     compressed step must hold Mosaic kernels (``tpu_custom_call``) and
     every loss must match phase 2 (``LOSS_RTOL``), and the fused 1-bit
     kernel must give the jnp path's bitmap bit for bit on the chip.

``--chips 4`` runs only ``bert-large`` on a (4, 1) data-parallel mesh,
global batch 128, with ``onebit_adam`` and with ``onebit_adam_32bit``
(the identity compressor): warmup losses must agree, the 1-bit
compressed losses must stay within ``BAND`` of the last warmup loss,
every device must hold about the same bytes, and the exchange itself,
run alone at BERT-Large length with the identity compressor, must give
the exact mean of the four workers' vectors.

Per-step wall times run to a host fetch of the step's metrics; they are
smoke times, not a benchmark.  The last line of standard output is one
JSON object, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "bert-large"
SEQ = 128
WARMUP_STEPS, STEPS = 3, 6
BATCH_ONE_CHIP = 32
BATCH_FOUR_CHIPS = 128
# kernels on vs off.  The fused Adam kernel agrees with XLA's elementwise
# chain to the rounding of x on a v5e, but the two step programs are
# compiled apart and their step-0 gradients already differ in the last
# bits (gradient norms 5.599576473 vs 5.599575996 on a v5e).  Adam's first
# steps move every parameter by about lr whatever its gradient's size, so
# gradient elements at rounding-noise level take updates of either sign,
# and the loss drifts apart: by 3.9e-5 of itself in warmup and 6.8e-5 in
# the compressed steps on a v5e.  A wrong kernel moves it by far more (a
# compressed step moves the loss by ~0.2), so the losses are held to 2e-4;
# the wire format is checked bit for bit on its own (``wire_check``).
LOSS_RTOL = 2e-4
WIRE_LEN = 1 << 24
SERVER_CHUNK = 4099 * 4096     # whole blocks, a partial last 1,024-row grid step
# 1-bit compressed losses: three steps at a warmup learning rate
# (<= 6e-5) move a loss of ~10.8 by a few percent at most unless the
# exchange is broken (a divergence, a wrong scale, a lost residual).  The
# 32-bit ablation is printed beside them but held to no band: after three
# warmup steps the frozen variance holds exact zeros (embedding rows of
# tokens no warmup batch held), and an element with v = 0 moves by
# m / eps once its token turns up.  On four v5e chips it went
# 10.81 -> 20.44 -> 1841.5 while the 1-bit run stayed near 10.9; the
# exchange it runs is checked on its own (``exchange_check``).
BAND = 0.10
EXCHANGE_LEN = 364_564_480      # bert-large's padded flat length
EXCHANGE_RTOL = 1e-6
OUT = ROOT / "chiprun_out" / "chip_smoke"


def find_devices(n_chips: int):
    import jax
    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (platform "
                         f"{d0.platform!r}); nothing is measured elsewhere")
    if len(devices) != n_chips:
        raise SystemExit(f"chip_smoke: expected {n_chips} chip(s), found "
                         f"{len(devices)}")
    return devices


def read_spans(tel_dir: Path):
    """The run's telemetry spans by name (``compile.*``: each program's
    first call; ``train.window``: one per logged step)."""
    spans = {}
    with open(tel_dir / "telemetry.jsonl") as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("type") == "span":
                spans.setdefault(rec["name"], []).append(rec)
    return spans


def train(tag: str, mesh_shape, batch: int, **kw):
    """One ``launch.train.run``; checks the stage schedule and finite
    losses, prints compile seconds and per-step loss and wall time."""
    from repro.launch.train import run
    tel = OUT / tag
    tel.mkdir(parents=True, exist_ok=True)
    programs = {}
    params, opt, _ = run(ARCH, STEPS, batch, SEQ, mesh_shape,
                         warmup_steps=WARMUP_STEPS, log_every=1,
                         log_file=str(tel / "history.json"),
                         telemetry=str(tel), programs=programs, **kw)
    with open(tel / "history.json") as f:
        history = json.load(f)
    spans = read_spans(tel)
    for name, recs in sorted(spans.items()):
        if name.startswith("compile."):
            print(f"{tag}: {name} {recs[0]['dur']:.1f}s "
                  f"(trace + lower + compile)", flush=True)
    walls = {r["step"]: r["dur"] for r in spans.get("train.window", [])}
    stages = [h["stage"] for h in history]
    want = ["warmup"] * WARMUP_STEPS + ["compressed"] * (STEPS - WARMUP_STEPS)
    if stages != want:
        raise AssertionError(f"{tag}: stages {stages} != {want}")
    losses = [float(h["loss"]) for h in history]
    for h, loss in zip(history, losses):
        print(f"{tag}: step {h['step']} {h['stage']:10s} loss {loss:.6f} "
              f"wall {walls.get(h['step'], float('nan')):.3f}s (smoke time, "
              f"to a host fetch)", flush=True)
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{tag}: non-finite loss in {losses}")
    return params, opt, losses, programs


def compressed_hlo(programs, params, opt, batch: int) -> str:
    """The compiled compressed step's HLO, built as the profile ledger
    builds it (``launch.train.emit_profile_ledger``)."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.data import SyntheticStream
    data = SyntheticStream(get_config(ARCH),
                           InputShape("custom", SEQ, batch, "train")
                           ).batch_at(0)
    fn = programs[("compressed", True)]
    return (fn.build(data).lower(params, opt, data, jnp.float32(1e-3))
            .compile().as_text())


def max_rel_diff(a, b) -> float:
    return max(abs(x - y) / max(abs(y), 1e-30) for x, y in zip(a, b))


def one_chip() -> None:
    print(f"batch {BATCH_ONE_CHIP} x seq {SEQ} on a 1x1 mesh, {ARCH}",
          flush=True)
    params, opt, jnp_losses, _ = train(
        "jnp", (1, 1), BATCH_ONE_CHIP, recipe="onebit_adam", kernels="off")
    del params, opt
    params, opt, pallas_losses, programs = train(
        "pallas", (1, 1), BATCH_ONE_CHIP, recipe="onebit_adam",
        kernels="on")
    hlo = compressed_hlo(programs, params, opt, BATCH_ONE_CHIP)
    del params, opt
    n_calls = hlo.count("tpu_custom_call")
    print(f"pallas: compressed step HLO holds {n_calls} tpu_custom_call "
          f"reference(s)", flush=True)
    if n_calls == 0:
        raise AssertionError("pallas: no Mosaic kernel in the compiled "
                             "compressed step")
    warm = max_rel_diff(pallas_losses[:WARMUP_STEPS],
                        jnp_losses[:WARMUP_STEPS])
    comp = max_rel_diff(pallas_losses[WARMUP_STEPS:],
                        jnp_losses[WARMUP_STEPS:])
    print(f"pallas vs jnp: max relative loss difference {warm:.3e} in "
          f"warmup, {comp:.3e} compressed (tolerance {LOSS_RTOL:.0e})",
          flush=True)
    if max(warm, comp) > LOSS_RTOL:
        raise AssertionError(f"pallas losses {pallas_losses} differ from "
                             f"jnp losses {jnp_losses}")
    wire_check()
    server_check()


def wire_check() -> None:
    """One error-feedback compress and decompress of ``WIRE_LEN`` random
    elements through the 1-bit compressor, jnp path against the fused
    kernel, bit for bit: a few steps' losses are too coarse a check of
    the wire format."""
    import jax
    import numpy as np
    from repro.optim.compressors import OneBitCompressor
    kx, ke = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (WIRE_LEN,))
    err = 0.1 * jax.random.normal(ke, (WIRE_LEN,))
    out = {}
    for tag, use_kernel in (("jnp", False), ("pallas", True)):
        comp = OneBitCompressor(use_kernel=use_kernel)
        payload, new_err = jax.jit(comp.ef_compress)(x, err)
        out[tag] = [np.asarray(a) for a in
                    (*payload, new_err, jax.jit(comp.decompress)(payload))]
    (pk_j, sc_j, err_j, de_j), (pk_k, sc_k, err_k, de_k) = (
        out["jnp"], out["pallas"])
    n_bad = int(np.sum(pk_j != pk_k))
    scale_rel = float(np.max(np.abs(sc_k - sc_j) / np.abs(sc_j)))
    # the scales differ in summation order only; the residual and the
    # decompressed values follow them
    err_abs = float(np.max(np.abs(err_k - err_j)))
    de_rel = float(np.max(np.abs(de_k - de_j) / np.abs(de_j)))
    print(f"wire check ({WIRE_LEN} elements): {n_bad} bitmap bytes differ; "
          f"scales max relative difference {scale_rel:.3e}, decompressed "
          f"{de_rel:.3e}; residual max absolute difference {err_abs:.3e}",
          flush=True)
    if n_bad or scale_rel > 1e-6 or de_rel > 1e-6 or err_abs > 1e-6:
        raise AssertionError("the fused 1-bit kernel's wire output differs "
                             "from the jnp path's")


def server_check() -> None:
    """The flat schedule's server step on ``n`` received chunks of
    ``SERVER_CHUNK`` elements (a partial last grid step), the server
    kernel against the jnp decompress, mean and compress, with the
    server error donated as the train step donates it: the same bitmap,
    the scales and residual up to summation order."""
    import jax
    import numpy as np
    from repro.optim.compressors import Compressor, OneBitCompressor
    comp = OneBitCompressor()
    steps = {"jnp": functools.partial(Compressor.server_ef_compress, comp),
             "pallas": comp.server_ef_compress}
    for n in (1, 4):
        kx, ke = jax.random.split(jax.random.PRNGKey(n))
        recv = jax.jit(jax.vmap(comp.compress))(
            jax.random.normal(kx, (n, SERVER_CHUNK)))
        out = {}
        for tag, step in steps.items():
            err = 0.1 * jax.random.normal(ke, (SERVER_CHUNK,))
            fn = jax.jit(lambda r, e: step(r, e, "mean"), donate_argnums=1)
            (pk, sc), new_err = fn(recv, err)
            out[tag] = [np.asarray(a) for a in (pk, sc, new_err)]
        (pk_j, sc_j, err_j), (pk_k, sc_k, err_k) = out["jnp"], out["pallas"]
        n_bad = int(np.sum(pk_j != pk_k))
        scale_rel = float(np.max(np.abs(sc_k - sc_j) / np.abs(sc_j)))
        err_abs = float(np.max(np.abs(err_k - err_j)))
        print(f"server check (n {n}, {SERVER_CHUNK} elements): {n_bad} "
              f"bitmap bytes differ; scales max relative difference "
              f"{scale_rel:.3e}; residual max absolute difference "
              f"{err_abs:.3e}", flush=True)
        if n_bad or scale_rel > 1e-6 or err_abs > 1e-6:
            raise AssertionError("the server kernel's output differs from "
                                 "the jnp server step's")


def four_chips(devices) -> None:
    print(f"global batch {BATCH_FOUR_CHIPS} x seq {SEQ} on a (4, 1) dp "
          f"mesh, {ARCH}", flush=True)
    losses = {}
    for recipe in ("onebit_adam", "onebit_adam_32bit"):
        params, opt, losses[recipe], _ = train(
            recipe, (4, 1), BATCH_FOUR_CHIPS, recipe=recipe)
        in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
        print(f"{recipe}: bytes_in_use per device "
              + " ".join(f"{b / 2**30:.3f}GiB" for b in in_use), flush=True)
        if max(in_use) > 1.5 * min(in_use):
            raise AssertionError(f"{recipe}: uneven device memory {in_use}")
        del params, opt
    onebit, ident = losses["onebit_adam"], losses["onebit_adam_32bit"]
    warm = max_rel_diff(onebit[:WARMUP_STEPS], ident[:WARMUP_STEPS])
    last_warm = onebit[WARMUP_STEPS - 1]
    drift = max(abs(x - last_warm) / last_warm
                for x in onebit[WARMUP_STEPS:])
    comp = max_rel_diff(onebit[WARMUP_STEPS:], ident[WARMUP_STEPS:])
    # the two warmup programs run the same allreduce, but their optimizer
    # states differ, so they are compiled apart: held as kernels on/off
    print(f"onebit vs 32-bit: warmup max relative difference {warm:.3e} "
          f"(tolerance {LOSS_RTOL:.0e}); compressed {comp:.3e} (no band, "
          f"see BAND); 1-bit compressed losses within {drift:.3e} of the "
          f"last warmup loss (band {BAND})", flush=True)
    if warm > LOSS_RTOL:
        raise AssertionError(f"warmup losses differ: {onebit} vs {ident}")
    if drift > BAND:
        raise AssertionError(f"1-bit compressed losses left the band: "
                             f"{onebit}")
    exchange_check()


def exchange_check() -> None:
    """``repro.core.comm.compressed_exchange`` alone on a dp-4 mesh at
    bert-large's flat length, identity compressor: every worker must
    get the mean of the four workers' vectors (up to summation order)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import comm
    from repro.launch.mesh import make_mesh
    from repro.optim import get_compressor
    d = EXCHANGE_LEN

    def body(key):
        rank = jax.lax.axis_index("data")
        m = jax.random.normal(jax.random.fold_in(key[0], rank), (d,))
        errs = {"worker": jnp.zeros((d,)), "server": jnp.zeros((d // 4,))}
        out, _ = comm.compressed_exchange(m, errs, ("data",), (),
                                          get_compressor("identity"))
        exact = jax.lax.pmean(m, "data")
        return (jnp.max(jnp.abs(out - exact))
                / jnp.max(jnp.abs(exact)))[None]

    fn = jax.jit(jax.shard_map(body, mesh=make_mesh((4,), ("data",)),
                               in_specs=P(), out_specs=P("data"),
                               check_vma=False))
    rel = np.asarray(fn(jax.random.PRNGKey(0)[None]))
    print(f"exchange check ({d} elements, identity, dp 4): max relative "
          f"difference from the exact mean per worker {rel.tolist()} "
          f"(tolerance {EXCHANGE_RTOL:.0e})", flush=True)
    if not np.all(rel <= EXCHANGE_RTOL):
        raise AssertionError("the dp-4 exchange does not give the mean")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip main paths; 4: the dp-4 "
                         "compressed exchange and its 32-bit ablation")
    args = ap.parse_args(argv)
    devices = find_devices(args.chips)
    from repro.launch.train import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    if args.chips == 1:
        one_chip()
    else:
        four_chips(devices)
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
