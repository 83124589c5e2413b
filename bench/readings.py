#!/usr/bin/env python3
"""Readings of one seed of a cell, taken the way a benchmark run takes
them, for cells where ``control.py`` does not fit the chip's memory.
The benchmark's own runs do not run this.

    python3 bench/readings.py --workload <cell> --seed 1 \\
        [--seconds 25] [--control] [--faults half_batch]

In one process, on the cell's chips and at its sizes: the set-up of
``run.py`` (its programs, the weights from the seed, the warmup and
followed steps through the timed program), a window of ``--seconds``
if given, then one float32 warmup of the reference, branched into the
reference's compressed steps, the control's (``--control``: the
reference in the nearest precision below the configuration's, in the
compressed stage) and each planted fault's.  ``control.py`` plans the
same readings, but rebuilds the program's state before it follows the
steps; at BERT-Large on one v5e that second state does not fit.

Prints the run's judgment under the cell's limits (a ``run`` line) and
one JSON line per reading, in ``control.py``'s form.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import control
import run
from harness import compare, reference
from harness.registry import Registry


def branches(cell, plans: dict) -> dict:
    """Each plan's readings of the followed steps, after the float32
    reference's warmup (taken once)."""
    import jax
    t = cell.t
    refs = {k: reference.Reference(reference.Plan(
        cell.model, cell.chips, t["block_size"], t["reference_rows"], *p),
        cell.devices) for k, p in plans.items()}
    first = refs["reference"]
    st, warm = first.init(cell.key), []
    for k in range(t["warmup_steps"]):
        st, loss, g = first.step(st, "warmup", cell.stream.warmup_batch(k),
                                 t["warmup_lr"])
        warm.append(loss)
        g.delete()
    host = {k: np.asarray(v) for k, v in st.items()}
    shard = {k: v.sharding for k, v in st.items()}
    for a in st.values():
        a.delete()
    out = {}
    for name, ref in refs.items():
        st = {k: jax.device_put(host[k], shard[k]) for k in host}
        losses = []
        for k in range(t["followed_steps"]):
            st, loss, g = ref.step(st, "compressed", cell.stream.batch(k),
                                   t["lr"])
            losses.append(loss)
            if k == 0:
                grad = np.asarray(g)
            g.delete()
        change = np.asarray(st["x"]) - host["x"]
        for a in st.values():
            a.delete()
        out[name] = {"warmup_losses": warm, "losses": losses, "grad": grad,
                     "change": change, "v": host["v"]}
    return out


def record(kind: str, seed: int, prog: dict, ref: dict, model) -> dict:
    sizes = reference.leaf_sizes(model)
    read = compare.readings(prog, ref, sizes, reference.leaf_names(model))
    return {"kind": kind, "seed": seed,
            **{k: read[k]["value"] for k in ("loss", "grad", "change")},
            "grad_leaf": read["grad"]["leaf"],
            "change_leaf": read["change"]["leaf"],
            "loss_gaps": [abs(a - b) / b for a, b in
                          zip(prog["losses"], ref["losses"])],
            "warmup_loss_gaps": [abs(a - b) / b for a, b in zip(
                prog["warmup_losses"], ref["warmup_losses"])],
            "change_gaps": control.leaf_gaps(prog, ref, "change", sizes),
            "grad_gaps": control.leaf_gaps(prog, ref, "grad", sizes),
            "ref_losses": ref["losses"]}


def main(argv=None, reg: Registry = None, require_tpu: bool = True) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    reg = reg or Registry()
    devices = run.find_devices(reg.cell(args.workload)["chips"],
                               require_tpu)
    if require_tpu:
        run.use_cache()
    if str(run.ROOT / "src") not in sys.path:
        sys.path.insert(0, str(run.ROOT / "src"))
    counter = run.CompileCounter()
    cell = run.Cell(reg, args.workload, args.seed, devices)
    cell.build()
    cell.follow()
    setup_s = time.perf_counter() - run.T_START
    head = {"kind": "run", "seed": args.seed, "setup_s": setup_s}
    if args.seconds > 0:
        values, info, _, bad = run.end_to_end(cell, args.seconds, setup_s,
                                              counter)
        head.update(end_to_end=values, window=info, window_bad=bad)
    head["memory_peak_bytes"] = cell.memory_peak()
    cell.free()
    low = reference.CONTROL[cell.c["compute_dtype"]]
    timed = ("compressed",)
    plans = {"reference": ("float32", "", ("warmup", "compressed"))}
    if args.control:
        plans[f"control:{low}"] = (low, "", timed)
    for fault in filter(None, args.faults.split(",")):
        plans[f"fault:{fault}"] = ("float32", fault, timed)
    got = branches(cell, plans)
    ref = got.pop("reference")
    ok, checks, _ = cell.judge(ref, reg.limits(args.workload))
    head.update(correct=ok, checks=checks)
    print(json.dumps(head), flush=True)
    out = [record("program", args.seed, cell.followed, ref, cell.model)]
    out += [record(kind, args.seed, other, ref, cell.model)
            for kind, other in got.items()]
    for rec in out:
        print(json.dumps(rec), flush=True)
    return [head] + out


if __name__ == "__main__":
    main()
