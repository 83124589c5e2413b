#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from.  The
benchmark's own runs do not run this.

    python3 bench/control.py --workload <cell> --program-seeds 1,2,3 \\
        --control-seeds 11,12,13 [--faults half_batch] \\
        [--witness-seeds 1,2]

In one process, on the cell's chips and at its sizes:

* for each program seed, the program's numbers against the reference,
  as a run reads them (the lower readings);
* for each control seed, the control -- the reference computed in the
  nearest precision below the configuration's (``CONTROL``) -- in the
  program's place, and each planted fault (``harness.reference.Plan``)
  in its place, against the reference (the upper readings).  Both hold
  in the compressed stage alone, the timed program's, after a warmup
  that is the reference's own;
* for each witness seed, the reference computed in the configuration's
  own precision in every stage, in the program's place: a second
  witness of how far that precision alone parts from float32.

A step that returns its state unchanged needs no run: its change reads
1 on every leaf.  Prints one JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import sys

import run
from harness import compare, reference
from harness.registry import Registry


def leaf_gaps(prog: dict, ref: dict, key: str, sizes) -> list:
    """Every leaf's gap of ``key``, as ``compare.readings`` takes it."""
    keep = compare.moving_leaves(compare.leaf_norms(ref["grad"], sizes))
    live = ref["v"] > 0 if key == "change" else None
    return compare.leaf_gaps(compare.leaf_norms(prog[key], sizes, live),
                             compare.leaf_norms(ref[key], sizes, live),
                             keep).tolist()


def seeds(text: str):
    return [int(x) for x in text.split(",") if x]


def main(argv=None, reg: Registry = None, require_tpu: bool = True) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--witness-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    reg = reg or Registry()
    spec = reg.cell(args.workload)
    devices = run.find_devices(spec["chips"], require_tpu)
    if require_tpu:
        run.use_cache()
    if str(run.ROOT / "src") not in sys.path:
        sys.path.insert(0, str(run.ROOT / "src"))
    seed0 = (args.program_seeds + args.control_seeds
             + args.witness_seeds)[0]
    cell = run.Cell(reg, args.workload, seed0, devices)
    sizes = reference.leaf_sizes(cell.model)
    names = reference.leaf_names(cell.model)
    out = []

    def emit(kind, seed, read, prog, ref):
        rec = {"kind": kind, "seed": seed,
               **{k: read[k]["value"] for k in ("loss", "grad", "change")},
               "grad_leaf": read["grad"]["leaf"],
               "change_leaf": read["change"]["leaf"],
               "loss_gaps": [abs(a - b) / b for a, b in
                             zip(prog["losses"], ref["losses"])],
               "warmup_loss_gaps": [abs(a - b) / b for a, b in zip(
                   prog["warmup_losses"], ref["warmup_losses"])],
               "change_gaps": leaf_gaps(prog, ref, "change", sizes),
               "grad_gaps": leaf_gaps(prog, ref, "grad", sizes),
               "ref_losses": ref["losses"]}
        out.append(rec)
        print(json.dumps(rec), flush=True)

    if args.program_seeds:
        cell.build()
        cell.free()
        for s in args.program_seeds:
            cell.reseed(s)
            cell.reset()
            cell.follow()
            cell.free()
            ref = cell.reference()
            emit("program", s, compare.readings(cell.followed, ref, sizes,
                                                names), cell.followed, ref)
    low = reference.CONTROL[cell.c["compute_dtype"]]
    timed = ("compressed",)
    for s in args.control_seeds:
        cell.reseed(s)
        ref = cell.reference()
        ctl = cell.reference(low, stages=timed)
        emit(f"control:{low}", s, compare.readings(ctl, ref, sizes, names),
             ctl, ref)
        for fault in filter(None, args.faults.split(",")):
            bad = cell.reference(fault=fault, stages=timed)
            emit(f"fault:{fault}", s, compare.readings(bad, ref, sizes,
                                                       names), bad, ref)
    own = cell.c["compute_dtype"]
    for s in args.witness_seeds:
        cell.reseed(s)
        ref = cell.reference()
        wit = cell.reference(own)
        emit(f"witness:{own}", s, compare.readings(wit, ref, sizes, names),
             wit, ref)
    return out


if __name__ == "__main__":
    main()
