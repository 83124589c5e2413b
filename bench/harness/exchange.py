"""What the per-layer metrics of the exchange read beyond the layer
scopes: the device time of its collectives, and the sizes of the cell
they were traced in.

``collective_s``: for each chip, the union of the spans of the
collectives whose instruction lies under an ``obs::exchange::<part>``
scope (``harness.scopes``), or has no source name at all, on both the
``XLA Ops`` line and the ``Async XLA Ops`` line, so that an
asynchronous collective counts from its start to its done even where
other ops ran meanwhile; averaged over the chips.  A collective with no
source name is one the compiler made of the program's own: on a v5e it
turns the all-gather of the 1-bit block scales into an all-reduce that
carries no ``op_name``.  The self time that ``scopes.scope_s`` books
under ``<scope>:collective`` holds only what the ``XLA Ops`` line shows
and counts those as ``unscoped``.

``bench/run.py`` keeps the traced run's reduction input under the
cell's name (``.bench_cache/trace/<cell>.json.gz``); ``kept`` finds the
one a ``trace.Reading`` was made from, as ``scopes.of_reading`` does,
and ``layout`` reads that cell's padded vector length, block size and
workers from the registry.
"""
from __future__ import annotations

import functools
import gzip
import json
from pathlib import Path
from typing import Dict, Optional, Tuple

from harness import reference, scopes, trace
from harness.intervals import merge_spans, span_length
from harness.registry import Registry


def collective_s(raw: trace.Raw, names: Dict[str, str], lo: float,
                 hi: float) -> float:
    """Seconds of the window [lo, hi] in which a collective of the
    exchange ran, mean over chips."""
    chips = sorted(raw.ops)
    if not chips:
        return 0.0
    total = 0.0
    for c in chips:
        evs = raw.ops[c] + raw.async_ops.get(c, [])
        total += span_length(merge_spans(
            (s, e) for s, e, n in trace._clip(evs, lo, hi)
            if trace.is_collective(n) and _in_exchange(
                names.get(trace.instr_name(n), ""))))
    return total / len(chips) / 1e9


def _in_exchange(op_name: str) -> bool:
    if not op_name:
        return True
    found = scopes.scope_of(op_name)
    return found is not None and found[0].startswith("obs::exchange::")


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime_ns: int, size: int):
    """(chips, window_s, exchange collective seconds) of one kept
    reduction input, or None where it holds no window."""
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    raw = trace.Raw.from_json(d["raw"])
    span = trace.window(raw)
    if span is None or not raw.ops:
        return None
    lo, hi = span
    return len(raw.ops), (hi - lo) / 1e9, collective_s(raw, d["op_names"],
                                                       lo, hi)


def kept(r, trace_dir: Path = None) -> Optional[Tuple[str, float]]:
    """(cell, exchange collective seconds) of the kept reduction input
    whose window and chips are ``r``'s (the newest such); None where
    there is none."""
    trace_dir = Path(trace_dir or scopes.TRACE_DIR)
    if r.steps <= 0 or not trace_dir.is_dir():
        return None
    paths = sorted(trace_dir.glob("*.json.gz"),
                   key=lambda p: p.stat().st_mtime_ns, reverse=True)
    for p in paths:
        st = p.stat()
        found = _load(str(p), st.st_mtime_ns, st.st_size)
        if found is not None and found[:2] == (r.chips, r.trace.window_s):
            return p.name[:-len(".json.gz")], found[2]
    return None


def layout(cell: str, reg: Registry = None) -> Optional[Tuple[int, int,
                                                              int]]:
    """(padded vector length, block size, workers) of ``cell``'s 1-bit
    exchange; None for a cell the registry does not hold or whose
    recipe is not 1-bit Adam."""
    reg = reg or Registry()
    try:
        spec = reg.cell(cell)
        c, t = reg.config(spec["config"]), reg.traffic(spec["traffic"])
    except KeyError:
        return None
    if t["recipe"] != "onebit_adam":
        return None
    workers, block = spec["chips"], t["block_size"]
    d = reference.flat_length(reference.Model.from_config(c), workers,
                              block)
    return d, block, workers
