"""Device time by the program's own layer scopes.

The train step names each layer's work with a ``jax.named_scope``
``obs::<layer>::<part>``, layer one of ``model``, ``optimizer`` and
``exchange``.  The name reaches the HLO ``op_name`` of every instruction
traced under it as one path component: bare
(``.../while/body/closed_call/obs::model::attention/dot_general``), or as
the argument of the transform it sits directly under
(``jit(step)/transpose(jvp(obs::model::head))/...``).  Autodiff keeps
it on the backward pass and on the recomputed forward, which runs under
a ``rematted_computation`` component.  A grid scope of the exchange's
collectives (``obs::<plan>::s<stage>::<Kind>~<tier>``) nests inside its
``obs::exchange::<compressor>`` scope and is not a layer scope.

``scope_s`` splits the self time of each ``XLA Ops`` event in a window
(``harness.trace.self_times``) by the innermost layer scope of its
instruction's ``op_name``, averaged over the chips:

* ``<scope>``: the scope's non-collective ops, recomputed ones included;
* ``<scope>:recompute``: the part of ``<scope>`` under
  ``rematted_computation``;
* ``<scope>:collective``: its collectives, kept apart;
* ``unscoped`` (with ``:recompute`` and ``:collective``): ops under no
  layer scope, named or unnamed (layout copies the compiler inserted).

Keys without ``:recompute`` tile the busy time.  Blind spot: a fusion
carries the ``op_name`` of one of its instructions, so a fusion that
crosses a scope boundary is counted whole under one scope.

``bench/run.py`` keeps the traced run's reduction input at
``.bench_cache/trace/<cell>.json.gz`` before it calls the readers;
``of_reading`` reduces the one that a ``Reading`` was made from.
"""
from __future__ import annotations

import functools
import gzip
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Optional, Tuple

from harness import trace

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_cache" / "trace"
LAYER_SCOPE = re.compile(
    r"^(?:[A-Za-z_]+\()*(obs::(?:model|optimizer|exchange)::[A-Za-z0-9_]+)"
    r"\)*$")
REMAT = "rematted_computation"
UNSCOPED = "unscoped"


def scope_of(op_name: str) -> Optional[Tuple[str, bool]]:
    """The innermost ``obs::<layer>::<part>`` component of ``op_name``
    (a transform's parentheses around it taken off), and whether the op
    is recomputed (a ``rematted_computation`` component on its path);
    None where no component is a layer scope."""
    parts = op_name.split("/")
    for part in reversed(parts):
        m = LAYER_SCOPE.match(part)
        if m:
            return m.group(1), REMAT in parts
    return None


def scope_s(raw: trace.Raw, names: Dict[str, str], lo: float, hi: float
            ) -> Dict[str, float]:
    """Self seconds of the window [lo, hi] by scope, mean over chips
    (keys as the module's docstring says)."""
    chips = sorted(raw.ops)
    if not chips:
        raise ValueError("the trace holds no TPU ops")
    out: Dict[str, float] = defaultdict(float)
    for c in chips:
        for ev, t in trace.self_times(trace._clip(raw.ops[c], lo, hi)):
            src = names.get(trace.instr_name(ev[2]), "")
            found = scope_of(src)
            key, remat = found or (UNSCOPED, REMAT in src.split("/"))
            if trace.is_collective(ev[2]):
                out[key + ":collective"] += t
                continue
            out[key] += t
            if remat:
                out[key + ":recompute"] += t
    return {k: v / len(chips) / 1e9 for k, v in out.items()}


def unscoped_share(scopes: Dict[str, float]) -> float:
    """The share of busy time that lies under no layer scope."""
    busy = sum(v for k, v in scopes.items() if not k.endswith(":recompute"))
    free = scopes.get(UNSCOPED, 0.0) + scopes.get(UNSCOPED + ":collective",
                                                  0.0)
    return free / busy if busy > 0 else 0.0


def has_layer_scopes(scopes: Optional[Dict[str, float]]) -> bool:
    """Whether the traced program names its layers (a program from before
    the scopes has none: its readings are not made)."""
    return bool(scopes) and any(k.startswith("obs::") for k in scopes)


def of_reading(r, trace_dir: Path = None) -> Optional[Dict[str, float]]:
    """``scope_s`` of the traced run ``r`` (a ``trace.Reading``) was
    reduced from: the newest reduction input under ``trace_dir`` whose
    window and chips are ``r``'s; None where there is none."""
    trace_dir = Path(trace_dir or TRACE_DIR)
    if r.steps <= 0 or not trace_dir.is_dir():
        return None
    paths = sorted(trace_dir.glob("*.json.gz"),
                   key=lambda p: p.stat().st_mtime_ns, reverse=True)
    for p in paths:
        st = p.stat()
        found = _reduce_file(str(p), st.st_mtime_ns, st.st_size, r.steps)
        if found is not None and found[0] == (r.chips, r.trace.window_s):
            return found[1]
    return None


@functools.lru_cache(maxsize=4)
def _reduce_file(path: str, mtime_ns: int, size: int, steps: int):
    """((chips, window_s), scope_s) of one kept reduction input, or None
    where it holds no window; prints the scopes' line once per file
    (``mtime_ns`` and ``size`` key the cache to the file's contents)."""
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    raw = trace.Raw.from_json(d["raw"])
    span = trace.window(raw)
    if span is None or not raw.ops:
        return None
    lo, hi = span
    scopes = scope_s(raw, d["op_names"], lo, hi)
    per_step = ", ".join(
        f"{k} {1e3 * v / steps:.3f} ms" for k, v in
        sorted(scopes.items(), key=lambda kv: -kv[1]))
    print(f"scopes: per step: {per_step}; unscoped share of busy time "
          f"{100 * unscoped_share(scopes):.2f}%", flush=True)
    return (len(raw.ops), (hi - lo) / 1e9), scopes
