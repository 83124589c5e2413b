"""Fold a ``jax.profiler`` trace back onto the plan grid.

The executors annotate every collective with an ``op_scope`` name on the
``obs::<plan>::[b<bucket>.]s<stage>::<Kind>~<tier>`` grid — the SAME
(bucket, stage, stream) grid ``repro.plan.cost.pipeline_breakdown``
prices.  This module closes the predict→measure loop: capture a trace of
N steady-state steps (``launch.train --profile DIR``), parse its chrome
trace events, and join them onto that grid — producing a measured
per-(plan, bucket, stage, kind, tier) timeline to hold against the
predicted one.

The join is two-hop, because XLA:CPU/GPU device trace events carry the
HLO *instruction* (``args: {hlo_module, hlo_op}``), not the named-scope
path:

  1. :func:`hlo_scope_map` parses the compiled HLO text of the traced
     step(s): every instruction whose ``metadata op_name`` contains an
     ``obs::`` scope maps ``(module, instr) -> parsed scope``.  Fusions
     inherit the scope of the op they fused from, so compress/decompress
     compute lands on its owning cell too — not just the wire legs.
  2. :func:`fold_trace` looks each trace event's ``hlo_op`` up in that
     map (falling back to scope names embedded in the event name, for
     host/GPU events that carry the full path).

On top of the fold:

  * :func:`overlap_audit` — per-stream busy / hidden / exposed time from
    any interval list, measured OR predicted (``pipeline_breakdown``'s
    ``intervals`` feed it directly), the measured generalization of
    ``benchmarks/overlap_check.py``'s boolean bracketing check;
  * :func:`attribution` — the ``profile`` telemetry event's fields:
    s/step, comm fraction, overlap efficiency, roofline fraction, and an
    explicit *unattributed residual* — attributed + residual sums to the
    profile window by construction, so coverage gaps are visible rather
    than silently dropped.

Everything here is stdlib-only (no jax import): trace parsing must work
offline, on a log dir copied off the machine that produced it.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# the span grammar of repro.obs.trace.span_name: plan names may contain
# "/", "(", ")", "+" (e.g. "pipe(flat/onebit)x2", "hier/onebit+outer_ef")
# so the plan segment is a non-greedy anything-up-to the next "::" that
# never runs into another "obs::" scope: a grid scope nests under its
# "obs::exchange::<compressor>" layer scope in an HLO op_name path.  The
# canonical tier separator is "~" (JAX's name stack eats "@" and all
# that follows before the scope reaches HLO metadata); "@" is still
# accepted for host-span logs written before the rename.
SCOPE_RE = re.compile(
    r"obs::(?P<plan>(?:(?!obs::).)+?)::(?:b(?P<bucket>\d+)\.)?"
    r"s(?P<stage>\d+)::(?P<kind>[A-Za-z]+)[~@](?P<tier>[a-z]+)")

# XLA mnemonics of the wire legs (vs fusions/etc = compute carrying the
# scope of the op they belong to); matches repro.obs.trace._COLLECTIVE_RE
_WIRE_RE = re.compile(
    r"^(?:all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute)(?:-start|-done)?(?:\.\d+)?$")

# the host-span name launch.train brackets the traced steps with
WINDOW_SPAN = "profile.window"


def parse_scope(name: str) -> Optional[Dict[str, object]]:
    """Parse the first ``obs::`` scope out of ``name`` (a span name, an
    HLO ``op_name`` path, or a trace event name); None when absent."""
    m = SCOPE_RE.search(name)
    if not m:
        return None
    b = m.group("bucket")
    return {"plan": m.group("plan"),
            "bucket": int(b) if b is not None else None,
            "stage": int(m.group("stage")),
            "kind": m.group("kind"), "tier": m.group("tier")}


def cell_key(scope: Dict[str, object]) -> Tuple:
    """The fold's grid key: (plan, bucket, stage, kind, tier)."""
    return (scope["plan"], scope["bucket"], scope["stage"],
            scope["kind"], scope["tier"])


# --------------------------------------------------------------------------
# compiled-HLO bridge: (module, instruction) -> scope
# --------------------------------------------------------------------------

_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# a computation definition header: column-0 "%name (args) -> type {"
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s*\(")
# instructions that execute another computation; its scope is theirs
_CALLS_RE = re.compile(r"(?:to_apply|calls)=%?([\w.-]+)")


def hlo_scope_map(hlo_texts) -> Dict[object, Dict[str, object]]:
    """Map HLO instructions to their ``obs::`` scopes.

    ``hlo_texts`` is one compiled-HLO text or an iterable of them (one
    per traced jitted step).  Returns a dict with BOTH ``(module,
    instr)`` tuple keys and bare ``instr`` string keys (the fallback for
    traces whose events carry no ``hlo_module``); instruction names are
    un-%-prefixed, matching the trace's ``hlo_op`` values.

    Two passes per module: the first maps every instruction whose own
    ``op_name`` carries an ``obs::`` scope AND tags each *computation*
    with the (unique) scope its instructions carry; the second assigns
    that computation scope to caller instructions (``call`` wrappers,
    ``fusion``s) whose metadata got dropped — XLA:CPU's parallel-task
    ``call.N`` wrappers around fused (de)compress compute carry no
    ``op_name`` of their own, only ``to_apply=`` the scoped computation.

    Ambiguity is dropped, not guessed: distinct jitted steps of one run
    all compile to modules named ``jit_step``, so an instruction name
    scoped in one program and differently-scoped (or UNscoped — e.g. a
    plain grad ``psum`` sharing ``all-reduce.N`` numbering with another
    program's plan op) in another cannot be attributed from the trace's
    ``(module, instr)`` alone — such keys are removed and their events
    land in the unattributed residual instead of the wrong cell.
    """
    if isinstance(hlo_texts, str):
        hlo_texts = [hlo_texts]
    out: Dict[object, Dict[str, object]] = {}
    ambiguous: set = set()
    unscoped_seen: set = set()
    for text in hlo_texts:
        module = None
        comp = None
        comp_scopes: Dict[str, Optional[Dict[str, object]]] = {}
        pending: List[Tuple[Optional[str], str, str]] = []
        local: Dict[object, Dict[str, object]] = {}
        seen: set = set()
        for line in text.splitlines():
            mm = _MODULE_RE.match(line)
            if mm:
                module = mm.group(1)
                continue
            if line and not line[0].isspace():
                cm = _COMPUTATION_RE.match(line)
                if cm:
                    comp = cm.group(1)
                continue
            im = _INSTR_RE.match(line)
            if im is None:
                continue
            instr = im.group(1)
            keys = [instr] if module is None else [instr, (module, instr)]
            seen.update(keys)
            nm = _OP_NAME_RE.search(line)
            scope = (parse_scope(nm.group(1))
                     if nm and "obs::" in nm.group(1) else None)
            if scope is None:
                km = _CALLS_RE.search(line)
                if km:
                    pending.append((module, instr, km.group(1)))
                continue
            for k in keys:
                local[k] = scope
            if comp is not None:
                # a computation maps to a scope only if unambiguous
                prev = comp_scopes.get(comp, scope)
                comp_scopes[comp] = (scope if prev is not None
                                     and cell_key(prev) == cell_key(scope)
                                     else None)
        for mod, instr, callee in pending:
            scope = comp_scopes.get(callee)
            if scope is None or instr in local:
                continue
            local[instr] = scope
            if mod is not None:
                local[(mod, instr)] = scope
        # merge with cross-text conflict detection
        for k, scope in local.items():
            prev = out.get(k)
            if prev is not None and cell_key(prev) != cell_key(scope):
                ambiguous.add(k)
            else:
                out[k] = scope
        unscoped_seen.update(k for k in seen if k not in local)
    for k in ambiguous | (set(out) & unscoped_seen):
        out.pop(k, None)
    return out


# --------------------------------------------------------------------------
# chrome-trace loading
# --------------------------------------------------------------------------

def find_trace_files(profile_dir: str) -> List[str]:
    """The chrome-trace JSON(.gz) files of the NEWEST profiler run under
    ``profile_dir`` (the log dir given to ``jax.profiler.start_trace``);
    perfetto protobuf traces are skipped."""
    runs = sorted(glob.glob(os.path.join(profile_dir, "plugins",
                                         "profile", "*")))
    search_dirs = [runs[-1]] if runs else [profile_dir]
    files = []
    for d in search_dirs:
        for pat in ("*.trace.json.gz", "*.trace.json"):
            files += [f for f in sorted(glob.glob(os.path.join(d, pat)))
                      if "perfetto" not in os.path.basename(f)]
    return files


def load_trace_events(path: str) -> List[dict]:
    """The complete-duration (``ph: "X"``) events of one chrome-trace
    JSON(.gz) file."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", data if isinstance(data, list) else [])
    return [e for e in events
            if isinstance(e, dict) and e.get("ph") == "X"
            and "ts" in e and "dur" in e]


def load_profile_dir(profile_dir: str) -> List[dict]:
    """All trace events of the newest run under ``profile_dir``."""
    events: List[dict] = []
    for path in find_trace_files(profile_dir):
        events += load_trace_events(path)
    return events


# --------------------------------------------------------------------------
# interval algebra (merged unions; everything in seconds)
# --------------------------------------------------------------------------

def merge_spans(spans: Iterable[Tuple[float, float]]
                ) -> List[Tuple[float, float]]:
    """Union of (start, end) intervals as a sorted disjoint list."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((s, e) for s, e in spans if e > s):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def span_length(merged: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def intersect_spans(a: Sequence[Tuple[float, float]],
                    b: Sequence[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    """Intersection of two merged disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip_spans(merged: Sequence[Tuple[float, float]], lo: float,
               hi: float) -> List[Tuple[float, float]]:
    return intersect_spans(merged, [(lo, hi)])


# --------------------------------------------------------------------------
# the fold: trace events -> measured grid timeline
# --------------------------------------------------------------------------

def fold_trace(events: Sequence[dict],
               scope_map: Dict[object, Dict[str, object]],
               window: Optional[Tuple[float, float]] = None
               ) -> Dict[str, object]:
    """Join trace events onto the plan grid (see module docstring).

    Returns a fold dict:

      * ``cells`` — ``{(plan, bucket, stage, kind, tier): {n, t_wire,
        t_compute, t_total}}``, every executor collective the trace saw,
        attributed to its grid cell;
      * ``intervals`` — the matched events as ``{stream, t_start, t_end,
        phase, plan, bucket, stage, kind, tier}`` records (stream = the
        op's tier for wire events, ``"compute"`` for fused compute),
        normalized so the window starts at 0 — directly comparable to
        ``pipeline_breakdown``'s predicted ``intervals``;
      * ``t_window`` / ``window`` — the ``profile.window`` host span
        when present (else ``window`` arg, else the trace extent);
      * ``t_attributed`` / ``t_residual`` — union length of the matched
        intervals inside the window, and the gap: the two SUM TO
        ``t_window`` by construction;
      * ``n_events`` / ``n_matched`` / ``n_unattributed``.
    """
    us = 1e-6
    # the window: an explicit arg, the profile.window TraceAnnotation,
    # or the trace extent
    if window is None:
        for e in events:
            if WINDOW_SPAN in str(e.get("name", "")):
                window = (e["ts"] * us, (e["ts"] + e["dur"]) * us)
                break
    if window is None and events:
        t0 = min(e["ts"] for e in events) * us
        t1 = max(e["ts"] + e["dur"] for e in events) * us
        window = (t0, t1)
    if window is None:
        window = (0.0, 0.0)

    cells: Dict[Tuple, Dict[str, float]] = {}
    intervals: List[dict] = []
    matched_spans: List[Tuple[float, float]] = []
    n_matched = 0
    w0, w1 = window
    for e in events:
        args = e.get("args") or {}
        instr = str(args.get("hlo_op", "") or "")
        module = str(args.get("hlo_module", "") or "")
        scope = None
        if instr:
            scope = scope_map.get((module, instr), scope_map.get(instr))
        if scope is None:
            name = str(e.get("name", ""))
            scope = parse_scope(name)
            if scope is not None and not instr:
                instr = name
        if scope is None:
            continue
        n_matched += 1
        t_start, t_end = e["ts"] * us, (e["ts"] + e["dur"]) * us
        wire = bool(_WIRE_RE.match(instr.split("/")[-1]))
        stream = scope["tier"] if wire else "compute"
        dur = t_end - t_start
        c = cells.setdefault(cell_key(scope), {
            "n": 0, "t_wire": 0.0, "t_compute": 0.0, "t_total": 0.0})
        c["n"] += 1
        c["t_wire" if wire else "t_compute"] += dur
        c["t_total"] += dur
        intervals.append({"stream": stream,
                          "phase": "wire" if wire else "compute",
                          "t_start": t_start - w0, "t_end": t_end - w0,
                          **scope})
        matched_spans.append((t_start, t_end))

    covered = clip_spans(merge_spans(matched_spans), w0, w1)
    t_window = w1 - w0
    t_attributed = span_length(covered)
    return {"window": window, "t_window": t_window,
            "cells": cells, "intervals": intervals,
            "t_attributed": t_attributed,
            "t_residual": t_window - t_attributed,
            "n_events": len(events), "n_matched": n_matched,
            "n_unattributed": len(events) - n_matched}


def fold_profile(profile_dir: str, hlo_texts,
                 window: Optional[Tuple[float, float]] = None
                 ) -> Dict[str, object]:
    """End-to-end: load ``profile_dir``'s newest trace, build the HLO
    scope bridge, fold."""
    return fold_trace(load_profile_dir(profile_dir),
                      hlo_scope_map(hlo_texts), window=window)


# --------------------------------------------------------------------------
# overlap audit: per-stream hidden vs exposed time
# --------------------------------------------------------------------------

def overlap_audit(intervals: Sequence[dict]) -> Dict[str, object]:
    """Per-stream busy / hidden / exposed seconds from an interval list
    (``{stream, t_start, t_end}`` records — a fold's measured intervals
    or ``pipeline_breakdown``'s predicted ones).

    ``busy`` is the union length of the stream's own intervals,
    ``hidden`` the part of it overlapped by ANY other stream, and
    ``exposed = busy - hidden`` — serialized time nothing else covers.
    ``overlap_efficiency`` aggregates the link streams — everything but
    ``compute`` and the ``bwd`` gradient-production stream (backward
    work is a thing comm hides UNDER, not comm to hide): hidden comm /
    busy comm, the fraction of wire time the schedule actually hid
    (1.0 when there is no comm to hide).
    """
    by_stream: Dict[str, List[Tuple[float, float]]] = {}
    for iv in intervals:
        by_stream.setdefault(str(iv["stream"]), []).append(
            (float(iv["t_start"]), float(iv["t_end"])))
    merged = {s: merge_spans(sp) for s, sp in by_stream.items()}
    streams: Dict[str, Dict[str, float]] = {}
    comm_busy = comm_hidden = 0.0
    for s, own in merged.items():
        others = merge_spans(
            [iv for o, sp in merged.items() if o != s for iv in sp])
        busy = span_length(own)
        hidden = span_length(intersect_spans(own, others))
        streams[s] = {"busy": busy, "hidden": hidden,
                      "exposed": busy - hidden}
        if s not in ("compute", "bwd"):
            comm_busy += busy
            comm_hidden += hidden
    return {"streams": streams, "comm_busy": comm_busy,
            "comm_hidden": comm_hidden,
            "comm_exposed": comm_busy - comm_hidden,
            "overlap_efficiency": (comm_hidden / comm_busy
                                   if comm_busy > 0 else 1.0)}


def audit_diff(measured: Dict[str, object],
               predicted: Dict[str, object]) -> List[dict]:
    """Side-by-side rows of two :func:`overlap_audit` results — the
    measured-vs-predicted overlap diff the report renders."""
    rows = []
    names = sorted(set(measured["streams"]) | set(predicted["streams"]))
    zero = {"busy": 0.0, "hidden": 0.0, "exposed": 0.0}
    for s in names:
        m = measured["streams"].get(s, zero)
        p = predicted["streams"].get(s, zero)
        rows.append({"stream": s,
                     "busy_measured": m["busy"],
                     "busy_predicted": p["busy"],
                     "hidden_measured": m["hidden"],
                     "hidden_predicted": p["hidden"],
                     "exposed_measured": m["exposed"],
                     "exposed_predicted": p["exposed"]})
    return rows


# --------------------------------------------------------------------------
# attribution report (the `profile` telemetry event's fields)
# --------------------------------------------------------------------------

def attribution(fold: Dict[str, object], n_steps: int,
                predicted: Optional[Dict[str, object]] = None,
                device=None, bytes_per_step: Optional[float] = None,
                source: Optional[str] = None) -> Dict[str, object]:
    """Fold + audit -> the flat field dict of one ``profile`` event
    (:mod:`repro.obs.events`).

    ``predicted`` is a ``pipeline_breakdown`` result for the traced
    exchange: its ``intervals`` feed the predicted-side overlap audit
    and its compute-stream busy time gives ``roofline_fraction`` —
    predicted roofline seconds / measured compute seconds, how close the
    measured compute stream runs to ``device``'s roofline (the
    prediction is already rooflined on the run's DeviceSpec, so the
    ratio needs no further device math; <1 = slower than roofline).
    """
    audit = overlap_audit(fold["intervals"])
    t_window = float(fold["t_window"])
    out: Dict[str, object] = {
        "n_steps": int(n_steps),
        "t_window": t_window,
        "t_attributed": float(fold["t_attributed"]),
        "t_residual": float(fold["t_residual"]),
        "n_cells": len(fold["cells"]),
        "n_unattributed": int(fold["n_unattributed"]),
        "s_per_step": t_window / n_steps if n_steps > 0 else 0.0,
        "comm_fraction": (audit["comm_busy"] / t_window
                          if t_window > 0 else 0.0),
        "overlap_efficiency": audit["overlap_efficiency"],
        "exposed_comm_s": float(audit["comm_exposed"]),
        "streams": audit["streams"],
        "cells": [
            {"plan": k[0], "bucket": k[1], "stage": k[2], "kind": k[3],
             "tier": k[4], **{f: v for f, v in c.items()}}
            for k, c in sorted(fold["cells"].items(),
                               key=lambda kv: str(kv[0]))],
    }
    if predicted is not None:
        p_audit = overlap_audit(predicted.get("intervals", []))
        out["audit_vs_predicted"] = audit_diff(audit, p_audit)
        t_pred_compute = float(predicted.get("busy", {})
                               .get("compute", 0.0)) * max(n_steps, 1)
        t_meas_compute = audit["streams"].get(
            "compute", {}).get("busy", 0.0)
        if t_pred_compute > 0 and t_meas_compute > 0:
            out["roofline_fraction"] = t_pred_compute / t_meas_compute
    if bytes_per_step is not None:
        out["bytes_per_step"] = float(bytes_per_step)
    if source is not None:
        out["source"] = source
    return out
