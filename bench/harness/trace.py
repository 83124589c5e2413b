"""From a profiler trace to the numbers the per-layer metrics read.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` into a plain ``Raw`` record (``extract``),
and the compiled step's HLO text, which names each instruction's source
(``op_names``).  On a TPU each chip is a plane ``/device:TPU:<i>``; its
line ``XLA Ops`` holds one event per executed HLO instruction, named by
the instruction's text (``%fusion.12 = bf16[...] fusion(...), ...``),
with a ``while`` event spanning the ops of its body; the line ``Async
XLA Ops`` holds asynchronous copies and collectives from their start to
their done.  The host plane ``/host:CPU`` holds the benchmark's own
``jax.profiler.TraceAnnotation`` spans (``bench.*``) on its threads'
lines.

Classification of an op (``classify``), in this order:

* ``collective``: its opcode is an all-to-all, all-gather, all-reduce,
  reduce-scatter or collective-permute (``-start``/``-done`` included);
* ``model``: its source op name holds ``jvp(`` -- the forward pass
  (``jvp(...)``) or the backward pass (``transpose(jvp(...))``) of the
  gradient computation, recomputed ops included;
* ``optimizer``: any other op with a source op name inside the step
  (flattening of the gradient, the update, 1-bit compress and
  decompress, error feedback);
* ``other``: an op with no source name (layout copies, transfers the
  compiler inserted).

A class's time is the self time of its ops (an event's duration less
the events nested in it on the same line) inside the window.  Busy time
is the union of all ``XLA Ops`` events in the window; a collective's
time is the union of its events on both lines; its exposed part is what
of that union no non-collective ``XLA Ops`` event covers.  Every number
is taken per chip and averaged over the chips.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from harness.intervals import gaps, merge_spans, span_length, subtract_spans

COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "collective-broadcast",
               "ragged-all-to-all")
CLASSES = ("model", "optimizer", "collective", "other")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIX = "bench."
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

# one event: (start_ns, end_ns, name)
Event = Tuple[float, float, str]


@dataclasses.dataclass
class Raw:
    """The parts of one trace the reduction reads."""
    ops: Dict[int, List[Event]]          # chip -> XLA Ops events
    async_ops: Dict[int, List[Event]]    # chip -> Async XLA Ops events
    host: List[Event]                    # bench.* annotations

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "async_ops": {str(k): v for k, v in self.async_ops.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Raw":
        def ev(xs):
            return [(float(a), float(b), str(c)) for a, b, c in xs]
        return cls({int(k): ev(v) for k, v in d["ops"].items()},
                   {int(k): ev(v) for k, v in d["async_ops"].items()},
                   ev(d["host"]))


def extract(xplane_path: str) -> Raw:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    ops, aops, host = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "Async XLA Ops"):
                evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
                (ops if line.name == "XLA Ops" else aops)[int(m.group(1))] = evs
            elif plane.name == "/host:CPU":
                host += [(e.start_ns, e.end_ns, e.name) for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    return Raw(ops, aops, host)


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> source op name, from compiled HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            src = _OP_NAME.search(line)
            if src:
                out[m.group(1)] = src.group(1)
    return out


def instr_name(event_name: str) -> str:
    m = _INSTR.match(event_name) or re.match(r"^%?([\w.\-]+)", event_name)
    return m.group(1) if m else event_name


def opcode(event_name: str) -> str:
    """The HLO opcode in an instruction's text, or its name's stem."""
    _, sep, rest = event_name.partition(" = ")
    if not sep:
        return re.sub(r"\.\d+$", "", instr_name(event_name))
    depth, i = 0, 0
    while i < len(rest):               # skip the (possibly tuple) shape
        c = rest[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    m = re.match(r"\s*([\w\-]+)\(", rest[i:])
    return m.group(1) if m else ""


def is_collective(event_name: str) -> bool:
    code = re.sub(r"-(start|done)$", "", opcode(event_name))
    return code in COLLECTIVES


def classify(event_name: str, names: Dict[str, str]) -> str:
    if is_collective(event_name):
        return "collective"
    src = names.get(instr_name(event_name))
    if src is None:
        return "other"
    return "model" if "jvp(" in src else "optimizer"


def self_times(events: List[Event]) -> List[Tuple[Event, float]]:
    """Each event with its duration less that of the events nested in
    it (events on one line nest or are disjoint)."""
    order = sorted(events, key=lambda e: (e[0], -e[1]))
    out: List[list] = []
    stack: List[list] = []
    for ev in order:
        while stack and stack[-1][0][1] <= ev[0]:
            stack.pop()
        rec = [ev, ev[1] - ev[0]]
        if stack and ev[1] <= stack[-1][0][1]:
            stack[-1][1] -= ev[1] - ev[0]
        out.append(rec)
        stack.append(rec)
    return [(e, max(t, 0.0)) for e, t in out]


@dataclasses.dataclass
class Reduced:
    chips: int
    window_s: float
    busy_s: float                        # mean over chips
    class_s: Dict[str, float]            # mean over chips, self time
    collective_s: float
    exposed_s: float
    top_ops: List[Tuple[str, float]]     # by self time, summed over chips
    idle_gaps: List[Tuple[str, float]]   # the longest, with the host span


def _clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def reduce(raw: Raw, names: Dict[str, str], lo: float, hi: float,
           n_top: int = 10) -> Reduced:
    """Reduce the window [lo, hi] (trace nanoseconds) of ``raw``."""
    chips = sorted(raw.ops)
    if not chips:
        raise ValueError("the trace holds no TPU ops")
    busy, coll, exposed = [], [], []
    cls = defaultdict(float)
    per_op = defaultdict(float)
    idle = []
    for c in chips:
        evs = _clip(raw.ops[c], lo, hi)
        union = merge_spans((s, e) for s, e, _ in evs)
        busy.append(span_length(union))
        for ev, t in self_times(evs):
            k = classify(ev[2], names)
            cls[k] += t
            name = instr_name(ev[2])
            src = names.get(name, "")
            per_op[f"{name} {src}".strip()[:160]] += t
        c_spans = merge_spans(
            (s, e) for s, e, n in evs + _clip(raw.async_ops.get(c, []), lo, hi)
            if is_collective(n))
        compute = merge_spans((s, e) for s, e, n in evs
                              if not is_collective(n))
        coll.append(span_length(c_spans))
        exposed.append(span_length(subtract_spans(c_spans, compute)))
        for g0, g1 in gaps(union, lo, hi):
            idle.append((g1 - g0, _host_label(raw.host, g0, g1)))
    n = len(chips)
    idle.sort(reverse=True)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:n_top]
    return Reduced(
        chips=n, window_s=(hi - lo) / 1e9, busy_s=sum(busy) / n / 1e9,
        class_s={k: cls.get(k, 0.0) / n / 1e9 for k in CLASSES},
        collective_s=sum(coll) / n / 1e9, exposed_s=sum(exposed) / n / 1e9,
        top_ops=[(k, v / 1e9) for k, v in top],
        idle_gaps=[(label, t / 1e9) for t, label in idle[:n_top]])


def _host_label(host: List[Event], g0: float, g1: float) -> str:
    best, label = 0.0, "none"
    for s, e, name in host:
        ov = min(e, g1) - max(s, g0)
        if ov > best and name != HOST_PREFIX + "window":
            best, label = ov, name
    return label


def window(raw: Raw, name: str = HOST_PREFIX + "window"
           ) -> Optional[Tuple[float, float]]:
    """The traced window: the host span ``name``, else the first to the
    last ``bench.*`` span, else None."""
    for s, e, n in raw.host:
        if n == name:
            return s, e
    if raw.host:
        return min(s for s, _, _ in raw.host), max(e for _, e, _ in raw.host)
    return None


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's ``read`` gets."""
    trace: Reduced
    steps: int                     # steps in the traced window
    chips: int
    flops_per_step: float          # model FLOPs of one step, all chips
    optimizer_least_bytes: float   # per worker and step
    peak: dict                     # harness.peaks entry of the chip
    memory_peak_bytes: int
