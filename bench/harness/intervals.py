"""Interval algebra on (start, end) pairs, copied from the program's
``repro.obs.profile`` so that the yardstick does not move with it."""
from __future__ import annotations

from typing import Iterable, List, Sequence, Tuple

Spans = List[Tuple[float, float]]


def merge_spans(spans: Iterable[Tuple[float, float]]) -> Spans:
    """Union of (start, end) intervals as a sorted disjoint list."""
    out: Spans = []
    for s, e in sorted((s, e) for s, e in spans if e > s):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def span_length(merged: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def intersect_spans(a: Sequence[Tuple[float, float]],
                    b: Sequence[Tuple[float, float]]) -> Spans:
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
               hi: float) -> Spans:
    return intersect_spans(merged, [(lo, hi)])


def subtract_spans(a: Sequence[Tuple[float, float]],
                   b: Sequence[Tuple[float, float]]) -> Spans:
    """The parts of merged list ``a`` that merged list ``b`` does not
    cover."""
    out: Spans = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> Spans:
    """The parts of [lo, hi] that merged list ``merged`` leaves empty."""
    return subtract_spans([(lo, hi)], merged)
