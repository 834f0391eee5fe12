"""Span arithmetic over the program's tracer (``repro.obs.trace``)."""
from __future__ import annotations

from typing import Dict, List, Sequence


def children(spans: Sequence) -> Dict[int, List]:
    out: Dict[int, List] = {}
    for s in spans:
        if s.parent is not None:
            out.setdefault(s.parent, []).append(s)
    return out


def covered_ns(parent, kids: Sequence) -> int:
    """Nanoseconds of ``parent`` covered by the union of ``kids``."""
    iv = sorted((max(k.ts, parent.ts), min(k.ts + k.dur, parent.ts + parent.dur))
                for k in kids if k.dur > 0)
    total, end = 0, None
    for s, e in iv:
        if e <= s:
            continue
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_ns(spans: Sequence, name: str, exclude_children=None) -> List[int]:
    """Self time of every closed span called ``name``: its duration less
    the part its children cover (children named in ``exclude_children``
    only, when given)."""
    kids = children(spans)
    out = []
    for s in spans:
        if s.name != name or s.dur < 0:
            continue
        ks = kids.get(s.sid, [])
        if exclude_children is not None:
            ks = [k for k in ks if k.name in exclude_children]
        out.append(s.dur - covered_ns(s, ks))
    return out


def args_of(spans: Sequence, name: str, key: str) -> List[float]:
    return [float(s.args[key]) for s in spans
            if s.name == name and s.args and key in s.args]
