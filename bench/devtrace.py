"""Reduction of a profiler trace to device times.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
structure: ``{plane name: {line name: [(event name, start ns, duration
ns), ...]}}``. Everything else works on that structure, so a test can
hand it a small recorded trace kept as JSON.

A device plane is one whose name starts with ``/device:`` and that has an
``XLA Ops`` line: the operations that ran on that chip. The window is the
host span ``bench.window`` that the harness opens around the measured
window; host spans named ``bench.*`` say what the host was doing.
"""
from __future__ import annotations

import glob
import json
from typing import Dict, List, Optional, Tuple

import numpy as np

Event = Tuple[str, float, float]
Trace = Dict[str, Dict[str, List[Event]]]

OPS = "XLA Ops"
WINDOW = "bench.window"


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return {}
    out: Trace = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            # one line per host thread, and threads can share a name
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return out


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace, f)


def read_json(path: str) -> Trace:
    with open(path) as f:
        return {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                for p, lines in json.load(f).items()}


def devices(trace: Trace) -> List[str]:
    return sorted(p for p, lines in trace.items()
                  if p.startswith("/device:") and lines.get(OPS))


def host_spans(trace: Trace, prefix: str = "bench.") -> List[Event]:
    return [e for p, lines in trace.items() if p.startswith("/host:")
            for evs in lines.values() for e in evs if e[0].startswith(prefix)]


def window(trace: Trace) -> Optional[Tuple[float, float]]:
    """(start, end) ns of the harness's measured-window span."""
    spans = [e for e in host_spans(trace) if e[0] == WINDOW]
    if not spans:
        return None
    _, s, d = max(spans, key=lambda e: e[2])
    return s, s + d


def aligned(trace: Trace, plane: str, win: Tuple[float, float]
            ) -> Tuple[float, float]:
    """The window on ``plane``'s clock. The profiler puts host and device
    events on one clock; where a trace does not (no device operation
    inside the host window), the device's own first and last operations
    bound it, since the trace starts and stops with the window."""
    lo, hi = win
    evs = trace[plane][OPS]
    if any(s < hi and s + d > lo for _, s, d in evs):
        return win
    return min(s for _, s, _ in evs), max(s + d for _, s, d in evs)


def _clip(events: List[Event], lo: float, hi: float) -> List[Tuple[float,
                                                                   float]]:
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(trace: Trace, plane: str, lo: float, hi: float) -> float:
    """Time in [lo, hi] during which some operation ran on the chip."""
    return sum(b - a for a, b in _union(_clip(trace[plane][OPS], lo, hi)))


def top_ops(trace: Trace, plane: str, lo: float, hi: float, n: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``n`` operation names with the most device time, in seconds."""
    tot: Dict[str, float] = {}
    for name, s, d in trace[plane][OPS]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            tot[name] = tot.get(name, 0.0) + (b - a)
    return [(k, v * 1e-9) for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, plane: str, lo: float, hi: float, n: int = 10
              ) -> List[Tuple[str, float]]:
    """Idle time of the chip in [lo, hi], summed by what the host was
    doing at the middle of each gap (the innermost ``bench.*`` span
    there, ``other`` where none is open), the ``n`` largest, seconds."""
    busy = _union(_clip(trace[plane][OPS], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    if not gaps:
        return []
    mids = np.array([(a + b) / 2 for a, b in gaps])
    order = np.argsort(mids)
    label = np.full(len(gaps), "other", dtype=object)
    # paint the widest spans first, so the innermost span has the last word
    spans = sorted((e for e in host_spans(trace) if e[0] != WINDOW),
                   key=lambda e: -e[2])
    sorted_mids = mids[order]
    for name, s, d in spans:
        i, j = np.searchsorted(sorted_mids, [s, s + d], side="left")
        label[order[i:j]] = name
    tot: Dict[str, float] = {}
    for (a, b), name in zip(gaps, label):
        tot[name] = tot.get(name, 0.0) + (b - a)
    return [(k, v * 1e-9) for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
