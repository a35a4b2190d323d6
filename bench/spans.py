"""The program's own spans and kernels in a reduced profiler trace
(``devtrace``).

The program opens its spans in the profiler's trace as ``ralm.<name>``
on a ``/host:`` plane, on the clock of the device's operations, and names
its Pallas kernels (``pallas_call(name=...)``), which the device's
operations carry as the name of their XLA instruction. Both readers
return None where the trace holds nothing of what they read, as in a
trace of a program that has no such span or kernel.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

import devtrace

Interval = Tuple[float, float]


def _overlap_ns(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_under(ctx, names: Iterable[str]) -> Optional[float]:
    """Share (%) of the window, mean over the cell's chips, in which no
    operation ran on the chip while one of the host spans ``names`` was
    open on some host line."""
    if not ctx.win or not ctx.planes:
        return None
    names = set(names)
    lo, hi = ctx.win
    opened = [e for e in devtrace.host_spans(ctx.trace, "ralm.")
              if e[0] in names]
    if not opened:
        return None
    covered = devtrace._union(devtrace._clip(opened, lo, hi))
    covered_ns = sum(b - a for a, b in covered)
    idle = []
    for p in ctx.planes:
        busy = devtrace._union(devtrace._clip(ctx.trace[p][devtrace.OPS],
                                              lo, hi))
        idle.append(covered_ns - _overlap_ns(covered, busy))
    return 100.0 * sum(idle) / len(idle) / (hi - lo)


def kernel(op: str) -> str:
    """The kernel an operation's name names: its XLA instruction's name
    (``%chamvs_scan.1 = (f32[...]...) custom-call(...)`` gives
    ``chamvs_scan``), not the operands the text goes on to name."""
    return op.lstrip("%").split(" ", 1)[0].split(".", 1)[0]


def kernel_busy(ctx, kernels: Iterable[str]) -> Optional[float]:
    """Share (%) of the window, mean over the cell's chips, in which an
    operation of one of ``kernels`` ran on the chip."""
    if not ctx.win or not ctx.planes:
        return None
    kernels = set(kernels)
    lo, hi = ctx.win
    busy = []
    for p in ctx.planes:
        ops = [e for e in ctx.trace[p][devtrace.OPS]
               if kernel(e[0]) in kernels]
        busy.append(sum(b - a for a, b in
                        devtrace._union(devtrace._clip(ops, lo, hi))))
    if not any(busy):
        return None
    return 100.0 * sum(busy) / len(busy) / (hi - lo)
