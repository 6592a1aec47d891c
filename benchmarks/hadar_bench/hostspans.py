"""The program's own spans in a traced window.

``repro.obs`` opens a ``jax.profiler.TraceAnnotation`` with every wall
span, so the profiler trace holds the consult's phases on its host
plane, on the same clock as the device.  ``idle_by_span`` puts each
idle gap of the device down to the innermost annotation of the
consulting thread that covers its midpoint: a program span where one
is open, else the benchmark's ``consult``/``engine`` mark.

``SPANS`` and ``COUNTERS`` name what per-layer metrics read per window
consult from the ``repro.obs`` recorder, beside ``hadar.dp`` and
``pricestate.refresh``: span totals in ms, the byte counter in MB.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from .devtrace import (DEVICE_PLANE, HOST_MARKS, OPS_LINE, TraceError,
                       _events, _union)

# metric -> the span or counter it reads
SPANS = {
    "backfill_loop_ms": "hadar.backfill",
    "solver_tables_ms": "solver.tables",
    "solver_wait_ms": "solver.device",
    "solver_finish_ms": "solver.finish",
    "wave_walk_ms": "solver.waves",
    "exact_dp_ms": "dp.exact",
    "engine_step_ms": "engine.step",
}
COUNTERS = {"h2d_mb": "solver.h2d_bytes"}


def idle_by_span(pd) -> Dict[str, int]:
    """Device-idle nanoseconds of the traced window (the span of the
    ``consult``/``engine`` marks), by the innermost host annotation of
    the marks' thread over each gap's midpoint; ``other`` where none
    is."""
    host: List[Tuple[int, int, str]] = []
    ops: List[Tuple[int, int]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(a, b) for _, a, b in _events(line)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(a, b, n) for n, a, b in _events(line)]
                if any(n in HOST_MARKS for _, _, n in evs):
                    host += evs
    marks = [(a, b) for a, b, n in host if n in HOST_MARKS]
    if not marks:
        raise TraceError("the trace holds no consult annotation")
    w0 = min(a for a, _ in marks)
    w1 = max(b for _, b in marks)
    busy = _union([(max(a, w0), min(b, w1)) for a, b in ops
                   if min(b, w1) > max(a, w0)])
    gaps: List[Tuple[int, int]] = []       # (midpoint, length)
    prev = w0
    for a, b in busy + [(w1, w1)]:
        if a > prev:
            gaps.append(((prev + a) // 2, a - prev))
        prev = max(prev, b)
    # sweep: annotations of one thread nest, so the innermost open one
    # over a point is the top of a stack of those begun before it
    host.sort(key=lambda e: (e[0], -e[1]))
    out: Dict[str, int] = {}
    stack: List[Tuple[int, int, str]] = []
    i = 0
    for mid, length in sorted(gaps):
        while i < len(host) and host[i][0] <= mid:
            while stack and stack[-1][1] <= host[i][0]:
                stack.pop()
            stack.append(host[i])
            i += 1
        while stack and stack[-1][1] <= mid:
            stack.pop()
        name = stack[-1][2] if stack else "other"
        out[name] = out.get(name, 0) + length
    return out


def program_share(idle: Dict[str, int]) -> float:
    """Share of the idle nanoseconds that fell under a program span
    rather than under a benchmark mark alone."""
    total = sum(idle.values())
    marks = sum(v for k, v in idle.items() if k in HOST_MARKS + ("other",))
    return (total - marks) / total if total else 0.0
