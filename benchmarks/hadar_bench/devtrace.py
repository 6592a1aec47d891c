"""The profiler trace of a traced window, reduced to device busy time,
time per device program, idle gaps by what the host was doing, and
device-op totals.  Reads the ``.xplane.pb`` that ``jax.profiler``
writes, with nothing but JAX.

Timestamps of host and device lines are on one clock in the trace.  The
traced window is the span of the benchmark's own ``consult`` and
``engine`` annotations, which tile it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

HOST_MARKS = ("consult", "engine")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class TraceError(RuntimeError):
    """The trace cannot give what the run's counters say it holds."""


class Reduced(NamedTuple):
    window_ns: Tuple[int, int]
    busy_ns: int                        # union of device-op intervals
    module_ns: Dict[str, int]           # device time per program name
    module_calls: Dict[str, int]
    op_ns: Dict[str, int]               # device time per op name
    gaps: List[Tuple[str, int]]         # idle gaps, by host activity
    device_planes: int
    consults: int                       # consult annotations traced


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise TraceError(f"the profiler wrote no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def program_name(event_name: str) -> str:
    """``jit_kernel(123)`` -> ``jit_kernel``."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    """``%while.67 = (u32[] ...) while(...)`` -> ``while.67``: the HLO
    instruction, without its operand list."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _events(line):
    for e in line.events:
        a = int(e.start_ns)
        yield e.name, a, a + int(e.duration_ns)


def reduce(pd) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``."""
    marks: List[Tuple[int, int, str]] = []
    dev_planes = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                marks += [(a, b, n) for n, a, b in _events(line)
                          if n in HOST_MARKS]
    consults = sum(1 for _, _, n in marks if n == "consult")
    if not consults:
        raise TraceError("the trace holds no consult annotation")
    w0 = min(a for a, _, _ in marks)
    w1 = max(b for _, b, _ in marks)
    busy_iv: List[Tuple[int, int]] = []
    module_ns: Dict[str, int] = {}
    module_calls: Dict[str, int] = {}
    op_ns: Dict[str, int] = {}
    for plane in dev_planes:
        for line in plane.lines:
            if line.name == OPS_LINE:
                for n, a, b in _events(line):
                    a, b = max(a, w0), min(b, w1)
                    if b > a:
                        busy_iv.append((a, b))
                        n = op_name(n)
                        op_ns[n] = op_ns.get(n, 0) + b - a
            elif line.name == MODULES_LINE:
                for n, a, b in _events(line):
                    a, b = max(a, w0), min(b, w1)
                    if b > a:
                        p = program_name(n)
                        module_ns[p] = module_ns.get(p, 0) + b - a
                        module_calls[p] = module_calls.get(p, 0) + 1
    busy = _union(busy_iv)
    marks.sort()
    gaps: List[Tuple[str, int]] = []
    prev = w0
    for a, b in busy + [(w1, w1)]:
        if a > prev:
            mid = (prev + a) // 2
            host = next((n for s, e, n in marks if s <= mid < e), "other")
            gaps.append((host, a - prev))
        prev = max(prev, b)
    return Reduced((w0, w1), sum(b - a for a, b in busy), module_ns,
                   module_calls, op_ns, gaps, len(dev_planes), consults)


def load(log_dir: str) -> Reduced:
    import jax
    return reduce(jax.profiler.ProfileData.from_file(find_xplane(log_dir)))


# the solver's jitted programs as the trace names them, and the obs
# counter of their calls
PRICING = ("jit_kernel", "solver_batch_calls")
SCAN = ("jit_scan_fn", "solver_scan_calls")


def program_ns(run, which) -> Optional[int]:
    """Device nanoseconds of one solver program over the traced
    consults; nothing where the solver counters show no call.  Where
    they show calls that the trace does not hold (the program's name
    moved), a ``TraceError`` rather than 0."""
    program, counter = which
    if run.dev is None:
        return None
    traced = run.consult_counters[:run.dev.consults]
    calls = sum(d.get(counter, 0) for d in traced)
    if calls == 0:
        return None
    ns = run.dev.module_ns.get(program, 0)
    if ns == 0:
        raise TraceError(f"{counter}={calls} in the traced consults but the "
                         f"trace has no device program named {program!r}")
    return ns
