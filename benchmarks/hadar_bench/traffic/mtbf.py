"""Seeded node-failure schedule, copied from
``repro.sim.faults.FailureModel.sample`` (hardware failures only, fixed
recovery): per node, exponential times between failures drawn from a
stream keyed on (seed, node), each outage lasting ``recovery_s``."""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import numpy as np

Window = Tuple[int, float, float]      # (node_id, fail_time, recover_time)


def node_rng(seed: int, node_id: int) -> np.random.RandomState:
    mix = (seed * 1000003 + int(node_id) * 7919 + 12345) % (2 ** 32)
    return np.random.RandomState(mix)


def mtbf_windows(node_ids: Sequence[int], mtbf_hours: float,
                 recovery_s: float, horizon_s: float,
                 seed: int) -> List[Window]:
    mtbf_s = float(mtbf_hours) * 3600.0
    out: List[Window] = []
    for node in node_ids:
        rng = node_rng(seed, node)
        t = 0.0
        while True:
            t += float(rng.exponential(mtbf_s))
            if t >= horizon_s:
                break
            dur = max(1e-9, float(recovery_s))
            out.append((int(node), t, t + dur))
            t += dur
    return sorted(out, key=lambda w: (w[1], w[0], w[2]))


def max_down(windows: Sequence[Window]) -> int:
    """Most nodes down at once over the schedule."""
    edges = sorted([(f, 1) for _, f, _ in windows]
                   + [(r, -1) for _, _, r in windows if math.isfinite(r)],
                   key=lambda e: (e[0], e[1]))   # recoveries first on ties
    cur = best = 0
    for _, d in edges:
        cur += d
        best = max(best, cur)
    return best
