"""Set-up warm-up of every device-kernel shape a cell can reach.

The batched solver compiles one program per cluster geometry and
power-of-two job bucket, for its pricing kernel and for its commit
scan.  Which buckets the window reaches depends on how each consult's
waves split the queue, so the set-up runs both kernels once at every
bucket from 8 up to the cell's largest possible queue, on every
geometry the cell's fault schedule can produce (nodes down at once), on
jobs of the cell's own trace.  That way nothing compiles inside the
window, whatever the seed.

This reaches below the scheduler's entry point, into
``repro.core.batch_solver``.  Where those functions are gone, it warms
nothing and says so; compiles then show in ``window_compiles``.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import List


from .traffic import mtbf

BUCKET_MIN = 8


def buckets(max_queue: int) -> List[int]:
    out, b = [], BUCKET_MIN
    while True:
        out.append(b)
        if b >= max_queue:
            return out
        b *= 2


def geometries(cluster, faults) -> list:
    """One representative view per count of nodes down at once, keeping
    every GPU type present."""
    from repro.core.types import Cluster

    views = [cluster]
    down = mtbf.max_down(faults) if faults else 0
    nodes = list(cluster.nodes)
    for d in range(1, down + 1):
        left = list(nodes)
        for n in reversed(nodes):
            if len(nodes) - len(left) >= d:
                break
            rest = [m for m in left if m is not n]
            if set(Cluster(rest).gpu_types) == set(cluster.gpu_types):
                left = rest
        views.append(Cluster(left))
    return views


def warm(dep, sched_cfg: dict) -> int:
    """Run both kernels at every bucket on every geometry; returns the
    number of (geometry, bucket) points warmed."""
    try:
        from repro.core.batch_solver import _scan_prefix, find_alloc_batch
    except ImportError as e:
        print(f"warm: solver entry points not found ({e}); nothing warmed",
              file=sys.stderr)
        return 0
    from repro.core.pricing import PriceState

    jobs = dep.jobs
    horizon = float(sched_cfg["horizon_s"])
    done = 0
    for view in geometries(dep.cluster, dep.faults):
        t0 = time.perf_counter()
        for b in buckets(dep.max_queue):
            batch = [dataclasses.replace(jobs[i % len(jobs)], job_id=i,
                                         arrival=0.0, alloc=None)
                     for i in range(b)]
            ps = PriceState(view, batch, horizon, now=0.0)
            avail, gamma = ps.free_arr.copy(), ps.gamma_arr.copy()
            find_alloc_batch(batch, avail, gamma, ps, 0.0, ps.utility,
                             avail_dev=ps.device_view("free"))
            _scan_prefix(batch, avail, gamma, ps, 0.0, ps.utility, {})
            done += 1
        print(f"warm: {len(view.nodes)} nodes, buckets up to "
              f"{buckets(dep.max_queue)[-1]}: "
              f"{time.perf_counter() - t0:.2f} s", file=sys.stderr)
    return done
