"""The one traffic generator: a configuration file (cluster, job
population) and a mix file (arrivals, faults, policy) become the
program's ``Cluster``, ``Job`` list and fault windows.

Every seed gets the same job population and the same arrival and outage
instants, drawn from the configuration's ``base_seed`` and the fault
schedule's own ``seed``; ``--seed`` permutes which job takes which
arrival slot, within blocks of ``SHUFFLE_BLOCK`` consecutive slots, and
which node each outage hits.  So runs on different seeds do the same
amount of work in another order.  Each replay of the trace within a run
takes a permutation of its own, drawn from ``--seed`` and the replay's
number, so that a window averages several orders.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Optional, Tuple

import numpy as np

from . import mtbf, philly

DEFAULT_RESTART_S = 10.0
# a seed permutes jobs only within runs of this many arrival slots, so
# that every seed offers the same load over time
SHUFFLE_BLOCK = 8


def derive_seed(seed: int, label: str) -> int:
    """A 32-bit stream seed for ``label`` from any whole-number seed."""
    words = [seed % (1 << 64), seed // (1 << 64) % (1 << 64)] \
        if seed >= 0 else [(-seed) % (1 << 64), 1 << 32]
    ss = np.random.SeedSequence(words + [int(b) for b in label.encode()])
    return int(ss.generate_state(1)[0])


@dataclasses.dataclass
class Deployment:
    cluster: object                  # repro.core.types.Cluster
    jobs: list                       # repro.core.types.Job, arrival order
    faults: Optional[List[Tuple[int, float, float]]]
    max_queue: int                   # most jobs a consult can see
    round_len: float
    until: float                     # simulated seconds per replay
    replay_jobs: Callable[[int], list]   # the jobs of replay k (0: jobs)


def _nodes(cfg: dict):
    c = cfg["cluster"]
    if c["layout"] == "grown":
        return philly.grown_nodes(int(c["queue_jobs"]))
    if c["layout"] == "simulation":
        return philly.simulation_nodes()
    raise ValueError(f"unknown cluster layout {c['layout']!r}")


def _arrival_instants(mix: dict, n: int, base_seed: int) -> np.ndarray:
    arr = mix["arrivals"]
    if arr["kind"] == "at_start":
        return np.zeros(n)
    if arr["kind"] == "uniform":
        rng = np.random.RandomState(derive_seed(base_seed, "arrivals"))
        return np.sort(rng.uniform(0.0, float(arr["span_s"]), n))
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def _permutation(n: int, seed: int) -> np.ndarray:
    """Which job takes each arrival slot: a permutation of the jobs
    within each run of ``SHUFFLE_BLOCK`` consecutive slots."""
    rng = np.random.RandomState(seed)
    return np.concatenate([a + rng.permutation(min(SHUFFLE_BLOCK, n - a))
                           for a in range(0, n, SHUFFLE_BLOCK)])


def build(cfg: dict, mix: dict, seed: int) -> Deployment:
    from repro.core.types import Cluster, Job, Node

    nodes = _nodes(cfg)
    cluster = Cluster([Node(nid, dict(g)) for nid, g in nodes])
    types = []
    for _, g in nodes:
        types += [r for r in g if r not in types]
    tr = cfg["trace"]
    base = int(tr["base_seed"])
    specs = philly.philly_jobs(int(tr["n_jobs"]), base, types)
    at = _arrival_instants(mix, len(specs), base)

    def replay_jobs(k: int) -> list:
        label = "order" if k == 0 else f"order.{k}"
        jobs = []
        for i, p in enumerate(_permutation(len(specs),
                                           derive_seed(seed, label))):
            model, size, w, epochs, ipe, tp, _ = specs[p]
            jobs.append(Job(i, float(at[i]), w, epochs=epochs,
                            iters_per_epoch=ipe, throughput=dict(tp),
                            model=model, size=size))
        return jobs

    jobs = replay_jobs(0)
    faults = None
    f = mix.get("faults")
    if f:
        if f["kind"] != "mtbf":
            raise ValueError(f"unknown fault kind {f['kind']!r}")
        ids = [nid for nid, _ in nodes]
        wins = mtbf.mtbf_windows(ids, f["mtbf_hours"], f["recovery_s"],
                                 f["horizon_s"], int(f["seed"]))
        hit = np.random.RandomState(derive_seed(seed, "faults")).permutation(
            len(ids))
        to = {ids[k]: ids[int(hit[k])] for k in range(len(ids))}
        faults = sorted(((to[n], a, b) for n, a, b in wins),
                        key=lambda w: (w[1], w[0], w[2]))
    policy = mix["policy"]
    copies = len(nodes) if policy["kind"] == "hadare" else 1
    return Deployment(cluster, jobs, faults, len(jobs) * copies,
                      float(policy["round_len_s"]),
                      float(mix.get("replay_until_s") or math.inf),
                      replay_jobs)
