"""Philly-style job trace and the paper's clusters, copied from
``repro.core.trace`` (``philly_trace``, ``simulation_cluster``) and
``benchmarks/fig5_scalability.py`` (``grown_cluster``) so that a later
change to the program cannot move the benchmark's inputs.

The generators return plain tuples; ``traffic.build`` turns them into
the program's ``Job``/``Cluster`` objects.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

# iterations/sec per single device, by (model, gpu type): relative
# magnitudes from Gavel's measurements (Narayanan et al., OSDI 2020)
THROUGHPUT_TABLE: Dict[str, Dict[str, float]] = {
    # model            V100    P100    T4     K80   TitanRTX  RTX3090 T400 A2000
    "resnet50":    {"v100": 3.00, "p100": 1.60, "t4": 1.30, "k80": 0.30,
                    "titanrtx": 3.20, "rtx3090": 3.60, "t400": 0.40,
                    "a2000": 1.10},
    "resnet18":    {"v100": 9.00, "p100": 5.40, "t4": 4.60, "k80": 1.50,
                    "titanrtx": 9.60, "rtx3090": 10.8, "t400": 1.70,
                    "a2000": 3.90},
    "lstm":        {"v100": 6.00, "p100": 4.20, "t4": 3.60, "k80": 2.00,
                    "titanrtx": 6.40, "rtx3090": 7.00, "t400": 2.10,
                    "a2000": 3.40},
    "cyclegan":    {"v100": 1.20, "p100": 0.65, "t4": 0.55, "k80": 0.12,
                    "titanrtx": 1.30, "rtx3090": 1.45, "t400": 0.15,
                    "a2000": 0.45},
    "transformer": {"v100": 4.00, "p100": 2.40, "t4": 2.00, "k80": 0.70,
                    "titanrtx": 4.30, "rtx3090": 4.80, "t400": 0.80,
                    "a2000": 1.90},
}
SIZE_GPU_HOURS = {"S": (0.1, 1.0), "M": (1.0, 10.0), "L": (10.0, 50.0),
                  "XL": (60.0, 100.0)}
MODEL_SIZE = {"resnet50": "XL", "resnet18": "S", "lstm": "L",
              "cyclegan": "M", "transformer": "L"}
# GPU demand by size class (Philly: big jobs request many GPUs)
WORKERS = {"S": [1, 1, 2], "M": [1, 2, 2, 4], "L": [2, 4, 4, 8],
           "XL": [4, 8, 8]}
MODELS = ["resnet50", "resnet18", "lstm", "cyclegan", "transformer"]

# (model, size, workers, epochs, iters_per_epoch, throughput, arrival)
JobSpec = Tuple[str, str, int, int, int, Dict[str, float], float]


def calibrate_iters(gpu_hours: float,
                    throughput: Dict[str, float]) -> Tuple[int, int]:
    """(epochs, iters_per_epoch) so the job takes ``gpu_hours`` on its
    median device type."""
    med = float(np.median(list(throughput.values())))
    total_iters = max(1.0, gpu_hours * 3600.0 * med)
    return max(1, int(total_iters // 100)), 100


def philly_jobs(n_jobs: int, seed: int, types: List[str],
                all_at_start: bool = True,
                span_s: float = 8 * 3600.0) -> List[JobSpec]:
    """Size classes sampled uniformly, GPU demand heavy-tailed in
    {1, 2, 4, 8}, runtimes drawn from the class's GPU-hour range; the
    RNG stream is that of ``repro.core.trace.philly_trace``."""
    rng = np.random.RandomState(seed)
    out: List[JobSpec] = []
    for _ in range(n_jobs):
        model = MODELS[rng.randint(len(MODELS))]
        size = MODEL_SIZE[model]
        lo, hi = SIZE_GPU_HOURS[size]
        gpu_hours = rng.uniform(lo, hi)
        w = int(rng.choice(WORKERS[size]))
        tp = {r: THROUGHPUT_TABLE[model][r] for r in types}
        epochs, ipe = calibrate_iters(gpu_hours, tp)
        arrival = 0.0 if all_at_start else float(rng.uniform(0, span_s))
        out.append((model, size, w, epochs, ipe, tp, arrival))
    return out


def grown_nodes(n_jobs: int) -> List[Tuple[int, Dict[str, int]]]:
    """Fig. 5's cluster that grows with the queue: max(15, n/8) nodes of
    4 GPUs, the type by node index mod 3."""
    types = ["v100", "p100", "k80"]
    return [(i, {types[i % 3]: 4}) for i in range(max(15, n_jobs // 8))]


def simulation_nodes() -> List[Tuple[int, Dict[str, int]]]:
    """Paper §IV: 15 nodes, 60 GPUs, 20 each of V100/P100/K80."""
    return [(5 * t + i, {r: 4})
            for t, r in enumerate(("v100", "p100", "k80"))
            for i in range(5)]
