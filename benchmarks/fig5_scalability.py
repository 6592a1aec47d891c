"""Paper Fig. 5: scheduling latency vs active job count (32..2048) on a
cluster that grows with the workload; Hadar and Gavel compared.  The paper
reports <7 min/round at ~2000 jobs — we report seconds per scheduling
decision.

Beyond the original all-at-start Philly trace, the vectorized engine is
also timed on a bursty arrival overlay (Philly/Helios characterization)
scheduled on a multi-pod topology with mixed-type nodes — the worst case
for consolidated packing.

``run_steady`` measures sustained simulation throughput with arrivals
flowing (not just one scheduling decision): the round engine's
rounds/sec and the event engine's events/sec on the same sparse trace,
plus the wall-clock ratio between the two paths.  With ``--steady
--n-jobs N1 N2 ...`` it sweeps multi-thousand-job Philly-style replays
and publishes the rounds/sec + events/sec curves *per pricing-solver
backend* (numpy vs the jit-batched kernel) to one JSON artifact
(``experiments/bench/fig5_steady_state.json``).  Large sweep points cap
the engines (``cap_rounds``/``cap_events``) so each point measures
sustained throughput in bounded wall-clock; capped rows are flagged."""
import argparse
import os
import sys
import time

if __package__ in (None, ""):   # direct script usage
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "src"))

from benchmarks.common import emit, save_json, timed
from repro.core.hadar import HadarScheduler
from repro.core.schedulers import GavelScheduler
from repro.core.trace import multi_cluster, philly_trace
from repro.core.types import Cluster, Node
from repro.sim.adapters import CountingScheduler
from repro.sim.engine import simulate_events, simulate_rounds
from repro.utils.compile_cache import enable_compile_cache


def grown_cluster(n_jobs: int) -> Cluster:
    n_nodes = max(15, n_jobs // 8)
    types = ["v100", "p100", "k80"]
    return Cluster([Node(i, {types[i % 3]: 4}) for i in range(n_nodes)])


def _time_round(sched, now, jobs, cluster) -> float:
    t0 = time.perf_counter()
    sched.schedule(now, 360.0, jobs, cluster)
    return time.perf_counter() - t0


def run(sizes=(32, 64, 128, 256, 512, 1024, 2048)):
    rows = {}
    with timed() as t:
        for n in sizes:
            # original workload: all-at-start Philly trace, homogeneous nodes
            cluster = grown_cluster(n)
            jobs = philly_trace(n_jobs=n, seed=1, types=cluster.gpu_types)
            h = HadarScheduler()
            th = _time_round(h, 0.0, jobs, cluster)
            tg = _time_round(GavelScheduler(), 0.0, jobs, cluster)

            # bursty arrivals on a multi-pod, partly mixed-node topology;
            # scheduled after the last burst so the whole queue is live
            pods = multi_cluster(n_pods=3, nodes_per_pod=max(5, n // 24),
                                 gpus_per_node=4,
                                 pod_types=["v100", "p100", "k80"],
                                 mixed_frac=0.25, seed=2)
            bjobs = philly_trace(n_jobs=n, seed=1, types=pods.gpu_types,
                                 arrival_pattern="bursty")
            now = max(j.arrival for j in bjobs)
            tb = _time_round(HadarScheduler(), now, bjobs, pods)
            tbg = _time_round(GavelScheduler(), now, bjobs, pods)

            rows[n] = {"hadar_s": th, "gavel_s": tg,
                       "hadar_bursty_s": tb, "gavel_bursty_s": tbg,
                       "alpha": h.alpha}
    save_json("fig5_scalability", rows)
    worst = rows[max(rows)]
    emit("fig5_scalability", t.us,
         f"{max(rows)} jobs: hadar {worst['hadar_s']:.2f}s/round "
         f"(bursty multi-pod {worst['hadar_bursty_s']:.2f}s), gavel "
         f"{worst['gavel_s']:.2f}s/round (paper: <7min; similar scaling)")
    return rows


def sparse_trace(n_jobs: int, round_len: float, seed: int = 5,
                 gap_factor: float = 600.0):
    """Arrivals stretched so inter-arrival gaps average >= ``gap_factor``
    times ``round_len`` — the regime where round quantization wastes
    O(max_rounds) work.  The default gap (~10 h of simulated time at the
    60 s round) is on the scale of the jobs' own durations, i.e. the
    cluster is mostly uncontended: a bursty backlogged queue is the
    *dense* regime the round engine already handles."""
    jobs = philly_trace(n_jobs=n_jobs, seed=seed, all_at_start=False)
    span = max(j.arrival for j in jobs) or 1.0
    stretch = gap_factor * round_len * n_jobs / span
    for j in jobs:
        j.arrival *= stretch
    return jobs


def measure_sparse(n_jobs: int, round_len: float, repeats: int = 1,
                   solver: str = None, cap_rounds: int = None,
                   cap_events: int = None):
    """Shared round-vs-event timing harness on one sparse trace (also
    drives the check_speedup.py perf gate — keep the regimes in sync by
    construction).  Wall-clocks are best-of-``repeats``; counts and TTDs
    come from the (deterministic) last run.  ``solver`` picks the Hadar
    pricing backend; ``cap_rounds``/``cap_events`` bound the engines for
    multi-thousand-job sweep points (throughput = work/wall either
    way)."""
    cluster = grown_cluster(n_jobs)
    max_rounds = cap_rounds if cap_rounds is not None else 2000000
    max_events = cap_events if cap_events is not None else 500000
    mk_sched = lambda: HadarScheduler(solver=solver or "auto")
    best_r = best_e = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        rr = simulate_rounds(mk_sched(), sparse_trace(n_jobs, round_len),
                             cluster, round_len=round_len,
                             max_rounds=max_rounds, solver=solver)
        best_r = min(best_r, time.perf_counter() - t0)

        inner = CountingScheduler(mk_sched())
        t0 = time.perf_counter()
        re = simulate_events(inner, sparse_trace(n_jobs, round_len),
                             cluster, round_len=round_len,
                             max_events=max_events, solver=solver)
        best_e = min(best_e, time.perf_counter() - t0)
    return {
        "n_jobs": n_jobs,
        "round_len": round_len,
        "solver": solver or "auto",
        "round_wall_s": best_r,
        "round_rounds": len(rr.rounds),
        "rounds_per_sec": len(rr.rounds) / max(best_r, 1e-9),
        "round_capped": cap_rounds is not None,
        "event_wall_s": best_e,
        "event_events": re.n_events,
        "events_per_sec": re.n_events / max(best_e, 1e-9),
        "event_capped": cap_events is not None,
        "event_sched_calls": inner.calls,
        "speedup": best_r / max(best_e, 1e-9),
        "ttd_round_s": rr.total_seconds,
        "ttd_event_s": re.total_seconds,
    }


# sweep points above this get bounded engines so each point costs
# bounded wall-clock; rates stay comparable (throughput = work / wall)
_CAP_ABOVE = 256
_CAP_ROUNDS = 4000
_CAP_EVENTS = 6000


def run_steady(n_jobs: int = 48, round_len: float = 60.0, sweep=None,
               solvers=None):
    """Steady-state simulation throughput, arrivals flowing: round engine
    rounds/sec vs event engine events/sec on sparse Philly traces.

    ``sweep`` (list of job counts) scales the replay to multi-thousand-job
    Philly-style workloads; curves are measured per pricing-solver
    backend in ``solvers`` and published to one JSON artifact."""
    if solvers is None:
        solvers = ["numpy", "jax"]
    sizes = list(sweep) if sweep else [n_jobs]
    out = {"round_len": round_len, "sizes": sizes, "curves": {}}
    sweep_us = {}
    for sv in solvers:
        curve = {}
        with timed() as t:
            for n in sizes:
                capped = n > _CAP_ABOVE
                curve[n] = measure_sparse(
                    n, round_len, solver=sv,
                    cap_rounds=_CAP_ROUNDS if capped else None,
                    cap_events=_CAP_EVENTS if capped else None)
        out["curves"][sv] = curve
        sweep_us[sv] = t.us
    save_json("fig5_steady_state", out)
    top = max(sizes)
    for sv in solvers:
        rows = out["curves"][sv][top]
        emit("fig5_steady_state", sweep_us[sv],
             f"[{sv}] {top} jobs sparse: round "
             f"{rows['rounds_per_sec']:.0f} rounds/s "
             f"({rows['round_wall_s']:.2f}s), event "
             f"{rows['events_per_sec']:.0f} events/s "
             f"({rows['event_wall_s']:.3f}s), "
             f"{rows['speedup']:.0f}x wall-clock")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steady", action="store_true",
                    help="run only the steady-state throughput benchmark")
    ap.add_argument("--n-jobs", type=int, nargs="+", default=None,
                    help="steady-state sweep sizes (e.g. 256 1024 2048)")
    ap.add_argument("--round-len", type=float, default=60.0)
    ap.add_argument("--solvers", nargs="+", default=None,
                    choices=["numpy", "jax", "auto"],
                    help="pricing backends to compare (default: numpy "
                         "+ jax)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.steady:
        run_steady(round_len=args.round_len, sweep=args.n_jobs,
                   solvers=args.solvers)
    else:
        run()
        run_steady(round_len=args.round_len, sweep=args.n_jobs,
                   solvers=args.solvers)
