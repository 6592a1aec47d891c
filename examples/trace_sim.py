"""Trace-driven simulation walkthrough (paper §IV): run the Philly-like
trace under all four schedulers and print the Fig. 3/4 metrics.

  PYTHONPATH=src python examples/trace_sim.py [--jobs 60]
  PYTHONPATH=src python examples/trace_sim.py --engine event
  PYTHONPATH=src python examples/trace_sim.py \
      --replay examples/traces/philly_mini.csv
  PYTHONPATH=src python examples/trace_sim.py --trace out.json --explain
  PYTHONPATH=src python examples/trace_sim.py --baselines

``--engine event`` uses the continuous-time engine (repro.sim): time
advances from event to event instead of fixed rounds — same metrics
within the documented quantization tolerance, O(events) on sparse
traces.  ``--replay`` replays a Philly/Helios-style CSV instead of the
synthetic generator.

``--trace OUT`` records the run with ``repro.obs`` and writes a
Perfetto-loadable trace (open at https://ui.perfetto.dev); ``--explain``
prints allocation provenance for the first few Hadar decisions (winning
keys with Eq. 5 marginal prices, payoff, runner-up).  Decisions are
bit-identical with observability on or off.

``--baselines`` appends the heterogeneity-blind classic baselines from
``repro.env.baselines`` (FCFS, SJF, SRTF, max-min share) to the table;
``python -m repro.env.compare`` renders the same comparison as a
schema-validated JSON quality table.
"""
import argparse
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import obs
from repro.core.hadar import HadarScheduler
from repro.core.schedulers import (GavelScheduler, TiresiasScheduler,
                                   YarnCSScheduler)
from repro.core.trace import philly_trace, simulation_cluster
from repro.obs.explain import explain_allocation
from repro.sim.adapters import run as run_engine
from repro.sim.replay import load_trace_csv
from repro.utils.compile_cache import enable_compile_cache

N_EXPLAIN = 5                   # decisions rendered under --explain


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=60)
    ap.add_argument("--round-len", type=float, default=360.0)
    ap.add_argument("--engine", choices=("round", "event"),
                    default="round")
    ap.add_argument("--replay", type=str, default=None,
                    help="replay a Philly/Helios-style CSV trace")
    ap.add_argument("--faults", type=str, default=None, metavar="CSV",
                    help="inject a failure-trace CSV (node_id, "
                         "fail_time, recover_time, kind); results gain "
                         "a goodput column")
    ap.add_argument("--trace", type=str, default=None, metavar="OUT",
                    help="write a Perfetto trace of the run to OUT "
                         "(repro.obs)")
    ap.add_argument("--explain", action="store_true",
                    help="print allocation provenance for the first "
                         f"{N_EXPLAIN} Hadar decisions")
    ap.add_argument("--baselines", action="store_true",
                    help="also run the classic heterogeneity-blind "
                         "baselines (repro.env.baselines)")
    args = ap.parse_args()
    enable_compile_cache()

    cluster = simulation_cluster()
    faults = None
    if args.faults:
        from repro.sim.replay import load_fault_csv
        faults = load_fault_csv(args.faults, cluster)
        print(f"injecting {len(faults)} fault windows from {args.faults}")
    print(f"cluster: {len(cluster.nodes)} nodes, "
          f"{cluster.total_gpus()} GPUs {cluster.capacity()} "
          f"(engine: {args.engine})")
    goodput_col = f" {'goodput':>8s} {'evict':>6s}" if faults else ""
    print(f"{'scheduler':10s} {'TTD(h)':>8s} {'GRU':>6s} {'median(h)':>10s} "
          f"{'JCT(h)':>8s} {'restart-rounds':>14s}" + goodput_col)
    observed = args.trace or args.explain
    explain_recs = []
    scheds = [HadarScheduler, GavelScheduler, TiresiasScheduler,
              YarnCSScheduler]
    if args.baselines:
        from repro.env.baselines import (FCFSScheduler,
                                         MaxMinShareScheduler,
                                         SJFScheduler, SRTFScheduler)
        scheds += [FCFSScheduler, SJFScheduler, SRTFScheduler,
                   MaxMinShareScheduler]
    for cls in scheds:
        if args.replay:
            jobs = load_trace_csv(args.replay, types=cluster.gpu_types)
        else:
            jobs = philly_trace(n_jobs=args.jobs, seed=1)
        if observed and cls is HadarScheduler:
            # record only the Hadar run: the trace stays focused and the
            # decision log carries pricing provenance (baselines don't)
            with obs.session(trace_path=args.trace) as ob:
                res = run_engine(cls(), jobs, cluster, mode=args.engine,
                                 round_len=args.round_len, faults=faults)
            explain_recs = ob.decisions.decisions[:N_EXPLAIN]
        else:
            res = run_engine(cls(), jobs, cluster, mode=args.engine,
                             round_len=args.round_len, faults=faults)
        goodput_val = (f" {res.goodput():8.3f} {res.evictions:6d}"
                       if faults else "")
        print(f"{res.scheduler:10s} {res.ttd_hours:8.2f} "
              f"{res.avg_gru():6.3f} {res.median_completion()/3600:10.2f} "
              f"{res.avg_jct()/3600:8.2f} {res.changed_round_frac():14.2f}"
              + goodput_val)

    if args.trace:
        print(f"\nwrote Perfetto trace to {args.trace} "
              f"(open at https://ui.perfetto.dev)")
    if args.explain:
        print(f"\nfirst {len(explain_recs)} Hadar allocation decisions:")
        for rec in explain_recs:
            print(explain_allocation(rec))


if __name__ == "__main__":
    main()
