"""Perf smoke gate: fail if the vectorized engine's per-round scheduling
latency at n=256 regresses more than 2x against the recorded baseline,
if the event engine loses its sparse-trace advantage over the
round-based path, or if the jit-batched price solver loses its edge
over the per-job NumPy scan.

Usage:
  python benchmarks/check_speedup.py             # gate against baselines
  python benchmarks/check_speedup.py --record    # re-record the baselines
  python benchmarks/check_speedup.py --quick     # smoke over a tiny trace
  python benchmarks/check_speedup.py --calibrate # record solver crossovers

To stay machine-independent, the gates compare *normalized* numbers:

- scheduling latency is divided by the runtime of the vendored scalar
  reference engine (tests/_seed_reference.py) on the same machine in
  the same process.  A 2x margin on the ratio-of-ratios catches an
  accidental return of the per-device Python loops (a ~30x cliff)
  without tripping on slower CI hardware.
- the event engine is compared against the round engine on the same
  sparse trace in the same process (baseline_event_sparse.json).  The
  gate enforces the absolute acceptance bar — event wall-clock at most
  1/5 of the round path — plus a 2x regression margin on the recorded
  ratio.
- the fault gate (baseline_event_faults.json) replays the same sparse
  trace with a seeded failure schedule (background MTBF windows plus a
  deterministic all-nodes blip that forces at least one eviction): the
  fault path must stay within 1.5x of the fault-free event wall-clock
  in the same process, report goodput strictly below GRU, and not
  regress more than 2x against the recorded overhead ratio.
- the jit gate (baseline_fig5_jit.json) prices the whole n=1024 fig5
  queue through ``find_alloc_batch`` (one fused call, post-compile) and
  through the per-job NumPy greedy scan in the same process: the batched
  solver must be >= 3x faster (acceptance bar) and must not regress more
  than 2x against the recorded speedup ratio — both are ratios of
  same-process wall-clocks, so slower CI hardware cancels out.  The
  gate also re-checks decision equality job by job.
- the commit gate (baseline_fig5_commit.json) runs the *end-to-end*
  greedy ``dp_allocation`` (pricing + wave/scan commit) over the full
  n=2048 fig5 queue under ``solver="jax"`` and under the sequential
  NumPy loop in the same process: the device commit must be >= 2x
  faster (acceptance bar), bit-identical in every decision, and must
  not regress more than 2x against the recorded ratio.

``--calibrate`` measures the two ``auto``-dispatch crossovers on this
machine — the queue size where the fused pricing kernel starts beating
the per-job NumPy scan, and the greedy-queue size where the wave/scan
commit starts beating the sequential loop — and records them into the
committed ``src/repro/core/solver_calibration.json`` consumed by
``repro.core.batch_solver`` (``REPRO_SOLVER_THRESHOLD`` still overrides
the pricing threshold at runtime).

``--quick`` runs a seconds-scale smoke over a tiny trace: both engines
and the HadarE backend must complete every job and agree within the
documented quantization tolerance, and the batched solver must match
the per-job path on small shapes.  It also
runs the policy-comparison harness (``repro.env.compare``) over two
baselines on a tiny fig5 trace — the emitted table must schema-validate
and match the committed ``baseline_policy_table.json`` bit-for-bit (the
simulation is deterministic, so any drift means an engine or
baseline-policy behaviour change; re-record with ``--record``) — and
lints src/ with ``repro.analysis`` against the committed
``analysis_baseline.json`` — zero non-baselined findings.  No perf
baselines are touched.
"""
import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))

from repro.obs import StopWatch  # noqa: E402  (path set above)

BASELINE = os.path.join(os.path.dirname(__file__),
                        "baseline_fig5_n256.json")
EVENT_BASELINE = os.path.join(os.path.dirname(__file__),
                              "baseline_event_sparse.json")
JIT_BASELINE = os.path.join(os.path.dirname(__file__),
                            "baseline_fig5_jit.json")
N_JOBS = 256
REPEATS = 3
MAX_REGRESSION = 2.0
EVENT_MAX_FRACTION = 0.2        # event engine must stay <= 1/5 round path
SPARSE_N_JOBS = 32
SPARSE_ROUND_LEN = 60.0
JIT_N_JOBS = 1024
JIT_MIN_SPEEDUP = 3.0           # batched solver vs per-job NumPy scan
COMMIT_BASELINE = os.path.join(os.path.dirname(__file__),
                               "baseline_fig5_commit.json")
COMMIT_N_JOBS = 2048
COMMIT_MIN_SPEEDUP = 2.0        # end-to-end greedy commit vs NumPy loop
FAULT_BASELINE = os.path.join(os.path.dirname(__file__),
                              "baseline_event_faults.json")
FAULT_MAX_OVERHEAD = 1.5        # fault path vs fault-free event engine
FAULT_MTBF_HOURS = 240.0
FAULT_SEED = 7
FAULT_BLIP_S = 900.0            # deterministic all-nodes outage length
# --calibrate sweeps (queue sizes, ascending)
AUTO_SWEEP = (4, 8, 12, 16, 24, 32, 48)
COMMIT_SWEEP = (24, 48, 96, 192, 384)
POLICY_BASELINE = os.path.join(os.path.dirname(__file__),
                               "baseline_policy_table.json")
POLICY_SMOKE_N = 6              # tiny fig5 trace for the compare smoke
POLICY_SMOKE_SEED = 9
POLICY_SMOKE_POLICIES = ("fcfs", "srtf")


def _best_round(mk_sched, jobs_factory, cluster) -> float:
    best = float("inf")
    for _ in range(REPEATS):
        jobs = jobs_factory()
        sched = mk_sched()
        with StopWatch() as sw:
            sched.schedule(0.0, 360.0, jobs, cluster)
        best = min(best, sw.seconds)
    return best


def measure():
    import _seed_reference as ref
    from benchmarks.fig5_scalability import grown_cluster
    from repro.core.hadar import HadarScheduler
    from repro.core.trace import philly_trace

    cluster = grown_cluster(N_JOBS)
    jobs_factory = lambda: philly_trace(n_jobs=N_JOBS, seed=1,
                                        types=cluster.gpu_types)
    return {
        "hadar_s": _best_round(HadarScheduler, jobs_factory, cluster),
        "ref_hadar_s": _best_round(ref.ReferenceHadarScheduler,
                                   jobs_factory, cluster),
    }


def measure_latency(n_jobs=SPARSE_N_JOBS, round_len=SPARSE_ROUND_LEN):
    """Decision-latency distribution of the event engine on the sparse
    fig5 trace: per-consult scheduler wall-clock quantiles read from the
    repro.obs histogram (metrics only — trace/decision recording off)."""
    from benchmarks.fig5_scalability import grown_cluster, sparse_trace
    from repro import obs
    from repro.core.hadar import HadarScheduler
    from repro.sim.engine import simulate_events

    cluster = grown_cluster(n_jobs)
    jobs = sparse_trace(n_jobs, round_len)
    with obs.session(trace=False, decisions=False) as ob:
        simulate_events(HadarScheduler(), jobs, cluster,
                        round_len=round_len)
    h = ob.metrics.histogram("decision_latency_s")
    return {"consults": h.count, "p50_s": h.quantile(0.50),
            "p95_s": h.quantile(0.95), "p99_s": h.quantile(0.99)}


def measure_event(n_jobs=SPARSE_N_JOBS, round_len=SPARSE_ROUND_LEN):
    """Round vs event engine wall-clock on one sparse fig5 trace — the
    same harness the fig5 steady-state benchmark reports from."""
    from benchmarks.fig5_scalability import measure_sparse

    rows = measure_sparse(n_jobs, round_len, repeats=REPEATS)
    return {k: rows[k] for k in ("n_jobs", "round_len", "round_wall_s",
                                 "event_wall_s")}


def measure_event_faults(n_jobs=SPARSE_N_JOBS, round_len=SPARSE_ROUND_LEN,
                         repeats=REPEATS):
    """Fault-injection overhead on the sparse fig5 trace: event-engine
    wall-clock with a seeded MTBF failure schedule vs the fault-free
    run, same trace, same process.  The schedule is dense enough to
    force at least one eviction (asserted — an eviction-free run would
    gate nothing) yet sparse enough that fault handling must stay within
    ``FAULT_MAX_OVERHEAD`` of the fault-free wall-clock."""
    from benchmarks.fig5_scalability import grown_cluster, sparse_trace
    from repro.core.hadar import HadarScheduler
    from repro.sim.engine import simulate_events
    from repro.sim.faults import FailureModel, FailureTrace, FaultWindow

    cluster = grown_cluster(n_jobs)
    arrivals = sorted(j.arrival for j in sparse_trace(n_jobs, round_len))
    model = FailureModel(mtbf_hours=FAULT_MTBF_HOURS, recovery_s=1800.0,
                         seed=FAULT_SEED, horizon=arrivals[-1])
    # deterministic blip: every node down for FAULT_BLIP_S while the
    # first job is mid-run — guarantees the eviction whichever node the
    # scheduler picked (sampled windows overlapping the blip dropped)
    blip_t = arrivals[0] + 600.0
    base = [w for w in model.sample(cluster)
            if w.recover_time <= blip_t
            or w.fail_time >= blip_t + FAULT_BLIP_S]
    blip = [FaultWindow(n.node_id, blip_t, blip_t + FAULT_BLIP_S)
            for n in cluster.nodes]
    trace = FailureTrace(base + blip, cluster)

    best_clean = best_fault = float("inf")
    res = None
    for _ in range(repeats):
        jobs = sparse_trace(n_jobs, round_len)
        with StopWatch() as sw:
            simulate_events(HadarScheduler(), jobs, cluster,
                            round_len=round_len)
        best_clean = min(best_clean, sw.seconds)
        jobs = sparse_trace(n_jobs, round_len)
        with StopWatch() as sw:
            res = simulate_events(HadarScheduler(), jobs, cluster,
                                  round_len=round_len, faults=trace)
        best_fault = min(best_fault, sw.seconds)
    assert res.evictions >= 1, \
        "fault benchmark produced no evictions — schedule too sparse"
    return {"n_jobs": n_jobs, "round_len": round_len,
            "clean_wall_s": best_clean, "fault_wall_s": best_fault,
            "overhead": best_fault / max(best_clean, 1e-9),
            "evictions": res.evictions, "goodput": res.goodput(),
            "gru": res.gru_overall()}


def measure_jit(n_jobs=JIT_N_JOBS, repeats=REPEATS):
    """Whole-queue pricing scan at ``n_jobs``: one fused batched call vs
    the per-job NumPy loop, same state, same process.  Returns wall
    clocks, the speedup ratio, and the count of decision mismatches
    (must be 0 — the backends are bit-identical by contract)."""
    from benchmarks.fig5_scalability import grown_cluster
    from repro.core.batch_solver import find_alloc_batch
    from repro.core.dp import _find_alloc_arrays
    from repro.core.pricing import PriceState
    from repro.core.trace import philly_trace
    from repro.core.utility import effective_throughput

    cluster = grown_cluster(n_jobs)
    jobs = philly_trace(n_jobs=n_jobs, seed=1, types=cluster.gpu_types)
    ps = PriceState(cluster, jobs, 7 * 24 * 3600.0, effective_throughput,
                    0.0)
    avail = ps.free_arr.copy()
    gamma = ps.gamma_arr.copy()

    best_np = float("inf")
    for _ in range(repeats):
        with StopWatch() as sw:
            ref_c = [_find_alloc_arrays(j, avail, gamma, ps, 0.0,
                                        effective_throughput, False)
                     for j in jobs]
        best_np = min(best_np, sw.seconds)

    jit_c = find_alloc_batch(jobs, avail, gamma, ps, 0.0,
                             effective_throughput)    # compile warmup
    best_jit = float("inf")
    for _ in range(repeats):
        with StopWatch() as sw:
            jit_c = find_alloc_batch(jobs, avail, gamma, ps, 0.0,
                                     effective_throughput)
        best_jit = min(best_jit, sw.seconds)

    mismatches = sum(
        1 for a, b in zip(ref_c, jit_c)
        if (a is None) != (b is None)
        or (a is not None and (a.alloc != b.alloc or a.cost != b.cost
                               or a.payoff != b.payoff)))
    return {"n_jobs": n_jobs, "numpy_s": best_np, "jit_s": best_jit,
            "speedup": best_np / max(best_jit, 1e-9),
            "mismatches": mismatches}


def measure_commit(n_jobs=COMMIT_N_JOBS, repeats=2):
    """End-to-end greedy ``dp_allocation`` at ``n_jobs``: pricing plus
    the wave/scan device commit (``solver="jax"``) vs the sequential
    per-job NumPy loop, fresh ``PriceState`` per run, same process.
    Returns wall clocks, the speedup ratio, and the decision-mismatch
    count (must be 0 — the commit path is bit-identical by contract)."""
    from benchmarks.fig5_scalability import grown_cluster
    from repro.core.dp import dp_allocation
    from repro.core.pricing import PriceState
    from repro.core.trace import philly_trace
    from repro.core.utility import effective_throughput

    cluster = grown_cluster(n_jobs)
    jobs = philly_trace(n_jobs=n_jobs, seed=1, types=cluster.gpu_types)

    def run(solver):
        ps = PriceState(cluster, jobs, 7 * 24 * 3600.0,
                        effective_throughput, 0.0)
        with StopWatch() as sw:
            sel = dp_allocation(jobs, None, ps, 0.0,
                                effective_throughput, solver=solver)
        return sw.seconds, sel

    run("jax")                              # compile warmup
    best_np = best_jx = float("inf")
    sel_np = sel_jx = {}
    for _ in range(repeats):
        t, sel_np = run("numpy")
        best_np = min(best_np, t)
        t, sel_jx = run("jax")
        best_jx = min(best_jx, t)
    if set(sel_np) != set(sel_jx):
        mismatches = len(set(sel_np) ^ set(sel_jx))
    else:
        mismatches = sum(
            1 for k in sel_np
            if (sel_np[k].alloc, sel_np[k].cost, sel_np[k].payoff,
                sel_np[k].rate)
            != (sel_jx[k].alloc, sel_jx[k].cost, sel_jx[k].payoff,
                sel_jx[k].rate))
    return {"n_jobs": n_jobs, "numpy_s": best_np, "jax_s": best_jx,
            "speedup": best_np / max(best_jx, 1e-9),
            "selected": len(sel_np), "mismatches": mismatches}


def measure_policy_table():
    """The compare-harness smoke table: two classic baselines over a
    tiny fig5 trace (deterministic, sub-second)."""
    from repro.core.trace import philly_trace, simulation_cluster
    from repro.env.compare import compare

    cluster = simulation_cluster()
    jobs = philly_trace(n_jobs=POLICY_SMOKE_N, seed=POLICY_SMOKE_SEED)
    return compare(jobs, cluster, policies=POLICY_SMOKE_POLICIES,
                   trace_name=f"fig5(n={POLICY_SMOKE_N}, "
                              f"seed={POLICY_SMOKE_SEED})")


def policy_table_drift(cur, base, rtol=1e-9):
    """Quality-metric drift between a freshly measured compare table and
    the committed baseline: the simulation is deterministic, so every
    row must match to float precision.  Returns a list of problems."""
    probs = []
    cr = {r["policy"]: r for r in cur.get("policies", [])}
    br = {r["policy"]: r for r in base.get("policies", [])}
    if set(cr) != set(br):
        return [f"policy set changed: {sorted(cr)} vs {sorted(br)}"]
    for name, b in br.items():
        c = cr[name]
        for f in ("ttd_hours", "avg_jct_s", "gru", "cru", "gru_overall",
                  "goodput"):
            if abs(c[f] - b[f]) > rtol * max(1.0, abs(b[f])):
                probs.append(f"{name}.{f}: {c[f]!r} != {b[f]!r}")
        for f in ("evictions", "restarts", "completed", "n_jobs"):
            if c[f] != b[f]:
                probs.append(f"{name}.{f}: {c[f]} != {b[f]}")
    return probs


def _suffix_crossover(rows, fallback):
    """Smallest sweep size from which the device path never loses
    (suffix-win rule — one noisy small point cannot drag the threshold
    down); ``fallback`` when the device path never sustains a win."""
    best = None
    for row in reversed(rows):
        if row["jax_s"] <= row["numpy_s"]:
            best = row["n_jobs"]
        else:
            break
    return best if best is not None else fallback


def calibrate() -> None:
    """Measure the two ``auto``-dispatch crossovers on this machine and
    record them into the committed calibration JSON (consumed by
    ``repro.core.batch_solver``; the ``REPRO_SOLVER_THRESHOLD`` env var
    still overrides the pricing threshold at runtime)."""
    from repro.core import batch_solver as bs
    from benchmarks.fig5_scalability import grown_cluster
    from repro.core.dp import _find_alloc_arrays, dp_allocation
    from repro.core.pricing import PriceState
    from repro.core.trace import philly_trace
    from repro.core.utility import effective_throughput

    def state(n):
        cluster = grown_cluster(n)
        jobs = philly_trace(n_jobs=n, seed=1, types=cluster.gpu_types)
        ps = PriceState(cluster, jobs, 7 * 24 * 3600.0,
                        effective_throughput, 0.0)
        return cluster, jobs, ps

    pricing_rows = []
    for n in AUTO_SWEEP:
        _, jobs, ps = state(n)
        avail = ps.free_arr.copy()
        gamma = ps.gamma_arr.copy()
        bs.find_alloc_batch(jobs, avail, gamma, ps, 0.0,
                            effective_throughput)       # compile warmup
        t_np = t_jx = float("inf")
        for _ in range(REPEATS):
            with StopWatch() as sw:
                for j in jobs:
                    _find_alloc_arrays(j, avail, gamma, ps, 0.0,
                                       effective_throughput, False)
            t_np = min(t_np, sw.seconds)
            with StopWatch() as sw:
                bs.find_alloc_batch(jobs, avail, gamma, ps, 0.0,
                                    effective_throughput)
            t_jx = min(t_jx, sw.seconds)
        pricing_rows.append({"n_jobs": n, "numpy_s": t_np, "jax_s": t_jx})
        print(f"pricing n={n}: numpy {t_np * 1e3:.2f}ms "
              f"jax {t_jx * 1e3:.2f}ms")

    commit_rows = []
    for n in COMMIT_SWEEP:
        cluster, jobs, _ = state(n)
        t_by = {}
        for solver in ("numpy", "jax"):
            best = float("inf")
            for rep in range(REPEATS + 1):
                _, _, ps = state(n)
                with StopWatch() as sw:
                    dp_allocation(jobs, None, ps, 0.0,
                                  effective_throughput, max_exact=0,
                                  solver=solver)
                if rep:                     # round 0 warms the compile
                    best = min(best, sw.seconds)
            t_by[solver] = best
        commit_rows.append({"n_jobs": n, "numpy_s": t_by["numpy"],
                            "jax_s": t_by["jax"]})
        print(f"commit n={n}: numpy {t_by['numpy'] * 1e3:.2f}ms "
              f"jax {t_by['jax'] * 1e3:.2f}ms")

    doc = {
        "auto_min_jobs": _suffix_crossover(pricing_rows,
                                           bs.AUTO_MIN_JOBS),
        "commit_min_jobs": _suffix_crossover(commit_rows,
                                             bs.COMMIT_MIN_JOBS),
        "pricing_sweep": pricing_rows,
        "commit_sweep": commit_rows,
    }
    with open(bs.CALIBRATION_FILE, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=1)
        fh.write("\n")
    print(f"calibration written to {bs.CALIBRATION_FILE}: "
          f"auto_min_jobs={doc['auto_min_jobs']} "
          f"commit_min_jobs={doc['commit_min_jobs']}")


def quick_smoke() -> None:
    """Tiny-trace smoke: engines + HadarE backend complete and agree."""
    from repro.core.hadar import HadarScheduler
    from repro.core.hadare import simulate_hadare
    from repro.core.trace import mix_jobs, philly_trace, testbed_cluster
    from repro.core.trace import simulation_cluster
    from repro.sim.engine import simulate_events, simulate_rounds

    cluster = simulation_cluster()
    L = 360.0
    rr = simulate_rounds(HadarScheduler(), philly_trace(n_jobs=8, seed=9),
                         cluster, round_len=L, max_rounds=8000)
    re = simulate_events(HadarScheduler(), philly_trace(n_jobs=8, seed=9),
                         cluster, round_len=L)
    assert all(j.finish_time is not None for j in rr.jobs), "round engine"
    assert all(j.finish_time is not None for j in re.jobs), "event engine"
    drift = abs(re.total_seconds - rr.total_seconds)
    assert drift <= max(2 * L, 0.02 * rr.total_seconds), \
        f"TTD drift {drift:.1f}s exceeds quantization tolerance"
    tb = testbed_cluster()
    rh = simulate_hadare(mix_jobs("M-3", tb), tb, round_len=90.0)
    assert all(p.finish_time is not None for p in rh.jobs), "hadare"

    # fault smoke: a seeded MTBF schedule through the event engine with
    # the sanitizer on — at least one eviction, goodput strictly below
    # GRU, every job still completes, zero invariant violations
    from repro.sim.faults import FailureModel
    rf = simulate_events(HadarScheduler(), philly_trace(n_jobs=8, seed=9),
                         cluster, round_len=L, sanitize=True,
                         faults=FailureModel(mtbf_hours=4.0,
                                             recovery_s=1200.0, seed=11))
    assert rf.evictions >= 1, "fault smoke: no evictions"
    assert rf.goodput() < rf.gru_overall(), \
        "fault smoke: eviction cost not reflected in goodput"
    assert all(j.finish_time is not None for j in rf.jobs), \
        "fault smoke: jobs starved after faults"
    fault_msg = (f"faults ok ({rf.evictions} evictions, goodput "
                 f"{rf.goodput():.3f} < gru {rf.gru_overall():.3f})")

    # observability smoke: re-run the event sim with recording on — the
    # decisions must not move, and the emitted trace must schema-validate
    from repro import obs
    from repro.obs.trace import validate_trace
    tmp = os.path.join(tempfile.mkdtemp(prefix="repro_obs_"),
                       "quick_trace.json")
    with obs.session(trace_path=tmp) as ob:
        ro = simulate_events(HadarScheduler(),
                             philly_trace(n_jobs=8, seed=9),
                             cluster, round_len=L)
    assert [j.finish_time for j in ro.jobs] \
        == [j.finish_time for j in re.jobs], \
        "obs-enabled run changed scheduling decisions"
    with open(tmp, "r", encoding="utf-8") as fh:
        doc = json.load(fh)
    probs = validate_trace(doc)
    assert not probs, "trace schema: " + "; ".join(probs[:3])
    lat = ob.metrics.histogram("decision_latency_s")
    assert lat.count > 0, "no decision-latency samples recorded"
    obs_msg = (f"obs trace valid ({len(doc['traceEvents'])} events, "
               f"{lat.count} consults)")

    # jit smoke: compile on small shapes, decisions must match the
    # per-job path exactly (seconds on CPU)
    jit = measure_jit(n_jobs=32, repeats=1)
    assert jit["mismatches"] == 0, \
        f"jit smoke: {jit['mismatches']} decision mismatches"
    jit_msg = f"jit n=32 match ({jit['jit_s']*1e3:.0f}ms/call)"

    # wave-commit smoke: the forced-jax greedy pass (wave partitioner +
    # device scan) must match the sequential NumPy loop decision for
    # decision, and report its waves through repro.obs
    from benchmarks.fig5_scalability import grown_cluster
    from repro.core.dp import dp_allocation
    from repro.core.pricing import PriceState
    from repro.core.utility import effective_throughput
    wcluster = grown_cluster(64)
    wjobs = philly_trace(n_jobs=64, seed=3, types=wcluster.gpu_types)
    sel = {}
    waves = 0
    for sv in ("numpy", "jax"):
        ps = PriceState(wcluster, wjobs, 7 * 24 * 3600.0,
                        effective_throughput, 0.0)
        if sv == "jax":
            with obs.session(trace=False, decisions=False) as wob:
                sel[sv] = dp_allocation(wjobs, None, ps, 0.0,
                                        effective_throughput,
                                        max_exact=0, solver=sv)
            waves = wob.metrics.summary()["counters"].get(
                "solver.commit_waves", 0)
            assert waves >= 1, "wave partitioner emitted no waves"
        else:
            sel[sv] = dp_allocation(wjobs, None, ps, 0.0,
                                    effective_throughput,
                                    max_exact=0, solver=sv)
    assert set(sel["numpy"]) == set(sel["jax"]), \
        "wave smoke: selections diverged"
    for k, a in sel["numpy"].items():
        b = sel["jax"][k]
        assert (a.alloc, a.cost, a.payoff, a.rate) \
            == (b.alloc, b.cost, b.payoff, b.rate), \
            f"wave smoke: job {k} decision diverged"
    wave_msg = (f"wave commit match (n=64, {waves} waves, "
                f"{len(sel['jax'])} selected)")

    # compare-harness smoke: two policies over a tiny trace must emit a
    # schema-valid table whose quality metrics match the committed
    # baseline to float precision — the simulation is deterministic, so
    # drift means an engine or baseline-policy behaviour change
    from repro.env.compare import validate_table
    pdoc = measure_policy_table()
    probs = validate_table(pdoc)
    assert not probs, "policy table schema: " + "; ".join(probs)
    assert os.path.exists(POLICY_BASELINE), \
        (f"no committed policy table at {POLICY_BASELINE}; run "
         f"benchmarks/check_speedup.py --record")
    with open(POLICY_BASELINE, "r", encoding="utf-8") as fh:
        pbase = json.load(fh)
    drift = policy_table_drift(pdoc, pbase)
    assert not drift, \
        "policy table drift vs baseline: " + "; ".join(drift)
    cmp_msg = (f"compare table ok ({len(pdoc['policies'])} policies, "
               f"no drift)")

    # analysis smoke: the shipped src/ tree must lint clean against the
    # committed baseline (same gate as tests/test_analysis_gate.py)
    from repro.analysis.engine import lint_paths
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report = lint_paths([os.path.join(repo, "src")], root=repo,
                        baseline_path=os.path.join(
                            repo, "analysis_baseline.json"))
    assert report.clean, "analysis smoke:\n" + "\n".join(
        f.render() for f in report.parse_errors + report.findings)
    lint_msg = f"lint clean ({len(report.suppressed)} baselined)"

    print(f"quick smoke passed: round TTD {rr.total_seconds:.0f}s, "
          f"event TTD {re.total_seconds:.0f}s "
          f"({re.n_events} events, {re.sched_calls} schedule calls), "
          f"hadare TTD {rh.total_seconds:.0f}s, {fault_msg}, {obs_msg}, "
          f"{jit_msg}, {wave_msg}, {cmp_msg}, {lint_msg}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--record", action="store_true",
                    help="re-record the baselines instead of gating")
    ap.add_argument("--quick", action="store_true",
                    help="seconds-scale smoke over a tiny trace; "
                         "no baseline comparison")
    ap.add_argument("--calibrate", action="store_true",
                    help="measure the auto-dispatch crossovers and "
                         "record src/repro/core/solver_calibration.json")
    args = ap.parse_args()

    if args.quick:
        quick_smoke()
        return
    if args.calibrate:
        calibrate()
        return

    if not args.record and not os.path.exists(BASELINE):
        print(f"no baseline at {BASELINE}; run with --record first")
        raise SystemExit(2)

    current = measure()
    latency = measure_latency()
    event = measure_event()
    faults = measure_event_faults()
    jit = measure_jit()
    commit = measure_commit()
    if args.record:
        with open(BASELINE, "w") as f:
            json.dump({"n_jobs": N_JOBS, **current, "latency": latency},
                      f, indent=1)
        with open(EVENT_BASELINE, "w") as f:
            json.dump(event, f, indent=1)
        with open(FAULT_BASELINE, "w") as f:
            json.dump(faults, f, indent=1)
        with open(JIT_BASELINE, "w") as f:
            json.dump(jit, f, indent=1)
        with open(COMMIT_BASELINE, "w") as f:
            json.dump(commit, f, indent=1)
        with open(POLICY_BASELINE, "w") as f:
            json.dump(measure_policy_table(), f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"recorded baselines: {current} | {event} | {faults} | "
              f"{jit} | {commit} | policy table -> {POLICY_BASELINE}")
        return

    failed = False
    with open(BASELINE) as f:
        base = json.load(f)

    cur_norm = current["hadar_s"] / max(current["ref_hadar_s"], 1e-9)
    base_norm = base["hadar_s"] / max(base["ref_hadar_s"], 1e-9)
    ratio = cur_norm / max(base_norm, 1e-9)
    print(f"hadar_s: current {current['hadar_s']:.3f}s "
          f"(scalar ref {current['ref_hadar_s']:.3f}s, "
          f"{1 / max(cur_norm, 1e-9):.1f}x speedup) vs baseline "
          f"{base['hadar_s']:.3f}s ({1 / max(base_norm, 1e-9):.1f}x) — "
          f"normalized ratio {ratio:.2f}x")
    if ratio > MAX_REGRESSION:
        print(f"FAIL: normalized scheduling latency regressed "
              f">{MAX_REGRESSION}x vs baseline")
        failed = True

    # ---- decision-latency p99 gate (obs histogram) ----------------------
    print(f"decision latency (event engine, sparse n={SPARSE_N_JOBS}): "
          f"p50 {latency['p50_s'] * 1e3:.2f}ms "
          f"p95 {latency['p95_s'] * 1e3:.2f}ms "
          f"p99 {latency['p99_s'] * 1e3:.2f}ms "
          f"over {latency['consults']} consults")
    if "latency" in base:
        # normalize p99 by the same-process scalar-reference runtime so
        # slower CI hardware cancels, exactly like the hadar_s gate
        cur_l = latency["p99_s"] / max(current["ref_hadar_s"], 1e-9)
        base_l = base["latency"]["p99_s"] / max(base["ref_hadar_s"], 1e-9)
        lratio = cur_l / max(base_l, 1e-9)
        print(f"normalized p99 ratio {lratio:.2f}x vs baseline "
              f"(margin {MAX_REGRESSION}x)")
        if lratio > MAX_REGRESSION:
            print(f"FAIL: decision-latency p99 regressed "
                  f">{MAX_REGRESSION}x vs baseline")
            failed = True
    else:
        print(f"no latency entry in {BASELINE}; "
              f"run with --record to add one")

    cur_frac = event["event_wall_s"] / max(event["round_wall_s"], 1e-9)
    print(f"event engine: {event['event_wall_s']:.3f}s vs round path "
          f"{event['round_wall_s']:.3f}s on the sparse trace "
          f"({1 / max(cur_frac, 1e-9):.0f}x)")
    if cur_frac > EVENT_MAX_FRACTION:
        print(f"FAIL: event engine wall-clock {cur_frac:.2f} of the round "
              f"path (must be <= {EVENT_MAX_FRACTION})")
        failed = True
    if os.path.exists(EVENT_BASELINE):
        with open(EVENT_BASELINE) as f:
            ebase = json.load(f)
        base_frac = ebase["event_wall_s"] / max(ebase["round_wall_s"], 1e-9)
        eratio = cur_frac / max(base_frac, 1e-9)
        print(f"event/round fraction {cur_frac:.4f} vs baseline "
              f"{base_frac:.4f} — ratio {eratio:.2f}x")
        if eratio > MAX_REGRESSION:
            print(f"FAIL: event-engine advantage regressed "
                  f">{MAX_REGRESSION}x vs baseline")
            failed = True
    else:
        print(f"no event baseline at {EVENT_BASELINE}; "
              f"run with --record to add one")

    # ---- fault-injection overhead gate ----------------------------------
    print(f"fault path: {faults['fault_wall_s']:.3f}s vs fault-free "
          f"{faults['clean_wall_s']:.3f}s on the sparse trace "
          f"({faults['overhead']:.2f}x, {faults['evictions']} evictions, "
          f"goodput {faults['goodput']:.4f} < gru {faults['gru']:.4f})")
    if faults["overhead"] > FAULT_MAX_OVERHEAD:
        print(f"FAIL: fault-injection overhead {faults['overhead']:.2f}x "
              f"exceeds the {FAULT_MAX_OVERHEAD}x bar")
        failed = True
    if not faults["goodput"] < faults["gru"]:
        print("FAIL: eviction cost not reflected in goodput")
        failed = True
    if os.path.exists(FAULT_BASELINE):
        with open(FAULT_BASELINE) as f:
            fbase = json.load(f)
        fratio = faults["overhead"] / max(fbase["overhead"], 1e-9)
        print(f"fault overhead {faults['overhead']:.2f}x vs baseline "
              f"{fbase['overhead']:.2f}x — regression ratio "
              f"{fratio:.2f}x (margin {MAX_REGRESSION}x)")
        if fratio > MAX_REGRESSION:
            print(f"FAIL: fault-injection overhead regressed "
                  f">{MAX_REGRESSION}x vs baseline")
            failed = True
    else:
        print(f"no fault baseline at {FAULT_BASELINE}; "
              f"run with --record to add one")

    # ---- jit-batched solver gate ----------------------------------------
    print(f"jit solver: batched {jit['jit_s']:.3f}s vs per-job numpy "
          f"{jit['numpy_s']:.3f}s at n={jit['n_jobs']} "
          f"({jit['speedup']:.1f}x, {jit['mismatches']} mismatches)")
    if jit["mismatches"]:
        print("FAIL: jit solver decisions diverged from the NumPy "
              "path")
        failed = True
    if jit["speedup"] < JIT_MIN_SPEEDUP:
        print(f"FAIL: jit solver speedup {jit['speedup']:.2f}x below "
              f"the {JIT_MIN_SPEEDUP}x acceptance bar")
        failed = True
    if os.path.exists(JIT_BASELINE):
        with open(JIT_BASELINE) as f:
            jbase = json.load(f)
        jratio = jbase["speedup"] / max(jit["speedup"], 1e-9)
        print(f"jit speedup {jit['speedup']:.1f}x vs baseline "
              f"{jbase['speedup']:.1f}x — regression ratio "
              f"{jratio:.2f}x (margin {MAX_REGRESSION}x)")
        if jratio > MAX_REGRESSION:
            print(f"FAIL: jit solver advantage regressed "
                  f">{MAX_REGRESSION}x vs baseline")
            failed = True
    else:
        print(f"no jit baseline at {JIT_BASELINE}; "
              f"run with --record to add one")

    # ---- end-to-end greedy commit gate ----------------------------------
    print(f"greedy commit: jax {commit['jax_s']:.3f}s vs numpy loop "
          f"{commit['numpy_s']:.3f}s at n={commit['n_jobs']} "
          f"({commit['speedup']:.2f}x, {commit['selected']} selected,"
          f" {commit['mismatches']} mismatches)")
    if commit["mismatches"]:
        print("FAIL: device commit decisions diverged from the "
              "NumPy oracle")
        failed = True
    if commit["speedup"] < COMMIT_MIN_SPEEDUP:
        print(f"FAIL: commit speedup {commit['speedup']:.2f}x below "
              f"the {COMMIT_MIN_SPEEDUP}x acceptance bar")
        failed = True
    if os.path.exists(COMMIT_BASELINE):
        with open(COMMIT_BASELINE) as f:
            cbase = json.load(f)
        cratio = cbase["speedup"] / max(commit["speedup"], 1e-9)
        print(f"commit speedup {commit['speedup']:.2f}x vs baseline "
              f"{cbase['speedup']:.2f}x — regression ratio "
              f"{cratio:.2f}x (margin {MAX_REGRESSION}x)")
        if cratio > MAX_REGRESSION:
            print(f"FAIL: commit advantage regressed "
                  f">{MAX_REGRESSION}x vs baseline")
            failed = True
    else:
        print(f"no commit baseline at {COMMIT_BASELINE}; "
              f"run with --record to add one")

    if failed:
        raise SystemExit(1)
    print("speedup gates passed")


if __name__ == "__main__":
    main()
