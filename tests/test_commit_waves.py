"""PR 8: wave-partitioned + device-scan greedy commit equivalence.

The conflict-free wave partitioner and the ``lax.scan`` commit loop are
pure performance structure: every decision they emit must be bitwise
the sequential NumPy loop's (the oracle kept verbatim in
``repro.core.dp``).  Property tests sweep random geometries with the
edge cases the wave-safety proof cares about — forced key conflicts,
gangs spanning sibling nodes, zero-throughput types, and payoff ties
that make the safety test reject a prefix — plus direct unit tests of
``_wave_accepts`` and ``PriceState.commit_batch``.
"""
import os

import numpy as np
import pytest

from _hypothesis_compat import given, settings
from _hypothesis_compat import strategies as st
from repro import obs
from repro.core.batch_solver import (ENV_THRESHOLD, _wave_accepts,
                                     commit_threshold,
                                     find_alloc_batch, load_calibration,
                                     resolve_backend, solver_threshold,
                                     use_commit)
from repro.core.dp import Candidate, dp_allocation
from repro.core.pricing import PriceState
from repro.core.types import Cluster, Job, Node
from repro.core.utility import effective_throughput

HORIZON = 7 * 24 * 3600.0
TYPES = ["v100", "p100", "k80", "t4"]


def _random_cluster(rng) -> Cluster:
    nodes = []
    for i in range(int(rng.randint(3, 7))):
        picks = rng.choice(len(TYPES), size=int(rng.randint(1, 3)),
                           replace=False)
        nodes.append(Node(i, {TYPES[t]: int(rng.randint(1, 5))
                              for t in picks}))
    return Cluster(nodes)


def _random_jobs(cluster, rng, n):
    jobs = []
    for j in range(n):
        tp = {t: (0.0 if rng.rand() < 0.2       # zero-throughput types
                  else float(rng.uniform(0.2, 4.0)))
              for t in cluster.gpu_types}
        if not any(tp.values()):        # t_max() needs >= 1 runnable type
            tp[cluster.gpu_types[int(rng.randint(
                len(cluster.gpu_types)))]] = float(rng.uniform(0.2, 4.0))
        jobs.append(Job(j, 0.0, int(rng.randint(1, 7)),
                        int(rng.randint(1, 50)), 10, tp,
                        single_node=bool(rng.rand() < 0.25)))
    return jobs


def _run_both(cluster, jobs):
    sel = {}
    for sv in ("numpy", "jax"):
        ps = PriceState(cluster, jobs, HORIZON, effective_throughput,
                        0.0)
        sel[sv] = dp_allocation(jobs, None, ps, 0.0,
                                effective_throughput, max_exact=0,
                                solver=sv)
    return sel["numpy"], sel["jax"]


def _assert_identical(a, b):
    assert set(a) == set(b), (sorted(a), sorted(b))
    for k in a:
        assert a[k].alloc == b[k].alloc, k
        assert a[k].cost == b[k].cost, k
        assert a[k].payoff == b[k].payoff, k
        assert a[k].rate == b[k].rate, k


# ---------------------------------------------------------------------------
# property: wave + scan commits == sequential oracle
# ---------------------------------------------------------------------------

@settings(max_examples=10)
@given(seed=st.integers(0, 9_999), n=st.integers(6, 40))
def test_commit_matches_oracle_random_geometry(seed, n):
    rng = np.random.RandomState(seed)
    cluster = _random_cluster(rng)
    jobs = _random_jobs(cluster, rng, n)
    ref, dev = _run_both(cluster, jobs)
    _assert_identical(ref, dev)


@settings(max_examples=6)
@given(seed=st.integers(0, 9_999))
def test_commit_forced_key_conflicts(seed):
    """Every job competes for the same single (node, type) key: waves
    stall immediately and the device scan carries the whole queue."""
    rng = np.random.RandomState(seed)
    cluster = Cluster([Node(0, {"v100": 4})])
    jobs = [Job(j, 0.0, int(rng.randint(1, 4)),
                int(rng.randint(1, 50)), 10,
                {"v100": float(rng.uniform(0.5, 3.0))})
            for j in range(12)]
    ref, dev = _run_both(cluster, jobs)
    _assert_identical(ref, dev)


@pytest.mark.parametrize("tol", [1e-3, 10.0])
def test_uncertain_scan_steps_go_to_the_host_oracle(monkeypatch, tol):
    """With the scan's float-error bound inflated, it certifies fewer
    steps (at 10x a payoff's magnitude, none with a live candidate):
    the host re-solves each with the reference FIND_ALLOC and the scan
    resumes after it, so decisions stay bitwise the oracle's."""
    from repro.core import batch_solver as bs
    monkeypatch.setattr(bs, "_SCAN_TOL", tol)
    monkeypatch.setattr(bs, "_COMMIT_KERNELS", {})
    rng = np.random.RandomState(3)
    cluster = Cluster([Node(0, {"v100": 4}), Node(1, {"v100": 2, "k80": 4})])
    jobs = [Job(j, 0.0, int(rng.randint(1, 4)), int(rng.randint(1, 50)),
                10, {"v100": float(rng.uniform(0.5, 3.0)),
                     "k80": float(rng.uniform(0.1, 1.0))})
            for j in range(14)]
    with obs.session(trace=False, decisions=False) as ob:
        ref, dev = _run_both(cluster, jobs)
    _assert_identical(ref, dev)
    counters = ob.metrics.summary()["counters"]
    assert counters.get("solver_scan_calls", 0) >= 2
    assert counters.get("solver.scan_host_steps", 0) >= 1


@settings(max_examples=6)
@given(seed=st.integers(0, 9_999))
def test_commit_gangs_span_sibling_nodes(seed):
    """Gang demands larger than any node force spread allocations
    across sibling nodes (the communication-penalty branch)."""
    rng = np.random.RandomState(seed)
    cluster = Cluster([Node(i, {"v100": 2, "p100": 2}) for i in range(4)])
    jobs = [Job(j, 0.0, int(rng.randint(5, 9)),     # W > any node's 4
                int(rng.randint(1, 50)), 10,
                {"v100": float(rng.uniform(0.5, 3.0)),
                 "p100": float(rng.uniform(0.2, 2.0))})
            for j in range(8)]
    ref, dev = _run_both(cluster, jobs)
    _assert_identical(ref, dev)


def test_commit_payoff_tie_rejects_prefix():
    """Two bitwise-identical jobs contending for one winner slot: the
    runner-up ties the winner's payoff, so the wave-safety test must
    reject the second job and re-price it after the first commit."""
    cluster = Cluster([Node(0, {"v100": 4}), Node(1, {"k80": 4})])
    tp = {"v100": 2.0, "k80": 0.5}
    jobs = [Job(j, 0.0, 2, 10, 10, dict(tp)) for j in range(2)]
    ref, dev = _run_both(cluster, jobs)
    _assert_identical(ref, dev)

    ps = PriceState(cluster, jobs, HORIZON, effective_throughput, 0.0)
    cands, det = find_alloc_batch(jobs, ps.free_arr.copy(),
                                  ps.gamma_arr.copy(), ps, 0.0,
                                  effective_throughput, details=True)
    accepted, consumed, tv = _wave_accepts(det, cands, [0, 1],
                                           ps.key_index)
    assert consumed == 1 and len(accepted) == 1
    assert tv.sum() == sum(cands[0].alloc.values())


def test_wave_accepts_disjoint_winners_in_one_wave():
    """Jobs usable only on pairwise-disjoint keys commit as one wave."""
    cluster = Cluster([Node(i, {TYPES[i]: 4}) for i in range(3)])
    jobs = [Job(j, 0.0, 2, 10, 10,
                {t: (1.0 + j if t == TYPES[j] else 0.0) for t in TYPES})
            for j in range(3)]
    ps = PriceState(cluster, jobs, HORIZON, effective_throughput, 0.0)
    cands, det = find_alloc_batch(jobs, ps.free_arr.copy(),
                                  ps.gamma_arr.copy(), ps, 0.0,
                                  effective_throughput, details=True)
    assert all(c is not None for c in cands)
    rows = sorted(range(3),
                  key=lambda i: -cands[i].payoff / jobs[i].n_workers)
    accepted, consumed, tv = _wave_accepts(det, cands, rows,
                                           ps.key_index)
    assert consumed == 3 and len(accepted) == 3
    assert tv.sum() == sum(sum(c.alloc.values()) for c in cands)
    # and the wave result is still bitwise the oracle's
    ref, dev = _run_both(cluster, jobs)
    _assert_identical(ref, dev)
    assert len(dev) == 3


def test_commit_path_reports_waves_through_obs():
    cluster = Cluster([Node(i, {TYPES[i % 3]: 4}) for i in range(6)])
    rng = np.random.RandomState(11)
    jobs = _random_jobs(cluster, rng, 24)
    ps = PriceState(cluster, jobs, HORIZON, effective_throughput, 0.0)
    with obs.session(trace=False, decisions=False) as ob:
        dp_allocation(jobs, None, ps, 0.0, effective_throughput,
                      max_exact=0, solver="jax")
    summ = ob.metrics.summary()
    assert summ["counters"].get("solver.commit_waves", 0) >= 1
    assert summ["histograms"]["solver.wave_size"]["count"] >= 1


# ---------------------------------------------------------------------------
# PriceState.commit_batch
# ---------------------------------------------------------------------------

def test_commit_batch_equals_sequential_commits():
    cluster = Cluster([Node(0, {"v100": 4, "k80": 2}),
                       Node(1, {"p100": 3})])
    jobs = [Job(0, 0.0, 2, 10, 10, {"v100": 1.0, "p100": 0.5, "k80": 0.2})]
    allocs = [{(0, "v100"): 2, (1, "p100"): 1},
              {(0, "v100"): 1, (0, "k80"): 2},
              {},                                # empty allocs are skipped
              {(1, "p100"): 2}]
    seq = PriceState(cluster, jobs, HORIZON, effective_throughput, 0.0)
    for a in allocs:
        seq.commit(a)
    bat = PriceState(cluster, jobs, HORIZON, effective_throughput, 0.0)
    bat.commit_batch(allocs)
    assert dict(seq.gamma) == dict(bat.gamma)
    assert np.array_equal(seq.free_arr, bat.free_arr)
    assert seq.snapshot() == bat.snapshot()


def test_commit_batch_single_sanitizer_check():
    """One aggregated conservation check per wave, not one per job."""
    cluster = Cluster([Node(0, {"v100": 8})])
    jobs = [Job(0, 0.0, 1, 10, 10, {"v100": 1.0})]
    allocs = [{(0, "v100"): 1} for _ in range(5)]
    with obs.session(trace=False, decisions=False) as ob:
        ps = PriceState(cluster, jobs, HORIZON, effective_throughput,
                        0.0, sanitize=True)
        base = ob.metrics.summary()["counters"].get(
            "invariant_checks.commit_amounts", 0)
        ps.commit_batch(allocs)
        after = ob.metrics.summary()["counters"].get(
            "invariant_checks.commit_amounts", 0)
    assert after - base == 1
    assert ps.gamma[(0, "v100")] == 5


def test_commit_batch_checks_aggregate_conservation():
    from repro.analysis.invariants import InvariantViolation
    cluster = Cluster([Node(0, {"v100": 4})])
    jobs = [Job(0, 0.0, 1, 10, 10, {"v100": 1.0})]
    ps = PriceState(cluster, jobs, HORIZON, effective_throughput, 0.0,
                    sanitize=True)
    # each delta fits capacity alone; the *aggregate* does not
    with pytest.raises(InvariantViolation):
        ps.commit_batch([{(0, "v100"): 3}, {(0, "v100"): 3}])


# ---------------------------------------------------------------------------
# calibration + dispatch plumbing
# ---------------------------------------------------------------------------

def test_committed_calibration_loads(monkeypatch):
    monkeypatch.delenv(ENV_THRESHOLD, raising=False)
    cal = load_calibration(refresh=True)
    assert cal["auto_min_jobs"] >= 1
    assert cal["commit_min_jobs"] >= 1
    assert solver_threshold() == cal["auto_min_jobs"]
    assert commit_threshold() == cal["commit_min_jobs"]


def test_missing_calibration_degrades_to_defaults(tmp_path):
    from repro.core.batch_solver import AUTO_MIN_JOBS, COMMIT_MIN_JOBS
    cal = load_calibration(path=str(tmp_path / "nope.json"))
    assert cal == {"auto_min_jobs": AUTO_MIN_JOBS,
                   "commit_min_jobs": COMMIT_MIN_JOBS}


def test_env_threshold_override(monkeypatch):
    monkeypatch.setenv(ENV_THRESHOLD, "77")
    assert solver_threshold() == 77
    monkeypatch.setenv(ENV_THRESHOLD, "not-a-number")
    with pytest.raises(ValueError):
        solver_threshold()


def test_use_commit_dispatch_rules(monkeypatch):
    monkeypatch.delenv(ENV_THRESHOLD, raising=False)
    assert not use_commit("numpy", 10_000)
    assert not use_commit("jax", 0)
    assert use_commit("jax", 1)
    thr = commit_threshold()
    assert not use_commit("auto", thr - 1)
    assert use_commit("auto", thr)


def test_resolve_backend_logs_crossover(monkeypatch):
    """An ``auto`` dispatch on each side of the pricing crossover: the
    ``solver_dispatch`` span carries the backend it ran on, the job
    count and the threshold that decided it."""
    monkeypatch.delenv(ENV_THRESHOLD, raising=False)
    thr = solver_threshold()
    assert thr < commit_threshold()      # both sides take the scan path
    rng = np.random.RandomState(11)
    cluster = _random_cluster(rng)
    args = {}
    for n in (thr - 1, thr):
        jobs = _random_jobs(cluster, rng, n)
        ps = PriceState(cluster, jobs, HORIZON, effective_throughput, 0.0)
        with obs.session(decisions=False) as ob:
            dp_allocation(jobs, None, ps, 0.0, effective_throughput,
                          max_exact=0, solver="auto")
        spans = [e for e in ob.trace.events
                 if e["name"] == "solver_dispatch"]
        assert len(spans) == 1
        args[n] = spans[0]["args"]
        assert args[n]["n_jobs"] == n and args[n]["threshold"] == thr
        assert args[n]["backend"] == resolve_backend("auto", n)
    assert args[thr - 1]["backend"] == "numpy"
    assert args[thr]["backend"] == "jax"


def test_engine_rejects_unknown_solver():
    from repro.core.trace import philly_trace, simulation_cluster
    from repro.sim.engine import simulate_rounds
    from repro.core.hadar import HadarScheduler
    cluster = simulation_cluster()
    with pytest.raises(ValueError, match="unknown solver"):
        simulate_rounds(HadarScheduler(), philly_trace(n_jobs=2, seed=0),
                        cluster, solver="tpu")
