"""JIT-batched dual price solver vs the per-job NumPy path.

The contract under test (ISSUE 3 acceptance): the batched jax backend
returns *bit-identical* scheduling decisions — same allocations, same
tie-breaks, costs/payoffs equal — for FIND_ALLOC candidates,
DP_allocation selections, whole Hadar rounds, and both simulation
engines, across the padding edge cases (empty queue, single job, queue
crossing the bucket boundary, zero-throughput types, single_node HadarE
copies).  Plus the incremental-PriceState invariants: persistent
free_arr deltas, device-buffer caching with write-through invalidation,
and no array rebuilds across event-engine consultations.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # offline CI image — vendored fallback
    from _hypothesis_compat import given, settings, strategies as st

import _seed_reference as ref
from repro.core.batch_solver import (bucket_size, resolve_solver,
                                     solver_threshold, use_batch)
from repro.core.dp import _find_alloc_arrays, dp_allocation, find_alloc
from repro.core.hadar import HadarScheduler
from repro.core.pricing import PriceState
from repro.core.trace import mix_jobs, multi_cluster, philly_trace
from repro.core.trace import simulation_cluster
from repro.core.trace import testbed_cluster as _testbed_cluster
from repro.core.types import Cluster, Job, Node
from repro.core.utility import effective_throughput, weighted_inverse
from repro.sim.engine import simulate_events, simulate_rounds

def _same_candidate(a, b):
    if (a is None) != (b is None):
        return False
    if a is None:
        return True
    return (a.alloc == b.alloc and a.cost == b.cost
            and a.payoff == b.payoff and a.rate == b.rate)


def _mixed_cluster():
    return Cluster([Node(0, {"v100": 2, "k80": 2}), Node(1, {"p100": 3}),
                    Node(2, {"v100": 1, "t4": 4}), Node(3, {"k80": 2})])


def _jobs_with_edges(cluster, seed, n):
    """Job set covering the solver's padding edge cases: zero-throughput
    types, single_node (HadarE copy) jobs, large gangs."""
    rng = np.random.RandomState(seed)
    jobs = []
    for jid in range(n):
        tp = {r: float(rng.uniform(0.05, 5.0)) for r in cluster.gpu_types
              if rng.rand() > 0.3}           # some types unusable per job
        jobs.append(Job(jid, 0.0, int(rng.randint(1, 7)),
                        int(rng.randint(1, 50)), 10, tp,
                        single_node=bool(rng.rand() < 0.25)))
    return jobs


# ---------------------------------------------------------------------------
# solver plumbing
# ---------------------------------------------------------------------------

def test_resolve_and_dispatch_rules():
    assert resolve_solver("numpy") == "numpy"
    assert resolve_solver(None) == "jax"
    with pytest.raises(ValueError):
        resolve_solver("tpu")
    assert not use_batch("numpy", 10_000)
    assert resolve_solver("auto") == "jax"
    assert use_batch("jax", 1)
    # the auto crossover comes from the calibration JSON (env var
    # overrides notwithstanding), not a hard-coded constant
    assert not use_batch("auto", solver_threshold() - 1)
    assert use_batch("auto", solver_threshold())


def test_bucket_size_powers_of_two():
    assert bucket_size(1) == 8 and bucket_size(8) == 8
    assert bucket_size(9) == 16 and bucket_size(1025) == 2048


# ---------------------------------------------------------------------------
# FIND_ALLOC equivalence: batched kernel vs per-job NumPy path
# ---------------------------------------------------------------------------

def test_batch_empty_queue():
    from repro.core.batch_solver import find_alloc_batch
    cluster = _mixed_cluster()
    ps = PriceState(cluster, [], horizon=86400.0)
    assert find_alloc_batch([], ps.free_arr.copy(), ps.gamma_arr.copy(),
                            ps, 0.0, effective_throughput) == []


@pytest.mark.parametrize("n", [1, 7, 19])   # below / at / across bucket 8|32
def test_batch_matches_perjob_padding_and_edges(n):
    """Bit-identical candidates across bucket-padding boundaries, with
    zero-throughput types, single_node jobs, and partial occupancy."""
    from repro.core.batch_solver import find_alloc_batch
    cluster = _mixed_cluster()
    jobs = _jobs_with_edges(cluster, seed=n, n=n)
    ps = PriceState(cluster, jobs, horizon=86400.0)
    rng = np.random.RandomState(n)
    ps.gamma.update({k: int(rng.randint(0, c + 1))
                     for k, c in cluster.free_map({}).items()
                     if rng.rand() < 0.5})
    free = cluster.free_map({k: int(rng.randint(0, c + 1))
                             for k, c in cluster.free_map({}).items()
                             if rng.rand() < 0.4})
    avail = ps.free_to_arr(free)
    gamma = ps.gamma_arr.copy()
    for force in (False, True):
        batch = find_alloc_batch(jobs, avail, gamma, ps, 0.0,
                                 effective_throughput, force=force)
        assert len(batch) == n
        for job, b in zip(jobs, batch):
            a = _find_alloc_arrays(job, avail, gamma, ps, 0.0,
                                   effective_throughput, force)
            assert _same_candidate(a, b), (job.job_id, force, a, b)


def test_batch_job_with_no_usable_types_is_none():
    from repro.core.batch_solver import find_alloc_batch
    cluster = _mixed_cluster()
    jobs = _jobs_with_edges(cluster, seed=3, n=4)
    jobs[2].throughput = {}                      # no usable type at all
    ps = PriceState(cluster, jobs, horizon=86400.0)
    out = find_alloc_batch(jobs, ps.free_arr.copy(), ps.gamma_arr.copy(),
                           ps, 0.0, effective_throughput)
    assert out[2] is None
    for ji in (0, 1, 3):
        a = _find_alloc_arrays(jobs[ji], ps.free_arr.copy(),
                               ps.gamma_arr.copy(), ps, 0.0,
                               effective_throughput, False)
        assert _same_candidate(a, out[ji])


def test_batch_single_node_copies_never_spread():
    """HadarE fork copies (single_node=True) must only receive
    consolidated candidates — identical to the per-job path."""
    from repro.core.batch_solver import find_alloc_batch
    from repro.core.hadare import fork_job
    cluster = _mixed_cluster()
    parent = Job(1, 0.0, 3, 20, 10, {"v100": 2.0, "p100": 1.0, "k80": 0.4})
    copies = fork_job(parent, len(cluster.nodes))
    ps = PriceState(cluster, copies, horizon=86400.0)
    out = find_alloc_batch(copies, ps.free_arr.copy(), ps.gamma_arr.copy(),
                           ps, 0.0, effective_throughput)
    for c, b in zip(copies, out):
        a = _find_alloc_arrays(c, ps.free_arr.copy(), ps.gamma_arr.copy(),
                               ps, 0.0, effective_throughput, False)
        assert _same_candidate(a, b)
        if b is not None:
            assert len({h for (h, _) in b.alloc}) == 1


def test_batch_custom_utility_fallback_path():
    """Non-default utilities take the scalar u-table path; results still
    match the per-job kernel exactly."""
    from repro.core.batch_solver import find_alloc_batch
    cluster = _mixed_cluster()
    jobs = _jobs_with_edges(cluster, seed=11, n=6)
    ps = PriceState(cluster, jobs, horizon=86400.0,
                    utility=weighted_inverse(3.0))
    u = weighted_inverse(3.0)
    out = find_alloc_batch(jobs, ps.free_arr.copy(), ps.gamma_arr.copy(),
                           ps, 100.0, u)
    for job, b in zip(jobs, out):
        a = _find_alloc_arrays(job, ps.free_arr.copy(),
                               ps.gamma_arr.copy(), ps, 100.0, u, False)
        assert _same_candidate(a, b)


# ---------------------------------------------------------------------------
# DP / scheduler / engine equivalence across backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,n,max_exact", [(0, 40, 24), (7, 8, 24),
                                              (3, 20, 24)])
def test_dp_allocation_solver_backends_identical(seed, n, max_exact):
    """Greedy (n > max_exact) and exact-DP (n <= max_exact) paths select
    the same jobs/allocations under solver='jax' and solver='numpy'."""
    cluster = simulation_cluster()
    jobs = philly_trace(n_jobs=n, seed=seed)
    free = cluster.free_map({})
    s_np = dp_allocation(jobs, free,
                         PriceState(cluster, jobs, horizon=86400.0),
                         0.0, effective_throughput, max_exact=max_exact,
                         solver="numpy")
    s_jx = dp_allocation(jobs, free,
                         PriceState(cluster, jobs, horizon=86400.0),
                         0.0, effective_throughput, max_exact=max_exact,
                         solver="jax")
    assert set(s_np) == set(s_jx)
    for jid in s_np:
        assert s_np[jid].alloc == s_jx[jid].alloc
        assert s_np[jid].cost == s_jx[jid].cost
        assert s_np[jid].payoff == s_jx[jid].payoff


@pytest.mark.parametrize("seed,n,now", [(1, 24, 0.0), (5, 80, 0.0),
                                        (2, 40, 7200.0)])
def test_hadar_round_jax_matches_seed_reference(seed, n, now):
    cluster = simulation_cluster()
    jobs = philly_trace(n_jobs=n, seed=seed, all_at_start=(now == 0.0))
    out_ref = ref.ReferenceHadarScheduler().schedule(now, 360.0, jobs,
                                                     cluster)
    out_jax = HadarScheduler(solver="jax").schedule(now, 360.0, jobs,
                                                    cluster)
    assert out_ref == out_jax


def test_hadar_round_jax_multipod_bursty():
    pods = multi_cluster(n_pods=3, nodes_per_pod=5, gpus_per_node=4,
                         pod_types=["v100", "p100", "k80"],
                         mixed_frac=0.25, seed=2)
    jobs = philly_trace(n_jobs=64, seed=1, types=pods.gpu_types,
                        arrival_pattern="bursty")
    now = max(j.arrival for j in jobs)
    assert (ref.ReferenceHadarScheduler().schedule(now, 360.0, jobs, pods)
            == HadarScheduler(solver="jax").schedule(now, 360.0, jobs,
                                                     pods))


@pytest.mark.parametrize("engine", [simulate_rounds, simulate_events])
def test_engines_solver_backends_identical(engine):
    """Whole simulations agree across backends: finish times, restarts,
    metrics — for both the round and the event engine."""
    mk = lambda: philly_trace(n_jobs=15, seed=2, all_at_start=False)
    r_np = engine(HadarScheduler(), mk(), simulation_cluster(),
                  round_len=360.0, solver="numpy")
    r_jx = engine(HadarScheduler(), mk(), simulation_cluster(),
                  round_len=360.0, solver="jax")
    for a, b in zip(r_np.jobs, r_jx.jobs):
        assert a.job_id == b.job_id
        assert a.finish_time == b.finish_time
        assert a.restarts == b.restarts
    assert r_np.total_seconds == r_jx.total_seconds
    assert abs(r_np.avg_gru() - r_jx.avg_gru()) == 0.0


def test_hadare_solver_backends_identical():
    """The vectorized HadarE backend (single_node copies through the
    batched kernel) is backend-independent end to end."""
    from repro.core.hadare import simulate_hadare
    tb = _testbed_cluster()
    r_np = simulate_hadare(mix_jobs("M-3", tb), tb, round_len=90.0,
                           solver="numpy")
    r_jx = simulate_hadare(mix_jobs("M-3", tb), tb, round_len=90.0,
                           solver="jax")
    for a, b in zip(r_np.jobs, r_jx.jobs):
        assert a.finish_time == b.finish_time
    assert r_np.total_seconds == r_jx.total_seconds


# ---------------------------------------------------------------------------
# incremental PriceState
# ---------------------------------------------------------------------------

def test_free_arr_tracks_commit_release():
    cluster = _mixed_cluster()
    jobs = _jobs_with_edges(cluster, seed=1, n=3)
    # sanitize=False: the double release below probes the clamping
    # contract of the unsanitized layer (the sanitizer rightly rejects
    # it — covered in test_analysis_invariants.py)
    ps = PriceState(cluster, jobs, horizon=86400.0, sanitize=False)
    assert np.array_equal(ps.free_arr, ps.cap_arr)
    alloc = {(0, "v100"): 2, (1, "p100"): 1}
    ps.commit(alloc)
    assert ps.free_arr[ps.key_index[(0, "v100")]] == ps.cap_arr[
        ps.key_index[(0, "v100")]] - 2
    ps.release(alloc)
    assert np.array_equal(ps.free_arr, ps.cap_arr)
    # release never overshoots capacity
    ps.release(alloc)
    assert np.array_equal(ps.free_arr, ps.cap_arr)


def test_refresh_reprimes_in_place_and_matches_fresh_state():
    cluster = _mixed_cluster()
    jobs_a = _jobs_with_edges(cluster, seed=5, n=4)
    jobs_b = _jobs_with_edges(cluster, seed=6, n=6)
    ps = PriceState(cluster, jobs_a, horizon=86400.0)
    ps.commit({(0, "v100"): 1})
    ids = (id(ps.gamma_arr), id(ps.free_arr), id(ps.umin_arr), id(ps.q_arr))
    ps.refresh(jobs_b, now=500.0)
    assert (id(ps.gamma_arr), id(ps.free_arr), id(ps.umin_arr),
            id(ps.q_arr)) == ids
    fresh = PriceState(cluster, jobs_b, horizon=86400.0, now=500.0)
    assert ps.u_min == fresh.u_min and ps.u_max == fresh.u_max
    assert np.array_equal(ps.umin_arr, fresh.umin_arr)
    assert np.array_equal(ps.q_arr, fresh.q_arr)
    assert np.array_equal(ps.gamma_arr, fresh.gamma_arr)
    assert np.array_equal(ps.free_arr, fresh.free_arr)
    assert dict(ps.gamma) == {}


def test_compute_bounds_hoist_matches_per_type_loop():
    """The hoisted O(J + R) bound scan must equal the seed's per-type
    O(R * J) loop exactly (it was type-invariant all along)."""
    import math
    cluster = _mixed_cluster()
    jobs = _jobs_with_edges(cluster, seed=9, n=8)
    ps = PriceState(cluster, jobs, horizon=86400.0)
    cap_total = sum(cluster.capacity().values())
    live = [j for j in jobs if j.throughput]
    eta = max(cap_total / max(j.t_max() * j.n_workers, 1e-9) for j in live)
    eta = max(eta, 1.0)
    for r in cluster.gpu_types:            # the seed's per-type scan
        best, worst = 0.0, float("inf")
        for j in live:
            u_best = ps.utility(j, max(j.t_min(), 1e-9))
            best = max(best, u_best / max(j.n_workers, 1))
            u_floor = ps.utility(j, max(ps.horizon - j.arrival,
                                        j.t_min(), 1e-9))
            worst = min(worst, u_floor / (j.t_max() * j.n_workers))
        u_max = max(best, 1e-12)
        u_min = max(min(worst / (4.0 * eta), u_max / math.e), 1e-15)
        assert ps.u_max[r] == u_max and ps.u_min[r] == u_min


def test_event_engine_reuses_pricestate_arrays(monkeypatch):
    """Acceptance: the event engine consults the scheduler without
    rebuilding PriceState arrays — one _build_arrays() for many
    schedule() calls, stable array identity throughout."""
    import repro.core.pricing as pricing
    builds = {"n": 0}
    orig = pricing.PriceState._build_arrays

    def counting(self):
        builds["n"] += 1
        return orig(self)

    monkeypatch.setattr(pricing.PriceState, "_build_arrays", counting)
    sched = HadarScheduler()
    res = simulate_events(sched, philly_trace(n_jobs=10, seed=3,
                                              all_at_start=False),
                          simulation_cluster(), round_len=360.0)
    assert res.sched_calls > 1
    assert builds["n"] == 1
    assert all(j.finish_time is not None for j in res.jobs)
    # identity: the same buffers served every consultation
    assert sched._ps is not None
    assert sched._ps.free_arr is not None


def test_scheduler_rebuilds_pricestate_on_new_cluster():
    sched = HadarScheduler(solver="numpy")
    jobs = philly_trace(n_jobs=6, seed=4)
    sched.schedule(0.0, 360.0, jobs, simulation_cluster())
    ps_first = sched._ps
    sched.schedule(0.0, 360.0, jobs, _mixed_cluster())
    assert sched._ps is not ps_first


def test_scheduler_rebuilds_pricestate_on_inplace_mutation():
    """Mutating the *same* Cluster object (node failure, added capacity)
    must invalidate the cached PriceState — geometry fingerprint, not
    object identity alone."""
    sched = HadarScheduler(solver="numpy")
    jobs = philly_trace(n_jobs=6, seed=4)
    cluster = _mixed_cluster()
    out1 = sched.schedule(0.0, 360.0, jobs, cluster)
    ps_first = sched._ps
    cluster.nodes[0].gpus["v100"] = 1            # GPU failure on node 0
    for j in jobs:                               # fresh scheduling point
        j.alloc = None
    sched.note_completion()
    out2 = sched.schedule(0.0, 360.0, jobs, cluster)
    assert sched._ps is not ps_first
    used_v100_n0 = sum(a.get((0, "v100"), 0) for a in out2.values())
    assert used_v100_n0 <= 1                     # stale cap would allow 2


# ---------------------------------------------------------------------------
# device-buffer cache invalidation (property test)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_gamma_mutations_always_invalidate_device_views(seed):
    """Property: any _GammaDict mutation dirties the cached device buffer,
    so the next device_view() re-upload equals the host array."""
    rng = np.random.RandomState(seed)
    cluster = _mixed_cluster()
    jobs = _jobs_with_edges(cluster, seed=seed % 7, n=3)
    # sanitize=False: random commits may over-commit on purpose — the
    # property under test is cache invalidation, not feasibility
    ps = PriceState(cluster, jobs, horizon=86400.0, sanitize=False)
    keys = ps.keys

    def dev_gamma():
        return np.asarray(ps.device_view("gamma"))

    assert np.array_equal(dev_gamma(), ps.gamma_arr)
    for _ in range(12):
        op = rng.randint(0, 7)
        key = keys[rng.randint(0, len(keys))]
        if op == 0:
            ps.gamma[key] = int(rng.randint(0, 5))
        elif op == 1:
            ps.gamma.update({key: int(rng.randint(0, 5))})
        elif op == 2 and key in ps.gamma:
            del ps.gamma[key]
        elif op == 3:
            ps.gamma.pop(key, None)
        elif op == 4:
            ps.gamma.setdefault(key, int(rng.randint(0, 5)))
        elif op == 5:
            ps.commit({key: int(rng.randint(1, 3))})
        else:
            ps.gamma.clear()
        assert "gamma" in ps._dirty or np.array_equal(dev_gamma(),
                                                      ps.gamma_arr)
        assert np.array_equal(dev_gamma(), ps.gamma_arr)
        assert "gamma" not in ps._dirty      # view freshly re-uploaded


def test_device_view_caches_until_dirty():
    cluster = _mixed_cluster()
    ps = PriceState(cluster, _jobs_with_edges(cluster, seed=2, n=2),
                    horizon=86400.0)
    v1 = ps.device_view("free")
    v2 = ps.device_view("free")
    assert v1 is v2                          # cached, no re-upload
    ps.commit({ps.keys[0]: 1})
    v3 = ps.device_view("free")
    assert v3 is not v1
    assert np.array_equal(np.asarray(v3), ps.free_arr)
    with pytest.raises(KeyError):
        ps.device_view("nope")


# ---------------------------------------------------------------------------
# find_alloc free=None path
# ---------------------------------------------------------------------------

def test_find_alloc_free_none_prices_against_free_arr():
    cluster = _mixed_cluster()
    jobs = _jobs_with_edges(cluster, seed=8, n=4)
    ps = PriceState(cluster, jobs, horizon=86400.0)
    kept = {(0, "v100"): 1, (2, "t4"): 2}
    ps.commit(kept)
    free_dict = cluster.free_map(kept)
    for job in jobs:
        a = find_alloc(job, free_dict, ps, 0.0, effective_throughput)
        b = find_alloc(job, None, ps, 0.0, effective_throughput)
        assert _same_candidate(a, b)
