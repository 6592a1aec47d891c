"""The backfill's feasibility gate (``hadar._FitGate``).

Contract: the gate skips a job only when ``_find_alloc_arrays(...,
force=True)`` would return None for it, so a consult decides the same
with the gate as when every leftover job is priced; the counters
``backfill.priced`` and ``backfill.skipped`` (and the ``hadar.backfill``
span's args of the same names) split the jobs the DP left unselected,
and nothing is counted with observability off.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, strategies as st

from repro import obs
from repro.core import hadar as hadar_mod
from repro.core.dp import _find_alloc_arrays
from repro.core.hadar import HadarScheduler, _FitGate
from repro.core.pricing import PriceState
from repro.core.trace import philly_trace, simulation_cluster
from repro.core.types import Cluster, Job, Node
from repro.core.utility import effective_throughput
from repro.sim.adapters import simulate_hadare
from repro.sim.engine import simulate_events
from repro.sim.faults import FailureModel

HORIZON = 7 * 24 * 3600.0
TYPES = ["v100", "p100", "k80"]


def _random_state(seed, free_mode):
    """A random cluster of 1-3 types, a free vector in ``free_mode``,
    and jobs with some zero throughputs, W in {1, 2, 4, 8}, with and
    without ``single_node``."""
    rng = np.random.RandomState(seed)
    types = TYPES[:rng.randint(1, 4)]
    nodes = []
    for i in range(rng.randint(1, 7)):
        have = [r for r in types if rng.rand() < 0.6] \
            or [types[i % len(types)]]
        nodes.append(Node(i, {r: int(rng.choice([1, 2, 4, 8]))
                              for r in have}))
    cluster = Cluster(nodes)
    jobs = []
    for jid in range(24):
        tp = {r: 0.0 if rng.rand() < 0.35 else float(rng.uniform(0.5, 5.0))
              for r in types}
        jobs.append(Job(jid, 0.0, int(rng.choice([1, 2, 4, 8])),
                        epochs=int(rng.randint(1, 5)), iters_per_epoch=100,
                        throughput=tp, single_node=bool(rng.rand() < 0.5)))
    priced = [j for j in jobs if any(x > 0 for x in j.throughput.values())]
    ps = PriceState(cluster, priced or [Job(99, 0.0, 1, 1, 100,
                                            {types[0]: 1.0})],
                    HORIZON, effective_throughput, 0.0)
    cap = ps.cap_arr
    if free_mode == "zero":
        free = np.zeros_like(cap)
    elif free_mode == "full":
        free = cap.copy()
    elif free_mode == "fragmented":
        free = np.floor(rng.uniform(0, cap + 1)).clip(0, cap)
    else:                               # "negative": over-committed keys
        free = np.floor(rng.uniform(-3, cap + 1)).clip(None, cap)
    ps.free_arr[:] = free
    ps.gamma_arr[:] = cap - free
    return ps, jobs


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2 ** 31 - 1))
@pytest.mark.parametrize("free_mode",
                         ["zero", "full", "fragmented", "negative"])
def test_gate_skips_only_jobs_find_alloc_cannot_place(free_mode, seed):
    ps, jobs = _random_state(seed, free_mode)
    gate = _FitGate(ps)
    for j in jobs:
        cand = _find_alloc_arrays(j, ps.free_arr.copy(), ps.gamma_arr.copy(),
                                  ps, 0.0, effective_throughput, force=True)
        fits = gate.may_fit(j)
        if not fits:
            assert cand is None, (j, ps.free_arr)
        if free_mode != "negative":
            # on a non-negative free vector the bound is also tight
            assert fits == (cand is not None), (j, ps.free_arr)
    assert gate.exhausted == (not (ps.free_arr > 0).any())


class _PriceAll(_FitGate):
    """The gate turned off: every leftover job is priced."""

    def refresh(self) -> None:
        super().refresh()
        self.exhausted = False

    def may_fit(self, job) -> bool:
        return True


class _Recording(HadarScheduler):
    def __init__(self, log, **kw):
        super().__init__(**kw)
        self.log = log

    def schedule(self, now, round_len, jobs, cluster):
        out = super().schedule(now, round_len, jobs, cluster)
        self.log.append((now, {jid: dict(a) for jid, a in out.items()}))
        return out


def _fig5_style(log):
    cluster = Cluster([Node(i, {TYPES[i % 3]: 4}) for i in range(15)])
    jobs = philly_trace(n_jobs=48, seed=4, types=TYPES)
    return simulate_events(_Recording(log, solver="numpy"), jobs, cluster,
                           round_len=360.0, max_events=150)


def _arrivals(log):
    cluster = simulation_cluster()
    jobs = philly_trace(n_jobs=40, seed=2, types=cluster.gpu_types,
                        all_at_start=False)
    return simulate_events(_Recording(log, solver="numpy", max_exact_dp=12),
                           jobs, cluster, round_len=360.0, max_events=300)


def _hadare_faults(log):
    cluster = simulation_cluster()
    jobs = philly_trace(n_jobs=10, seed=2, types=cluster.gpu_types)
    faults = FailureModel(mtbf_hours=6.0, recovery_s=1800.0, seed=2)
    return simulate_hadare(jobs, cluster, round_len=360.0, max_rounds=60,
                           scheduler=_Recording(log), solver="numpy",
                           faults=faults)


@pytest.mark.parametrize("run", [_fig5_style, _arrivals, _hadare_faults],
                         ids=["fig5", "arrivals", "hadare-faults"])
def test_schedule_identical_with_the_gate_and_pricing_every_job(
        run, monkeypatch):
    gated, every = [], []
    with obs.session(trace=False, decisions=False) as ob:
        run(gated)
    with monkeypatch.context() as mp:
        mp.setattr(hadar_mod, "_FitGate", _PriceAll)
        run(every)
    assert len(gated) > 1
    assert gated == every
    # the gate engaged: most leftover jobs went unpriced
    m = ob.metrics
    assert m.counter("backfill.skipped").value > \
        m.counter("backfill.priced").value


def _spans(ob, name):
    return [e for e in ob.trace.events
            if e["ph"] == "X" and e["name"] == name]


def test_counters_split_the_jobs_the_dp_left(monkeypatch):
    unselected = []
    dp = hadar_mod.dp_allocation

    def spy_dp(queue, *a, **kw):
        sel = dp(queue, *a, **kw)
        unselected.append(len(queue) - len(sel))
        return sel

    monkeypatch.setattr(hadar_mod, "dp_allocation", spy_dp)
    with obs.session(decisions=False) as ob:
        _arrivals([])
    spans = _spans(ob, "hadar.backfill")
    assert len(spans) == len(unselected) > 1
    for sp, n in zip(spans, unselected):
        assert sp["args"]["priced"] + sp["args"]["skipped"] == n
    priced = ob.metrics.counter("backfill.priced").value
    skipped = ob.metrics.counter("backfill.skipped").value
    assert priced == sum(sp["args"]["priced"] for sp in spans)
    assert skipped == sum(sp["args"]["skipped"] for sp in spans)
    assert priced + skipped == sum(unselected)
    # both sides engage, and the gate is tight: with the free devices
    # re-read after each backfill commit, every job priced is placed
    # (without a decision log the DP's winners go in one commit_batch)
    assert priced > 0 and skipped > 0
    assert ob.metrics.counter("pricestate_commits").value == priced


def test_nothing_counted_with_obs_off(monkeypatch):
    counted = []
    count = obs.Observer.count

    def spy(self, name, n=1):
        counted.append(name)
        return count(self, name, n)

    monkeypatch.setattr(obs.Observer, "count", spy)
    cluster = simulation_cluster()
    jobs = philly_trace(n_jobs=64, seed=1, types=cluster.gpu_types)
    off = HadarScheduler(solver="numpy").schedule(0.0, 360.0, jobs, cluster)
    assert counted == []
    with obs.session(trace=False, decisions=False) as ob:
        on = HadarScheduler(solver="numpy").schedule(0.0, 360.0, jobs,
                                                     cluster)
    assert on == off
    assert {"backfill.priced", "backfill.skipped"} <= set(counted)
    assert (ob.metrics.counter("backfill.priced").value
            + ob.metrics.counter("backfill.skipped").value) > 0
