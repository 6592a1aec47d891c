"""The consult's phase spans and the host-to-device byte counter.

Contract: a session without a decision log schedules on exactly the
code path of observability off (one ``commit_batch`` for the DP
winners, no runner-up tracking), the phase spans nest under the consult
where the work happens, ``solver.h2d_bytes`` counts every byte handed
to either kernel, and the jitted programs keep the names a profiler
trace is read by.
"""
import jax
import numpy as np
import pytest

from repro import obs
from repro.core import batch_solver as bs
from repro.core import hadar as hadar_mod
from repro.core.hadar import HadarScheduler
from repro.core.pricing import PriceState
from repro.core.trace import philly_trace, simulation_cluster
from repro.core.utility import effective_throughput
from repro.obs.trace import validate_trace
from repro.sim.adapters import simulate_hadare
from repro.sim.engine import simulate_events

HORIZON = 7 * 24 * 3600.0
SOLVER_SPANS = ("solver.tables", "solver.device", "solver.finish")


def _storm():
    """A full-queue consult shaped like the 2048-job one, small: every
    job queued at t=0, more demand than devices, so the greedy pass runs
    the pricing kernel, the wave walk and the commit scan."""
    cluster = simulation_cluster()
    return philly_trace(n_jobs=64, seed=1, types=cluster.gpu_types), cluster


def _consult(session_kw=None):
    """One consult of the storm; returns (allocations, observer, DP
    selection, commit_batch calls, commit calls)."""
    jobs, cluster = _storm()
    sched = HadarScheduler(solver="auto")
    seen = {"sel": None, "batch": [], "commit": []}
    dp = hadar_mod.dp_allocation

    def spy_dp(*a, **kw):
        seen["sel"] = dp(*a, **kw)
        return seen["sel"]

    batch, commit = PriceState.commit_batch, PriceState.commit

    def spy_batch(self, allocs):
        allocs = list(allocs)
        seen["batch"].append(allocs)
        return batch(self, allocs)

    def spy_commit(self, alloc):
        seen["commit"].append(alloc)
        return commit(self, alloc)

    mp = pytest.MonkeyPatch()
    mp.setattr(hadar_mod, "dp_allocation", spy_dp)
    mp.setattr(PriceState, "commit_batch", spy_batch)
    mp.setattr(PriceState, "commit", spy_commit)
    try:
        if session_kw is None:
            out, ob = sched.schedule(0.0, 360.0, jobs, cluster), None
        else:
            with obs.session(**session_kw) as ob:
                out = sched.schedule(0.0, 360.0, jobs, cluster)
    finally:
        mp.undo()
    return out, ob, seen["sel"], seen["batch"], seen["commit"]


def test_session_without_decision_log_takes_the_obs_off_path():
    off, _, sel_off, batch_off, commit_off = _consult()
    on, ob, sel_on, batch_on, commit_on = _consult({"decisions": False})
    logged, ob_log, sel_log, batch_log, commit_log = _consult({})
    assert on == off == logged                       # bit-identical
    assert ob.metrics.counter("solver_scan_calls").value >= 1
    winners = [c.alloc for c in sel_on.values()]
    assert winners
    # kept pins, then the DP winners in one aggregated delta; the
    # backfill commits one job at a time, as with obs off
    assert batch_on == batch_off == [[], winners]
    assert commit_on == commit_off
    assert all(c.runner_up is None for c in sel_on.values())
    assert all(c.runner_up is None for c in sel_off.values())
    # a decision log brings back the provenance path
    assert batch_log == [[]]
    assert commit_log[:len(winners)] == winners
    assert any(c.runner_up is not None for c in sel_log.values())
    assert len(ob_log.decisions) == len(logged)


@pytest.mark.parametrize("path", ["find_alloc", "find_alloc_batch",
                                  "scan_prefix"])
def test_runner_up_only_with_a_decision_log(path):
    jobs, cluster = _storm()
    got = {}
    for log, kw in (("off", {"decisions": False}), ("on", {})):
        ps = PriceState(cluster, jobs, HORIZON, effective_throughput, 0.0)
        avail, gamma = ps.free_arr.copy(), ps.gamma_arr.copy()
        with obs.session(**kw):
            if path == "find_alloc":
                from repro.core.dp import find_alloc
                cands = [find_alloc(j, None, ps, 0.0, effective_throughput)
                         for j in jobs]
            elif path == "find_alloc_batch":
                cands = bs.find_alloc_batch(jobs, avail, gamma, ps, 0.0,
                                            effective_throughput)
            else:
                res = {}
                bs._scan_prefix(jobs, avail, gamma, ps, 0.0,
                                effective_throughput, res)
                cands = list(res.values())
        got[log] = [c for c in cands if c is not None]
    assert got["off"] == got["on"] and got["on"]      # same decisions
    assert all(c.runner_up is None for c in got["off"])
    assert any(c.runner_up is not None for c in got["on"])


def _spans(ob, name):
    return [(e["ts"], e["ts"] + e["dur"]) for e in ob.trace.events
            if e["ph"] == "X" and e["name"] == name]


def test_solver_spans_lie_inside_dp_and_backfill_outside():
    _, ob, _, _, _ = _consult({"decisions": False})
    assert validate_trace(ob.trace.to_json()) == []
    (dp0, dp1), = _spans(ob, "hadar.dp")
    (bf0, bf1), = _spans(ob, "hadar.backfill")
    assert bf0 >= dp1
    for name in SOLVER_SPANS + ("solver.waves", "solver_dispatch"):
        spans = _spans(ob, name)
        assert spans, name
        assert all(dp0 <= a and b <= dp1 for a, b in spans), name
    # one tables/device/finish triple per kernel call, pricing or scan
    calls = (ob.metrics.counter("solver_batch_calls").value
             + ob.metrics.counter("solver_scan_calls").value)
    assert all(len(_spans(ob, n)) == calls for n in SOLVER_SPANS)


@pytest.mark.parametrize("engine", ["events", "hadare"])
def test_phase_spans_nest_under_both_engines(engine):
    cluster = simulation_cluster()
    jobs = philly_trace(n_jobs=40, seed=2, types=cluster.gpu_types)
    with obs.session(decisions=False) as ob:
        if engine == "events":
            simulate_events(HadarScheduler(solver="auto"), jobs, cluster,
                            round_len=360.0)
        else:
            simulate_hadare(jobs[:12], cluster, round_len=360.0,
                            solver="auto", max_rounds=40)
    doc = ob.trace.to_json()
    assert validate_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    # replayed queues fall under the exact DP's bound; HadarE's copies
    # (one per node) always make the greedy pass's long queue
    phase = "dp.exact" if engine == "events" else "solver.waves"
    assert {"hadar.dp", "hadar.backfill", "engine.step", phase} <= names
    assert set(SOLVER_SPANS) <= names
    # the engine's step and the consult never overlap
    steps = _spans(ob, "engine.step")
    consults = _spans(ob, "consult")
    assert steps and consults
    for a, b in steps:
        assert all(b <= c0 or a >= c1 for c0, c1 in consults)


def test_h2d_bytes_counts_every_uploaded_kernel_operand(monkeypatch):
    """Spy on both kernels: the counter equals the bytes of the distinct
    arrays handed to them, each uploaded once in the session (a fresh
    PriceState's views start dirty)."""
    handed = {}                       # id -> (array, nbytes), kept alive

    def spy(getter):
        get = getattr(bs, getter)

        def wrapped(*a):
            kern = get(*a)

            def call(*args):
                for x in args:
                    handed[id(x)] = (x, x.nbytes)
                return kern(*args)
            return call
        monkeypatch.setattr(bs, getter, wrapped)

    spy("_get_kernel")
    spy("_get_commit_kernel")
    _, ob, _, _, _ = _consult({"decisions": False})
    assert ob.metrics.counter("solver_scan_calls").value >= 1
    got = ob.metrics.counter("solver.h2d_bytes").value
    assert got == sum(n for _, n in handed.values()) > 0


def test_jitted_programs_keep_their_trace_names(monkeypatch):
    """A profiler trace finds the kernels as ``jit_kernel`` and
    ``jit_scan_fn``: the benchmark's reduction matches those names."""
    lowered = {}

    def spy(getter):
        get = getattr(bs, getter)

        def wrapped(*a):
            kern = get(*a)

            def call(*args):
                if getter not in lowered:
                    with jax.enable_x64():
                        lowered[getter] = kern.lower(*[
                            jax.ShapeDtypeStruct(x.shape, x.dtype)
                            for x in args]).as_text()
                return kern(*args)
            return call
        monkeypatch.setattr(bs, getter, wrapped)

    spy("_get_kernel")
    spy("_get_commit_kernel")
    _consult()
    assert "module @jit_kernel" in lowered["_get_kernel"]
    assert "module @jit_scan_fn" in lowered["_get_commit_kernel"]


def test_span_records_args_and_nests(monkeypatch):
    entered = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("/" + self.name)

    monkeypatch.setattr(obs, "_TraceAnnotation", Ann)
    with obs.session(decisions=False) as ob:
        with ob.span("outer", a=1) as sp:
            sp.set(b=2)
            inner = ob.span("inner").open()
            inner.close()
    assert entered == ["outer", "inner", "/inner", "/outer"]
    outer, = [e for e in ob.trace.events if e["name"] == "outer"]
    assert outer["args"] == {"a": 1, "b": 2}
    assert validate_trace(ob.trace.to_json()) == []
    with obs.NO_SPAN as sp:
        assert sp is obs.NO_SPAN
    # a metrics-only session still annotates the profiler trace
    with obs.session(trace=False, decisions=False) as ob:
        with ob.span("bare"):
            pass
    assert entered[-2:] == ["bare", "/bare"]


def test_removed_per_call_records_stay_gone():
    _, ob, _, batches, commits = _consult({"decisions": False})
    names = {e["name"] for e in ob.trace.events}
    assert not {"solver.resolve", "pricestate.commit",
                "pricestate.commit_batch", "pricestate.release"} & names
    summ = ob.metrics.summary()
    assert "solver.auto_min_jobs" not in summ["gauges"]
    assert summ["counters"].get("pricestate_commits", 0) == len(commits)
    assert summ["counters"]["pricestate_commit_batchs"] \
        == sum(1 for b in batches if b)


def test_pricestate_view_upload_counts_bytes():
    cluster = simulation_cluster()
    ps = PriceState(cluster, philly_trace(n_jobs=4, seed=0), HORIZON,
                    effective_throughput, 0.0)
    with obs.session(trace=False, decisions=False) as ob:
        ps.device_view("free")
        ps.device_view("free")                   # cached: no upload
        ps.commit({ps.keys[0]: 1})
        ps.device_view("free")                   # dirty: re-upload
    assert ob.metrics.counter("solver.h2d_bytes").value \
        == 2 * ps.free_arr.nbytes
    assert np.asarray(ps.device_view("free")).tolist() \
        == ps.free_arr.tolist()
