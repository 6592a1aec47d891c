"""Integration tests for repro.obs against the scheduling stack.

The contract under test: enabling observability changes **nothing**
about scheduling decisions (bit-identical finish times, restarts, and
records across all three engines), while the recorded artifacts are
faithful — trace "interval" spans carry the engine's own record
boundaries bitwise, and every decision-log price re-derives exactly
against the Eq. 5 closed form from its logged inputs.
"""
import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro import obs
from repro.core.hadar import HadarScheduler
from repro.core.trace import mix_jobs, philly_trace, simulation_cluster
from repro.core.trace import testbed_cluster as _testbed_cluster
from repro.obs.trace import SIM_PID, validate_trace
from repro.sim.adapters import simulate_hadare
from repro.sim.engine import simulate_events, simulate_rounds

N_JOBS = 10
ROUND_LEN = 360.0


def _jobs():
    return philly_trace(n_jobs=N_JOBS, seed=3)


def _norm_records(res):
    """Records with the wall-clock field zeroed (the only field allowed
    to differ between an observed and an unobserved run)."""
    return [dataclasses.replace(r, sched_seconds=0.0) for r in res.rounds]


def _fingerprint(res):
    return ([j.finish_time for j in res.jobs],
            [j.restarts for j in res.jobs],
            [j.done_iters for j in res.jobs],
            _norm_records(res))


# ---------------------------------------------------------------------------
# bit-identity: obs on == obs off
# ---------------------------------------------------------------------------

def test_rounds_engine_bit_identical_under_obs():
    cluster = simulation_cluster()
    plain = simulate_rounds(HadarScheduler(), _jobs(), cluster,
                            round_len=ROUND_LEN)
    with obs.session() as ob:
        observed = simulate_rounds(HadarScheduler(), _jobs(), cluster,
                                   round_len=ROUND_LEN)
    assert _fingerprint(observed) == _fingerprint(plain)
    assert validate_trace(ob.trace.to_json()) == []
    assert ob.metrics.counter("consults").value > 0


def test_events_engine_bit_identical_under_obs():
    cluster = simulation_cluster()
    plain = simulate_events(HadarScheduler(), _jobs(), cluster,
                            round_len=ROUND_LEN)
    with obs.session() as ob:
        observed = simulate_events(HadarScheduler(), _jobs(), cluster,
                                   round_len=ROUND_LEN)
    assert _fingerprint(observed) == _fingerprint(plain)
    assert validate_trace(ob.trace.to_json()) == []
    assert ob.metrics.counter("consults").value == observed.sched_calls
    assert ob.metrics.counter("jobs_completed").value \
        == sum(1 for j in observed.jobs if j.finish_time is not None)


def test_hadare_backend_bit_identical_under_obs():
    tb = _testbed_cluster()
    plain = simulate_hadare(mix_jobs("M-3", tb), tb, round_len=90.0)
    with obs.session() as ob:
        observed = simulate_hadare(mix_jobs("M-3", tb), tb,
                                   round_len=90.0)
    assert _fingerprint(observed) == _fingerprint(plain)
    assert validate_trace(ob.trace.to_json()) == []
    cons = [e for e in ob.trace.events
            if e["name"] == "hadare.consolidation"]
    assert cons and all(ev["args"]["raw"] >= ev["args"]["kept"]
                        for ev in cons)


# ---------------------------------------------------------------------------
# artifact faithfulness
# ---------------------------------------------------------------------------

def test_interval_spans_match_interval_records_bitwise():
    cluster = simulation_cluster()
    with obs.session() as ob:
        res = simulate_events(HadarScheduler(), _jobs(), cluster,
                              round_len=ROUND_LEN)
    spans = [e for e in ob.trace.events
             if e["ph"] == "X" and e["pid"] == SIM_PID
             and e["name"] == "interval"]
    assert len(spans) == len(res.rounds)
    for ev, rec in zip(spans, res.rounds):
        assert ev["ts"] == rec.t * 1e6          # bitwise, no tolerance
        assert ev["dur"] == rec.dt * 1e6
        assert ev["args"]["gru"] == rec.gru
        assert ev["args"]["cru"] == rec.cru
        assert ev["args"]["running"] == rec.running
        assert ev["args"]["waiting"] == rec.waiting
        assert ev["args"]["changed"] == rec.changed


def test_decision_log_prices_rederive_exactly(tmp_path):
    cluster = simulation_cluster()
    dpath = tmp_path / "decisions.jsonl"
    with obs.session(decisions_path=str(dpath)) as ob:
        simulate_events(HadarScheduler(), _jobs(), cluster,
                        round_len=ROUND_LEN)
    assert len(ob.decisions) > 0
    from repro.obs.explain import load_jsonl
    records = load_jsonl(str(dpath))
    assert records == ob.decisions.decisions     # JSONL round-trip
    for rec in records:
        assert rec["phase"] in ("dp", "backfill")
        total = 0
        for row in rec["alloc"]:
            # Eq. 5 at the logged pre-commit gamma: the recorded price
            # must equal the PriceState closed form bitwise
            rederived = row["u_min"] * (
                row["u_max"] / row["u_min"]) ** (
                row["gamma"] / max(row["cap"], 1))
            assert rederived == row["unit_price"]
            total += row["count"]
        assert total == rec["workers"]           # gang atomicity
        assert rec["utility"] == rec["payoff"] + rec["cost"]


def test_decision_log_runner_up_never_beats_winner():
    cluster = simulation_cluster()
    with obs.session(trace=False) as ob:
        simulate_events(HadarScheduler(), _jobs(), cluster,
                        round_len=ROUND_LEN)
    rus = [r for r in ob.decisions.decisions if r["runner_up"]]
    assert rus, "expected at least one decision with a runner-up"
    for rec in rus:
        assert rec["runner_up"]["payoff"] <= rec["payoff"]
        assert rec["runner_up"]["kind"] in ("pack", "spread")


def test_invariant_check_counters_tick_under_sanitize():
    cluster = simulation_cluster()
    with obs.session(trace=False, decisions=False) as ob:
        simulate_events(HadarScheduler(), _jobs(), cluster,
                        round_len=ROUND_LEN, sanitize=True)
    counters = ob.metrics.summary()["counters"]
    ticked = [k for k in counters if k.startswith("invariant_checks.")]
    assert "invariant_checks.cluster_allocs" in ticked
    assert "invariant_checks.progress" in ticked
    assert "invariant_checks.monotonic" in ticked


def test_jax_recompile_counter_on_batched_path():
    cluster = simulation_cluster()
    with obs.session(trace=False, decisions=False) as ob:
        simulate_events(HadarScheduler(solver="jax"), _jobs(), cluster,
                        round_len=ROUND_LEN)
    counters = ob.metrics.summary()["counters"]
    # per-session shape dedupe: >= 1 distinct dispatch shape seen
    assert counters.get("jax_recompiles", 0) >= 1
    assert counters.get("solver_batch_calls", 0) >= 1


# ---------------------------------------------------------------------------
# example entry point
# ---------------------------------------------------------------------------

def test_trace_sim_example_emits_trace_and_explains(tmp_path):
    repo = Path(__file__).resolve().parent.parent
    out = tmp_path / "trace.json"
    proc = subprocess.run(
        [sys.executable, str(repo / "examples" / "trace_sim.py"),
         "--jobs", "8", "--engine", "event",
         "--trace", str(out), "--explain"],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "Hadar allocation decisions" in proc.stdout
    assert "marginal unit price" in proc.stdout
    doc = json.loads(out.read_text())
    assert validate_trace(doc) == []
    names = {e.get("name") for e in doc["traceEvents"]}
    assert {"consult", "interval"} <= names
