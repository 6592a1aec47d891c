"""Smoke run of the scheduler's device path on one TPU chip.

Run from the repository root, with nothing else holding the chip:

    python chip_smoke.py

One process, no fallback: it exits nonzero unless JAX's devices are
TPUs, and nonzero if any phase below fails.  Each phase prints its own
line; the last line of a passing run is one JSON object naming the
device.  Every phase compares the jax backend with the NumPy oracle on
the same state, decision for decision:

- ``consult``  a full-queue consult at paper Fig. 5 scale (2048 Philly
  jobs on ``grown_cluster(2048)``: 256 nodes, 1024 GPUs).  The per-job
  FIND_ALLOC sweep through the batched pricing kernel, and the greedy
  ``dp_allocation`` through the wave partitioner and the device scan.
- ``events``   an event-engine replay with ``HadarScheduler``, capped
  at a fixed number of events; allocations per consult and the
  resulting TTD / JCT / GRU must be identical.
- ``hadare``   a HadarE replay under a seeded ``FailureModel``.
- ``pallas``   the three Pallas kernels compiled (not interpreted) at
  the widths of ``repro.configs`` against ``repro.kernels.ref``.

Wall times are printed for information; none is a benchmark metric.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
HORIZON = 7 * 24 * 3600.0

CONSULT_JOBS = 2048           # paper Fig. 5's largest queue
EVENT_JOBS = 128
EVENT_CAP = 1500              # events per replay
HADARE_JOBS = 48
HADARE_ROUNDS = 400

# Pallas widths: llama3.2-1b attention (32 q heads, 8 kv heads, head dim
# 64), rwkv6-7b WKV (64 heads of 64), RMSNorm over rwkv6-7b's d_model
ATTN = dict(hq=32, hkv=8, dh=64, seq=2048)
WKV = dict(h=64, d=64, seq=2048)
NORM = dict(rows=2048, d=4096)
# max |kernel - ref| / max(1, max |ref|): bf16 inputs and outputs
PALLAS_TOL = 2e-2


def _line(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _platforms(tree) -> set:
    import jax
    return {d.platform for x in jax.tree_util.tree_leaves(tree)
            for d in x.devices()}


class KernelSpy:
    """Records the device platforms of every solver-kernel output while
    installed, by wrapping the batch solver's kernel getters."""

    def __init__(self):
        from repro.core import batch_solver as bs
        self._bs = bs
        self.seen = {}

    def __enter__(self):
        bs = self._bs
        self._orig = {n: getattr(bs, n)
                      for n in ("_get_kernel", "_get_commit_kernel")}
        for name, get in self._orig.items():
            def wrapped(*a, _get=get, _name=name):
                kern = _get(*a)

                def call(*args):
                    out = kern(*args)
                    self.seen.setdefault(_name, set()).update(
                        _platforms(out))
                    return out
                return call
            setattr(bs, name, wrapped)
        return self

    def __exit__(self, *exc):
        for name, get in self._orig.items():
            setattr(self._bs, name, get)


def _cand_key(c):
    return None if c is None else (c.alloc, c.cost, c.payoff, c.rate)


def _mismatches(ref, got) -> dict:
    """Count decision differences between two candidate lists (or
    {job_id: Candidate} maps): ``alloc`` where the placement differs,
    ``value`` where only cost / payoff / rate differ."""
    if isinstance(ref, dict):
        ids = sorted(set(ref) | set(got))
        pairs = [(ref.get(i), got.get(i)) for i in ids]
    else:
        pairs = list(zip(ref, got))
    alloc = sum(1 for a, b in pairs
                if (a is None) != (b is None)
                or (a is not None and a.alloc != b.alloc))
    value = sum(1 for a, b in pairs
                if _cand_key(a) != _cand_key(b)) - alloc
    return {"alloc": alloc, "value": value}


def phase_consult(n_jobs: int = CONSULT_JOBS) -> list:
    from benchmarks.fig5_scalability import grown_cluster
    from repro import obs
    from repro.core.batch_solver import find_alloc_batch
    from repro.core.dp import _find_alloc_arrays, dp_allocation
    from repro.core.pricing import PriceState
    from repro.core.trace import philly_trace
    from repro.core.utility import effective_throughput as util

    fails = []
    cluster = grown_cluster(n_jobs)
    jobs = philly_trace(n_jobs=n_jobs, seed=1, types=cluster.gpu_types)
    ps = PriceState(cluster, jobs, HORIZON, util, 0.0)
    avail, gamma = ps.free_arr.copy(), ps.gamma_arr.copy()

    sw = obs.StopWatch().start()
    ref = [_find_alloc_arrays(j, avail, gamma, ps, 0.0, util, False)
           for j in jobs]
    numpy_s = sw.stop()
    with KernelSpy() as spy, \
            obs.session(trace=False, decisions=False) as ob:
        times = []
        for _ in range(2):            # first call compiles
            sw.start()
            got = find_alloc_batch(jobs, avail, gamma, ps, 0.0, util,
                                   avail_dev=ps.device_view("free"))
            times.append(sw.stop())
        sweep = _mismatches(ref, got)

        sel = {}
        dp_s = {}
        for solver in ("numpy", "jax", "jax"):
            fresh = PriceState(cluster, jobs, HORIZON, util, 0.0)
            sw.start()
            sel[solver] = dp_allocation(jobs, None, fresh, 0.0, util,
                                        max_exact=0, solver=solver)
            dp_s.setdefault(solver, []).append(sw.stop())
        greedy = _mismatches(sel["numpy"], sel["jax"])
        views = _platforms([ps.device_view(v)
                            for v in ("free", "node_row")])
    counters = ob.metrics.summary()["counters"]
    batch_calls = counters.get("solver_batch_calls", 0)
    scan_calls = counters.get("solver_scan_calls", 0)
    kernels = {k: sorted(v) for k, v in spy.seen.items()}

    _line("consult", jobs=n_jobs, nodes=len(cluster.nodes),
          gpus=sum(cluster.capacity().values()),
          sweep_alloc_mismatch=sweep["alloc"],
          sweep_value_mismatch=sweep["value"],
          greedy_selected=len(sel["numpy"]),
          greedy_alloc_mismatch=greedy["alloc"],
          greedy_value_mismatch=greedy["value"])
    _line("consult", device_views=sorted(views),
          kernel_outputs=json.dumps(kernels, sort_keys=True),
          solver_batch_calls=batch_calls, solver_scan_calls=scan_calls,
          scan_host_steps=counters.get("solver.scan_host_steps", 0))
    _line("consult", info="wall seconds, not a metric",
          sweep_numpy=numpy_s, sweep_jax_first=times[0],
          sweep_jax_warm=times[1], greedy_numpy=dp_s["numpy"][0],
          greedy_jax_first=dp_s["jax"][0], greedy_jax_warm=dp_s["jax"][1])
    if sweep["alloc"] or sweep["value"]:
        fails.append(f"consult: FIND_ALLOC sweep mismatches {sweep}")
    if greedy["alloc"] or greedy["value"]:
        fails.append(f"consult: greedy selection mismatches {greedy}")
    if batch_calls <= 0 or scan_calls <= 0:
        fails.append(f"consult: device path idle (batch {batch_calls}, "
                     f"scan {scan_calls})")
    platforms = set(views).union(*[set(v) for v in kernels.values()])
    if platforms != {_platform()} or set(kernels) != {
            "_get_kernel", "_get_commit_kernel"}:
        fails.append(f"consult: work not on the accelerator: views "
                     f"{sorted(views)}, kernels {kernels}")
    return fails


def _recording(inner):
    """``inner`` wrapped so that every consult's allocations are kept."""
    from repro.sim.adapters import CountingScheduler

    class Recording(CountingScheduler):
        def __init__(self, inner):
            super().__init__(inner)
            self.consults = []

        def schedule(self, now, round_len, jobs, cluster):
            out = super().schedule(now, round_len, jobs, cluster)
            self.consults.append((now, sorted(
                (jid, sorted(a.items())) for jid, a in out.items())))
            return out
    return Recording(inner)


def _replay_pair(phase: str, replay) -> tuple:
    """Run ``replay(solver) -> (per-step records, result)`` on the NumPy
    oracle and on jax, each under its own ``repro.obs`` session, and
    count the steps whose records differ.  Returns the failures, the
    jax result and its counters."""
    from repro import obs
    runs = {}
    for solver in ("numpy", "jax"):
        with obs.session(trace=False, decisions=False) as ob, \
                obs.StopWatch() as sw:
            steps, res = replay(solver)
        per_job = tuple((j.job_id, j.finish_time, j.done_iters, j.restarts,
                         j.evictions, j.lost_iters) for j in res.jobs)
        outcome = (per_job, res.total_seconds, res.avg_jct(),
                   res.avg_gru(), res.gru_overall(), res.goodput(),
                   res.evictions)
        runs[solver] = (steps, outcome, res, sw.seconds,
                        ob.metrics.summary()["counters"])
    (ref, ref_out, _, ref_s, _), (got, got_out, res, got_s, counters) = \
        runs["numpy"], runs["jax"]
    differ = sum(1 for a, b in zip(ref, got) if a != b) \
        + abs(len(ref) - len(got))
    _line(phase, steps=len(got), step_mismatch=differ,
          outcome_identical=ref_out == got_out, ttd_s=res.total_seconds,
          avg_jct_s=res.avg_jct(), avg_gru=res.avg_gru(),
          goodput=res.goodput(), evictions=res.evictions,
          solver_batch_calls=counters.get("solver_batch_calls", 0),
          solver_scan_calls=counters.get("solver_scan_calls", 0),
          scan_host_steps=counters.get("solver.scan_host_steps", 0))
    _line(phase, info="wall seconds, not a metric", numpy=ref_s, jax=got_s)
    fails = []
    if differ or ref_out != got_out:
        fails.append(f"{phase}: {differ} steps differ, outcome identical "
                     f"{ref_out == got_out}")
    if counters.get("solver_batch_calls", 0) <= 0:
        fails.append(f"{phase}: no consult reached the device path")
    return fails, res, counters


def phase_events(n_jobs: int = EVENT_JOBS, cap: int = EVENT_CAP) -> list:
    """Event replay; a step is one consult and its allocations."""
    from benchmarks.fig5_scalability import grown_cluster
    from repro.core.hadar import HadarScheduler
    from repro.core.trace import philly_trace
    from repro.sim.engine import simulate_events

    cluster = grown_cluster(n_jobs)

    def replay(solver):
        sched = _recording(HadarScheduler(solver=solver))
        jobs = philly_trace(n_jobs=n_jobs, seed=2, types=cluster.gpu_types,
                            all_at_start=False)
        res = simulate_events(sched, jobs, cluster, max_events=cap)
        return sched.consults, res

    fails, res, _ = _replay_pair("events", replay)
    _line("events", jobs=n_jobs, events=res.n_events, event_cap=cap)
    return fails


def phase_hadare(n_jobs: int = HADARE_JOBS,
                 max_rounds: int = HADARE_ROUNDS) -> list:
    """HadarE replay under a seeded FailureModel; a step is one round."""
    from repro.core.trace import philly_trace, simulation_cluster
    from repro.sim.adapters import simulate_hadare
    from repro.sim.faults import FailureModel

    cluster = simulation_cluster()

    def replay(solver):
        jobs = philly_trace(n_jobs=n_jobs, seed=3, types=cluster.gpu_types)
        faults = FailureModel(mtbf_hours=12.0, recovery_s=1800.0, seed=4,
                              horizon=max_rounds * 360.0)
        res = simulate_hadare(jobs, cluster, max_rounds=max_rounds,
                              solver=solver, faults=faults)
        return [(r.t, r.gru, r.cru, r.running, r.waiting, r.changed)
                for r in res.rounds], res

    fails, res, _ = _replay_pair("hadare", replay)
    _line("hadare", jobs=n_jobs, nodes=len(cluster.nodes),
          max_rounds=max_rounds)
    if res.evictions <= 0:
        fails.append("hadare: the failure model evicted nothing")
    return fails


def _rel_err(got, want) -> float:
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(1.0, jnp.max(jnp.abs(want))))


def phase_pallas(attn=ATTN, wkv=WKV, norm=NORM) -> list:
    import jax
    import jax.numpy as jnp
    from repro.kernels import interpret_default, ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rmsnorm import rmsnorm
    from repro.kernels.rwkv6_scan import rwkv6_scan

    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 10)

    def rand(key, shape, scale=1.0, dtype=bf):
        return (scale * jax.random.normal(key, shape)).astype(dtype)

    errs = {}
    q = rand(ks[0], (1, attn["hq"], attn["seq"], attn["dh"]))
    k = rand(ks[1], (1, attn["hkv"], attn["seq"], attn["dh"]))
    v = rand(ks[2], (1, attn["hkv"], attn["seq"], attn["dh"]))
    out = jax.jit(flash_attention)(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = ref.flash_attention_ref(q.astype(jnp.float32), k, v)
    errs["flash_attention"] = _rel_err(out, want)

    shape = (1, wkv["h"], wkv["seq"], wkv["d"])
    r, kk, vv = (rand(ks[i], shape, 0.5) for i in (3, 4, 5))
    w = (jax.nn.sigmoid(jax.random.normal(ks[6], shape) - 1.0) * 0.98
         + 0.01).astype(bf)
    u = rand(ks[7], (wkv["h"], wkv["d"]), 0.3)
    s0 = rand(ks[8], (1, wkv["h"], wkv["d"], wkv["d"]), 0.2, jnp.float32)
    out, sT = jax.jit(rwkv6_scan)(r, kk, vv, w, u, s0)
    with jax.default_matmul_precision("highest"):
        want, wsT = jax.jit(ref.rwkv6_scan_ref)(r, kk, vv, w, u, s0)
    errs["rwkv6_out"] = _rel_err(out, want)
    errs["rwkv6_state"] = _rel_err(sT, wsT)

    x = rand(ks[9], (norm["rows"], norm["d"]))
    scale = rand(ks[0], (norm["d"],))
    errs["rmsnorm"] = _rel_err(jax.jit(rmsnorm)(x, scale),
                               ref.rmsnorm_ref(x.astype(jnp.float32),
                                               scale))
    interpreted = interpret_default()
    _line("pallas", interpret=interpreted, tol=PALLAS_TOL,
          **{k: f"{e:.3e}" for k, e in errs.items()})
    fails = [f"pallas: {k} error {e:.3e} > {PALLAS_TOL}"
             for k, e in errs.items() if not e <= PALLAS_TOL]
    if interpreted != (_platform() != "tpu"):
        fails.append(f"pallas: interpret={interpreted} on {_platform()}")
    return fails


def _platform() -> str:
    import jax
    return jax.devices()[0].platform


def main() -> int:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    _line("device", platform=dev["platform"], kind=repr(dev["kind"]),
          count=dev["count"])
    if dev["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; refusing to run on "
              f"{dev['platform']}", file=sys.stderr)
        return 1
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.utils.compile_cache import enable_compile_cache
    _line("device", compile_cache=enable_compile_cache())

    fails = []
    for phase in (phase_consult, phase_events, phase_hadare, phase_pallas):
        t0 = time.perf_counter()
        fails += phase()
        _line(phase.__name__[len("phase_"):], info="phase wall seconds",
              seconds=time.perf_counter() - t0)
    if fails:
        for f in fails:
            print("FAIL " + f, file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
