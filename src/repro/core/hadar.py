"""Hadar (Algorithm 1): round-based primal-dual scheduling with the
DP dual subroutine (Algorithm 2) for task-level heterogeneous allocation.

Incremental behaviour per the paper's scalability discussion: running jobs
keep their allocations and only the waiting queue is allocated against the
residual capacity; a full re-optimization (which may preempt) happens when
resources were freed by completions — matching the observed "only ~30% of
rounds require allocation changes".
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from repro import obs as _obs
from repro.core.dp import _find_alloc_arrays, dp_allocation
from repro.core.pricing import PriceState
from repro.core.schedulers import Scheduler
from repro.core.types import Alloc, Cluster, Job
from repro.core.utility import UtilityFn, effective_throughput


class HadarScheduler(Scheduler):
    name = "hadar"
    # incremental mode pins running jobs' allocations between completions,
    # so rounds with an empty waiting queue are provably no-ops
    stable_when_idle = True

    def __init__(self, horizon: float = 7 * 24 * 3600.0,
                 utility: UtilityFn = effective_throughput,
                 reallocate_on_free: bool = True,
                 max_exact_dp: int = 24,
                 work_conserving: bool = True,
                 solver: str = "auto"):
        self.horizon = horizon
        self.utility = utility
        self.reallocate_on_free = reallocate_on_free
        self.max_exact_dp = max_exact_dp
        # After the primal-dual selection, backfill still-idle devices with
        # still-waiting jobs (mu gate skipped).  The admission price keeps
        # its role for job *selection order*; idle-with-waiting states —
        # which the paper's own Fig. 1 never exhibits — are eliminated.
        self.work_conserving = work_conserving
        # pricing backend for the queue-wide candidate scans:
        # "jax" (batched device kernel) | "numpy" | "auto" (by size).
        # Decisions are bit-identical across backends.
        self.solver = solver
        self._had_completion = True     # force full pass on round 0
        self.last_sched_seconds = 0.0   # scalability metric (Fig. 5)
        self.alpha = 0.0                # Thm 2 constant, for reporting
        self._ps: PriceState = None     # persistent across consultations

    def note_completion(self) -> None:
        self._had_completion = True

    def _log_decision(self, ob, now, job, cand, ps, phase) -> None:
        """Allocation provenance (repro.obs.explain): record the winning
        keys with their Eq. 5 marginal unit prices *at the pre-commit
        gamma* plus the inputs the price was derived from, so each log
        line re-derives exactly against ``PriceState.price``."""
        rows = []
        for (node, gtype), count in cand.alloc.items():
            key = (node, gtype)
            cap = ps._cap_by_key.get(key, 0)
            rows.append({
                "node": node, "type": gtype, "count": int(count),
                "unit_price": ps.price(node, gtype, cap),
                "gamma": int(ps.gamma.get(key, 0)), "cap": int(cap),
                "u_min": ps.u_min[gtype], "u_max": ps.u_max[gtype]})
        ob.decision(_obs.decision_record(
            now, job.job_id, job.n_workers, phase, self.solver, rows,
            cand.cost, cand.payoff, cand.rate, cand.runner_up))

    def _backfill(self, queue, out, extra, ps, now, ob, log):
        """Work-conserving backfill: waiting jobs onto idle devices, in
        queue order (``schedule`` sorts it by arrival, then id).  The
        reference prices against (pre-selection free) - extra; extra is
        exactly the allocations committed since the kept jobs, so that
        difference *is* the live free_arr — no dict.  Jobs the
        :class:`_FitGate` proves cannot fit are skipped unpriced; free
        devices only shrink here, so none is priced once none is free.
        Returns the counts of jobs priced and skipped."""
        pending = [j for j in queue if j.job_id not in out]
        gate = _FitGate(ps)
        priced = 0
        for j in pending:
            if gate.exhausted:
                break
            if not gate.may_fit(j):
                continue
            priced += 1
            avail = ps.free_arr.copy()
            gamma = ps.gamma_arr.copy()
            for k, v in extra.items():      # seed double-count kept
                m = ps.key_index.get(k)
                if m is not None:
                    gamma[m] += v
            cand = _find_alloc_arrays(j, avail, gamma, ps, now,
                                      self.utility, force=True)
            if cand is None:
                continue
            out[j.job_id] = cand.alloc
            if log:
                self._log_decision(ob, now, j, cand, ps, "backfill")
            ps.commit(cand.alloc)
            for k, v in cand.alloc.items():
                extra[k] = extra.get(k, 0) + v
            gate.refresh()
        return priced, len(pending) - priced

    def schedule(self, now, round_len, jobs, cluster):
        _ob = _obs.get()
        sw = _obs.StopWatch().start()
        active = [j for j in jobs if not j.is_done() and j.arrival <= now]
        out: Dict[int, Alloc] = {}

        full_pass = self.reallocate_on_free and self._had_completion
        self._had_completion = False

        running = [j for j in active if j.alloc]
        waiting = [j for j in active if not j.alloc]
        if full_pass:
            queue = sorted(active, key=lambda j: (j.arrival, j.job_id))
            kept: List[Job] = []
        else:
            queue = sorted(waiting, key=lambda j: (j.arrival, j.job_id))
            kept = running

        # persistent PriceState: the key arrays (and the batched solver's
        # cached device buffers) are built once per cluster geometry; each
        # consultation re-primes bounds/gamma/free in place, so the event
        # engine prices every event step without rebuilding state
        if self._ps is None or not self._ps.matches(cluster):
            self._ps = PriceState(cluster, active, self.horizon,
                                  self.utility, now)
        else:
            self._ps.refresh(active, now)
        ps = self._ps
        self.alpha = ps.alpha()
        for j in kept:                      # running jobs pin their gammas
            out[j.job_id] = j.alloc
        # one aggregated free/gamma delta (and one sanitizer pass)
        ps.commit_batch(j.alloc for j in kept)

        with (_ob.span("hadar.dp", t=now, queue_len=len(queue),
                       full_pass=full_pass)
              if _ob.enabled else _obs.NO_SPAN) as sp:
            sel = dp_allocation(queue, None, ps, now, self.utility,
                                max_exact=self.max_exact_dp,
                                solver=self.solver)
            if _ob.enabled:
                sp.set(selected=len(sel))
        extra: Dict = {}
        for jid, cand in sel.items():
            out[jid] = cand.alloc
            for k, v in cand.alloc.items():
                extra[k] = extra.get(k, 0) + v
        log = _ob.decisions is not None
        if log:
            # decision provenance snapshots each winner's Eq. 5 prices
            # at its *pre-commit* gamma, so a decision log keeps the
            # sequential log-then-commit interleaving
            by_id = {j.job_id: j for j in queue}
            for jid, cand in sel.items():
                self._log_decision(_ob, now, by_id[jid], cand, ps, "dp")
                ps.commit(cand.alloc)
        else:
            ps.commit_batch(cand.alloc for cand in sel.values())

        if self.work_conserving:
            with (_ob.span("hadar.backfill") if _ob.enabled
                  else _obs.NO_SPAN) as sp:
                priced, skipped = self._backfill(queue, out, extra, ps,
                                                 now, _ob, log)
                if _ob.enabled:
                    sp.set(priced=priced, skipped=skipped)
                    _ob.count("backfill.priced", priced)
                    _ob.count("backfill.skipped", skipped)

        self.last_sched_seconds = sw.stop()
        if _ob.enabled:
            _ob.free_capacity(ps.keys, ps.free_arr)
        return out


class _FitGate:
    """Exact feasibility gate for the backfill's FIND_ALLOC calls.

    With ``force`` FIND_ALLOC returns None exactly when the job has no
    consolidated candidate (W free usable devices on one node) and no
    spread candidate (W free usable devices in all; a single-node HadarE
    copy has none), usable meaning a type of throughput > 0.  Both
    counts are bounded by the positive free devices of those types, so
    a job whose bound is below W gets None whether priced or not.  The
    bounds are cached per usable-type set until the next commit."""

    def __init__(self, ps: PriceState):
        self.ps = ps
        self.types = ps.cluster.gpu_types
        self.refresh()

    def refresh(self) -> None:
        """Re-read ``ps.free_arr``; call after every commit."""
        ps = self.ps
        self.node_free = np.zeros((ps.n_node_rows, len(self.types)))
        self.node_free[ps.node_row, ps.type_col] = np.maximum(
            ps.free_arr, 0.0)
        # no job (W >= 1) fits once no device is free
        self.exhausted = not self.node_free.any()
        self._bounds = {}         # usable cols -> (in all, on one node)

    def may_fit(self, job: Job) -> bool:
        cols = tuple(c for c, r in enumerate(self.types)
                     if job.throughput.get(r, 0) > 0)
        bound = self._bounds.get(cols)
        if bound is None:
            per_node = self.node_free[:, list(cols)].sum(axis=1)
            bound = self._bounds[cols] = (per_node.sum(),
                                          per_node.max(initial=0.0))
        return bound[1 if job.single_node else 0] >= job.n_workers
