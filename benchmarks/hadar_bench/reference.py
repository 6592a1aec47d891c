"""Plain reference of one Hadar consult, independent of the program.

It implements the semantics of the seed scheduler (paper Algorithm 1
with Algorithm 2's DP_allocation / FIND_ALLOC, plus the work-conserving
backfill) from a snapshot of the consult's inputs: the up nodes, every
job handed to the scheduler with its progress and current allocation,
the time, and whether a completion since the last consult asks for a
full pass.  It imports nothing of ``repro`` and takes no state the
program built.

``dtype`` sets the precision of every price, utility, cost and payoff.
The configurations state float64; ``numpy.float32`` gives the control,
the step down that moving the pricing floats onto the TPU would tempt.

Summation orders follow the stated semantics: a consolidated
allocation's cost adds its unit prices one by one in preference order
(``numpy.cumsum``); a spread allocation's cost is the sum of its chosen
units in price/throughput order, taken with ``numpy.sum`` as the
program does.
"""
from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

COMM_COST_FRAC = 0.05      # price per extra server spanned by a spread


class JobRow(NamedTuple):
    job_id: int
    arrival: float
    workers: int
    total_iters: float
    done_iters: float
    throughput: Tuple[Tuple[str, float], ...]
    single_node: bool
    alloc: Optional[Tuple[Tuple[Tuple[int, str], int], ...]]


class Snapshot(NamedTuple):
    now: float
    full_pass: bool
    nodes: Tuple[Tuple[int, Tuple[Tuple[str, int], ...]], ...]
    jobs: Tuple[JobRow, ...]


class Params(NamedTuple):
    horizon: float
    max_exact: int
    work_conserving: bool
    reallocate_on_free: bool


Alloc = Dict[Tuple[int, str], int]


class _Cand(NamedTuple):
    alloc: Alloc
    payoff: object


class _Cluster:
    """Key axis of the up nodes: one key per (node, gpu type)."""

    def __init__(self, nodes, dt):
        self.dt = dt
        self.types: List[str] = []
        self.keys: List[Tuple[int, str]] = []
        caps, rows = [], []
        for row, (nid, gpus) in enumerate(nodes):
            for r, c in gpus:
                if r not in self.types:
                    self.types.append(r)
                self.keys.append((nid, r))
                caps.append(c)
                rows.append(row)
        self.node_ids = [nid for nid, _ in nodes]
        self.n_nodes = len(nodes)
        self.key_index = {k: m for m, k in enumerate(self.keys)}
        self.cap = np.array(caps, dtype=float)
        self.node_row = np.array(rows, dtype=np.intp)
        self.type_col = np.array([self.types.index(r) for _, r in self.keys],
                                 dtype=np.intp)


class _Job:
    def __init__(self, row: JobRow, F):
        self.id = row.job_id
        self.arrival = row.arrival
        self.W = row.workers
        self.tp = dict(row.throughput)
        self.single = row.single_node
        self.alloc = dict(row.alloc) if row.alloc else None
        self.total = F(row.total_iters)
        self.rem = max(F(0.0), F(row.total_iters) - F(row.done_iters))
        self.F = F

    def done(self) -> bool:
        return self.rem <= 1e-9

    def utility(self, ct):
        return self.total / max(ct, self.F(1e-9))

    def t_min(self):
        return self.total / (self.W * self.F(max(self.tp.values())))

    def t_max(self):
        return self.total / (self.W * self.F(min(
            x for x in self.tp.values() if x > 0)))


class Reference:
    def __init__(self, params: Params, dtype=np.float64):
        self.p = params
        self.F = np.dtype(dtype).type

    # ---- Eqs. 6-7: price bounds from the active jobs -------------------
    def _bounds(self, cl: _Cluster, active: List[_Job]):
        F = self.F
        jobs = [j for j in active if j.tp]
        if not jobs:
            return F(1.0), F(1.0) / F(math.e)
        cap_total = F(cl.cap.sum())
        eta = max(cap_total / max(j.t_max() * j.W, F(1e-9)) for j in jobs)
        eta = max(eta, F(1.0))
        best, worst = F(0.0), F(np.inf)
        for j in jobs:
            best = max(best, j.utility(max(j.t_min(), F(1e-9)))
                       / max(j.W, 1))
            u_floor = j.utility(max(F(self.p.horizon) - F(j.arrival),
                                    j.t_min(), F(1e-9)))
            worst = min(worst, u_floor / (j.t_max() * j.W))
        u_max = max(best, F(1e-12))
        u_min = max(min(worst / (F(4.0) * eta), u_max / F(math.e)),
                    F(1e-15))
        return u_max, u_min

    def _prices(self, gamma: np.ndarray, units: int) -> np.ndarray:
        """Eq. 5: price of the (i+1)-th extra device on every key."""
        cl, F = self.cl, self.F
        i = np.arange(units)
        expo = ((gamma[:, None] + i[None, :])
                / np.maximum(cl.cap, 1.0)[:, None]).astype(F)
        umin = np.full(len(cl.keys), self.u_min, dtype=F)
        q = np.full(len(cl.keys), self.u_max, dtype=F) / umin
        return umin[:, None] * q[:, None] ** expo

    # ---- FIND_ALLOC ------------------------------------------------------
    def find_alloc(self, job: _Job, avail: np.ndarray, gamma: np.ndarray,
                   now: float, force: bool) -> Optional[_Cand]:
        cl, F = self.cl, self.F
        W = job.W
        types = sorted([r for r in cl.types if job.tp.get(r, 0) > 0],
                       key=lambda r: -job.tp[r])
        if not types:
            return None
        K = len(types)
        x = [F(job.tp[r]) for r in types]
        u = [job.utility(max(F(now) + job.rem / (xr * max(1, W))
                             - F(job.arrival), F(1e-9))) for xr in x]
        u_arr = np.array(u, dtype=F)
        rank_of_col = np.full(len(cl.types), K)
        for k, r in enumerate(types):
            rank_of_col[cl.types.index(r)] = k
        rank = rank_of_col[cl.type_col]
        usable = rank < K
        units = int(max(avail.max(initial=0.0), 0.0))
        P = self._prices(gamma, units)

        # consolidated: every task on one server, fastest types first
        N = cl.n_nodes
        A = np.zeros((N, K))
        kid = np.full((N, K), -1)
        A[cl.node_row[usable], rank[usable]] = avail[usable]
        kid[cl.node_row[usable], rank[usable]] = np.nonzero(usable)[0]
        feas = np.cumsum(A, axis=1) >= W          # total free per prefix
        first = np.where(feas.any(axis=1), np.argmax(feas, axis=1), K)
        Apos = np.maximum(A, 0.0)
        before = np.cumsum(Apos, axis=1) - Apos
        take = np.clip(W - before, 0.0, Apos).astype(int)
        cost_units = np.zeros((N, max(W, 1)), dtype=F)
        for k in range(K):
            for i in range(W):
                h = np.nonzero(take[:, k] > i)[0]
                if h.size:
                    cost_units[h, before[h, k].astype(int) + i] = \
                        P[kid[h, k], i]
        pack_cost = np.cumsum(cost_units, axis=1)[:, W - 1] if W else \
            np.zeros(N, dtype=F)
        slowest = np.where(take > 0, np.arange(K)[None, :], -1).max(axis=1)

        # spread: globally cheapest devices per unit of throughput
        flat_key = np.repeat(np.arange(len(cl.keys)), units)
        flat_i = np.tile(np.arange(units), len(cl.keys))
        ok_unit = usable[flat_key] & (flat_i < avail[flat_key])
        x_key = np.array(x, dtype=F)[np.minimum(rank, K - 1)]
        ratio = P.ravel() / x_key[flat_key] if units else np.zeros(0, F)

        def packed(h):
            return {(cl.node_ids[h], types[r]): int(take[h, r])
                    for r in range(K) if take[h, r] > 0}

        # enumeration order: per type prefix, servers in node order, then
        # the prefix's spread; the first of equal payoffs wins.  A server
        # first feasible at an earlier prefix repeats its earlier payoff,
        # so only first appearances can win.
        best = None                      # (payoff, alloc)
        for k in range(1, K + 1):
            hs = np.nonzero(first == k - 1)[0]
            if hs.size:
                pay = u_arr[slowest[hs]] - pack_cost[hs]
                i = int(np.argmax(pay))
                if best is None or pay[i] > best[0]:
                    best = (pay[i], packed(hs[i]))
            if job.single:
                continue
            pool = np.nonzero(ok_unit & (rank[flat_key] < k))[0]
            if pool.size < W:
                continue
            chosen = pool[np.argsort(ratio[pool], kind="stable")[:W]]
            keys = flat_key[chosen]
            cost = P.ravel()[chosen].sum()
            j = int(rank[keys].max())
            n_servers = np.unique(cl.node_row[keys]).size
            if n_servers > 1:
                cost = cost + F(COMM_COST_FRAC) * max(u[j], F(0.0)) \
                    * (n_servers - 1)
            if best is None or u[j] - cost > best[0]:
                alloc = {}
                for m in keys:
                    alloc[cl.keys[m]] = alloc.get(cl.keys[m], 0) + 1
                best = (u[j] - cost, alloc)

        if best is None:
            return None
        if best[0] <= 0 and not force:
            return None
        return _Cand(best[1], best[0])

    # ---- DP_allocation ---------------------------------------------------
    def _add(self, vec: np.ndarray, alloc: Alloc, sign: int) -> None:
        for key, c in alloc.items():
            m = self.cl.key_index.get(key)
            if m is not None:
                vec[m] += sign * c

    def dp_allocation(self, queue: List[_Job], free: np.ndarray,
                      gamma: np.ndarray, now: float) -> Dict[int, _Cand]:
        if len(queue) > self.p.max_exact:
            order = []
            for j in queue:
                c = self.find_alloc(j, free, gamma, now, False)
                if c:
                    order.append((c.payoff / max(1, j.W), j))
            order.sort(key=lambda t: -t[0])
            chosen: Dict[int, _Cand] = {}
            avail, gam = free.copy(), gamma.copy()
            for _, j in order:
                c = self.find_alloc(j, avail, gam, now, False)
                if c:
                    chosen[j.id] = c
                    self._add(avail, c.alloc, -1)
                    self._add(gam, c.alloc, +1)
            return chosen

        memo: Dict = {}

        def rec(idx: int, extra: Dict):
            if idx >= len(queue):
                return self.F(0.0), {}
            key = (idx, tuple(sorted((k, v) for k, v in extra.items() if v)))
            if key in memo:
                return memo[key]
            best_v, best_sel = rec(idx + 1, extra)
            avail, gam = free.copy(), gamma.copy()
            self._add(avail, extra, -1)
            self._add(gam, extra, +1)
            cand = self.find_alloc(queue[idx], avail, gam, now, False)
            if cand is not None:
                extra2 = dict(extra)
                for k, v in cand.alloc.items():
                    extra2[k] = extra2.get(k, 0) + v
                v2, sel2 = rec(idx + 1, extra2)
                if cand.payoff + v2 > best_v:
                    best_v = cand.payoff + v2
                    best_sel = dict(sel2)
                    best_sel[queue[idx].id] = cand
            memo[key] = (best_v, best_sel)
            return memo[key]

        return rec(0, {})[1]

    # ---- one consult (Algorithm 1) ---------------------------------------
    def schedule(self, snap: Snapshot) -> Dict[int, Alloc]:
        now = snap.now
        self.cl = cl = _Cluster(snap.nodes, self.F)
        jobs = [_Job(r, self.F) for r in snap.jobs]
        active = [j for j in jobs if not j.done() and j.arrival <= now]
        full = self.p.reallocate_on_free and snap.full_pass
        by_arrival = lambda j: (j.arrival, j.id)      # noqa: E731
        if full:
            queue = sorted(active, key=by_arrival)
            kept: List[_Job] = []
        else:
            queue = sorted([j for j in active if not j.alloc],
                           key=by_arrival)
            kept = [j for j in active if j.alloc]
        self.u_max, self.u_min = self._bounds(cl, active)
        # Theorem 2's competitive-ratio constant, which the scheduler
        # reports for the consult
        self.alpha = max(self.F(1.0),
                         self.F(math.log(self.u_max / self.u_min)))
        gamma = np.zeros(len(cl.keys))
        free = cl.cap.copy()
        out: Dict[int, Alloc] = {}
        for j in kept:
            out[j.id] = j.alloc
            self._add(gamma, j.alloc, +1)
            self._add(free, j.alloc, -1)
        sel = self.dp_allocation(queue, free, gamma, now)
        extra = np.zeros(len(cl.keys))   # this consult's selections
        for jid, c in sel.items():
            out[jid] = c.alloc
            for vec, sign in ((gamma, 1), (free, -1), (extra, 1)):
                self._add(vec, c.alloc, sign)
        if self.p.work_conserving:
            # backfill idle devices, best payoff first, mu gate skipped;
            # prices count this consult's selections twice, as the seed
            for j in queue:
                if j.id in out:
                    continue
                c = self.find_alloc(j, free.copy(), gamma + extra, now, True)
                if c is None:
                    continue
                out[j.id] = c.alloc
                for vec, sign in ((gamma, 1), (free, -1), (extra, 1)):
                    self._add(vec, c.alloc, sign)
        return out
