"""JIT-batched dual price solver: FIND_ALLOC for the whole queue in one
fused ``jax.jit``/``vmap`` call (Algorithm 2, lines 22-27, batched).

The per-job NumPy kernel in :mod:`repro.core.dp` prices one job per call;
this module evaluates the standalone candidates of *every* queued job
against one shared cluster state in a single device dispatch.  Shapes are
static — the job axis is padded to a power-of-two bucket so the number of
recompiles is bounded by ``log2(max queue)`` per cluster geometry.

Tensor axes (names used throughout), mapped to Algorithm 2:

==========  =============================================================
axis        meaning
==========  =============================================================
``B``       padded job bucket (queue axis; line 13's loop over the queue)
``M``       cluster *keys* — one per (node, gpu_type) pair, in
            ``PriceState.keys`` order (the ``h``/``r`` double loop)
``N``       node rows (line 24's "each server h")
``R``       global GPU types; per job, column ``k`` is the rank in the
            job's throughput-descending preference order (line 23's sort;
            ``rank == R`` marks a type the job cannot use)
``C``       marginal units per key, unit ``i`` = the (i+1)-th extra
            device (Eq. 5's gamma+i exponent)
==========  =============================================================

Per-job inputs are gathered on the key axis via ``rank[B, M]`` (each
job's preference rank of key m's type).  The pricing kernel computes,
batched, in int32 only:

- consolidated candidates (line 24): per-key availability scattered into
  (node, rank) layout, prefix sums over the rank axis, feasibility and
  packed take counts;
- spread candidates (lines 25-27): over the (key, unit) pool in the
  host's stable price/throughput sort order, each preference prefix's
  first W eligible units (their *positions* in that order), the slowest
  rank used, and the server count (the communication penalty's
  ``n_servers - 1`` term).

Decision fidelity: the device decides nothing with floats.  The
unit-price matrix ``P``, its prefix sums, the utility table ``u_tab``
(line 28's U_j), and every candidate's cost and payoff are computed on
the host from the kernel's integer outputs with the reference's own
NumPy operations and summation order.  Selection then replays the
reference enumeration order (per preference prefix: consolidated nodes
in node order, then the prefix's spread candidate; first maximum wins)
on those host-exact payoffs, so emitted ``Candidate``s are bit-identical
to ``repro.core.dp._find_alloc_arrays`` on any backend, including a TPU,
whose float64 is emulated and not IEEE.  Enforced against
``tests/_seed_reference.py`` by the engine-equivalence suite.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64

from repro import obs as _obs
from repro.core.utility import effective_throughput

# Default crossover points when no calibration file is present.  Queue
# sizes below the pricing threshold stay on the per-job NumPy path under
# solver="auto" (kernel dispatch overhead dominates tiny batches);
# solver="jax" forces the device path at any size.  The committed
# calibration JSON (recorded by ``benchmarks/check_speedup.py
# --calibrate`` on the target container) overrides these, and the
# ``REPRO_SOLVER_THRESHOLD`` env var overrides the pricing threshold on
# top of that.
AUTO_MIN_JOBS = 16              # pricing crossover fallback
COMMIT_MIN_JOBS = 96            # greedy-commit crossover fallback
_BUCKET_MIN = 8

ENV_THRESHOLD = "REPRO_SOLVER_THRESHOLD"
CALIBRATION_FILE = os.path.join(os.path.dirname(__file__),
                                "solver_calibration.json")

_KERNELS: Dict = {}
_COMMIT_KERNELS: Dict = {}
_calibration: Optional[Dict] = None


def load_calibration(path: Optional[str] = None,
                     refresh: bool = False) -> Dict:
    """The committed solver-crossover calibration, cached per process.

    Missing/unreadable file degrades to the module defaults — the
    calibration only moves dispatch thresholds, never decisions."""
    global _calibration
    if path is None and _calibration is not None and not refresh:
        return _calibration
    cal = {"auto_min_jobs": AUTO_MIN_JOBS,
           "commit_min_jobs": COMMIT_MIN_JOBS}
    try:
        with open(path or CALIBRATION_FILE, "r", encoding="utf-8") as fh:
            doc = json.load(fh)
        for k in ("auto_min_jobs", "commit_min_jobs"):
            if isinstance(doc.get(k), (int, float)) and doc[k] >= 1:
                cal[k] = int(doc[k])
    except (OSError, ValueError):
        pass
    if path is None:
        _calibration = cal
    return cal


def solver_threshold() -> int:
    """Pricing crossover: smallest queue the ``auto`` backend sends to
    the fused device kernel.  ``REPRO_SOLVER_THRESHOLD`` overrides the
    calibration JSON; a malformed value fails loudly."""
    raw = os.environ.get(ENV_THRESHOLD, "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            raise ValueError(
                f"{ENV_THRESHOLD}={raw!r} is not an integer")
    return load_calibration()["auto_min_jobs"]


def commit_threshold() -> int:
    """Greedy-commit crossover: smallest greedy queue the ``auto``
    backend routes through the wave/scan commit path."""
    return load_calibration()["commit_min_jobs"]


def to_device(arr: np.ndarray):
    """Upload a host array as a float64/int64 JAX buffer (x64 semantics,
    scoped — the rest of the repo keeps jax's default float32)."""
    with enable_x64():
        return jnp.asarray(arr)


def check_solver(solver: Optional[str]) -> str:
    """Validate a ``solver`` flag name without touching backend
    availability — the engines fail fast on typos at their entry point
    instead of deep inside the dual subroutine."""
    mode = solver or "auto"
    if mode not in ("jax", "numpy", "auto"):
        raise ValueError(f"unknown solver {solver!r} "
                         "(expected 'jax', 'numpy', or 'auto')")
    return mode


def resolve_solver(solver: Optional[str]) -> str:
    """Map a ``solver`` flag (None/'auto'/'jax'/'numpy') to the backend
    that will run: ``auto`` prefers jax."""
    mode = check_solver(solver)
    return "jax" if mode == "auto" else mode


def resolve_backend(solver: Optional[str], n_jobs: int) -> str:
    """The backend a queue of ``n_jobs`` actually runs on: applies the
    calibrated ``auto`` crossover (see :func:`solver_threshold`) on top
    of :func:`resolve_solver`.  Traces show the side of the crossover
    a dispatch landed on in the ``solver_dispatch`` span's args."""
    mode = check_solver(solver)
    if mode == "auto":
        return "jax" if n_jobs >= solver_threshold() else "numpy"
    return resolve_solver(mode)


def crossover(solver: Optional[str], commit: bool = False) -> Optional[int]:
    """The queue-size crossover ``solver`` applies — the pricing one, or
    with ``commit`` the greedy-commit one — or None where the flag
    forces a backend (the ``threshold`` arg of ``solver_dispatch``)."""
    if check_solver(solver) != "auto":
        return None
    return commit_threshold() if commit else solver_threshold()


def use_batch(solver: Optional[str], n_jobs: int) -> bool:
    """Should this call take the batched device path?  Purely a
    performance dispatch — both paths return bit-identical decisions."""
    return n_jobs > 0 and resolve_backend(solver, n_jobs) == "jax"


def use_commit(solver: Optional[str], n_jobs: int) -> bool:
    """Should ``dp_allocation``'s greedy pass take the device commit
    path (wave partitioner + ``lax.scan`` loop)?  The crossover is
    calibrated separately from the pricing threshold — the commit path
    amortizes differently (one scan dispatch vs J kernel replays)."""
    mode = check_solver(solver)
    if mode == "auto":
        return n_jobs >= commit_threshold()
    return resolve_solver(mode) == "jax" and n_jobs > 0


def bucket_size(n_jobs: int) -> int:
    """Pad the job axis to the next power of two (>= 8) so recompiles per
    cluster geometry are bounded by log2 of the largest queue."""
    b = _BUCKET_MIN
    while b < n_jobs:
        b *= 2
    return b


def _consolidated(avail, node_row, rank, W, Kj, N: int, R: int):
    """Consolidated slots of one job (line 24), in exact integers: the
    job's usable per-key availability scattered into (node, preference
    rank) layout — each cell has at most one key, so the scatter-add is
    a placement — then per node the feasibility, first feasible prefix,
    slowest rank used and packed take counts."""
    av_use = jnp.where(rank < Kj, avail, 0)
    A = jnp.zeros((N, R + 1), jnp.int32).at[node_row, rank].add(
        av_use)[:, :R]
    Apos = jnp.maximum(A, 0)
    rawcum = jnp.cumsum(A, axis=1)   # the reference's total_free per prefix
    poscum = jnp.cumsum(Apos, axis=1)
    feas_any = rawcum >= W
    feasible = feas_any.any(axis=1)
    k_first = jnp.argmax(feas_any, axis=1).astype(jnp.int32)
    take = jnp.clip(W - (poscum - Apos), 0, Apos)
    j_last = jnp.argmax(poscum >= W, axis=1).astype(jnp.int32)
    return feasible, k_first, j_last, take


# row-wise first position at which a running count reaches each target
_searchsorted_rows = jax.vmap(
    lambda c, t: jnp.searchsorted(c, t, side="left"), in_axes=(0, None))


def _spread(elig_units, s_rank, s_node, W, Kj, single, R: int,
            wmax: int):
    """Spread slots of one job (lines 25-27), in exact integers.  The
    pool is the (key, unit) table in the host's stable sort order, and
    prefix k chooses its first W units that are in ``elig_units`` and of
    a type ranked below k.  A chosen prefix holds at most ``W <= wmax``
    units, so their positions are extracted with ``searchsorted`` on the
    running eligibility count.  Returns per prefix the slot's liveness,
    the chosen positions ``(R, wmax)`` and which of them exist, the
    slowest rank used, and the number of distinct servers."""
    L = elig_units.shape[0]
    ks = jnp.arange(1, R + 1, dtype=jnp.int32)
    targets = jnp.arange(1, wmax + 1, dtype=jnp.int32)
    elig = elig_units[None, :] & (s_rank[None, :] < ks[:, None])
    csum = jnp.cumsum(elig.astype(jnp.int32), axis=1)
    n_elig = csum[:, -1]
    pos = jnp.minimum(_searchsorted_rows(csum, targets),
                      L - 1).astype(jnp.int32)
    valid = (targets[None, :] <= W) & (targets[None, :] <= n_elig[:, None])
    g_rank = jnp.take(s_rank, pos)
    g_node = jnp.take(s_node, pos)
    jmax = jnp.max(jnp.where(valid, g_rank, -1), axis=1)
    # a chosen unit adds a server iff no earlier chosen unit shares it
    earlier = jnp.arange(wmax)[None, :] < jnp.arange(wmax)[:, None]
    dup = jnp.any((g_node[:, :, None] == g_node[:, None, :])
                  & valid[:, None, :] & earlier[None], axis=2)
    nserv = jnp.sum(valid & jnp.logical_not(dup), axis=1, dtype=jnp.int32)
    ok = (n_elig >= W) & jnp.logical_not(single) & (ks <= Kj)
    return ok, pos, valid, jmax, nserv


def _spread_width(W: np.ndarray) -> int:
    """Static width of the compact spread gather: the largest gang,
    padded to a power of two (min 8) so recompiles stay bounded like
    :func:`bucket_size`."""
    return int(max(8, 1 << (int(W.max(initial=1.0)) - 1).bit_length()))


def _build_kernel(N: int, R: int, wmax: int):
    """The fused per-(cluster-geometry) pricing kernel: vmap over the
    job bucket, jitted once per (B, M, L) shape triple.  Integer inputs
    and outputs only (see the decision-fidelity note above); the pool's
    stable argsort arrives pre-computed from the host (NumPy's batched
    mergesort is the reference operation)."""

    def per_job(avail, node_row, W, Kj, rank, single, s_valid, s_rank,
                s_node):
        feasible, k_first, j_last, take = _consolidated(
            avail, node_row, rank, W, Kj, N, R)
        ok, pos, _, jmax, nserv = _spread(s_valid, s_rank, s_node, W, Kj,
                                          single, R, wmax)
        return feasible, k_first, j_last, take, ok, pos, jmax, nserv

    batched = jax.vmap(per_job, in_axes=(None, None) + (0,) * 7)

    def kernel(avail, node_row, *job_tables):
        # the cached state views are float64 / int64 host mirrors;
        # their values are small integers
        return batched(avail.astype(jnp.int32), node_row.astype(jnp.int32),
                       *job_tables)

    return jax.jit(kernel)


def _get_kernel(N: int, R: int, wmax: int):
    key = (N, R, wmax)
    if key not in _KERNELS:
        _ob = _obs.get()
        if _ob.enabled:       # process-global cache: 0 in warm processes
            _ob.count("jax_kernel_builds")
        _KERNELS[key] = _build_kernel(N, R, wmax)
    return _KERNELS[key]


@dataclasses.dataclass
class _JobTables:
    """Per-job host gather tables shared by the batch pricing kernel and
    the device commit scan (identical scalar math — Eq. 1b/line 23)."""

    W: np.ndarray          # (B,) gang sizes (float, integer-valued)
    single: np.ndarray     # (B,) single-node flag
    Kj: np.ndarray         # (B,) usable-type count
    pref: np.ndarray       # (B, R) preference order over global types
    x_sorted: np.ndarray   # (B, R) throughput per preference rank
    u_tab: np.ndarray      # (B, R) U_j per preference rank
    rank: np.ndarray       # (B, M) preference rank of each key's type
    usable: np.ndarray     # (B, M)
    x_key: np.ndarray      # (B, M) throughput per key (1.0 if unusable)


def _job_tables(jobs: List, ps, now: float, utility,
                B: int) -> _JobTables:
    """Build the per-job tables on the host with the exact per-job-path
    scalar operations (see the decision-fidelity note above); rows at or
    beyond ``len(jobs)`` are inert padding (W=0, Kj=0)."""
    gtypes = ps.cluster.gpu_types
    J = len(jobs)
    R = len(gtypes)
    W = np.zeros(B)
    W[:J] = [j.n_workers for j in jobs]
    single = np.ones(B, dtype=bool)       # padded rows: no spread
    single[:J] = [bool(j.single_node) for j in jobs]
    tp = np.zeros((B, R))
    tp[:J] = [[j.throughput.get(r, 0) for r in gtypes] for j in jobs]
    usable_t = tp > 0
    Kj = usable_t.sum(axis=1)
    # preference order: throughput descending, gpu_types-order tiebreak —
    # a stable argsort on -tp reproduces the reference's sorted() exactly
    pref = np.argsort(-tp, axis=1, kind="stable")       # (B, R)
    x_sorted = np.take_along_axis(tp, pref, axis=1)
    kk = np.arange(R)
    x_sorted = np.where(kk[None, :] < Kj[:, None], x_sorted, 0.0)
    rank_t = np.empty((B, R), dtype=np.int64)
    np.put_along_axis(rank_t, pref, np.broadcast_to(kk, (B, R)), axis=1)
    rank_t = np.where(usable_t, rank_t, R)              # R == unusable
    # U_j once per preference rank (Eq. 1b: payoff depends on the alloc
    # only through its bottleneck rate)
    rem = np.zeros(B)
    rem[:J] = [j.remaining_iters for j in jobs]
    arrival = np.zeros(B)
    arrival[:J] = [j.arrival for j in jobs]
    x_safe = np.where(kk[None, :] < Kj[:, None], x_sorted, 1.0)
    ct = np.maximum(now + rem[:, None] / (x_safe * np.maximum(W, 1.0)
                                          [:, None]) - arrival[:, None],
                    1e-9)
    if utility is effective_throughput:
        # the default utility vectorizes bitwise: total_iters / max(., .)
        tot = np.zeros(B)
        tot[:J] = [j.total_iters for j in jobs]
        u_tab = tot[:, None] / np.maximum(ct, 1e-9)
    else:
        u_tab = np.zeros((B, R))
        for ji, job in enumerate(jobs):
            for k in range(int(Kj[ji])):
                u_tab[ji, k] = utility(job, float(ct[ji, k]))
    u_tab = np.where(kk[None, :] < Kj[:, None], u_tab, 0.0)
    rank = rank_t[:, ps.type_col]                       # (B, M)
    usable = rank < Kj[:, None]
    x_key = np.where(
        usable,
        x_sorted[np.arange(B)[:, None], np.minimum(rank, R - 1)], 1.0)
    return _JobTables(W=W, single=single, Kj=Kj, pref=pref,
                      x_sorted=x_sorted, u_tab=u_tab, rank=rank,
                      usable=usable, x_key=x_key)


@dataclasses.dataclass
class BatchDetails:
    """Host-side solver state exported by ``find_alloc_batch`` for the
    conflict-free wave partitioner: the full candidate-payoff matrix in
    the reference enumeration layout, the winner decode, and the tables
    the payoff-gap bound is computed from.  All job-axis arrays are
    sliced to the live (unpadded) queue; every float is host-exact."""

    avail0: np.ndarray        # (M,) free units at solve time (copy)
    cumP: np.ndarray          # (M, C+1) Eq. 5 unit-price prefix sums
    u_tab: np.ndarray         # (J, R) utility per preference rank
    rank: np.ndarray          # (J, M) preference rank of each key's type
    usable: np.ndarray        # (J, M) rank < Kj
    Kj: np.ndarray            # (J,) usable-type count
    W: np.ndarray             # (J,) gang sizes
    single: np.ndarray        # (J,) single-node flag (no spread slots)
    feasible: np.ndarray      # (J, N) consolidated slot feasible
    k_first: np.ndarray       # (J, N) first feasible preference prefix-1
    packed_payoff: np.ndarray  # (J, N)
    sp_ok: np.ndarray         # (J, R) spread slot live
    sp_cost: np.ndarray       # (J, R) spread cost, comm penalty included
    sp_pay: np.ndarray        # (J, R)
    sp_jmax: np.ndarray       # (J, R) slowest rank used by spread slot
    sp_nserv: np.ndarray      # (J, R) servers spanned by spread slot
    sp_pos: np.ndarray        # (J, R, wmax) chosen positions in pool order
    s_m: np.ndarray           # (J, M*C) key of each pool position
    found: np.ndarray         # (J,) a best candidate exists
    win_pay: np.ndarray       # (J,) its payoff
    kb: np.ndarray            # (J,) its preference prefix-1
    slot: np.ndarray          # (J,) node row, or N for the spread slot
    node_row: np.ndarray      # (M,) key -> node row

    def spread_counts(self, r: int, k: int) -> np.ndarray:
        """(M,) units per key chosen by row ``r``'s spread prefix ``k``."""
        keys = self.s_m[r, self.sp_pos[r, k - 1, :int(self.W[r])]]
        return np.bincount(keys, minlength=self.avail0.shape[0])


def find_alloc_batch(jobs: List, avail: np.ndarray, gamma: np.ndarray,
                     ps, now: float, utility, force: bool = False,
                     avail_dev=None, details: bool = False):
    """Standalone FIND_ALLOC candidates for every job in ``jobs`` against
    one shared cluster state — the batched equivalent of calling
    ``repro.core.dp._find_alloc_arrays`` per job.

    ``avail_dev`` may carry a cached device buffer of ``avail`` (e.g.
    ``ps.device_view('free')``) to skip the host->device upload.
    Returns a list aligned with ``jobs``; entries are ``Candidate`` or
    ``None``, bit-identical to the per-job path.  With ``details=True``
    returns ``(results, BatchDetails)`` so the wave partitioner can run
    its safety test without re-pricing.
    """
    from repro.core.dp import COMM_COST_FRAC, Candidate

    J = len(jobs)
    if J == 0:
        return ([], None) if details else []

    gtypes = ps.cluster.gpu_types
    M = len(ps.keys)
    N = ps.n_node_rows
    R = len(gtypes)
    C = int(max(ps.cap_arr.max(initial=1.0), avail.max(initial=1.0), 1.0))

    _ob = _obs.get()
    with _ob.span("solver.tables") if _ob.enabled else _obs.NO_SPAN:
        # ---- per-job gather tables (host; identical scalar math) -----------
        B = bucket_size(J)
        jt = _job_tables(jobs, ps, now, utility, B)
        W, single, Kj, pref = jt.W, jt.single, jt.Kj, jt.pref
        x_sorted, u_tab = jt.x_sorted, jt.u_tab
        rank, usable, x_key = jt.rank, jt.usable, jt.x_key

        # ---- shared price tables (host NumPy: bitwise Eq. 5 prefixes) ------
        P = ps.unit_prices(np.asarray(gamma, dtype=float), C)
        cumP = np.zeros((M, C + 1))
        np.cumsum(P, axis=1, out=cumP[:, 1:])

        # ---- batched stable sort of the spread pool (host: NumPy's
        # mergesort is the reference op and beats XLA's CPU sort) -----------
        avf = np.asarray(avail, dtype=float)
        unit_ok = np.arange(C)[None, :] < avf[:, None]          # (M, C)
        valid = usable[:, :, None] & unit_ok[None, :, :]        # (B, M, C)
        ratio = np.where(valid, P[None, :, :] / x_key[:, :, None], np.inf)
        order = np.argsort(ratio.reshape(B, M * C), axis=-1, kind="stable")
        s_m = order // C                                        # pool key
        s_rank = np.take_along_axis(rank, s_m, axis=1)
        s_valid = np.take_along_axis(valid.reshape(B, -1), order, axis=-1)
        s_price = P.reshape(-1)[order]
        node_row = np.asarray(ps.node_row)
        wmax = _spread_width(W[:J])
        i32 = np.int32
        # the kernel's job tables, uploaded on every call
        host = (W.astype(i32), Kj.astype(i32), rank.astype(i32), single,
                s_valid, s_rank.astype(i32), node_row[s_m].astype(i32))
    with _ob.span("solver.device") if _ob.enabled else _obs.NO_SPAN:
        kern = _get_kernel(N, R, wmax)
        if _ob.enabled:
            _ob.count("solver_batch_calls")
            # one XLA compilation per distinct dispatch-shape tuple
            _ob.kernel_shape((N, R, wmax, B, M, C))
            _ob.count("solver.h2d_bytes", sum(a.nbytes for a in host) + (
                avf.nbytes if avail_dev is None else 0))
        with enable_x64():
            avail_d = avail_dev if avail_dev is not None \
                else jnp.asarray(avf)
            out = kern(avail_d, ps.device_view("node_row"),
                       *(jnp.asarray(a) for a in host))
        (feasible, k_first, j_last, take, sp_ok, sp_pos, sp_jmax,
         sp_nserv) = (np.asarray(o)[:J] for o in out)

    with _ob.span("solver.finish") if _ob.enabled else _obs.NO_SPAN:
        # ---- costs and payoffs: host-exact, in the reference's order -------
        # consolidated: per (node, rank) the key's Eq. 5 prefix at its take,
        # summed over the job's usable ranks like the reference's (N, K) sum
        Jr = np.arange(J)[:, None]
        rk, us, u_j = rank[:J], usable[:J], u_tab[:J]
        t_key = np.where(us, take[Jr, node_row, np.minimum(rk, R - 1)], 0)
        vs = np.zeros((J, N, R + 1))            # column R: unusable keys
        vs[Jr, node_row, rk] = np.where(us, cumP[np.arange(M), t_key], 0.0)
        packed_cost = np.zeros((J, N))
        for kj in np.unique(Kj[:J]):
            rows = np.nonzero(Kj[:J] == kj)[0]
            packed_cost[rows] = np.ascontiguousarray(
                vs[rows, :, :int(kj)]).sum(axis=-1)
        packed_payoff = u_j[Jr, j_last] - packed_cost
        # spread: the chosen units' prices summed in pool order, one gang
        # size at a time so each row sums exactly like the reference's 1-D
        # np.sum over W elements, plus the communication penalty
        sp_cost = np.zeros((J, R))
        Wj = W[:J].astype(np.intp)
        for w in np.unique(Wj):
            rows = np.nonzero(Wj == w)[0]
            sp_cost[rows] = s_price[rows[:, None, None],
                                    sp_pos[rows, :, :w]].sum(axis=-1)
        u_jmax = u_j[Jr, np.maximum(sp_jmax, 0)]
        sp_cost = np.where(sp_nserv > 1,
                           sp_cost + COMM_COST_FRAC * np.maximum(u_jmax, 0.0)
                           * (sp_nserv - 1), sp_cost)
        sp_pay = u_jmax - sp_cost

        # ---- winner selection in the reference enumeration order -----------
        # flat candidate axis, per job: for each preference prefix k=1..R,
        # the N consolidated node slots (a node is live under its *first*
        # feasible prefix only), then the prefix's spread slot; np.argmax's
        # first-maximum matches the reference's strict-> scan.
        pay = np.full((J, R * (N + 1)), -np.inf)
        for k in range(1, R + 1):
            base = (k - 1) * (N + 1)
            live = feasible & (k_first == k - 1)
            pay[:, base:base + N] = np.where(live, packed_payoff, -np.inf)
            pay[:, base + N] = np.where(sp_ok[:, k - 1], sp_pay[:, k - 1],
                                        -np.inf)
        pay[Kj[:J] == 0] = -np.inf
        win = np.argmax(pay, axis=1)
        win_pay = pay[np.arange(J), win]

        # ---- winner materialization -----------------------------------------
        found = win_pay > -np.inf
        kb, slot = np.divmod(win, N + 1)
        results: List = [None] * J
        node_ids = [n.node_id for n in ps.cluster.nodes]

        if _ob.decisions is not None:
            # runner-up provenance (repro.obs.explain): masked second argmax
            # over the same candidate axis — matches the per-job path's
            # second-best tracking, including first-maximum tie handling
            pay2 = pay.copy()
            pay2[np.arange(J), win] = -np.inf
            win2 = np.argmax(pay2, axis=1)
            win2_pay = pay2[np.arange(J), win2]
            k2, slot2 = np.divmod(win2, N + 1)

            def _ru_of(j: int) -> Optional[dict]:
                if not win2_pay[j] > -np.inf:
                    return None
                s2 = int(slot2[j])
                if s2 < N:
                    return {"kind": "pack", "node": node_ids[s2],
                            "payoff": float(win2_pay[j])}
                kp = int(k2[j]) + 1
                return {"kind": "spread", "prefix": kp,
                        "n_servers": int(sp_nserv[j, kp - 1]),
                        "payoff": float(win2_pay[j])}
        else:
            def _ru_of(j: int) -> Optional[dict]:
                return None

        for j in np.nonzero(found)[0].tolist():
            h, k = int(slot[j]), int(kb[j]) + 1
            if h < N:
                payoff, cost = packed_payoff[j, h], packed_cost[j, h]
                rate = x_sorted[j, j_last[j, h]]
            else:
                payoff, cost = sp_pay[j, k - 1], sp_cost[j, k - 1]
                rate = x_sorted[j, sp_jmax[j, k - 1]]
            if payoff <= 0 and not force:        # mu_j <= 0 (lines 29-33)
                continue
            if h < N:
                tk = take[j, h]
                alloc = {(node_ids[h], gtypes[pref[j, kk]]): int(tk[kk])
                         for kk in range(int(Kj[j])) if tk[kk] > 0}
            else:
                counts = np.bincount(s_m[j, sp_pos[j, k - 1, :Wj[j]]],
                                     minlength=M)
                alloc = {ps.keys[m]: int(counts[m])
                         for m in np.nonzero(counts)[0]}
            results[j] = Candidate(alloc, float(cost), float(payoff),
                                   float(rate), runner_up=_ru_of(j))
        from repro.analysis import invariants as _inv
        if _inv.sanitize_enabled():
            for job, cand in zip(jobs, results):
                if cand is not None:
                    _inv.check_candidate(job.job_id, job.n_workers,
                                         cand.alloc, cand.payoff, cand.cost,
                                         forced=force,
                                         context="(find_alloc_batch)")
        if details:
            det = BatchDetails(
                avail0=avf.copy(), cumP=cumP, u_tab=u_j, rank=rk, usable=us,
                Kj=Kj[:J], W=Wj, single=single[:J], feasible=feasible,
                k_first=k_first, packed_payoff=packed_payoff, sp_ok=sp_ok,
                sp_cost=sp_cost, sp_pay=sp_pay, sp_jmax=sp_jmax,
                sp_nserv=sp_nserv, sp_pos=sp_pos, s_m=s_m[:J], found=found,
                win_pay=win_pay, kb=kb, slot=slot, node_row=node_row)
            return results, det
        return results


# --------------------------------------------------------------------------
# Conflict-free wave partitioner (greedy commit without host round-trips)
# --------------------------------------------------------------------------
#
# The sequential oracle re-solves FIND_ALLOC per job at the accumulated
# state.  A wave accepts a prefix of the commit order for which that
# re-solve provably returns the already-known standalone winner:
#
# - *winner invariance*: the winner's own slot sees none of the keys
#   committed so far in the wave (a consolidated slot sees its node's
#   usable keys; a spread slot at prefix k sees every usable key of
#   rank < k), so its take/cost/payoff/position are all bitwise
#   unchanged.  A corollary: accepted winners' key sets are pairwise
#   disjoint, so the wave delta never stacks counts on one key.
# - *payoff-gap bound* on every affected competitor slot: committing v_m
#   units on key m removes its v_m cheapest units (Eq. 5 prices increase
#   with gamma), which can only shift a competitor onto *cheaper* less-
#   preferred keys — raising its payoff by at most ``topv(m)``, the
#   price of m's v_m most expensive free units (cumP differences).  The
#   bound needs the utility non-increasing along the preference order
#   (true for effective_throughput; checked per job, else the wave
#   breaks).  Affected slots must stay strictly below the winner with a
#   relative margin, so last-ulp float slack can never flip a decision;
#   payoff ties against the runner-up therefore reject the prefix.
# - feasibility/eligibility only shrink when availability shrinks, so
#   slots dead at wave start stay dead, and a job whose standalone
#   re-solve was rejected (mu_j <= 0) stays rejected iff no affected
#   slot's bound can cross the admission gate.

_WAVE_EPS = 1e-9         # relative strictness margin on payoff bounds
_WAVE_MIN_RESCAN = 8     # waves consuming fewer jobs stall -> scan


def _spread_bound(det: BatchDetails, r: int, k: int, T: np.ndarray,
                  tv: np.ndarray, d: float, comm_frac: float) -> float:
    """Upper bound on spread slot ``k``'s payoff after the wave delta.

    The slot's raw unit cost (comm term stripped) can drop by at most
    ``d`` (the topv sum over touched keys in its pool), and its utility
    can rise at most to the slowest rank still guaranteed in the chosen
    set (committed units evict a key's cheapest units first, so a key's
    surviving chosen count is ``count - v_m``)."""
    jmax = int(det.sp_jmax[r, k - 1])
    nserv = int(det.sp_nserv[r, k - 1])
    u_jmax = float(det.u_tab[r, jmax])
    comm = comm_frac * max(u_jmax, 0.0) * (nserv - 1) if nserv > 1 \
        else 0.0
    unit_cost = float(det.sp_cost[r, k - 1]) - comm
    counts = det.spread_counts(r, k)
    kept = counts - np.where(T, np.minimum(counts, tv), 0)
    mk = np.nonzero(kept > 0)[0]
    r_keep = int(det.rank[r, mk].max()) if mk.size else 0
    return float(det.u_tab[r, r_keep]) - (unit_cost - d)


def _wave_safe(det: BatchDetails, r: int, T: np.ndarray, tv: np.ndarray,
               a0: np.ndarray, comm_frac: float,
               has_winner: bool) -> bool:
    """Is row ``r``'s standalone outcome (its winner, or its rejection
    when ``has_winner`` is False) provably unchanged by the wave delta
    ``tv`` on touched keys ``T``?"""
    kj = int(det.Kj[r])
    if kj == 0:
        return True                       # no usable type: None forever
    u_row = det.u_tab[r, :kj]
    if kj > 1 and np.any(np.diff(u_row) > 0):
        return False                      # exotic utility: exact re-solve
    ms = np.nonzero(T)[0]
    rank_r = det.rank[r]
    N = det.packed_payoff.shape[1]
    if has_winner:
        slot = int(det.slot[r])
        k_win = int(det.kb[r]) + 1
        win_is_pack = slot < N
        if win_is_pack:
            if np.any(det.node_row[ms] == slot):
                return False              # winner's node was touched
        elif np.any(rank_r[ms] < k_win):
            return False                  # winner's spread pool touched
        win_pay = float(det.win_pay[r])
        bar = win_pay - _WAVE_EPS * max(1.0, abs(win_pay))
    else:
        slot = -1
        k_win = 0
        win_is_pack = False
        bar = 0.0                         # the mu_j admission gate

    # topv(m): price of key m's tv[m] most expensive free units — the
    # largest amount a competitor's cost can drop by re-sourcing the
    # displaced demand (cumP rows are host-exact Eq. 5 prefixes)
    topv = det.cumP[ms, a0[ms]] - det.cumP[ms, a0[ms] - tv[ms]]
    node_ms = det.node_row[ms]
    for h in np.unique(node_ms):
        if win_is_pack and h == slot:
            continue
        if not det.feasible[r, h]:
            continue                      # availability only shrinks
        bound = float(det.packed_payoff[r, h]) + float(
            topv[node_ms == h].sum())
        if not bound < bar - _WAVE_EPS * max(0.0, abs(bound) - 1.0):
            return False
    if not det.single[r]:
        rmin = int(rank_r[ms].min())
        for k in range(rmin + 1, kj + 1):
            if not win_is_pack and has_winner and k == k_win:
                continue
            if not det.sp_ok[r, k - 1]:
                continue                  # eligibility only shrinks
            d = float(topv[rank_r[ms] < k].sum())
            bound = _spread_bound(det, r, k, T, tv, d, comm_frac)
            if not bound < bar - _WAVE_EPS * max(0.0, abs(bound) - 1.0):
                return False
    return True


def _wave_accepts(det: BatchDetails, cands: List, rows: List[int],
                  key_index: Dict) -> Tuple[List, int, np.ndarray]:
    """Walk ``rows`` (det-row indices in commit order) accepting jobs
    while the wave-safety test holds.  Returns ``(accepted, consumed,
    delta)``: the accepted ``(row, Candidate)`` pairs, how many leading
    rows were consumed (accepts + provably-still-rejected skips), and
    the aggregated per-key commit counts of the wave."""
    from repro.core.dp import COMM_COST_FRAC

    M = det.avail0.shape[0]
    touched = np.zeros(M, dtype=bool)
    tv = np.zeros(M, dtype=np.int64)
    a0 = det.avail0.astype(np.int64)
    accepted: List = []
    consumed = 0
    for r in rows:
        c = cands[r]
        T = touched & det.usable[r]
        if T.any() and not _wave_safe(det, r, T, tv, a0, COMM_COST_FRAC,
                                      has_winner=c is not None):
            break
        consumed += 1
        if c is None:
            continue
        accepted.append((r, c))
        for key, v in c.alloc.items():
            m = key_index[key]
            touched[m] = True
            tv[m] += v
    return accepted, consumed, tv


# --------------------------------------------------------------------------
# Device-side commit loop: lax.scan over the conflicting remainder
# --------------------------------------------------------------------------

# Bound on |device payoff - host-exact payoff| relative to the magnitudes
# it is computed from.  Orders of magnitude above float64 rounding and
# above the TPU's float32-pair emulation of float64 (about 2^-47).
_SCAN_TOL = 1e-11


def _build_commit_kernel(N: int, R: int, comm_frac: float, wmax: int):
    """One fused ``lax.scan`` running the sequential greedy commit on
    device: each step is a full FIND_ALLOC at the carried state, and the
    winner's take is committed into the ``(free, gamma)`` carry before
    the next step — no host round-trip between conflicting winners.

    Feasibility, takes and the spread choice are exact integers, shared
    with the pricing kernel (:func:`_consolidated`, :func:`_spread`).
    The spread pool needs *no in-scan sort*: the reference's stable
    argsort key is ``(price/throughput, m*c + i)``, each key's ratio
    sequence is non-decreasing in the absolute unit index ``u`` (Eq. 5,
    q >= 1), and the flat-index tie-break across keys depends only on
    the key index (``i < c`` makes ``m`` the dominant digit) — so one
    gamma-independent total order over the whole (key, unit) *table*,
    computed per job with the host's stable mergesort, is the pool order
    at *every* scan step.  A step only applies the current validity
    window ``gamma_m <= u < gamma_m + free_m`` as a mask in that order.

    Selection needs payoffs, which the device computes in float64 from
    the host-exact Eq. 5 table ``P_tab[m, u] = umin (umax/umin)^(u/cap)``
    — approximately: accumulation orders differ from NumPy's, and a TPU
    emulates float64.  So every step also reports whether its decision
    is *certain* to be the reference's.  With each payoff known to
    within ``_SCAN_TOL`` of its magnitude, the contenders are the slots
    whose interval reaches the best one's.  The step is certain when the
    mu_j gate clears the same way at both ends of the winner's interval
    and all contenders provably have bitwise-equal reference payoffs, so
    that the first contender in enumeration order is the reference's
    first maximum: consolidated slots whose nodes carry the same
    (Eq. 5 price row, gamma, take) on every preference rank and the same
    slowest rank, or a spread slot that takes exactly one such slot's
    units on a single key (fewer than 8 of them, where ``np.sum`` is the
    same sequential sum as ``np.cumsum``) — that one is shadowed by the
    consolidated slot, which comes first.  The host accepts the steps
    before the first uncertain one (:func:`_scan_commit`).

    The init carry buffers are donated (fresh uploads, never reused on
    the host), killing the copy overhead per dispatch."""

    def scan_fn(free0, gamma0, P_tab, node_row, prow, Wi, Kj, single,
                rank, u_tab, s_m, s_u, s_rank, s_price, s_node):
        M, C = P_tab.shape
        node_row = node_row.astype(jnp.int32)
        rows = jnp.arange(R)[:, None]

        def step(carry, xs):
            free, gamma = carry
            wi, kj, sing, rk, ut, smj, suj, srkj, sprj, sndj = xs
            feasible, k_first, j_last, take = _consolidated(
                free, node_row, rk, wi, kj, N, R)
            take_pad = jnp.concatenate(
                [take, jnp.zeros((N, 1), jnp.int32)], axis=1)
            t_key = take_pad[node_row, rk]      # 0 on unusable keys

            # ---- consolidated payoffs: sequential unit accumulation
            # over the P_tab gathers (masked lanes clip and add 0)
            def unit_add(i, acc):
                col = jnp.minimum(gamma + i, C - 1)
                p = jnp.take_along_axis(P_tab, col[:, None],
                                        axis=1)[:, 0]
                return acc + jnp.where(i < t_key, p, 0.0)
            vkey = jax.lax.fori_loop(
                0, C, unit_add, jnp.zeros((M,), P_tab.dtype))
            packed_cost = jnp.zeros((N,), P_tab.dtype).at[node_row].add(
                vkey)
            packed_u = ut[j_last]
            packed_pay = packed_u - packed_cost

            # ---- spread payoffs: fixed pool order + validity window ----
            win_lo = jnp.take(gamma, smj)
            in_window = (suj >= win_lo) \
                & (suj - win_lo < jnp.take(free, smj))
            sp_ok, pos, valid, jmax, sp_nserv = _spread(
                in_window, srkj, sndj, wi, kj, sing, R, wmax)
            g_m = jnp.take(smj, pos)
            sp_cost = jnp.sum(jnp.where(valid, jnp.take(sprj, pos), 0.0),
                              axis=1)
            u_jmax = jnp.take(ut, jnp.maximum(jmax, 0))
            sp_cost = sp_cost + jnp.where(
                sp_nserv > 1,
                comm_frac * jnp.maximum(u_jmax, 0.0) * (sp_nserv - 1),
                0.0)
            sp_pay = u_jmax - sp_cost
            m0 = g_m[:, 0]
            shadow = jnp.all(jnp.logical_not(valid) | (g_m == m0[:, None]),
                             axis=1) \
                & (wi < 8) & jnp.take(feasible, jnp.take(node_row, m0)) \
                & (jnp.take(t_key, m0) == wi)

            # ---- selection: reference enumeration order ---------------
            live = feasible[None, :] & (k_first[None, :] == rows)
            pay = jnp.concatenate(
                [jnp.where(live, packed_pay[None, :], -jnp.inf),
                 jnp.where(sp_ok & jnp.logical_not(shadow), sp_pay,
                           -jnp.inf)[:, None]], axis=1).reshape(-1)
            pay = jnp.where(kj > 0, pay, -jnp.inf)
            err = _SCAN_TOL * jnp.concatenate(
                [jnp.broadcast_to(jnp.abs(packed_u) + packed_cost, (R, N)),
                 (jnp.abs(u_jmax) + sp_cost)[:, None]], axis=1).reshape(-1)
            is_live = pay > -jnp.inf
            best = jnp.argmax(pay)
            contender = is_live & (pay + err >= pay[best] - err[best])
            win = jnp.argmax(contender).astype(jnp.int32)
            slot = win % (N + 1)
            k_sel = win // (N + 1)
            # consolidated slots with the winner's (Eq. 5 price row,
            # gamma, take) on every rank and its slowest rank
            sig = jnp.full((N, R + 1, 3), -1, jnp.int32).at[
                node_row, rk].set(jnp.stack([prow, gamma, t_key], 1))[:, :R]
            h_win = jnp.minimum(slot, N - 1)
            same = jnp.all(sig == sig[h_win], axis=(1, 2)) \
                & (j_last == j_last[h_win]) & (slot < N)
            # spread slots choosing the winner's very units
            chosen = jnp.where(valid, pos, -1)
            same_sp = jnp.all(chosen == chosen[k_sel], axis=1) & (slot == N)
            in_class = jnp.concatenate(
                [jnp.broadcast_to(same, (R, N)), same_sp[:, None]],
                axis=1).reshape(-1)
            won = pay[win] > 0.0                   # mu_j gate
            sure = jnp.logical_not(is_live.any()) | (
                jnp.all(in_class | jnp.logical_not(contender))
                & ((pay[win] - err[win] > 0.0)
                   | (jnp.max(pay + err) <= 0.0)))

            # spread counts only materialize for the winning prefix:
            # one wmax-sized integer scatter (duplicate keys add)
            sp_cnt_win = jnp.zeros((M,), jnp.int32).at[g_m[k_sel]].add(
                valid[k_sel].astype(jnp.int32))
            counts = jnp.where(
                won,
                jnp.where(slot < N,
                          jnp.where(node_row == slot, t_key, 0),
                          sp_cnt_win),
                jnp.zeros((M,), jnp.int32))
            pay2 = pay.at[win].set(-jnp.inf)
            win2 = jnp.argmax(pay2)
            outs = (won, win, counts, win2.astype(jnp.int32), pay2[win2],
                    sp_nserv, sure)
            return (free - counts, gamma + counts), outs

        (free_f, gamma_f), ys = jax.lax.scan(
            step, (free0, gamma0), (Wi, Kj, single, rank, u_tab,
                                    s_m, s_u, s_rank, s_price, s_node))
        return (free_f, gamma_f) + ys

    return jax.jit(scan_fn, donate_argnums=(0, 1))


def _get_commit_kernel(N: int, R: int, comm_frac: float, wmax: int):
    key = (N, R, comm_frac, wmax)
    if key not in _COMMIT_KERNELS:
        _ob = _obs.get()
        if _ob.enabled:
            _ob.count("jax_kernel_builds")
        _COMMIT_KERNELS[key] = _build_commit_kernel(N, R, comm_frac,
                                                    wmax)
    return _COMMIT_KERNELS[key]


def _scan_commit(jobs: List, avail: np.ndarray, gamma: np.ndarray,
                 ps, now: float, utility) -> Dict:
    """Run the sequential greedy commit over ``jobs`` (already in commit
    order) through the device scan; mutates ``avail``/``gamma`` in place
    and returns ``{job_id: Candidate}`` for the winners.  A step the
    device cannot certify (see :func:`_build_commit_kernel`) ends the
    accepted prefix: the host re-solves that job with the reference
    FIND_ALLOC at the accumulated state, and the scan resumes after it."""
    from repro.core.dp import _find_alloc_arrays

    _ob = _obs.get()
    results: Dict = {}
    start = 0
    while start < len(jobs):
        start += _scan_prefix(jobs[start:], avail, gamma, ps, now,
                              utility, results)
        if start == len(jobs):
            break
        if _ob.enabled:
            _ob.count("solver.scan_host_steps")
        job = jobs[start]
        cand = _find_alloc_arrays(job, avail, gamma, ps, now, utility,
                                  force=False)
        if cand:
            results[job.job_id] = cand
            for key, v in cand.alloc.items():
                m = ps.key_index[key]
                avail[m] -= v
                gamma[m] += v
        start += 1
    return results


def _scan_prefix(jobs: List, avail: np.ndarray, gamma: np.ndarray,
                 ps, now: float, utility, results: Dict) -> int:
    """One scan dispatch over ``jobs``: commits the steps before the
    first uncertain one into ``avail``/``gamma``/``results`` and returns
    how many that was.  Winner cost/payoff/rate are re-derived
    host-exact from the per-step counts and the accumulated gamma."""
    from repro.core.dp import COMM_COST_FRAC, Candidate

    J = len(jobs)
    M = len(ps.keys)
    N = ps.n_node_rows
    R = len(ps.cluster.gpu_types)
    # price-table depth: unit indices reach gamma + free - 1, and the
    # per-key sum gamma_m + free_m is invariant across the scan (commits
    # move units from free to gamma).  gamma may legitimately exceed
    # cap - free (externally replayed occupancy), so size on both.
    depth = (np.asarray(gamma, dtype=float)
             + np.asarray(avail, dtype=float)).max(initial=1.0)
    C = int(max(ps.cap_arr.max(initial=1.0), depth, 1.0))
    _ob = _obs.get()
    with _ob.span("solver.tables") if _ob.enabled else _obs.NO_SPAN:
        B = bucket_size(J)
        jt = _job_tables(jobs, ps, now, utility, B)
        # Eq. 5 gather table: gamma is integer-valued on the greedy path and
        # every *used* unit index satisfies gamma + i < cap, so P_tab rows
        # are bitwise the reference's unit_prices(gamma) at every scan step;
        # keys with equal rows price identically at equal gamma
        P_tab = ps.unit_prices(np.zeros(M), C)
        prow = np.unique(P_tab, axis=0, return_inverse=True)[1].reshape(-1)
        node_row = np.asarray(ps.node_row)

        # fixed per-job spread-pool order over the whole (key, unit) table
        # (gamma-independent — see the kernel docstring): NumPy's stable
        # mergesort is the reference sort, computed once per scan
        L = M * C
        ratio_tab = np.where(jt.usable[:, :, None],
                             P_tab[None, :, :] / jt.x_key[:, :, None],
                             np.inf)
        order = np.argsort(ratio_tab.reshape(B, L), axis=-1, kind="stable")
        s_m = (order // C).astype(np.int32)
        s_u = (order % C).astype(np.int32)
        s_rank = np.take_along_axis(jt.rank, s_m, axis=1).astype(np.int32)
        s_price = P_tab.reshape(-1)[order]
        s_node = node_row[s_m].astype(np.int32)

        wmax = _spread_width(jt.W[:J])
        i32 = np.int32
        # the scan's operands but the cached node_row view, uploaded on
        # every call in argument order around it
        carry = (np.asarray(avail).astype(i32), np.asarray(gamma).astype(i32))
        head = (P_tab,)
        tail = (prow.astype(i32), jt.W.astype(i32), jt.Kj.astype(i32),
                jt.single, jt.rank.astype(i32), jt.u_tab, s_m, s_u, s_rank,
                s_price, s_node)
    with _ob.span("solver.device") if _ob.enabled else _obs.NO_SPAN:
        kern = _get_commit_kernel(N, R, COMM_COST_FRAC, wmax)
        if _ob.enabled:
            _ob.count("solver_scan_calls")
            _ob.observe("solver.scan_jobs", J)
            # one XLA compile per distinct (geometry, carry/xs shape) tuple
            _ob.kernel_shape(("commit_scan", N, R, COMM_COST_FRAC, B, M, C,
                              wmax))
            _ob.count("solver.h2d_bytes",
                      sum(a.nbytes for a in carry + head + tail))
        with enable_x64():
            # fresh uploads: the kernel donates the carry buffers
            out = kern(*(jnp.asarray(a) for a in carry + head),
                       ps.device_view("node_row"),
                       *(jnp.asarray(a) for a in tail))
        (free_f, gamma_f, won, win, counts, win2, win2_pay, sp_nserv,
         sure) = map(np.asarray, out)
    with _ob.span("solver.finish") if _ob.enabled else _obs.NO_SPAN:
        n_ok = int(np.argmin(sure[:J])) if not sure[:J].all() else J

        node_ids = [n.node_id for n in ps.cluster.nodes]
        gam_run = np.asarray(gamma, dtype=np.int64).copy()
        want_ru = _ob.decisions is not None
        for p in range(n_ok):
            if not won[p]:
                continue
            cnts = counts[p]
            ms = np.nonzero(cnts)[0]
            kbp, slotp = divmod(int(win[p]), N + 1)
            ru = None
            if want_ru and win2_pay[p] > -np.inf:
                k2, s2 = divmod(int(win2[p]), N + 1)
                if s2 < N:
                    ru = {"kind": "pack", "node": node_ids[s2],
                          "payoff": float(win2_pay[p])}
                else:
                    ru = {"kind": "spread", "prefix": k2 + 1,
                          "n_servers": int(sp_nserv[p, k2]),
                          "payoff": float(win2_pay[p])}
            jl = int(jt.rank[p, ms].max())      # slowest rank actually used
            if slotp < N:
                # consolidated: cost = sum over preference ranks of the
                # key's sequential unit-price prefix (np.cumsum order);
                # ps.keys[m] is the reference's (node_id, gpu_type) tuple
                cost = 0.0
                alloc = {}
                for m in ms[np.argsort(jt.rank[p, ms], kind="stable")]:
                    g = int(gam_run[m])
                    cnt = int(cnts[m])
                    cost += float(np.cumsum(P_tab[m, g:g + cnt])[-1])
                    alloc[ps.keys[m]] = cnt
            else:
                unit_m = np.repeat(ms, cnts[ms])
                unit_i = np.concatenate([np.arange(cnts[m]) for m in ms])
                prices = P_tab[unit_m, gam_run[unit_m] + unit_i]
                # reference summation order == stable sort of the chosen
                # units by (ratio, flat index)
                o = np.lexsort((unit_m * C + unit_i,
                                prices / jt.x_key[p, unit_m]))
                cost = float(prices[o].sum())
                nserv = int(np.unique(node_row[ms]).size)
                if nserv > 1:
                    cost += COMM_COST_FRAC * max(jt.u_tab[p, jl], 0.0) \
                        * (nserv - 1)
                alloc = {ps.keys[m]: int(cnts[m]) for m in ms}
            payoff = float(jt.u_tab[p, jl] - cost)
            results[jobs[p].job_id] = Candidate(alloc, float(cost), payoff,
                                                float(jt.x_sorted[p, jl]),
                                                runner_up=ru)
            gam_run[ms] += cnts[ms]

        total = counts[:n_ok].sum(axis=0)
        avail -= total
        gamma += total
        from repro.analysis import invariants as _inv
        if _inv.sanitize_enabled():
            # the donated-carry outputs must agree with the host accounting
            # when every step was accepted (all quantities are integers)
            if n_ok == J and not np.array_equal(
                    free_f.astype(float), np.asarray(avail, dtype=float)):
                _inv.violate("conservation",
                             "scan carry free_arr diverged from host delta",
                             max_err=float(np.abs(free_f
                                                  - np.asarray(avail)).max()))
            for job in jobs[:n_ok]:
                cand = results.get(job.job_id)
                if cand is not None:
                    _inv.check_candidate(job.job_id, job.n_workers,
                                         cand.alloc, cand.payoff, cand.cost,
                                         context="(scan_commit)")
        return n_ok


def _dispatch(ob, jobs: List, avail: np.ndarray, gamma: np.ndarray, ps,
              now: float, utility, avail_dev, threshold: Optional[int]):
    """``find_alloc_batch`` with details, in a ``solver_dispatch``
    span."""
    with (ob.span("solver_dispatch", backend="jax", n_jobs=len(jobs),
                  threshold=threshold, bucket=bucket_size(len(jobs)))
          if ob.enabled else _obs.NO_SPAN) as sp:
        cands, det = find_alloc_batch(jobs, avail, gamma, ps, now, utility,
                                      avail_dev=avail_dev, details=True)
        if ob.enabled:
            sp.set(candidates=sum(1 for c in cands if c is not None))
    return cands, det


def commit_greedy(queue: List, avail: np.ndarray, gamma: np.ndarray,
                  ps, now: float, utility, avail_dev=None,
                  threshold: Optional[int] = None) -> Dict:
    """The greedy pass of ``dp_allocation`` without per-job host
    round-trips: one fused pricing dispatch ranks all standalone
    winners, conflict-free waves commit in aggregated deltas, and the
    conflicting remainder runs through the device-side scan.  Mutates
    ``avail``/``gamma`` in place and returns ``{job_id: Candidate}``
    bit-identical to the sequential NumPy loop (the equivalence
    oracle kept verbatim in ``repro.core.dp``).  ``threshold`` is the
    crossover that routed the queue here, for the trace only."""
    _ob = _obs.get()
    cands, det = _dispatch(_ob, queue, avail, gamma, ps, now, utility,
                           avail_dev, threshold)
    # payoff *density* order (per requested device), ties in queue order
    # — identical to the sequential loop's sort
    dens = [(c.payoff / max(1, j.n_workers), i)
            for i, (j, c) in enumerate(zip(queue, cands)) if c]
    dens.sort(key=lambda t: -t[0])
    rows = [i for _, i in dens]
    chosen: Dict = {}
    cur_jobs = queue
    key_index = ps.key_index
    while rows:
        with _ob.span("solver.waves") if _ob.enabled else _obs.NO_SPAN:
            accepted, consumed, tv = _wave_accepts(det, cands, rows,
                                                   key_index)
        if _ob.enabled:
            _ob.count("solver.commit_waves")
            _ob.observe("solver.wave_size", consumed)
        for r, c in accepted:
            chosen[cur_jobs[r].job_id] = c
        if tv.any():
            avail -= tv.astype(avail.dtype)
            gamma += tv.astype(gamma.dtype)
        rows = rows[consumed:]
        if not rows:
            break
        rest = [cur_jobs[r] for r in rows]
        if consumed < _WAVE_MIN_RESCAN:
            # the wave stalled on conflicts: finish the remainder in one
            # fused device scan (sequential re-pricing stays on device)
            chosen.update(_scan_commit(rest, avail, gamma, ps, now,
                                       utility))
            break
        cands, det = _dispatch(_ob, rest, avail, gamma, ps, now, utility,
                               None, threshold)
        cur_jobs = rest
        rows = list(range(len(rest)))
    return chosen
