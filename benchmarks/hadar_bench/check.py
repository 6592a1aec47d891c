"""The comparison that decides ``correct``: snapshots of consult inputs
and the program's answers, replayed through the plain reference."""
from __future__ import annotations

from typing import Dict, List, NamedTuple

import numpy as np

from .reference import JobRow, Params, Reference, Snapshot  # noqa: F401


class Checked(NamedTuple):
    snap: Snapshot
    answer: Dict[int, Dict]          # the program's allocations
    alpha: float                     # the program's Theorem 2 constant
    in_window: bool


def snapshot(now: float, full_pass: bool, view, jobs) -> Snapshot:
    """The consult's inputs as plain data (jobs that have arrived)."""
    nodes = tuple((n.node_id, tuple(n.gpus.items())) for n in view.nodes)
    rows = tuple(
        JobRow(j.job_id, float(j.arrival), int(j.n_workers),
               float(j.total_iters), float(j.done_iters),
               tuple(j.throughput.items()), bool(j.single_node),
               tuple(j.alloc.items()) if j.alloc else None)
        for j in jobs if j.arrival <= now)
    return Snapshot(float(now), bool(full_pass), nodes, rows)


def params(sched_cfg: dict) -> Params:
    return Params(float(sched_cfg["horizon_s"]),
                  int(sched_cfg["max_exact_dp"]),
                  bool(sched_cfg["work_conserving"]),
                  bool(sched_cfg["reallocate_on_free"]))


def freeze(out: Dict) -> Dict[int, Dict]:
    return {int(j): dict(a) for j, a in out.items()}


def differs(a: Dict[int, Dict], b: Dict[int, Dict]) -> int:
    """Jobs whose allocation differs (absent counts as differing)."""
    return sum(1 for j in set(a) | set(b) if a.get(j) != b.get(j))


def compare(items: List[Checked], prm: Params) -> Dict[str, float]:
    """Replay each sampled consult through the float64 reference: count
    consults and jobs whose allocations differ, and take the widest
    relative gap of the reported Theorem 2 constant."""
    ref = Reference(prm, np.float64)
    bad_consults = bad_jobs = bad_window = 0
    alpha_gap = 0.0
    for it in items:
        d = differs(ref.schedule(it.snap), it.answer)
        bad_consults += d > 0
        bad_window += d > 0 and it.in_window
        bad_jobs += d
        alpha_gap = max(alpha_gap,
                        abs(float(it.alpha) - float(ref.alpha))
                        / float(ref.alpha))
    return {"checked_consults": len(items),
            "checked_in_window": sum(it.in_window for it in items),
            "mismatched_consults": bad_consults,
            "mismatched_in_window": bad_window,
            "mismatched_jobs": bad_jobs,
            "alpha_rel_gap": alpha_gap}


def as_control(items: List[Checked], prm: Params,
               dtype=np.float32) -> List[Checked]:
    """The items with the program's answers replaced by the reference's
    computed in ``dtype``: the control."""
    ctl = Reference(prm, dtype)
    out = []
    for it in items:
        answer = ctl.schedule(it.snap)
        out.append(it._replace(answer=answer, alpha=float(ctl.alpha)))
    return out
