"""The timed replay: the cell's deployment stepped by the program's own
engine, every consult timed, the window opened after the set-up
consults and closed at the first consult that ends after ``seconds``."""
from __future__ import annotations

import math
import time
from typing import Callable, List, Optional

import numpy as np

from repro import obs
from repro.core.hadar import HadarScheduler

from .check import Checked, freeze, snapshot
from .traffic import derive_seed


class WindowClosed(Exception):
    """Raised from a consult to stop the engine once the window closes."""


class Recorder:
    """Consult bookkeeping shared by both engines: set-up/window split,
    per-consult times, sampled snapshots for the reference, per-consult
    solver counters and profiler annotations in a traced run."""

    COUNTERS = ("solver_batch_calls", "solver_scan_calls")

    def __init__(self, setup_consults: int, seconds: float, seed: int,
                 check: dict, traced: bool,
                 on_window_start: Callable[[], None],
                 on_window_end: Callable[[], None],
                 trace_s: Optional[float] = None,
                 on_trace_end: Callable[[], None] = lambda: None):
        self.setup_consults = setup_consults
        self.seconds = seconds
        # the profiler traces the first ``trace_s`` seconds of the window:
        # the device's trace buffer holds a bounded number of events
        self.trace_s = seconds if trace_s is None else min(trace_s, seconds)
        self._trace_end = on_trace_end
        self.tracing = False
        self.rng = np.random.RandomState(derive_seed(seed, "check"))
        self.share = float(check["share"])
        self.traced = traced
        self._start, self._end = on_window_start, on_window_end
        self.n = 0
        self.t0 = self.t1 = self.paused = 0.0
        self.consult_s: List[float] = []      # window consults only
        self.consult_counters: List[dict] = []
        self.checked: List[Checked] = []
        self.max_jobs = 0                     # most jobs one consult saw
        self.max_now = 0.0                    # latest simulated time
        self._ann = None
        self._ctr0 = {}

    def _annotate(self, name: Optional[str]) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if name is not None and self.tracing:
            import jax
            self._ann = jax.profiler.TraceAnnotation(name)
            self._ann.__enter__()

    def _counters(self) -> dict:
        m = obs.get().metrics
        return {c: m.counter(c).value for c in self.COUNTERS} \
            if m is not None else {}

    def before(self) -> None:
        self._annotate("consult")
        if self.traced:
            self._ctr0 = self._counters()

    def after(self, now, jobs, view, out, alpha: float, full_pass: bool,
              seconds: float) -> None:
        self.n += 1
        self.max_jobs = max(self.max_jobs, len(jobs))
        self.max_now = max(self.max_now, now)
        in_window = self.n > self.setup_consults
        if in_window:
            self.consult_s.append(seconds)
            if self.traced:
                c1 = self._counters()
                self.consult_counters.append(
                    {k: c1[k] - self._ctr0.get(k, 0) for k in c1})
        # the first window consult is always checked, the rest by draw
        if (self.n == self.setup_consults + 1
                or self.rng.random_sample() < self.share):
            self.checked.append(Checked(snapshot(now, full_pass, view, jobs),
                                        freeze(out), alpha, in_window))
        if self.n == self.setup_consults:
            self._start()
            self.tracing = self.traced
            self.t0 = time.perf_counter()
        elif in_window:
            now_s = time.perf_counter()
            if now_s - self.t0 - self.paused >= self.seconds:
                self.t1 = now_s
                self._stop_trace()
                self._end()
                raise WindowClosed
            if self.tracing and now_s - self.t0 >= self.trace_s:
                self._stop_trace()
        self._annotate("engine")

    @property
    def window_s(self) -> float:
        """Measured seconds: the window less the profiler's stop."""
        return self.t1 - self.t0 - self.paused

    def _stop_trace(self) -> None:
        """Stop the profiler; inside the window its seconds (it collects
        and writes the trace) are left out of the measurement."""
        if self.tracing:
            self._annotate(None)
            self.tracing = False
            t = time.perf_counter()
            self._trace_end()
            if not self.t1:
                self.paused += time.perf_counter() - t

    def close(self) -> None:
        self._annotate(None)


class TimedHadar(HadarScheduler):
    """The program's scheduler, each consult timed by ``obs.StopWatch``
    and reported to the recorder.  The allocations come back as host
    Python objects, so the device work of a consult is done when
    ``schedule`` returns."""

    def __init__(self, sched_cfg: dict, rec: Recorder):
        super().__init__(horizon=float(sched_cfg["horizon_s"]),
                         max_exact_dp=int(sched_cfg["max_exact_dp"]),
                         work_conserving=bool(sched_cfg["work_conserving"]),
                         reallocate_on_free=bool(
                             sched_cfg["reallocate_on_free"]),
                         solver=sched_cfg["solver"])
        self.rec = rec
        self._full = True       # the first consult is a full pass

    def note_completion(self) -> None:
        self._full = True
        super().note_completion()

    def schedule(self, now, round_len, jobs, cluster):
        full, self._full = self._full, False
        self.rec.before()
        sw = obs.StopWatch().start()
        out = super().schedule(now, round_len, jobs, cluster)
        seconds = sw.stop()
        self.rec.after(now, jobs, cluster, out, self.alpha, full, seconds)
        self.last_seconds = seconds
        return out


def replay_events(dep, jobs, sched: TimedHadar) -> None:
    """Hadar on the continuous-time engine, driven as
    ``simulate_events`` drives it, up to ``dep.until`` simulated
    seconds."""
    from repro.sim.engine import event_stream

    gen = event_stream(jobs, dep.cluster, round_len=dep.round_len,
                       faults=dep.faults, stable=sched.stable_when_idle,
                       name=sched.name)
    send = None
    try:
        while True:
            try:
                cp = gen.send(send)
            except StopIteration:
                return
            if cp.t >= dep.until:
                return
            if cp.completed:
                sched.note_completion()
            desired = sched.schedule(cp.t, cp.round_len, cp.jobs, cp.view)
            send = (desired, sched.last_seconds)
    finally:
        gen.close()


def replay_hadare(dep, jobs, sched: TimedHadar) -> None:
    """HadarE rounds, parents forked into one copy per node, up to
    ``dep.until`` simulated seconds."""
    from repro.sim.adapters import simulate_hadare

    rounds = min(10 ** 7, math.ceil(dep.until / dep.round_len))
    simulate_hadare(jobs, dep.cluster, round_len=dep.round_len,
                    max_rounds=rounds, scheduler=sched, faults=dep.faults)


def replay(kind: str, dep, sched: TimedHadar) -> None:
    """Replay the deployment until the window closes.  Each replay ends
    where the trace ends or at the mix's ``replay_until_s``; the next
    starts the trace again, in the next order the seed gives
    (``dep.replay_jobs``), with a full pass.  So a window holds whole
    replays and a part of one, whatever the scheduler's speed, and never
    runs out of work."""
    policy = POLICIES[kind]
    k = 0
    while True:
        before = sched.rec.n
        try:
            policy(dep, dep.jobs if k == 0 else dep.replay_jobs(k), sched)
        except WindowClosed:
            return
        if sched.rec.n == before:
            raise RuntimeError("a replay of the trace made no consult")
        k += 1
        sched.note_completion()


POLICIES = {"hadar_events": replay_events, "hadare": replay_hadare}
