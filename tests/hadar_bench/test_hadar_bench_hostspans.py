"""Device-idle time put down to the innermost host annotation, program
spans included, on synthetic traces and on a recorded chip trace."""
from types import SimpleNamespace

import pytest

import hadar_bench_path  # noqa: F401  (benchmarks/ on the path)

from hadar_bench import devtrace, hostspans, registry


def _line(name, events):
    return SimpleNamespace(name=name, events=[
        SimpleNamespace(name=n, start_ns=a, duration_ns=d, stats=[])
        for n, a, d in events])


def _pd(host_events, ops, other_lines=()):
    host = SimpleNamespace(name="/host:CPU", lines=[
        _line("python", host_events), *other_lines])
    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        _line("XLA Ops", ops)])
    return SimpleNamespace(planes=[host, dev])


def test_gaps_go_to_the_innermost_annotation():
    host = [("engine", 0, 100),
            ("engine.step", 10, 80),
            ("consult", 100, 300),
            ("hadar.dp", 110, 190),
            ("solver.tables", 120, 20),
            ("solver.device", 140, 60),
            ("hadar.backfill", 310, 80)]
    ops = [("fusion", 150, 40), ("while", 240, 20)]
    # a second thread's annotations never name a gap
    noise = _line("worker", [("transfer", 0, 400)])
    idle = hostspans.idle_by_span(_pd(host, ops, [noise]))
    # gaps: [0,150) mid 75 engine.step; [190,240) mid 215 hadar.dp;
    # [260,400) mid 330 hadar.backfill
    assert idle == {"engine.step": 150, "hadar.dp": 50,
                    "hadar.backfill": 140}
    assert hostspans.program_share(idle) == 1.0


def test_idle_total_matches_the_reduction():
    host = [("engine", 0, 100), ("consult", 100, 300),
            ("hadar.dp", 150, 50), ("engine", 400, 200)]
    ops = [("fusion.1", 150, 60), ("fusion.2", 200, 50), ("while", 450, 60),
           ("late", 590, 100)]
    pd = _pd(host, ops)
    idle = hostspans.idle_by_span(pd)
    red = devtrace.reduce(pd)
    w = red.window_ns[1] - red.window_ns[0]
    assert sum(idle.values()) == w - red.busy_ns \
        == sum(t for _, t in red.gaps)
    # no program span is open over the gaps at 75, 300 and 545
    assert idle == {"engine": 230, "consult": 200}
    assert hostspans.program_share(idle) == 0.0


def test_a_gap_at_a_span_end_goes_to_its_parent():
    host = [("consult", 0, 100), ("solver.waves", 10, 40)]
    ops = [("a", 0, 10), ("b", 50, 50)]
    # the gap [10, 50) has its midpoint at 30, inside solver.waves;
    # one [50, 50) is empty
    assert hostspans.idle_by_span(_pd(host, ops)) == {"solver.waves": 40}
    ops = [("a", 0, 50), ("b", 70, 30)]
    # [50, 70): midpoint 60, after solver.waves closed at 50
    assert hostspans.idle_by_span(_pd(host, ops)) == {"consult": 20}


def test_needs_the_benchmark_marks():
    with pytest.raises(devtrace.TraceError, match="consult"):
        hostspans.idle_by_span(_pd([("hadar.dp", 0, 10)], []))


def test_names_are_the_programs():
    """Every span and counter named here is one the program records."""
    import inspect

    from repro.core import batch_solver, dp, hadar, pricing
    from repro.sim import adapters, engine
    src = "".join(inspect.getsource(m) for m in (
        batch_solver, dp, hadar, pricing, adapters, engine))
    for name in (*hostspans.SPANS.values(), *hostspans.COUNTERS.values()):
        assert f'"{name}"' in src, name


def _recorded(name):
    import gzip
    import os

    import jax
    path = os.path.join(registry.HERE, "testdata", name)
    with gzip.open(path) as fh:
        return jax.profiler.ProfileData.from_serialized_xspace(fh.read())


def test_recorded_chip_trace_with_program_spans():
    """A traced window of ``sim-60.arrivals`` on a TPU v5e (seed
    2147491311, ``trace_s`` 1.5) with the program's spans on the host
    plane: 16 consults, one pricing-kernel call, and a 5.5 s exact-DP
    consult that holds nearly all the idle time."""
    pd = _recorded("arrivals-spans.xplane.pb.gz")
    idle = hostspans.idle_by_span(pd)
    red = devtrace.reduce(pd)
    assert red.consults == 16 and red.module_calls == {"jit_kernel": 1}
    assert sum(idle.values()) == \
        red.window_ns[1] - red.window_ns[0] - red.busy_ns
    # the reduction's own gaps see only the benchmark's marks
    assert {n for n, _ in red.gaps} == {"consult"}
    assert idle == {"dp.exact": 5534153739, "np.asarray(jax.Array)": 78}
    assert hostspans.program_share(idle) == 1.0
