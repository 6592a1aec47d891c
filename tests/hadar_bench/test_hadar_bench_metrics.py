"""Each metric reader on recorded spans, counters and trace reductions,
and the trace reduction itself."""
from types import SimpleNamespace

import pytest

import hadar_bench_path  # noqa: F401  (benchmarks/ on the path)

from hadar_bench import devtrace, registry
from hadar_bench.kernelcost import pricing_kernel_bytes


def _dev(busy_ns=2_000, window=(0, 10_000), modules=None):
    return devtrace.Reduced(window, busy_ns, modules or {}, {}, {}, [], 1, 4)


def _run(**kw):
    base = dict(
        setup_s=12.5, window_s=2.0, consult_s=[0.1, 0.3, 0.2, 0.4],
        consult_counters=[{"solver_batch_calls": 1, "solver_scan_calls": 0},
                          {"solver_batch_calls": 0, "solver_scan_calls": 0},
                          {"solver_batch_calls": 2, "solver_scan_calls": 1},
                          {"solver_batch_calls": 1, "solver_scan_calls": 0}],
        spans={"hadar.dp": 400_000.0, "pricestate.refresh": 40_000.0},
        compiles=3, dev=_dev(modules={"jit_kernel": 4_000_000,
                                      "jit_scan_fn": 2_000_000}),
        kernel_bytes=[819_000_000, 819_000_000],
        peaks=lambda: {"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return SimpleNamespace(**base)


EXPECTED = {
    "setup_s": 12.5,
    "consults_per_s": 2.0,
    "decision_p50_ms": 250.0,
    "decision_p95_ms": 400.0,
    "engine_ms": 250.0,
    "dp_ms": 100.0,
    "refresh_ms": 10.0,
    "backfill_ms": 140.0,
    "device_consult_share": 75.0,
    "pricing_kernel_ms": 1.0,
    "commit_scan_ms": 0.5,
    "pricing_kernel_roofline": 50.0,
    "device_idle_share": 80.0,
    "window_compiles": 3,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader(name):
    assert registry.reader(name)(_run()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", ["pricing_kernel_ms", "commit_scan_ms",
                                  "pricing_kernel_roofline"])
def test_kernel_reader_fails_without_trace_events(name):
    with pytest.raises(devtrace.TraceError, match="no device program named"):
        registry.reader(name)(_run(dev=_dev(modules={})))


def test_roofline_fails_without_recorded_bytes():
    with pytest.raises(devtrace.TraceError, match="bytes"):
        registry.reader("pricing_kernel_roofline")(_run(kernel_bytes=[]))


@pytest.mark.parametrize("name", ["pricing_kernel_ms", "commit_scan_ms",
                                  "device_idle_share"])
def test_kernel_reader_reads_nothing_without_device(name):
    assert registry.reader(name)(_run(dev=None)) is None


def test_reader_reads_nothing_without_calls():
    zero = [{"solver_batch_calls": 0, "solver_scan_calls": 0}] * 4
    assert registry.reader("commit_scan_ms")(
        _run(consult_counters=zero)) is None


def test_pricing_kernel_bytes_counts_gathered_operand():
    import numpy as np
    args = [np.zeros(10, np.float64)] + [np.zeros((4, 6), np.int32)] * 2 \
        + [np.zeros((4, 100), np.int32)]
    outs = [np.zeros((4, 3), np.int32)] * 5 + [np.zeros((4, 3, 2), np.int32)]
    want = 80 + 2 * 96 + 4 * 4 * 3 * 2 + 5 * 48 + 96
    assert pricing_kernel_bytes(args, outs) == want


class _E(SimpleNamespace):
    pass


def _line(name, events):
    return SimpleNamespace(name=name, events=[
        _E(name=n, start_ns=a, duration_ns=d, stats=[]) for n, a, d in events])


def test_reduce_busy_gaps_and_programs():
    host = SimpleNamespace(name="/host:CPU", lines=[_line("python", [
        ("engine", 0, 100), ("consult", 100, 300), ("engine", 400, 200)])])
    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        _line("XLA Modules", [("jit_kernel(7)", 150, 100),
                              ("jit_scan_fn(9)", 450, 60)]),
        _line("XLA Ops", [("fusion.1", 150, 60), ("fusion.2", 200, 50),
                          ("while", 450, 60), ("late", 590, 100)])])
    red = devtrace.reduce(SimpleNamespace(planes=[host, dev]))
    assert red.window_ns == (0, 600)
    assert red.busy_ns == 100 + 60 + 10
    assert red.module_ns == {"jit_kernel": 100, "jit_scan_fn": 60}
    assert red.gaps == [("engine", 150), ("consult", 200),
                        ("engine", 80)]
    assert red.op_ns["late"] == 10
    assert red.consults == 1


def test_reduce_needs_annotations():
    with pytest.raises(devtrace.TraceError):
        devtrace.reduce(SimpleNamespace(planes=[]))


def test_reduce_needs_a_consult():
    host = SimpleNamespace(name="/host:CPU",
                           lines=[_line("python", [("engine", 0, 100)])])
    with pytest.raises(devtrace.TraceError, match="consult"):
        devtrace.reduce(SimpleNamespace(planes=[host]))


def test_load_fails_without_a_trace_file(tmp_path):
    with pytest.raises(devtrace.TraceError, match="xplane"):
        devtrace.load(str(tmp_path))


def _recorded(name):
    import gzip
    import os

    import jax
    path = os.path.join(registry.HERE, "testdata", name)
    with gzip.open(path) as fh:
        return devtrace.reduce(
            jax.profiler.ProfileData.from_serialized_xspace(fh.read()))


def test_recorded_chip_trace():
    """A 1.5 s traced window of ``sim-60.arrivals`` on a TPU v5e (seed
    1104): two pricing-kernel calls, no commit scan."""
    red = _recorded("arrivals-1104.xplane.pb.gz")
    assert red.device_planes == 1 and red.consults == 18
    assert red.module_calls == {"jit_kernel": 2}
    run = _run(dev=red, consult_counters=[{"solver_batch_calls": 1,
                                           "solver_scan_calls": 0}] * 2
               + [{"solver_batch_calls": 0, "solver_scan_calls": 0}] * 16)
    idle = registry.reader("device_idle_share")(run)
    assert idle == pytest.approx(100.0 * (1 - 230471 / 4563068444))
    assert registry.reader("pricing_kernel_ms")(run) == \
        pytest.approx(231185 / 1e6 / 18)
    assert registry.reader("commit_scan_ms")(run) is None
    assert max(t for _, t in red.gaps) < red.window_ns[1] - red.window_ns[0]
    assert all(n in ("consult", "engine") for n, _ in red.gaps)
