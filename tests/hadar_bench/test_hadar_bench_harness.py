"""Whole runs on the CPU at small sizes, past the harness's look for a
chip: the program agrees with the reference, the control does not, and
the timed path broken underneath makes ``correct`` false."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import hadar_bench_path  # noqa: F401  (benchmarks/ on the path)

from hadar_bench import check, devtrace, kernelcost, registry, run

BENCH = registry.benchmark()
SMALL = {"fig5-2048.storm": 96, "sim-60.arrivals": 60,
         "sim-60.hadare-faults": 30}


def _small(cell_name, share=1.0, n_max=400):
    cell = registry.cell(BENCH, cell_name)
    cfg = registry.config(cell["config"])
    mix = registry.mix(cell["traffic"])
    cfg["trace"]["n_jobs"] = SMALL[cell_name]
    if cfg["cluster"]["layout"] == "grown":
        cfg["cluster"]["queue_jobs"] = SMALL[cell_name]
    mix["check"] = {"share": share, "max": n_max}
    return cell, cfg, mix


def _run(cell_name, seed=2 ** 31 + 5, seconds=1.0, **kw):
    cell, cfg, mix = _small(cell_name)
    items = []
    res = run.run_cell(BENCH, cell, seed, seconds, False, cfg=cfg, mix=mix,
                       sample_out=items, **kw)
    return res, items, cfg


@pytest.mark.parametrize("cell_name", sorted(SMALL))
def test_program_agrees_and_control_fails(cell_name):
    res, items, cfg = _run(cell_name)
    assert res["correct"], res["check"]
    assert res["check"]["checked_consults"]["value"] >= 3
    assert list(res)[-1] == "check"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    # the control: the reference in float32 in the program's place
    prm = check.params(cfg["scheduler"])
    ctl = check.compare(check.as_control(items, prm), prm)
    assert ctl["alpha_rel_gap"] > 100 * run.ALPHA_GAP_LIMIT


def _unchanged(orig):
    def schedule(self, now, round_len, jobs, cluster):
        orig(self, now, round_len, jobs, cluster)
        return {j.job_id: j.alloc for j in jobs if j.alloc}
    return schedule


def _half_batch(orig):
    def schedule(self, now, round_len, jobs, cluster):
        return orig(self, now, round_len, jobs[::2], cluster)
    return schedule


def _altered(orig):
    def schedule(self, now, round_len, jobs, cluster):
        out = orig(self, now, round_len, jobs, cluster)
        if out:
            out.pop(min(out))
        return out
    return schedule


@pytest.mark.parametrize("cell_name", ["sim-60.arrivals",
                                       "sim-60.hadare-faults"])
@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered],
                         ids=["state-unchanged", "half-batch", "altered"])
def test_broken_timed_path_is_not_correct(cell_name, fault, monkeypatch):
    from repro.core.hadar import HadarScheduler
    monkeypatch.setattr(HadarScheduler, "schedule",
                        fault(HadarScheduler.schedule))
    res, _, _ = _run(cell_name)
    assert not res["correct"]
    assert res["check"]["mismatched_consults"]["value"] > 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/hadar_bench/run.py", "--workload",
         "fig5-2048.storm", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    p = _cli(registry.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr and "No result" in p.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(registry.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_trace_error_gives_no_result(monkeypatch, capsys):
    def fails(*_a, **_kw):
        raise devtrace.TraceError("solver_batch_calls=3 in the traced "
                                  "consults but the trace has no device "
                                  "program named 'jit_kernel'")
    monkeypatch.setattr(run, "_device", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(run, "run_cell", fails)
    rc = run.main(["--workload", "sim-60.arrivals", "--seed", "3",
                   "--seconds", "1", "--trace", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "jit_kernel" in out.err and "No result" in out.err


def test_traced_run_fails_when_the_spy_cannot_install(monkeypatch):
    monkeypatch.setattr(kernelcost.PricingSpy, "install", lambda self: False)
    cell, cfg, mix = _small("sim-60.arrivals")
    with pytest.raises(devtrace.TraceError, match="_get_kernel"):
        run.run_cell(BENCH, cell, 5, 1.0, True, cfg=cfg, mix=mix)


def test_result_line_is_json_with_check_last():
    res, _, _ = _run("sim-60.arrivals")
    line = json.dumps(res)
    assert list(json.loads(line))[-1] == "check"


class _Clock:
    """A window clock that moves 10 ms at each reading, so the window
    holds the same consults however loaded the host is."""

    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        self.t += 0.01
        return self.t


def test_trace_replays_when_it_ends(monkeypatch):
    from hadar_bench import window
    monkeypatch.setattr(window, "time", _Clock())
    cell, cfg, mix = _small("sim-60.arrivals")
    cfg["trace"]["n_jobs"] = 12
    mix["replay_until_s"] = 7200.0
    items = []
    res = run.run_cell(BENCH, cell, 9, 2.0, False, cfg=cfg, mix=mix,
                       sample_out=items)
    assert res["correct"], res["check"]
    times = [it.snap.now for it in items if it.in_window]
    assert max(times) < 7200.0
    assert sum(b < a for a, b in zip(times, times[1:])) >= 2
