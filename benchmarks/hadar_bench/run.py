#!/usr/bin/env python3
"""Chip benchmark of the Hadar scheduler.

    python3 benchmarks/hadar_bench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process on the chips the cell asks for, from the repository root:

1. fail unless JAX's devices are TPUs, as many as the cell asks for;
2. turn on JAX's persistent compilation cache (``.jax_cache/`` in the
   checkout, or ``$JAX_COMPILATION_CACHE_DIR``);
3. build the cell's deployment from ``--seed`` (``traffic/``);
4. warm every kernel shape the cell can reach (``warm.py``) and replay
   the trace's opening consults untimed: the set-up;
5. time the window (``window.py``): every consult of
   ``HadarScheduler.schedule`` on the program's own engine, until the
   first consult that ends after ``--seconds``;
6. compare a seeded sample of the consults with the plain reference
   (``reference.py``) and print one JSON line.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, from the ``repro.obs`` spans and counters and from a
profiler trace of the window.  Each metric is read by
``metrics/<name>.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if __package__ in (None, ""):
    sys.path[:0] = [os.path.dirname(HERE), os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from hadar_bench import devtrace, registry  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
SPANS = ("hadar.dp", "pricestate.refresh")
# widest relative gap of the reported Theorem 2 constant from the float64
# reference.  The program reads 0 (bitwise) on every seed; the reference
# in float32 reads 2.3e-9 and more; float64 rounding is 1e-16 (PERF.md)
ALPHA_GAP_LIMIT = 1e-11


class Compiles:
    """Counts programs JAX lowers (compiled or read from the cache)
    while installed."""

    def __init__(self):
        self.n = 0

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1

    def __enter__(self) -> "Compiles":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def _device() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _window_spans(ob, t0_us: float, t1_us: float) -> dict:
    out = {n: 0.0 for n in SPANS}
    for e in ob.trace.events:
        if (e.get("ph") == "X" and e["name"] in out
                and t0_us <= e["ts"] < t1_us):
            out[e["name"]] += e["dur"]
    return out


def _sample(checked, rng, n_max: int):
    """The first window consult and a seeded draw of the others, in the
    order they were made."""
    first = next((i for i, c in enumerate(checked) if c.in_window), None)
    rest = [i for i in range(len(checked)) if i != first]
    k = max(0, min(len(rest), n_max - (first is not None)))
    pick = list(rng.choice(rest, size=k, replace=False)) if k else []
    keep = sorted(pick + ([first] if first is not None else []))
    return [checked[i] for i in keep]


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             traced: bool, base: str = registry.HERE, cfg: dict = None,
             mix: dict = None, sample_out: list = None) -> dict:
    """One run of ``cell``; the result line as a dict.  ``cfg`` and
    ``mix`` replace the cell's files; ``sample_out`` receives the
    consults compared."""
    import jax

    from repro import obs
    from repro.utils.compile_cache import enable_compile_cache

    from hadar_bench import check, traffic, warm
    from hadar_bench.kernelcost import PricingSpy
    from hadar_bench.window import Recorder, TimedHadar, replay

    enable_compile_cache()
    # keep every program, however quick to compile, so that a cell's runs
    # after its first find each warm-up kernel in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg = cfg or registry.config(cell["config"], base)
    mix = mix or registry.mix(cell["traffic"], base)
    sched_cfg = cfg["scheduler"]
    dep = traffic.build(cfg, mix, seed)
    marks = SimpleNamespace(c0=0, c1=0, us0=0.0, us1=0.0, log_dir=None)
    spy = PricingSpy() if traced else None
    with contextlib.ExitStack() as stack:
        compiles = stack.enter_context(Compiles())
        t_warm = time.perf_counter()
        warmed = warm.warm(dep, sched_cfg)
        warm_s = time.perf_counter() - t_warm
        ob = stack.enter_context(obs.session(
            trace=True, metrics=True, decisions=False)) if traced else None

        def window_start():
            marks.c0 = compiles.n
            if traced:
                marks.log_dir = tempfile.mkdtemp(prefix="hadar-bench-trace-")
                spy.on = True
                # no Python tracer: it records every Python call and slows
                # the host, whose work is most of a consult
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(marks.log_dir,
                                         profiler_options=opts)
                marks.us0 = ob.trace.now()

        def trace_end():
            jax.profiler.stop_trace()
            spy.on = False

        def window_end():
            marks.c1 = compiles.n
            if traced:
                marks.us1 = ob.trace.now()

        rec = Recorder(int(mix["setup_consults"]), seconds, seed,
                       mix["check"], traced, window_start, window_end,
                       trace_s=mix.get("trace_s"), on_trace_end=trace_end)
        sched = TimedHadar(sched_cfg, rec)
        if traced:
            if not spy.install():
                raise devtrace.TraceError(
                    "batch_solver._get_kernel is gone: no pricing-kernel "
                    "call can be sized")
            stack.callback(spy.remove)
        stack.callback(rec.close)
        replay(mix["policy"]["kind"], dep, sched)
    window_s = rec.window_s
    setup_s = rec.t0 - T_START
    memory_peak = _memory_peak()

    dev = None
    if traced:
        try:
            dev = devtrace.load(marks.log_dir)
        finally:
            shutil.rmtree(marks.log_dir, ignore_errors=True)
    device = _device()
    run = SimpleNamespace(
        setup_s=setup_s, window_s=window_s, consult_s=rec.consult_s,
        consult_counters=rec.consult_counters,
        spans=_window_spans(ob, marks.us0, marks.us1) if traced else {},
        compiles=marks.c1 - marks.c0, dev=dev,
        kernel_bytes=spy.bytes if traced else [],
        peaks=lambda: registry.peaks(device["kind"], base))
    metrics = {}
    for m in registry.metrics_for(bench, cell["name"], traced):
        v = registry.reader(m["name"], base)(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # correctness: a seeded sample of consults against the reference
    rng = np.random.RandomState(traffic.derive_seed(seed, "sample"))
    items = _sample(rec.checked, rng, int(mix["check"]["max"]))
    prm = check.params(sched_cfg)
    t_ref = time.perf_counter()
    got = check.compare(items, prm)
    reference_s = time.perf_counter() - t_ref
    if sample_out is not None:
        sample_out.extend(items)
    limits = {"mismatched_consults": 0, "alpha_rel_gap": ALPHA_GAP_LIMIT,
              "checked_in_window": 1}
    correct = (got["mismatched_consults"] <= limits["mismatched_consults"]
               and got["alpha_rel_gap"] <= limits["alpha_rel_gap"]
               and got["checked_in_window"] >= limits["checked_in_window"])
    device["memory_peak_bytes"] = memory_peak
    result = {"correct": bool(correct), "attempted": len(rec.consult_s),
              "failed": got["mismatched_in_window"], "metrics": metrics,
              "device": device}
    if dev is not None:
        device["busy_s"] = dev.busy_ns / 1e9
        device["window_s"] = (dev.window_ns[1] - dev.window_ns[0]) / 1e9
        ops = sorted(dev.op_ns.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(dev.gaps, key=lambda g: -g[1])[:10]
        result["breakdown"] = {
            "device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[n, t / 1e9] for n, t in gaps]}
    result["diag"] = {"warm_s": warm_s, "warm_points": warmed,
                      "setup_consults": rec.setup_consults,
                      "window_compiles": marks.c1 - marks.c0,
                      "setup_compiles": marks.c0, "reference_s": reference_s,
                      "consults": rec.n, "max_jobs": rec.max_jobs,
                      "max_sim_time_s": rec.max_now}
    result["check"] = {
        "mismatched_consults": {"value": got["mismatched_consults"],
                                "limit": limits["mismatched_consults"],
                                "rule": "at most"},
        "alpha_rel_gap": {"value": got["alpha_rel_gap"],
                          "limit": limits["alpha_rel_gap"],
                          "rule": "at most"},
        "checked_in_window": {"value": got["checked_in_window"],
                              "limit": limits["checked_in_window"],
                              "rule": "at least"},
        "checked_consults": {"value": got["checked_consults"],
                             "limit": None, "rule": "reported"},
        "mismatched_jobs": {"value": got["mismatched_jobs"],
                            "limit": None, "rule": "reported"}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    dev = _device()
    if dev["platform"] != "tpu" or dev["count"] < int(cell["chips"]):
        print(f"hadar_bench: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {dev['count']} {dev['platform']} "
              "device(s). No result.", file=sys.stderr)
        return 2
    try:
        result = run_cell(bench, cell, args.seed, args.seconds,
                          bool(args.trace))
    except devtrace.TraceError as e:
        print(f"hadar_bench: {args.workload}: {e}. No result.",
              file=sys.stderr)
        return 3
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']} ({c['rule']} {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
