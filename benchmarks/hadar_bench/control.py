#!/usr/bin/env python3
"""The control of the correctness check, and the readings its limit is
set from.

    python3 benchmarks/hadar_bench/control.py --workload <cell> \
        --seconds <s> --seeds <n> [<n> ...]

For each seed, one run of the cell as the benchmark makes it, then on
the same sampled consults: the program's mismatches against the plain
reference (the lower reading, which has to be 0), and the mismatches of
the reference computed in float32 against it in float64 (the control,
which has to read above 0).  One process, one chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if __package__ in (None, ""):
    sys.path[:0] = [os.path.dirname(HERE),
                    os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                 "src")]

from hadar_bench import check, registry, run  # noqa: E402


def readings(bench, cell, seed, seconds, **kw) -> dict:
    items: list = []
    res = run.run_cell(bench, cell, seed, seconds, False, sample_out=items,
                       **kw)
    cfg = kw.get("cfg") or registry.config(cell["config"])
    prm = check.params(cfg["scheduler"])
    ctl = check.compare(check.as_control(items, prm), prm)
    return {"seed": seed, "correct": res["correct"],
            "attempted": res["attempted"],
            "program": {k: v["value"] for k, v in res["check"].items()},
            "control": ctl}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    if run._device()["platform"] != "tpu":
        print("control: needs a TPU chip; no readings", file=sys.stderr)
        return 2
    for seed in args.seeds:
        print(json.dumps(readings(bench, cell, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
