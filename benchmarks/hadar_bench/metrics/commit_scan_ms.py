"""Device time of the commit scan (``jit_scan_fn``) per traced consult."""
from hadar_bench.devtrace import SCAN, program_ns


def read(run):
    ns = program_ns(run, SCAN)
    return None if ns is None else ns / 1e6 / run.dev.consults
