"""Device time of the batched pricing kernel (``jit_kernel``) per
traced consult."""
from hadar_bench.devtrace import PRICING, program_ns


def read(run):
    ns = program_ns(run, PRICING)
    return None if ns is None else ns / 1e6 / run.dev.consults
