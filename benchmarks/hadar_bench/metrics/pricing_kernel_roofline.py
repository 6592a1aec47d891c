"""The pricing kernel's share of its roofline: the bytes its calls must
move (``kernelcost.pricing_kernel_bytes``) at the chip's HBM peak, over
its measured device time.  Int32 only, so bytes bound it."""
from hadar_bench.devtrace import PRICING, TraceError, program_ns


def read(run):
    ns = program_ns(run, PRICING)
    if ns is None:
        return None
    if not run.kernel_bytes:
        raise TraceError("the pricing kernel ran in the traced consults but "
                         "the spy recorded no call's bytes")
    least_s = sum(run.kernel_bytes) / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
