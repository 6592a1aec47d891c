"""95th percentile (nearest rank) of the window's consult wall times,
from the raw samples."""
import math


def read(run):
    xs = sorted(run.consult_s)
    if not xs:
        return None
    return 1e3 * xs[max(0, math.ceil(0.95 * len(xs)) - 1)]
