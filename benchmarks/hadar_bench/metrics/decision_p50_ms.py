"""Median wall time of the window's consults of ``schedule``."""
import statistics


def read(run):
    return 1e3 * statistics.median(run.consult_s) if run.consult_s else None
