"""Engine time per consult: window wall time not spent in ``schedule``
(event queue, progress accrual, HadarE aggregation, the benchmark's own
sampling), over the consults."""


def read(run):
    n = len(run.consult_s)
    return 1e3 * (run.window_s - sum(run.consult_s)) / n if n else None
