"""Share of the window's consults that dispatched the pricing kernel or
the commit scan at least once (solver counters)."""


def read(run):
    c = run.consult_counters
    if not c:
        return None
    hit = sum(1 for d in c if d.get("solver_batch_calls", 0) > 0
              or d.get("solver_scan_calls", 0) > 0)
    return 100.0 * hit / len(c)
