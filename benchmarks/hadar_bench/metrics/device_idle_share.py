"""1 - (union of device-op intervals) / traced window, from the profiler
trace."""


def read(run):
    d = run.dev
    if d is None or d.window_ns[1] <= d.window_ns[0]:
        return None
    return 100.0 * (1.0 - d.busy_ns / (d.window_ns[1] - d.window_ns[0]))
