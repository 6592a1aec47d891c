"""Programs JAX lowered inside the window (compiled, or read from the
persistent cache), in total: shapes the set-up did not warm."""


def read(run):
    return run.compiles
