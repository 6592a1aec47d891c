"""``dp_allocation`` per consult: the obs span ``hadar.dp``."""


def read(run):
    n = len(run.consult_s)
    return run.spans["hadar.dp"] / 1e3 / n if n else None
