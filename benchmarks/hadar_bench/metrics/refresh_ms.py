"""``PriceState.refresh`` per consult: the obs span
``pricestate.refresh``."""


def read(run):
    n = len(run.consult_s)
    return run.spans["pricestate.refresh"] / 1e3 / n if n else None
