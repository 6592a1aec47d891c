"""Consult time outside ``hadar.dp`` and ``pricestate.refresh``: the
work-conserving backfill and the commit bookkeeping, per consult."""


def read(run):
    n = len(run.consult_s)
    if not n:
        return None
    rest = (sum(run.consult_s) * 1e6 - run.spans["hadar.dp"]
            - run.spans["pricestate.refresh"])
    return rest / 1e3 / n
