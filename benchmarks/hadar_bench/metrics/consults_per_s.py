"""Consults completed in the window over its wall seconds, engine work
included."""


def read(run):
    return len(run.consult_s) / run.window_s if run.consult_s else None
