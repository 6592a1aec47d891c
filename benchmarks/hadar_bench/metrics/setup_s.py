"""Set-up: process start to window start (JAX start-up, deployment,
kernel warm-up and compiles, opening consults)."""


def read(run):
    return run.setup_s
