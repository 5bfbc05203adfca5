"""Set-up seconds: process start to the start of the measured serve
(JAX start, weights, engine, warm-up, and any compile or cache load)."""


def read(run):
    return run.setup_s
