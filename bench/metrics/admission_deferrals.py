"""Scheduler steps in which the head of the queue waited for page-pool
headroom (``engine.pages_exhausted_steps``)."""


def read(run):
    return run.pages_exhausted_steps
