"""Median wait from scheduled arrival to the start of a request's prefill,
ms (the scheduler's ``Request.queue_s``)."""
from bench.stats import percentile


def read(run):
    v = percentile([r.queue_s for r in run.done], 50)
    return None if v is None else v * 1e3
