"""Spans and phase timing of the serving scheduler.

Every span is a ``jax.profiler.TraceAnnotation``: while a profiler trace
runs (``jax.profiler.trace``, ``launch/serve.py --profile-dir``) it lands
on the host plane of the same ``.xplane.pb`` as the device's program runs,
with its arguments as stats; with no profiler running it costs the
annotation's own check (about a microsecond).  There is no switch.

A :class:`phase` span also adds its ``time.perf_counter`` wall to one key
of the engine's ``phase_s`` (``prefill``, ``decode``, ``idle``,
``refresh``), and :class:`step` keeps the slowest scheduler step of the
serve in ``engine.slowest_step`` — the record an untraced run leaves of
where a stall went: its seconds, number, and how its wall split among
the phases.

The span names (``SPANS``) are stable: the benchmark's trace reader
(``bench/spans.py``) finds them by name.  Arguments are counts or ids;
a request's work carries its ``uid`` (:func:`uid_arg`).
"""
from __future__ import annotations

import time
from typing import Dict, Union

from jax.profiler import StepTraceAnnotation, TraceAnnotation

# every span the scheduler opens; nesting in a chunked paged serve:
#   sched_step
#     reap
#     assemble_run
#       idle_sleep
#     prefill_quantum  kv_insert  complete_run
#     decode_guard                          (prefix sharing / refresh only)
#     decode_step
#       decode_args  decode_launch  decode_wait  sample
#     refresh
# the one-shot admission path opens prefill_launch / prefix_hit instead
SPANS = ("sched_step", "reap", "assemble_run", "idle_sleep",
         "prefill_quantum", "kv_insert", "complete_run", "decode_guard",
         "decode_step", "decode_args", "decode_launch", "decode_wait",
         "sample", "refresh", "prefill_launch", "prefix_hit")


def span(name: str, **args) -> TraceAnnotation:
    """A named host span carrying ``args`` (counts and ids)."""
    return TraceAnnotation(name, **args)


def uid_arg(requests) -> Union[int, str]:
    """The ``uid`` argument of work done for ``requests``: the uid of one
    request, or ``"u4+u5"`` for a packed run (the trace reads a
    comma-separated string back as its first number)."""
    if len(requests) == 1:
        return int(requests[0].uid)
    return "+".join(f"u{r.uid}" for r in requests)


class phase:
    """A span whose wall time also accrues to ``phase_s[key]``; ``s`` holds
    that wall once the span closes (also when its body raised)."""

    __slots__ = ("_acc", "_key", "_ann", "_t", "s")

    def __init__(self, phase_s: Dict[str, float], key: str, name: str,
                 **args):
        self._acc, self._key = phase_s, key
        self._ann = TraceAnnotation(name, **args)
        self.s = 0.0

    def __enter__(self) -> "phase":
        self._ann.__enter__()
        self._t = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.s = time.perf_counter() - self._t
        self._acc[self._key] += self.s
        self._ann.__exit__(*exc)


class step:
    """One scheduler step: the ``sched_step`` span (a
    ``StepTraceAnnotation`` numbered ``num``), and the slowest-step record
    ``engine.slowest_step`` — its seconds, number, the ``phase_s`` key that
    took most of it, and its wall by ``phase_s`` key (``other``: the
    scheduler's own bookkeeping outside every phase)."""

    __slots__ = ("_eng", "_num", "_ann", "_before", "_t")

    def __init__(self, engine, num: int, **args):
        self._eng, self._num = engine, num
        self._ann = StepTraceAnnotation("sched_step", step_num=num, **args)

    def __enter__(self) -> "step":
        self._ann.__enter__()
        self._before = dict(self._eng.phase_s)
        self._t = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        s = time.perf_counter() - self._t
        eng = self._eng
        if s > eng.slowest_step["s"]:
            spent = {k: v - self._before[k] for k, v in eng.phase_s.items()}
            spent["other"] = s - sum(spent.values())
            eng.slowest_step = {"s": s, "step": self._num,
                                "phase": max(spent, key=spent.get),
                                "phase_s": spent}
        self._ann.__exit__(*exc)
