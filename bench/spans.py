"""The program's host spans against the chip's time, from one profiler trace.

The serving program marks its layer boundaries with named host spans
(``repro.serving.tracing``, ``jax.profiler.TraceAnnotation``); the
profiler writes them, with their arguments, on the host plane of the same
``.xplane.pb`` that holds the chip's program runs.  This module puts the
two on one clock and charges device work and device idle time to spans:

* launches: the host's ``PJRT_LoadedExecutable_Execute`` events, one per
  program launch, found by name on any thread.  The k-th launch is paired
  with the chip's k-th ``XLA Modules`` run.  Where the counts differ
  nothing is paired, ``offset_ns`` is None and the span quantities read None:
  the reduction does not guess.
* clock: the device clock is shifted by the smallest offset that puts
  every run at or after its launch (on a v5e the device clock read 0.3 to
  1.5 ms behind the host's, moving from trace to trace).
* each program run's device time goes to the innermost span open at its
  launch's start;
* each device idle interval (a gap between busy stretches, busy being the
  union of operation intervals as ``xplane.reduce`` defines it) is split
  among the innermost spans open over it;
* what lies outside every span is ``unattributed``.

Over several chips each chip is paired and attributed on its own, and the
seconds are averaged over the chips.  ``load`` is the only part that
touches the file format; ``reduce`` works on plain tuples, so it is tested
on a recorded trace and on hand-built events alike.

The result line of ``bench/run.py`` does not read spans; this module
reports them on its own, from the repository root:

    python3 -m bench.spans <trace dir or .xplane.pb>
    python3 -m bench.spans --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import math
import os
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import xplane

LAUNCH = "PJRT_LoadedExecutable_Execute"
NONE = "unattributed"
# the program's span names (repro.serving.tracing.SPANS); kept here too so
# the reader runs beside a program that has none of them
SPANS = ("sched_step", "reap", "assemble_run", "idle_sleep",
         "prefill_quantum", "kv_insert", "complete_run", "decode_guard",
         "decode_step", "decode_args", "decode_launch", "decode_wait",
         "sample", "refresh", "prefill_launch", "prefix_hit")
# the spans whose device idle time is admission's host work, and the
# decode step with its children
ADMISSION = ("assemble_run", "prefill_quantum", "kv_insert", "complete_run")
DECODE = ("decode_step", "decode_args", "decode_launch", "decode_wait",
          "sample")


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    args: Dict[str, object]


@dataclasses.dataclass
class Host:
    spans: List[Span]
    launches: List[float]           # launch start times, ns, sorted


@dataclasses.dataclass
class Summary:
    launches: int                   # per chip
    runs: List[int]                 # program runs, per chip
    offset_ns: Optional[float]      # first chip's; None: no pairing
    count: Dict[str, int]           # span name -> spans
    device_s: Dict[str, float]      # name -> device s of runs launched inside
    idle_s: Dict[str, float]        # name -> device idle s inside
    spans: List[Span]

    @property
    def paired(self) -> bool:
        return self.offset_ns is not None

    def share(self, seconds: Dict[str, float]) -> Optional[float]:
        """Attributed share of ``device_s`` or ``idle_s``."""
        total = sum(seconds.values())
        if not self.paired or total <= 0:
            return None
        return 1.0 - seconds.get(NONE, 0.0) / total

    def device_ms(self, name: str) -> Optional[float]:
        """Mean device ms of the programs launched inside one ``name``."""
        n = self.count.get(name, 0)
        if not self.paired or not n:
            return None
        return 1e3 * self.device_s.get(name, 0.0) / n

    def idle_ms(self, names: Iterable[str], per: str) -> Optional[float]:
        """Device idle ms inside ``names``, per ``per`` span."""
        n = self.count.get(per, 0)
        if not self.paired or not n:
            return None
        return 1e3 * sum(self.idle_s.get(k, 0.0) for k in names) / n

    def mean_arg(self, name: str, key: str) -> Optional[float]:
        vals = [s.args[key] for s in self.spans
                if s.name == name and key in s.args]
        return sum(vals) / len(vals) if vals else None

    def longest(self, name: str = "sched_step") -> Optional[Dict]:
        """The longest ``name`` span: its seconds, its arguments, and the
        host seconds of the spans inside it, by name (nested spans count
        under each of their names)."""
        def dur(s: Span) -> float:
            return (s.end_ns - s.start_ns) * 1e-9

        top = max((s for s in self.spans if s.name == name), key=dur,
                  default=None)
        if top is None:
            return None
        inside: Dict[str, float] = defaultdict(float)
        for s in self.spans:
            if (s is not top and top.start_ns <= s.start_ns
                    and s.end_ns <= top.end_ns):
                inside[s.name] += dur(s)
        args = {k: v for k, v in top.args.items() if not k.startswith("_")}
        return {"s": dur(top), **args, "inside": dict(inside)}


def load(path: str, names: Sequence[str] = SPANS) -> Host:
    """The spans named ``names`` and the launches on the host planes."""
    from jax.profiler import ProfileData
    wanted = set(names)
    spans, launches = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == LAUNCH:
                    launches.append(float(e.start_ns))
                elif e.name in wanted:
                    st = float(e.start_ns)
                    spans.append(Span(e.name, st, st + float(e.duration_ns),
                                      dict(e.stats)))
    return Host(spans, sorted(launches))


def pieces(spans: Sequence[Span]) -> List[Tuple[float, float, int]]:
    """The time line cut where a span opens or closes: contiguous
    ``(start_ns, end_ns, i)`` pieces in order, ``i`` the innermost span
    open over the piece, -1 where none is."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].start_ns, -spans[i].end_ns))
    out: List[Tuple[float, float, int]] = []
    stack: List[int] = []
    t = -math.inf

    def cut(upto: float) -> None:
        nonlocal t
        if upto > t:
            out.append((t, upto, stack[-1] if stack else -1))
            t = upto

    for i in order:
        while stack and spans[stack[-1]].end_ns <= spans[i].start_ns:
            cut(spans[stack[-1]].end_ns)
            stack.pop()
        cut(spans[i].start_ns)
        stack.append(i)
    while stack:
        cut(spans[stack[-1]].end_ns)
        stack.pop()
    out.append((t, math.inf, -1))
    return out


def reduce(host: Host, chips: Sequence[xplane.Chip]) -> Summary:
    count: Dict[str, int] = defaultdict(int)
    for s in host.spans:
        count[s.name] += 1
    runs = [len(c.modules) for c in chips]
    unpaired = Summary(len(host.launches), runs, None, dict(count), {}, {},
                       host.spans)
    if not chips or any(n != len(host.launches) for n in runs):
        return unpaired
    cuts = pieces(host.spans)
    starts = [p[0] for p in cuts]

    def label(k: int) -> str:
        i = cuts[k][2]
        return host.spans[i].name if i >= 0 else NONE

    device_s: Dict[str, float] = defaultdict(float)
    idle_s: Dict[str, float] = defaultdict(float)
    offsets = []
    for chip in chips:
        mods = sorted(chip.modules, key=lambda m: m[1])
        offset = max(t - m[1] for t, m in zip(host.launches, mods))
        offsets.append(offset)
        for t, (_, _, d) in zip(host.launches, mods):
            device_s[label(bisect.bisect_right(starts, t) - 1)] += d * 1e-9
        merged = xplane.union([(s, s + d)
                               for _, s, d in chip.ops or chip.modules])
        for (_, a), (b, _) in zip(merged, merged[1:]):
            a, b = a + offset, b + offset
            k = bisect.bisect_right(starts, a) - 1
            while k < len(cuts) and cuts[k][0] < b:
                over = min(b, cuts[k][1]) - max(a, cuts[k][0])
                if over > 0:
                    idle_s[label(k)] += over * 1e-9
                k += 1
    n = len(chips)
    return Summary(len(host.launches), runs, offsets[0], dict(count),
                   {k: v / n for k, v in device_s.items()},
                   {k: v / n for k, v in idle_s.items()}, host.spans)


def read(path: str) -> Summary:
    """The summary of one trace file."""
    return reduce(load(path), xplane.load(path))


def quantities(s: Summary) -> Dict[str, Optional[float]]:
    """The per-layer quantities the spans give (PERF.md, section 3); None
    where the trace holds nothing to read."""
    return {"prefill_quantum_ms": s.device_ms("prefill_quantum"),
            "kv_insert_ms": s.device_ms("kv_insert"),
            "admission_host_gap_ms": s.idle_ms(ADMISSION,
                                               per="prefill_quantum"),
            "decode_host_gap_ms": s.idle_ms(DECODE, per="decode_step"),
            "decode_batch_mean": s.mean_arg("decode_step", "occupied")}


def _rounded(v):
    if isinstance(v, float):
        return round(v, 4)
    if isinstance(v, dict):
        return {k: _rounded(x) for k, x in v.items()}
    return v


def report(s: Summary) -> Dict[str, object]:
    """The pairing, the clock offset, device idle seconds by span, the
    attributed shares, the longest ``sched_step`` span and the quantities
    that read something."""
    out: Dict[str, object] = {"launches": s.launches, "runs": s.runs}
    if s.paired:
        out["offset_ms"] = s.offset_ns * 1e-6
        out["idle_s"] = sum(s.idle_s.values())
        out["idle_attributed"] = s.share(s.idle_s) or 0.0
        out["busy_attributed"] = s.share(s.device_s) or 0.0
        out["idle_by_span"] = dict(
            sorted(s.idle_s.items(), key=lambda kv: -kv[1]))
    longest = s.longest()
    if longest is not None:
        out["longest_step"] = longest
    out.update({k: v for k, v in quantities(s).items() if v is not None})
    return _rounded(out)


def serve(bench, name: str, seed: int, seconds: float, trace: bool = True,
          log=lambda *a, **k: None) -> Dict[str, object]:
    """Serve cell ``name`` once as the benchmark's run does (its set-up,
    the seed's requests), under the profiler when ``trace``: the report of
    that trace, the window, and the engine's ``phase_s`` and slowest
    scheduler step (the stall record, on every run)."""
    import jax
    from bench.harness import Session
    s = Session(bench, name, seed, seconds, t_start=time.perf_counter(),
                log=log)
    out: Dict[str, object] = {}
    if trace:
        tdir = tempfile.mkdtemp(prefix="spans-trace-")
        try:
            jax.profiler.start_trace(tdir)
            try:
                run = s.serve(seed, seconds)
            finally:
                jax.profiler.stop_trace()
            out = report(read(xplane.find(tdir)))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        run = s.serve(seed, seconds)
    out["window_s"] = run.window_s
    out["phase_s"] = s.engine.phase_s
    out["slowest_step"] = s.engine.slowest_step
    return _rounded(out)


def main(argv=None) -> int:
    """``python3 -m bench.spans TRACE`` reports an existing trace (a
    ``.xplane.pb`` or a directory holding one, as ``launch/serve.py
    --profile-dir`` writes); ``python3 -m bench.spans --workload <cell>
    --seed <n> --seconds <s> [--trace 0|1]`` serves a benchmark cell and
    reports it.  One JSON line on standard output."""
    ap = argparse.ArgumentParser(description=main.__doc__.split("\n")[0])
    ap.add_argument("path", nargs="?")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    if (args.path is None) == (args.workload is None):
        ap.error("give either a trace path or --workload")
    if args.path is not None:
        path = (args.path if os.path.isfile(args.path)
                else xplane.find(args.path))
        out = report(read(path))
    else:
        from bench import harness
        sys.path.insert(0, str(harness.ROOT / "src"))
        harness.use_compile_cache()
        out = serve(harness.Bench(), args.workload, args.seed, args.seconds,
                    bool(args.trace), log=harness.log)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
