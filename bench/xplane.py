"""Reduction of a profiler trace (``*.xplane.pb``) to device metrics.

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``).  Its
``XLA Modules`` line has one event per run of a compiled program (named
after the jitted function, ``jit_<name>(...)``), and its ``XLA Ops`` line
one event per operation inside it; a Pallas kernel is one operation.  The
reduction reads only those two lines:

* busy time: the union of the operation intervals of a chip (operations
  never count twice where they overlap), averaged over the chips;
* device time per program name, and per operation keyed
  ``<program>:<instruction>`` (the HLO instruction name without its
  numeric suffix: ``fusion``, ``copy-done``, or a Pallas kernel's name);
* idle gaps: the intervals between busy stretches, each named by the
  program that ran just before it.

The traced window is the host's start-to-stop span of the profiler, given
by the caller.  ``load`` is the only part that touches the file format;
``reduce`` works on plain tuples, so it is tested on a recorded trace and
on hand-built events alike.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]        # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Chip:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Summary:
    busy_s: float                       # mean over chips
    window_s: float
    op_s: Dict[str, float]              # op name -> device seconds (all chips)
    module_s: Dict[str, float]          # program name -> device seconds
    module_n: Dict[str, int]            # program name -> runs
    gaps: List[Tuple[str, float]]       # (after program, seconds), longest first
    chips: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> List[Chip]:
    """The TPU planes of one xplane file."""
    from jax.profiler import ProfileData
    chips = []
    for plane in ProfileData.from_file(path).planes:
        if not re.match(r"/device:TPU:\d+$", plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        ev = lambda n: [(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in lines[n].events] if n in lines else []
        chips.append(Chip(plane.name, ev("XLA Ops"), ev("XLA Modules")))
    return chips


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(op: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    m = re.match(r"%?([A-Za-z0-9_\-]+?)(?:\.\d+)*\s*=", op)
    return m.group(1) if m else op.split(" ", 1)[0]


def program_name(module: str) -> str:
    """``jit_decode_step(123)`` -> ``decode_step``."""
    m = re.match(r"(?:jit_)?([A-Za-z0-9_.\-]+)", module)
    return m.group(1) if m else module


def reduce(chips: Sequence[Chip], window_s: float, top: int = 10) -> Summary:
    if not chips:
        raise ValueError("the trace holds no TPU plane")
    busy = 0.0
    op_s: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[str, float]] = []
    for chip in chips:
        ops = chip.ops or chip.modules
        merged = union([(s, s + d) for _, s, d in ops])
        busy += sum(e - s for s, e in merged) * 1e-9
        mods = sorted(chip.modules, key=lambda m: m[1])
        for name, _, d in mods:
            module_s[program_name(name)] += d * 1e-9
            module_n[program_name(name)] += 1
        k = -1
        for name, st, d in sorted(chip.ops, key=lambda o: o[1]):
            while k + 1 < len(mods) and mods[k + 1][1] <= st:
                k += 1
            prog = program_name(mods[k][0]) if k >= 0 else "?"
            op_s[f"{prog}:{op_name(name)}"] += d * 1e-9
        k = 0
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            while k + 1 < len(mods) and mods[k + 1][1] < e0:
                k += 1
            after = program_name(mods[k][0]) if mods else "?"
            gaps.append((f"after {after}", (s1 - e0) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(busy / len(chips), window_s, dict(op_s), dict(module_s),
                   dict(module_n), gaps[:top], len(chips))


def breakdown(s: Summary, top: int = 10) -> Dict[str, list]:
    """The ``breakdown`` of a traced run's result line."""
    ops = sorted(s.module_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, v] for n, v in ops],
            "idle_gaps": [[n, v] for n, v in s.gaps[:top]]}
