"""One run of one cell: set-up, the measured serve, metrics, the check.

Everything specific to a configuration, mix, cell or metric is read from
files named after it (see ``bench/__init__.py``); this module holds only
what every cell does:

1. load the configuration's family (``bench/families/<family>.py``),
   build the program's model from the file's sizes as the family maps
   them, fill its parameter tree from the seed (``weights.py``) in the
   served dtype, and build the ``ServingEngine`` the file's ``engine``
   fields describe;
2. warm up with one serve of the mix's warm-up requests (one per sequence
   bucket the run reaches, from a stream no run uses), so every program the
   window drives is compiled or loaded from the persistent cache;
3. serve the run's requests, arriving as the mix says (open loop over
   ``seconds``, or a backlog due at once), and drain them; with ``trace``
   the profiler covers this serve;
4. read the metrics by name from ``bench/metrics/<name>.py``;
5. free the program's state and run the comparison (``check.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache: a fixed path inside the checkout (the
# path is part of the cache key), so only a checkout's first run compiles
CACHE_DIR = ROOT / ".jax_cache"

# the family of a configuration file that names none
DEFAULT_FAMILY = "dense_gqa"


def use_compile_cache() -> None:
    """Point JAX's persistent compilation cache at ``CACHE_DIR`` and cache
    every program; call before the first compile."""
    import jax
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size cap, so no eviction bookkeeping beside each entry: a cap set
    # in the environment made every write fail on a missing access-time file
    jax.config.update("jax_compilation_cache_max_size", -1)


def log(phase: str, **fields) -> None:
    """One progress line on standard error."""
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          file=sys.stderr, flush=True)


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """The benchmark's files: ``spec`` (BENCHMARK.json) and the directory
    holding ``configs/``, ``families/``, ``traffic/``, ``limits/``,
    ``metrics/`` and ``peaks.json``."""

    def __init__(self, spec: Path = ROOT / "BENCHMARK.json",
                 home: Path = ROOT / "bench"):
        self.spec = load_json(spec)
        self.home = Path(home)

    def cell(self, name: str) -> Dict:
        for c in self.spec["workloads"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        return load_json(self.home / "configs" / f"{name}.json")

    def mix(self, name: str) -> Dict:
        return load_json(self.home / "traffic" / f"{name}.json")

    def limits(self, cell: str) -> Dict:
        return load_json(self.home / "limits" / f"{cell}.json")

    def peaks(self, device_kind: str) -> Dict:
        table = load_json(self.home / "peaks.json")["devices"]
        if device_kind not in table:
            raise KeyError(f"device {device_kind!r} is not in bench/peaks.json")
        return table[device_kind]

    def metrics(self, cell: str, trace: bool) -> List[Dict]:
        """The cell's end-to-end metrics (``trace`` off) or per-layer
        metrics (``trace`` on); a metric without ``workloads`` applies
        wherever the end-to-end metric it moves is reported."""
        e2e = [m for m in self.spec["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not trace:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in names)]

    def _load(self, kind: str, name: str):
        path = self.home / kind / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reader(self, metric: str):
        return self._load("metrics", metric).read

    def family(self, name: str):
        """The module ``families/<name>.py``, which holds every fact of one
        architecture that the benchmark uses:

        * ``model_fields(conf)``: the ``ModelConfig`` fields a configuration
          file sets, laid over its registry entry;
        * ``layout(sizes)``: ``path -> shape`` of the parameter tree that
          ``weights.fill`` requires of the program and fills;
        * ``fan_in(path, shape)``: a leaf's contracted size, its standard
          deviation ``fan_in ** -0.5``;
        * ``logits(params, sizes, tokens, rows, *, precision, pad, block)``:
          the plain float32 reference, and its float8 control;
        * ``matmul_params(sizes)``: weights multiplied once per token in
          the ``layers`` and in the ``head``;
          ``attention_flops(blocks, block, sizes)``: prefill attention
          over ``blocks`` (block x block) tiles kept in each head of each
          layer; ``key_flops(sizes)``: decode attention per key of
          context; both summed over heads and layers."""
        return self._load("families", name)


@dataclasses.dataclass
class Run:
    """What one run leaves for the metric readers."""
    cell: Dict
    family: ModuleType        # the configuration's family module
    sizes: Dict               # the configuration file's model sizes
    block: int                # the pattern block size (tokens)
    requests: List            # repro Request objects, all offered
    setup_s: float
    window_s: float           # host wall of the measured serve
    compiles: int             # programs compiled or loaded inside it
    pages_exhausted_steps: int
    peaks: Dict
    trace: Optional[object] = None    # xplane.Summary of the traced serve

    @property
    def done(self) -> List:
        return [r for r in self.requests if r.state == "done"]


class CompileCounter:
    """Backend compiles and persistent-cache loads, from JAX's monitoring
    events (the idea of chip_smoke.py's CompileClock)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax
        self.counts = defaultdict(int)
        jax.monitoring.register_event_duration_secs_listener(
            lambda e, s, **_: self._hit(e))
        jax.monitoring.register_event_listener(lambda e, **_: self._hit(e))

    def _hit(self, event: str) -> None:
        self.counts[event] += 1

    def total(self) -> int:
        return sum(self.counts[e] for e in self.EVENTS)


def model_config(conf: Dict, family):
    """The program's ModelConfig for a configuration file: the registry
    entry with every field the ``family`` maps from the file laid over
    it."""
    from repro.configs import get_config
    from repro.configs.base import SharePrefillConfig
    cfg = get_config(conf["registry"])
    over = family.model_fields(conf)
    over["dtype"] = conf["dtype"]
    if "share_prefill" in conf:
        over["share_prefill"] = SharePrefillConfig(**conf["share_prefill"])
    return dataclasses.replace(cfg, **over)


def _requests(offers):
    from repro.serving import Request
    return [Request(uid=o.uid, prompt=np.asarray(o.prompt, np.int32),
                    max_new_tokens=o.max_new, arrival_s=o.arrival_s)
            for o in offers]


class Session:
    """One cell's program, built and warmed up once (set-up), then driven
    by :meth:`serve`; ``bench/calibrate.py`` reuses it across seeds."""

    def __init__(self, bench: Bench, name: str, seed: int, seconds: float,
                 *, t_start: float, device=None, log=lambda *a, **k: None):
        import jax
        import jax.numpy as jnp
        from repro.models import build_model
        from repro.serving import EngineConfig, ServingEngine

        self.bench, self.name, self.log = bench, name, log
        self.cell = bench.cell(name)
        self.conf = bench.config(self.cell["config"])
        self.mix = bench.mix(self.cell["traffic"])
        self.limits = bench.limits(name)
        self.dev = device or jax.devices()[0]
        self.peaks = (bench.peaks(self.dev.device_kind)
                      if self.dev.platform == "tpu" else {})
        self.family = bench.family(self.conf.get("family", DEFAULT_FAMILY))
        self.sizes = self.conf["model"]
        self.dtype = jnp.dtype(self.conf["dtype"])
        self.clock = CompileCounter()

        self.model = build_model(model_config(self.conf, self.family))
        self.shape = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.sp = self.model.default_share_prefill()
        fields = {k: tuple(v) if isinstance(v, list) else v
                  for k, v in self.conf["engine"].items()}
        self.engine = ServingEngine(self.model, None, self.sp,
                                    EngineConfig(**fields))
        self.set_weights(seed)
        self.buckets = tuple(self.engine.ecfg.seq_buckets)
        self.warm(seconds)
        log("warm-up", compiles=self.clock.total(),
            s=round(time.perf_counter() - t_start, 3))
        self.t_start = t_start

    def warm(self, seconds: float, n: int = 0) -> None:
        """Serve the warm-up requests of a run of ``seconds`` (or of an
        ``n``-request ladder)."""
        from bench import traffic
        warm = _requests(traffic.warmup_offers(
            self.mix, seconds, self.sizes["vocab_size"], self.buckets, n=n))
        self.engine.serve(warm)
        bad = [r.uid for r in warm if r.state != "done"]
        if bad:
            raise RuntimeError(f"warm-up requests {bad} did not finish")

    def set_weights(self, seed: int) -> None:
        from bench import weights
        self.engine.params = None
        gc.collect()
        self.engine.params = weights.fill(self.shape, self.family,
                                          self.sizes, seed, self.dtype)

    def serve(self, seed: int, seconds: float, trace: bool = False,
              rate: float = 0.0) -> Run:
        """The measured serve of the seed's requests; with ``trace`` the
        profiler covers it and the run carries the trace's summary."""
        import jax
        from bench import traffic, xplane
        reqs = _requests(traffic.offers(self.mix, seconds, seed,
                                        self.sizes["vocab_size"], rate))
        tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        before = self.clock.total()
        if trace:
            jax.profiler.start_trace(tdir)
        setup_s = time.perf_counter() - self.t_start
        t0 = time.perf_counter()
        self.engine.serve(reqs)
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        compiles = self.clock.total() - before
        summary = None
        if trace:
            t1 = time.perf_counter()
            try:
                summary = xplane.reduce(xplane.load(xplane.find(tdir)),
                                        window_s)
            finally:
                shutil.rmtree(tdir, ignore_errors=True)
            self.log("trace", read_s=round(time.perf_counter() - t1, 3),
                     busy_s=round(summary.busy_s, 3))
        return Run(self.cell, self.family, self.sizes,
                   self.sp.cfg.block_size, reqs, setup_s, window_s, compiles,
                   self.engine.pages_exhausted_steps, self.peaks, summary)

    def metrics(self, run: Run, trace: bool) -> Dict:
        out = {}
        for m in self.bench.metrics(self.name, trace):
            v = self.bench.reader(m["name"])(run)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out

    def reference_params(self, seed: int):
        from bench import weights
        return weights.make(self.family, self.sizes, seed, self.dtype)


def run_cell(bench: Bench, name: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, device=None,
             log=lambda *a, **k: None) -> Dict:
    """Run cell ``name`` once; returns the result line's object."""
    import jax
    from bench import check, xplane

    s = Session(bench, name, seed, seconds, t_start=t_start, device=device,
                log=log)
    run = s.serve(seed, seconds, trace)
    peak = (s.dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    metrics = s.metrics(run, trace)

    # the comparison, once the program's state is freed
    picked = check.sample(run.requests, seed, s.mix["check_tokens"],
                          s.mix["check_requests"])
    s.engine = None
    gc.collect()
    t2 = time.perf_counter()
    gaps = check.reference_gaps(s.family, s.reference_params(seed), s.sizes,
                                picked, pad=s.limits.get("pad", 1024))
    failed = sum(r.state != "done" for r in run.requests)
    numbers = check.numbers(gaps["logit_gap"], failed, s.limits)
    correct = check.correct(numbers, picked)
    log("check", requests=gaps["requests"], tokens=gaps["tokens"],
        s=round(time.perf_counter() - t2, 3))

    device_rec = {"platform": s.dev.platform, "kind": s.dev.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(run.requests),
           "failed": failed, "metrics": metrics, "device": device_rec}
    if run.trace is not None:
        device_rec["busy_s"] = run.trace.busy_s
        device_rec["window_s"] = run.trace.window_s
        out["breakdown"] = xplane.breakdown(run.trace)
    for k, v in numbers.items():
        print(f"check {k}={v['value']} limit={v['limit']}", file=sys.stderr)
    out["check"] = numbers
    return out
