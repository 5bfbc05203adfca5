"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.  JAX's persistent compilation cache lives at
``<checkout>/.jax_cache``, a fixed path, so only the first run of a cell in
a checkout compiles.  The last line of standard output is the result: one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` with ``--trace 1``), and last the numbers the
correctness check compared, each with its limit; the same numbers are the
last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import Bench, log, run_cell, use_compile_cache
    bench = Bench()
    cell = bench.cell(args.workload)

    import jax
    use_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log("device", error=f"needs a TPU, found {devices[0].platform}")
        return 1
    if len(devices) < cell["chips"]:
        log("device", error=f"needs {cell['chips']} chips, found "
            f"{len(devices)}")
        return 1
    bench.peaks(devices[0].device_kind)         # unknown device: an error
    log("device", kind=devices[0].device_kind, count=len(devices))

    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start=T_START, device=devices[0],
                   log=log)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
