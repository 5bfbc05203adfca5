"""Readings that set a cell's rate and limits, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        [--sweep r1,r2,...] [--seeds a,b,...] [--control k]

Set-up is paid once; then, on the same warmed engine:

``--sweep``  serves ``--sweep-n`` requests of the cell's mix (the same
             lengths at every rate) at each offered rate (requests per
             second) and prints whether the backlog grew: the knee is the
             highest rate whose queue waits do not grow from the first
             third of the arrivals to the last and whose drain after the
             last arrival stays near one request's service time.
``--seeds``  serves each seed's requests at the mix's own rate, with that
             seed's weights, and prints the comparison's readings: the
             widest logit gap of the served tokens below the reference's
             best; for the first ``--control`` seeds also the float8
             control's gap and the run's verdict with the control in the
             program's place (``control_correct``, which must be false).

This is not a benchmark run: it prints one JSON line per reading.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--sweep-n", type=int, default=8)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import check, stats
    from bench.harness import Bench, Session, log, use_compile_cache
    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1

    seeds = [int(x) for x in args.seeds.split(",") if x]
    s = Session(Bench(), args.workload, seeds[0] if seeds else 1,
                args.seconds, t_start=T_START, log=log)
    dev = s.dev
    emit(kind="setup", setup_s=time.perf_counter() - T_START,
         bytes_limit=(dev.memory_stats() or {}).get("bytes_limit"),
         peak=(dev.memory_stats() or {}).get("peak_bytes_in_use"))

    rates = [float(x) for x in args.sweep.split(",") if x]
    if rates:
        s.warm(0, n=args.sweep_n)
    for rate in rates:
        run = s.serve(seeds[0] if seeds else 1, args.sweep_n / rate,
                      rate=rate)
        done = run.done
        arr = sorted(done, key=lambda r: r.arrival_s)
        third = max(len(arr) // 3, 1)
        q = [r.queue_s for r in arr]
        last_arrival = max(r.arrival_s for r in run.requests)
        emit(kind="sweep", rate=rate, offered=len(run.requests),
             done=len(done), window_s=run.window_s,
             drain_s=run.window_s - last_arrival,
             ttft_p50_s=stats.percentile([r.ttft_s for r in done], 50),
             ttft_p90_s=stats.percentile([r.ttft_s for r in done], 90),
             queue_first_third_s=sum(q[:third]) / third,
             queue_last_third_s=sum(q[-third:]) / third,
             deferrals=run.pages_exhausted_steps, compiles=run.compiles,
             occupancy=s.engine.slot_occupancy())

    for i, seed in enumerate(seeds):
        if i:
            s.set_weights(seed)
        run = s.serve(seed, args.seconds)
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        met = s.metrics(run, False)
        picked = check.sample(run.requests, seed, s.mix["check_tokens"],
                              s.mix["check_requests"])
        s.engine.params = None
        gc.collect()
        ref = s.reference_params(seed)
        t0 = time.perf_counter()
        gaps = check.reference_gaps(s.family, ref, s.sizes, picked,
                                    control=i < args.control,
                                    pad=s.limits.get("pad", 1024))
        check_s = time.perf_counter() - t0
        failed = len(run.requests) - len(run.done)
        del ref
        gc.collect()
        emit(kind="seed", seed=seed, offered=len(run.requests),
             done=len(run.done), window_s=run.window_s, peak=peak,
             compiles=run.compiles, check_s=check_s,
             sample=[[len(r.prompt), len(r.output_tokens)] for r in picked],
             logit_gap=gaps["logit_gap"],
             first_token_gap=gaps["first_token_gap"],
             control_gap=gaps.get("control_gap"),
             limit=s.limits["logit_gap"],
             correct=check.correct(check.numbers(
                 gaps["logit_gap"], failed, s.limits), picked),
             control_correct=(check.correct(check.numbers(
                 gaps["control_gap"], failed, s.limits), picked)
                 if "control_gap" in gaps else None),
             metrics={k: v["value"] for k, v in met.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
