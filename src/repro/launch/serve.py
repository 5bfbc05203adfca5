"""Serving launcher: long-context requests through the engine.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
        --smoke --num-requests 4 --prompt-len 512 --method share

``--scheduler`` serves through the slot-based continuous-batching
scheduler (per-slot decode positions, EOS early exit, in-flight slot
refill with DecodePlan splicing) instead of batch-at-a-time grouping;
``--arrival-rate R`` simulates a Poisson-ish open-loop arrival process by
spacing request arrivals 1/R seconds apart (the scheduler admits each
request only once it has "arrived"; the batch path records the arrival
only in the queue/TTFT metrics).  ``--max-new`` accepts a comma-separated
list cycled over requests to build mixed-length workloads — the traffic
shape where continuous batching wins (short rows stop idling behind the
batch's longest member).  ``--paged`` serves from the block-paged KV
cache (``repro.serving.paged_cache``): decode state in a shared page pool
addressed through per-slot page tables, one cross-bucket scheduler, and
admission gated on pool headroom (``--num-pages`` caps the pool; 0
auto-sizes it).  ``--prefix-sharing`` (paged only) serves duplicate
prompts from one prefill: a completed prefill publishes its page run to
the prefix index, matching requests map the pages read-only (refcount++)
and skip the launch, and copy-on-write moves writers onto private pages
at the decode boundary — bitwise-invisible, so outputs equal the
unshared serve.  ``--repeat-prompt N`` makes the first N requests share
request 0's prompt so the sharing path is observable from the CLI.

``--model-parallel N`` (N > 1) serves under a heads-sharded (data, model)
mesh: the engine's sparse prefill AND sparse decode hot paths run under
``shard_map`` with per-shard index tables (the mesh-active routing rule —
``repro.distributed.sharding.active_model_mesh``), the weights are split
over the mesh by ``repro.distributed.param_specs`` and the page pool
along the kv heads.  On a CPU container, combine with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to get N
placeholder devices.  Attention is head-parallel with no cross-device
reduction, but the split projections reduce across devices, so logits
can differ from the unsharded serve in the last bits.
``--decode-sparse`` additionally reuses the prefill pattern dictionary
for decode via the build-once DecodePlan.

The exit code is 1 when any request ends ``failed`` (the per-request
quarantine keeps the serve going past it), else 0.

``--refresh-every N`` (paged + ``--decode-sparse``) turns on adaptive
pattern refresh during long decodes: every N generated tokens a slot's
plan row is re-estimated from the strip scores of its recent-query
window, collapsing the grown dense tail to a bounded horizon under
per-head score-mass budgets (``--refresh-mass``).  Refresh trades the
frozen-plan bitwise guarantee for measured decode-traffic reduction;
with the default 0 the serve is bitwise-identical to the frozen path.
"""
from __future__ import annotations

import argparse
import contextlib
import sys
import time

import jax

from repro.configs import get_config, get_smoke_config
from repro.data import DataConfig, sample
from repro.distributed.param_specs import param_shardings
from repro.distributed.sharding import ShardingRules, use_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.serving import EngineConfig, Request, ServingEngine


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--num-requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--max-new", default="8",
                    help="tokens to generate; a comma-separated list is "
                    "cycled over requests (mixed-length workload)")
    ap.add_argument("--scheduler", action="store_true",
                    help="slot-based continuous batching (per-slot decode "
                    "positions, EOS early exit, in-flight slot refill) "
                    "instead of batch-at-a-time")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="step-cadence chunked admission: tokens per "
                    "prefill quantum interleaved with decode steps (0 = "
                    "whole-sequence one-shot admission); scheduler only")
    ap.add_argument("--prefill-pack", type=int, default=1,
                    help="pack up to N same-bucket queued prompts into one "
                    "chunked prefill run (block-diagonal isolation mask, "
                    "one slot per segment); needs --prefill-chunk")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache: decode state in a shared "
                    "page pool with per-slot page tables (page_size == "
                    "pattern block size); ONE cross-bucket scheduler, "
                    "admission gated on pool headroom")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool capacity incl. the reserved null page "
                    "(0 = auto-size so max-batch slots can never starve); "
                    "undersized pools keep requests WAITING, never crash")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="prefill-once prompt sharing over the paged pool: "
                    "duplicate (clipped) prompts map the donor's KV pages "
                    "read-only and skip their prefill launch; bitwise-"
                    "invisible (COW at the decode boundary); needs --paged")
    ap.add_argument("--repeat-prompt", type=int, default=0,
                    help="first N requests reuse request 0's prompt (a "
                    "shared-prefix workload for --prefix-sharing)")
    ap.add_argument("--preempt-after", type=int, default=0,
                    help="preempt the lowest-priority decoding victim once "
                    "admission has been pool-starved for this many "
                    "consecutive steps (paged only; 0 = never preempt — "
                    "starved requests wait indefinitely)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request wall budget from arrival; exceeded "
                    "requests finish with reason 'timeout' (0 = none; "
                    "scheduler only)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="simulated request arrivals per second (0 = all "
                    "requests arrive at once); the scheduler honours "
                    "arrival times for admission")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="decode slots (scheduler) / batch size (legacy)")
    ap.add_argument("--method", default="share",
                    choices=["share", "dense", "vertical_slash", "flex"])
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "sparse", "chunked"],
                    help="prefill attention backend (sparse = the Pallas "
                    "kernel unconditionally, interpret mode off-TPU)")
    ap.add_argument("--decode-sparse", action="store_true",
                    help="decode-phase pattern sharing via the build-once "
                    "DecodePlan (needs --method share)")
    ap.add_argument("--refresh-every", type=int, default=0,
                    help="adaptive pattern refresh: re-estimate a slot's "
                    "decode plan from the strip scores of its recent-query "
                    "window every N decode steps (paged + --decode-sparse "
                    "only; 0 = frozen plans, the bitwise default)")
    ap.add_argument("--refresh-mass", type=float, default=0.95,
                    help="per-head cumulative score-mass budget a refreshed "
                    "row must cover (higher = wider keep-sets)")
    ap.add_argument("--refresh-tail-threshold", type=float, default=0.0,
                    help="also refresh early when a slot's dense-tail "
                    "fraction crosses this value (0 = cadence only)")
    ap.add_argument("--model-parallel", type=int, default=0,
                    help="model-axis size of the serving mesh; > 1 runs "
                    "prefill and decode heads-sharded under shard_map")
    ap.add_argument("--task", default="retrieval")
    return ap.parse_args(argv)


def serve(args: argparse.Namespace):
    """Build the model, requests and engine the flags describe and serve
    the requests; returns ``(engine, requests, wall_s)``."""
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    # weights are served in the compute dtype.  With f32 weights the
    # activations and KV pages are f32 too, and on a TPU every step program
    # also holds a bf16 copy of all scanned layer weights as temp (the MXU
    # multiplies f32 at default precision in bf16): internlm2-1.8b's 4x4k
    # serve then does not fit a 16 GB v5e.  One jitted init never holds the
    # f32 weights: under a mesh each device makes only its own slice, split
    # by the per-leaf specs (the page pool follows along Hkv)
    def init(key):
        return jax.tree.map(lambda p: p.astype(cfg.dtype), model.init(key))

    key = jax.random.PRNGKey(0)
    mesh, shardings = None, None
    if args.model_parallel > 1:
        mesh = make_serving_mesh(args.model_parallel)
        shardings = param_shardings(jax.eval_shape(init, key), mesh,
                                    fsdp=False)
    params = jax.jit(init, out_shardings=shardings)(key)
    sp = model.default_share_prefill()

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.prompt_len,
                      global_batch=1, task=args.task)
    max_new = [int(m) for m in str(args.max_new).split(",")]
    gap = 1.0 / args.arrival_rate if args.arrival_rate > 0 else 0.0
    requests = [
        Request(uid=i,
                prompt=sample(dcfg, 0 if i < args.repeat_prompt
                              else i)["tokens"],
                max_new_tokens=max_new[i % len(max_new)],
                arrival_s=i * gap, deadline_s=args.deadline_s)
        for i in range(args.num_requests)
    ]

    engine = ServingEngine(
        model, params, sp,
        EngineConfig(method=args.method,
                     attn_impl=args.attn_impl,
                     decode_sparse=args.decode_sparse,
                     max_batch=args.max_batch,
                     scheduler=args.scheduler,
                     prefill_chunk=args.prefill_chunk,
                     prefill_pack=args.prefill_pack,
                     paged=args.paged,
                     num_pages=args.num_pages,
                     preempt_after_steps=args.preempt_after,
                     prefix_sharing=args.prefix_sharing,
                     refresh_every=args.refresh_every,
                     refresh_mass=args.refresh_mass,
                     refresh_tail_threshold=args.refresh_tail_threshold,
                     seq_buckets=(args.prompt_len,)))

    # one mesh for the whole serve: prefill and decode trace under the same
    # rules context, so both hot paths resolve their sharded twin
    ctx = contextlib.ExitStack()
    if mesh is not None:
        ctx.enter_context(use_rules(ShardingRules(mesh)))
        ctx.enter_context(mesh)
        print(f"serving under mesh {dict(mesh.shape)}")

    with ctx:
        t0 = time.time()
        engine.serve(requests)
        wall = time.time() - t0
    return engine, requests, wall


def report(args: argparse.Namespace, engine: ServingEngine, requests,
           wall: float) -> None:
    """Print one line per request and the serve's summary."""
    for r in requests:
        m = r.metrics()
        lifecycle = (f" deferred={m['waiting_deferred_steps']}"
                     f" preempts={m['preempted_count']}"
                     if (m["waiting_deferred_steps"]
                         or m["preempted_count"]) else "")
        if r.prefix_hit:
            lifecycle += " prefix-hit"
        if r.refreshes:
            lifecycle += f" refreshes={r.refreshes}"
        err = f" error={r.error}" if r.error is not None else ""
        # plan-shape telemetry: how dense the slot's decode tail is and what
        # fraction of its allocated KV the plan row actually touches — the
        # signals the adaptive refresh acts on (reported with refresh off
        # too, so a frozen serve shows the tail growth refresh would collapse)
        plan_shape = (f" tail={r.tail_fraction:.3f}"
                      f" traffic={r.plan_traffic_fraction:.3f}"
                      if r.plan_traffic_fraction > 0 else "")
        print(f"req {r.uid}: queue={r.queue_s:.3f}s ttft={r.ttft_s:.3f}s "
              f"prefill={r.prefill_s:.3f}s decode={r.decode_s:.3f}s "
              f"({r.decode_tokens_per_s:.1f} tok/s, "
              f"{r.finish_reason}/{r.state}){lifecycle}{plan_shape}{err} "
              f"out={r.output_tokens[:8].tolist()} "
              f"stats={r.pattern_stats}")
    # the engine silently falls back to batch-at-a-time for MLA / the
    # non-transformer families — label the mode by what actually ran
    sched_req = args.scheduler or args.paged
    mode = ("scheduler" if sched_req and engine._supports_scheduler()
            else "batch")
    if sched_req and mode == "batch":
        print("note: --scheduler/--paged requested but this family has no "
              "per-slot cache layout; served batch-at-a-time (dense "
              "carve-out)")
    if mode == "scheduler" and engine._chunk_tokens(args.prompt_len):
        mode = "scheduler-chunked"
    if mode != "batch" and args.paged:
        mode += "-paged"
        pool = {k: round(v, 3) if isinstance(v, float) else v
                for k, v in engine.page_pool_stats.items()}
        print(f"page pool: {pool} admissions deferred on headroom: "
              f"{engine.pages_exhausted_steps}, preemptions: "
              f"{engine.preemptions}")
        if args.prefix_sharing and engine.prefix_stats:
            pfx = {k: round(v, 3) for k, v in engine.prefix_stats.items()}
            print(f"prefix sharing: {pfx}")
        if args.refresh_every > 0:
            print(f"pattern refresh: { {k: int(v) for k, v in engine.refresh_stats.items()} }")
    elif args.prefill_chunk > 0 and args.scheduler:
        print("note: --prefill-chunk requested but this config cannot be "
              "chunk-admitted (see ServingEngine._chunk_tokens); served "
              "with one-shot admission")
    print(f"total wall {wall:.2f}s, method={args.method}, mode={mode}, "
          f"slot occupancy {engine.slot_occupancy():.3f}, "
          f"phase_s={ {k: round(v, 3) for k, v in engine.phase_s.items()} }")


def main(argv=None) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    engine, requests, wall = serve(args)
    report(args, engine, requests, wall)
    # the quarantine wall keeps a serve alive past a failing request; the
    # exit code still says that one failed
    failed = [r.uid for r in requests if r.state == "failed"]
    if failed:
        print(f"error: {len(failed)} request(s) failed: uids {failed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
