"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --smoke --steps 50 --task lm

On this CPU container ``--smoke`` (reduced config) is the practical mode;
the full configs are exercised via the dry-run.  On real hardware the same
entry point runs the production mesh: params/opt-state shardings come from
repro.distributed.param_specs and the train step is pjit'd.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.data import DataConfig, batches
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.training import TrainConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--task", default="lm")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--metrics-out")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, task=args.task)
    tcfg = TrainConfig(num_steps=args.steps, microbatches=args.microbatches,
                       warmup_steps=max(args.steps // 10, 1),
                       optimizer=AdamWConfig(learning_rate=args.lr))

    extra_fn = None
    if cfg.family == "vlm":
        def extra_fn(batch):
            b, s = batch["tokens"].shape
            return {"positions": jnp.broadcast_to(
                jnp.arange(s)[None, None], (3, b, s))}
    elif cfg.family == "encdec":
        def extra_fn(batch):
            b = batch["tokens"].shape[0]
            return {"embeds": jnp.zeros(
                (b, cfg.encdec.encoder_seq_len, cfg.d_model))}

    def log(step, m):
        print(f"step {step:5d} loss={m['total_loss']:.4f} "
              f"ppl={m['perplexity']:.2f} acc={m['accuracy']:.3f} "
              f"gnorm={m['grad_norm']:.2f} wall={m['wall_s']:.1f}s")

    t0 = time.time()
    params, opt_state, history = train(
        model, tcfg, batches(dcfg), ckpt_dir=args.ckpt_dir,
        extra_kwargs_fn=extra_fn, log_fn=log)
    print(f"done in {time.time() - t0:.1f}s; "
          f"final loss {history['total_loss'][-1]:.4f}")
    if args.metrics_out:
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=1)


if __name__ == "__main__":
    main()
