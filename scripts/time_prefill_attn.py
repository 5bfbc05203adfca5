"""Time the dense prefill chunk attention alone on a TPU.

For each shape and chunk start it times, on the host clock around
``--iters`` back-to-back calls that end in ``block_until_ready``:

* ``chunked_full``: ``kernels/chunked.chunked_attention`` over every key of
  the bucket (the quantum before the frontier slice);
* ``chunked_front``: the same over the keys of the chunk's causal frontier;
* ``flash_<bq>x<bk>``: ``kernels/flash_prefill.flash_chunk_attention``;
* ``splash_<b>``: JAX's splash attention with a causal mask offset to the
  chunk (q pre-scaled, GQA K/V repeated outside the timed call).

Each line gives ms per call, the useful causal work's TFLOP/s and its share
of 197 TFLOP/s, and the largest difference from ``chunked_full``.  Runs
only on a TPU::

    python scripts/time_prefill_attn.py [--iters 20] [--out timings.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.chunked import chunked_attention
from repro.kernels.flash_prefill import flash_chunk_attention
from repro.models.common import repeat_kv

PEAK = 197e12
CHUNK = 1024
# (name, q heads, kv heads, qk width, v width, bucket, chunk starts)
SHAPES = [
    ("mla_32k", 16, 16, 192, 128, 32768, (0, 15, 31)),
    ("gqa_8k", 16, 8, 128, 128, 8192, (0, 3, 7)),
]
FLASH = ((512, 512), (1024, 512), (1024, 1024))


def causal_flops(h, cn, off, d, dv):
    """QK^T and PV over the chunk's causal pairs, all heads."""
    pairs = cn * off + cn * (cn + 1) / 2
    return 2.0 * h * pairs * (d + dv)


def timed(fn, args, iters):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        res = fn(*args)
    jax.block_until_ready(res)
    return out, (time.perf_counter() - t0) / iters


def splash_fn(h, cn, off, d, block):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    front = off + cn
    mask = sm.MultiHeadMask([sm.CausalMask((cn, front), offset=off)
                             for _ in range(h)])
    bq, bkv = min(block, cn), min(block, front)
    kern = sk.make_splash_mha(
        mask, head_shards=1, q_seq_shards=1,
        block_sizes=sk.BlockSizes(block_q=bq, block_kv=bkv,
                                  block_kv_compute=bkv))
    scale = 1.0 / d ** 0.5

    def fn(q_c, kx, vx):
        q_s = (q_c[0].astype(jnp.float32) * scale).astype(q_c.dtype)
        return kern(q_s, kx[0, :, :front], vx[0, :, :front])[None]
    return fn


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    rows = []
    for name, h, hkv, d, dv, n, starts in SHAPES:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, h, n, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, hkv, n, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, hkv, n, dv), jnp.bfloat16)
        kx, vx = repeat_kv(k, h // hkv), repeat_kv(v, h // hkv)
        for c in starts:
            off = c * CHUNK
            front = off + CHUNK
            q_c = q[:, :, off:front]
            cands = {
                "chunked_full": (jax.jit(lambda q_c, kx, vx, off=off:
                                         chunked_attention(
                                             q_c, kx, vx, block_size=128,
                                             q_offset=off)[0]),
                                 (q_c, kx, vx)),
                "chunked_front": (jax.jit(lambda q_c, kx, vx, off=off,
                                          front=front: chunked_attention(
                                              q_c, kx[:, :, :front],
                                              vx[:, :, :front],
                                              block_size=128,
                                              q_offset=off)[0]),
                                  (q_c, kx, vx)),
            }
            for bq, bk in FLASH:
                cands[f"flash_{bq}x{bk}"] = (
                    jax.jit(lambda q_c, k, v, off=off, bq=bq, bk=bk:
                            flash_chunk_attention(q_c, k, v, q_offset=off,
                                                  block_q=bq, block_k=bk)),
                    (q_c, k, v))
            for b in (512, 1024):
                cands[f"splash_{b}"] = (jax.jit(splash_fn(h, CHUNK, off, d,
                                                          b)), (q_c, kx, vx))
            flops = causal_flops(h, CHUNK, off, d, dv)
            ref = None
            for cand, (fn, fargs) in cands.items():
                try:
                    out, s = timed(fn, fargs, args.iters)
                except Exception as e:      # noqa: BLE001 — report, go on
                    row = dict(shape=name, start=c, cand=cand,
                               error=f"{type(e).__name__}: {e}"[:300])
                else:
                    out = np.asarray(out, np.float32)
                    if ref is None:
                        ref = out
                    row = dict(shape=name, start=c, cand=cand, ms=s * 1e3,
                               tflops=flops / s / 1e12,
                               roofline_pct=100 * flops / s / PEAK,
                               max_abs_diff=float(np.abs(out - ref).max()))
                rows.append(row)
                print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(dict(device=dev.device_kind, rows=rows), f, indent=1)


if __name__ == "__main__":
    main()
