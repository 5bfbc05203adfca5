"""Smoke run of the paged SharePrefill serve on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the serve, sharded over four chips

With one chip, in one process:

  (a) device check: anything but a TPU exits non-zero before any result;
  (b) the three Pallas kernels of the serving path against their plain-jnp
      oracles at internlm2-1.8b widths (16 query / 8 kv heads, head_dim
      128): batched block-sparse prefill over 8,192 tokens with a mask of
      about half density, one chunk launch of the same kernel
      (``q_block_offset``), the strip scorer, and sparse paged decode over
      8 slots;
  (c) one serve through the ``repro.launch.serve`` entry point at the full
      published width of internlm2-1.8b with random weights from seed 0:
      4 requests of 4,096 tokens, two sharing a prompt, 32 new tokens
      each, paged KV with chunked admission, prefix sharing and sparse
      decode.  Every request must finish ``done`` with 32 tokens and no
      error (the scheduler's finite-logits guard ran on every step), the
      page pool must drain, and the lowered prefill (the ``layer_begin``
      quantum, which scores strips), chunk-attention and decode programs
      must carry a Mosaic kernel (``tpu_custom_call``) rather than the
      chunked or einsum fallbacks.

``--chips 4`` runs only the serve of (c), twice: on one chip, then
heads-sharded over ``make_serving_mesh(4)`` with the weights split by
``distributed/param_specs.py`` and the page pool split along the kv heads.
The greedy tokens of the two serves must match.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
a failed check raises before it is printed.  Timings printed on the way
are smoke readings, not benchmark results.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

ARCH = "internlm2-1.8b"
KERNEL_TOKENS = 8192
DECODE_SLOTS = 8
SERVE_ARGV = ["--arch", ARCH, "--paged", "--scheduler", "--decode-sparse",
              "--method", "share", "--prefix-sharing", "--repeat-prompt", "2",
              "--prefill-chunk", "1024", "--max-batch", "4",
              "--num-requests", "4", "--prompt-len", "4096", "--max-new", "32"]
BF16_TOL = dict(atol=2e-2, rtol=2e-2)


def _log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _close(name: str, got, want, *, atol: float, rtol: float) -> float:
    """Assert ``got`` ≈ ``want`` (non-finite entries must coincide);
    returns the largest absolute difference over the finite entries."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    fin = np.isfinite(want)
    if not np.array_equal(np.isfinite(got), fin):
        raise AssertionError(f"{name}: non-finite entries differ")
    np.testing.assert_allclose(got[fin], want[fin], atol=atol, rtol=rtol,
                               err_msg=name)
    return float(np.max(np.abs(got[fin] - want[fin]), initial=0.0))


# --------------------------------------------------------------------------
# (b) kernels against their oracles
# --------------------------------------------------------------------------

def check_prefill(key, *, n: int, bs: int, h: int, hkv: int, d: int) -> None:
    """Batched prefill (full launch and one chunk launch) vs
    ``block_sparse_attention_ref``."""
    import jax
    import jax.numpy as jnp
    from repro.core.patterns import causal_block_mask
    from repro.kernels import batched_sparse_attention_fn
    from repro.kernels.ref import block_sparse_attention_ref

    kq, kk, kv, km = jax.random.split(key, 4)
    nb, g = n // bs, h // hkv
    q = jax.random.normal(kq, (1, h, n, d), jnp.bfloat16)
    k = jax.random.normal(kk, (1, hkv, n, d), jnp.bfloat16)
    v = jax.random.normal(kv, (1, hkv, n, d), jnp.bfloat16)
    causal = causal_block_mask(nb)
    mask = ((jax.random.bernoulli(km, 0.5, (1, h, nb, nb))
             | jnp.eye(nb, dtype=bool)) & causal)
    density = float(mask.sum() / (h * causal.sum()))

    t0 = time.time()
    out, at = jax.block_until_ready(
        batched_sparse_attention_fn(block_size=bs)(q, k, v, mask))
    full_s = time.time() - t0

    @jax.jit
    def oracle(q, k, v, mask):
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(
                lambda a: block_sparse_attention_ref(
                    a[0][None], a[1][None], a[2][None], a[3][None],
                    block_size=bs),
                (q[0], jnp.repeat(k[0], g, 0), jnp.repeat(v[0], g, 0),
                 mask[0]))

    ref_out, ref_at = oracle(q, k, v, mask)
    ref_out, ref_at = ref_out[:, 0], ref_at[:, 0]
    d_out = _close("prefill out", out[0], ref_out, **BF16_TOL)
    d_at = _close("prefill stats", at[0], ref_at, **BF16_TOL)

    # one chunk launch: 1,024 query tokens (at most half the prompt) from
    # the middle of the prompt
    cq = max(min(1024 // bs, nb // 2), 1)
    c0 = (nb - cq) // 2
    rows = slice(c0 * bs, (c0 + cq) * bs)
    out_c, at_c = jax.block_until_ready(
        batched_sparse_attention_fn(block_size=bs, q_block_offset=c0)(
            q[:, :, rows], k, v, mask[:, :, c0:c0 + cq]))
    d_out_c = _close("chunk out", out_c[0], ref_out[:, rows], **BF16_TOL)
    d_at_c = _close("chunk stats", at_c[0], ref_at[:, c0:c0 + cq],
                    **BF16_TOL)
    same = bool(jnp.array_equal(out_c, out[:, :, rows]))
    _log("kernel prefill", tokens=n, block=bs, density=round(density, 4),
         max_abs_out=d_out, max_abs_stats=d_at, first_call_s=round(full_s, 3))
    _log("kernel chunk", q_block_offset=c0, q_tokens=cq * bs,
         max_abs_out=d_out_c, max_abs_stats=d_at_c,
         bitwise_equal_to_full_launch_rows=same)


def check_strip(key, *, n: int, bs: int, h: int, hkv: int, d: int) -> None:
    """Strip scorer vs the ``strip_scores`` oracle."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.strip import compute_strips

    kq, kk = jax.random.split(key)
    q = jax.random.normal(kq, (h, n, d), jnp.bfloat16)
    k = jax.random.normal(kk, (hkv, n, d), jnp.bfloat16)
    got = jax.block_until_ready(
        compute_strips(q, k, block_size=bs, impl="pallas"))
    with jax.default_matmul_precision("highest"):
        want = compute_strips(q.astype(jnp.float32), k.astype(jnp.float32),
                              block_size=bs, impl="jnp")
    diff = _close("strip", got, want, atol=1e-5, rtol=2e-2)
    _log("kernel strip", tokens=n, block=bs, max_abs=diff)


def check_decode(key, *, n: int, bs: int, h: int, hkv: int, d: int,
                 slots: int) -> None:
    """Sparse paged decode vs ``decode_attention_ref`` per slot and head."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attn import (DecodePlan, flash_decode_plan_paged,
                                           gather_pages)
    from repro.kernels.indices import compact_block_mask
    from repro.kernels.ref import decode_attention_ref

    kq, kk, kv, kp, kl, kb = jax.random.split(key, 6)
    nb, g = n // bs, h // hkv
    pages = slots * nb + 1                          # page 0 is the null page
    q = jax.random.normal(kq, (slots, h, d), jnp.bfloat16)
    pool_k = jax.random.normal(kk, (pages, hkv, bs, d), jnp.bfloat16)
    pool_v = jax.random.normal(kv, (pages, hkv, bs, d), jnp.bfloat16)
    table = (jax.random.permutation(kp, pages - 1) + 1).reshape(slots, nb)
    lens = jax.random.randint(kl, (slots,), bs, n + 1)
    valid = jnp.arange(n)[None, :] < lens[:, None]                 # (B, S)
    live = jnp.arange(nb)[None, :] * bs < lens[:, None]            # (B, NB)
    last = (jnp.arange(nb)[None, :] == (lens[:, None] - 1) // bs)  # (B, NB)
    keep = ((jax.random.bernoulli(kb, 0.5, (slots, hkv, nb, g))
             | last[:, None, :, None]) & live[:, None, :, None])
    idx, cnt = compact_block_mask(jnp.any(keep, axis=-1))
    plan = DecodePlan(idx.astype(jnp.int32), cnt, keep)
    got = jax.block_until_ready(flash_decode_plan_paged(
        q, pool_k, pool_v, table.astype(jnp.int32), plan, valid,
        impl="kernel"))

    kc = jnp.repeat(gather_pages(pool_k, table), g, axis=1)       # (B,H,S,D)
    vc = jnp.repeat(gather_pages(pool_v, table), g, axis=1)
    tok = (jnp.repeat(jnp.moveaxis(keep, -1, -2).reshape(slots, h, nb), bs,
                      axis=-1) & valid[:, None, :])               # (B, H, S)
    one = lambda qh, kh, vh, mh: decode_attention_ref(
        qh[None], kh[None], vh[None], length_mask=mh)[0]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(jax.vmap(one)))(q, kc, vc, tok)
    diff = _close("decode", got, want, **BF16_TOL)
    density = float(keep.sum() / (g * live.sum() * hkv))
    _log("kernel decode", slots=slots, tokens=n, block=bs,
         keep_density=round(density, 4), max_abs=diff)


# --------------------------------------------------------------------------
# (c) the serve
# --------------------------------------------------------------------------

class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.secs = defaultdict(float)
        self.counts = defaultdict(int)
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_) -> None:
        self.secs[event] += secs

    def _event(self, event: str, **_) -> None:
        self.counts[event] += 1

    def snapshot(self):
        return (self.secs["/jax/core/compile/backend_compile_duration"],
                self.counts["/jax/compilation_cache/cache_hits"])


def _custom_call_programs(dump_dir: str, names) -> dict:
    """For each jitted program name, whether any lowered module of that
    name carries a Mosaic kernel."""
    found = {}
    for name in names:
        files = glob.glob(os.path.join(dump_dir, f"*jit_{name}_*.mlir"))
        found[name] = any("tpu_custom_call" in Path(f).read_text()
                          for f in files)
    return found


def run_serve(argv, programs, clock: CompileClock, devices):
    """One serve through the launcher; checks what (c) promises and
    returns ``{uid: tokens}``."""
    import jax
    from repro.launch import serve as serve_cli

    args = serve_cli.parse_args(argv)
    c0, h0 = clock.snapshot()
    with tempfile.TemporaryDirectory() as dump:
        jax.config.update("jax_dump_ir_to", dump)
        jax.config.update("jax_include_debug_info_in_dumps", False)
        try:
            engine, requests, wall = serve_cli.serve(args)
        finally:
            jax.config.update("jax_dump_ir_to", "")
        kernels = _custom_call_programs(dump, programs)
    c1, h1 = clock.snapshot()
    serve_cli.report(args, engine, requests, wall)

    for r in requests:
        if r.state != "done" or r.error is not None:
            raise AssertionError(f"request {r.uid} ended {r.state}: {r.error}")
        if len(r.output_tokens) != int(args.max_new):
            raise AssertionError(f"request {r.uid} made "
                                 f"{len(r.output_tokens)} tokens")
    in_use = engine.page_pool_stats.get("pages_in_use_at_end")
    if in_use != 0:
        raise AssertionError(f"page pool holds {in_use} pages after the serve")
    missing = [p for p, ok in kernels.items() if not ok]
    if missing:
        raise AssertionError(f"no tpu_custom_call in programs {missing}")

    mem = [d.memory_stats() or {} for d in devices]
    _log("serve", wall_s=round(wall, 3), compile_s=round(c1 - c0, 3),
         cache_hits=h1 - h0, tpu_custom_call=kernels)
    _log("serve", ttft_s=[round(r.ttft_s, 3) for r in requests],
         decode_tok_per_s_smoke=[round(r.decode_tokens_per_s, 2)
                                 for r in requests],
         block_density=[round(r.pattern_stats["block_density"], 4)
                        if r.pattern_stats else None for r in requests],
         prefix_hits=engine.prefix_stats.get("prefix_hits"))
    _log("memory", bytes_in_use=[m.get("bytes_in_use") for m in mem],
         peak_bytes_in_use=[m.get("peak_bytes_in_use") for m in mem])
    return {r.uid: list(map(int, r.output_tokens)) for r in requests}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the serve, unsharded then sharded")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    _log("device", kind=dev.device_kind, count=len(devices),
         compile_cache=cache_dir)
    clock = CompileClock()

    from repro.configs import get_config
    cfg = get_config(ARCH)
    if args.chips == 1:
        widths = dict(h=cfg.num_heads, hkv=cfg.num_kv_heads,
                      d=cfg.resolved_head_dim)
        key = jax.random.PRNGKey(0)
        for bs in (64, 128):
            k1, k2, k3, key = jax.random.split(key, 4)
            check_prefill(k1, n=KERNEL_TOKENS, bs=bs, **widths)
            check_strip(k2, n=KERNEL_TOKENS, bs=bs, **widths)
            check_decode(k3, n=KERNEL_TOKENS, bs=bs, slots=DECODE_SLOTS,
                         **widths)
        run_serve(SERVE_ARGV, ("layer_begin", "attn", "decode_step"), clock,
                  devices[:1])
    else:
        single = run_serve(SERVE_ARGV, ("layer_begin", "attn", "decode_step"),
                           clock, devices)
        gc.collect()            # drop the first engine's pool and weights
        sharded = run_serve(SERVE_ARGV + ["--model-parallel", "4"],
                            ("prefill_step", "decode_step"), clock, devices)
        if single != sharded:
            # uid -> first generated position where the two serves differ
            first = {u: next(i for i, (a, b) in enumerate(
                         zip(single[u], sharded[u])) if a != b)
                     for u in single if single[u] != sharded[u]}
            raise AssertionError(f"sharded tokens differ: {first}")
        _log("sharded", tokens_match=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
