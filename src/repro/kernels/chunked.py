"""Chunked exact attention in pure JAX (flash-style lax.scan over q blocks).

This is the O(N)-memory attention used (a) as the differentiable training
attention, (b) as the numerical fallback of the sparse execution path
(:func:`repro.kernels.sparse_attention_fn`) on shapes the Pallas kernel
cannot take — non-block-aligned sequences, too-few blocks — and (c) as the
large-N variant of the block-sparse oracle.  Semantics match
:mod:`repro.kernels.ref` exactly; tests assert allclose between the two and
against the Pallas kernel.

Accepts an optional block mask: masked blocks contribute nothing to the
softmax and carry −inf in the emitted Ã, token-for-token identical to the
Pallas block-sparse kernel — but as a *dense* path it issues the FLOPs for
every block.  It is the oracle and the fallback, not the sparse hot path:
the default SharePrefill backend is ``repro.kernels.sparse_attention_fn``,
whose Pallas kernel skips inactive blocks (compute *and* DMA) on TPU.

The dense chunked-admission quantum (``models/chunked_prefill.py``
``chunk_prefill_attn`` without masks) reads only the keys of its chunk's
causal frontier: on TPU through the Pallas flash kernel of
``kernels/flash_prefill.py``, elsewhere (and under a sliding window, or on
shapes the kernel cannot tile) through this function over the K/V sliced
to that frontier.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = float("-inf")

# below this block size, the divisor fallback pads instead of shrinking
_MIN_FALLBACK_BLOCK = 16


def largest_divisor_block(n: int, nkv: int, block_size: int) -> int:
    """Largest common divisor of ``n`` and ``nkv`` that is ≤ ``block_size``.

    The naive ``while n % bs: bs -= 1`` fallback degrades to ``bs == 1`` for
    prime-ish sequence lengths — an O(N)-iteration scan of 1-row blocks.
    Searching the divisors of gcd(n, nkv) from ``block_size`` down finds the
    best block in O(block_size) host-side work at trace time.
    """
    g = math.gcd(n, nkv)
    for bs in range(min(block_size, g), 0, -1):
        if g % bs == 0:
            return bs
    return 1


def chunked_attention(
    q: jnp.ndarray,                     # (B, H, N, Dqk)
    k: jnp.ndarray,                     # (B, H, Nkv, Dqk)  (kv pre-expanded)
    v: jnp.ndarray,                     # (B, H, Nkv, Dv)
    *,
    block_size: int = 128,
    causal: bool = True,
    block_mask: Optional[jnp.ndarray] = None,   # (B, H, NBq, NBkv) bool
    window: int = 0,                    # sliding window in tokens (0 = full)
    sink: int = 0,                      # always-visible prefix tokens
    collect_stats: bool = False,
    q_offset: Optional[int] = None,     # global position of q row 0 (tokens)
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Exact attention, scanned over query blocks.

    Returns ``(out (B,H,N,Dv), a_tilde (B,H,NBq,NBkv) | None)``.

    ``q_offset`` places the queries inside the key timeline: q row ``i`` is
    global position ``q_offset + i``.  The default ``Nkv − N`` keeps the
    legacy suffix alignment (one-shot prefill, decode tails); chunked
    prefill passes the chunk's token cursor so an interior Q-chunk sees the
    causal/window bounds of its own rows.

    When no block mask is given and no usable divisor of ``N`` exists (see
    :func:`largest_divisor_block`), the inputs are zero-padded to the
    requested block.  ``out`` is sliced back to ``N``; ``a_tilde`` then
    follows the *padded* block grid — padded queries/keys are excluded from
    every block mean (rows/blocks touching only padding are −inf), but
    callers that need an exact N-aligned grid should pass block-aligned
    inputs.
    """
    b, h, n, d = q.shape
    nkv = k.shape[2]
    n_orig, nkv_orig = n, nkv
    pad_q = pad_kv = 0
    if block_mask is None:
        # no mask to respect — shrink to the largest divisor, or, when only
        # a degenerate block divides (prime-ish N), pad to the requested
        # block instead of scanning 1-row blocks
        best = largest_divisor_block(n, nkv, block_size)
        if best >= min(block_size, _MIN_FALLBACK_BLOCK):
            block_size = best
        else:
            pad_q = -n % block_size
            pad_kv = -nkv % block_size
            if pad_q or pad_kv:
                q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
                k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
                n, nkv = n + pad_q, nkv + pad_kv
    nbq = n // block_size
    nbkv = nkv // block_size
    scale = 1.0 / (d ** 0.5)
    q32 = jnp.asarray(q, jnp.float32)
    k32 = jnp.asarray(k, jnp.float32)
    v32 = jnp.asarray(v, jnp.float32)
    # query i is global position i+offset (original, pre-pad alignment)
    offset = (nkv_orig - n_orig) if q_offset is None else int(q_offset)

    kpos = jnp.arange(nkv)

    def body(carry, i):
        del carry
        qb = jax.lax.dynamic_slice_in_dim(q32, i * block_size, block_size, 2)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qb, k32) * scale
        qidx = i * block_size + jnp.arange(block_size)
        qpos = qidx + offset
        valid = jnp.ones((block_size, nkv), dtype=bool)
        if pad_kv:
            valid &= kpos[None, :] < nkv_orig
        if pad_q:
            # padded query rows must not leak into collect_stats block means
            valid &= qidx[:, None] < n_orig
        if causal:
            valid &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            in_win = (qpos[:, None] - kpos[None, :]) < window
            valid &= in_win | (kpos[None, :] < sink)
        if block_mask is not None:
            row = jax.lax.dynamic_slice_in_dim(block_mask, i, 1, 2)[:, :, 0]
            tokrow = jnp.repeat(row, block_size, axis=-1)     # (B,H,Nkv)
            valid = valid[None, None] & tokrow[:, :, None, :]
        else:
            valid = jnp.broadcast_to(valid[None, None],
                                     (b, h, block_size, nkv))
        masked = jnp.where(valid, logits, NEG_INF)
        m = jnp.max(masked, axis=-1, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.where(valid, jnp.exp(masked - m), 0.0)
        denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        ob = jnp.einsum("bhqk,bhkd->bhqd", p / denom, v32)

        if collect_stats:
            lg = logits.reshape(b, h, block_size, nbkv, block_size)
            vd = valid.reshape(b, h, block_size, nbkv, block_size)
            cnt = jnp.sum(vd, axis=(2, 4))
            s = jnp.sum(jnp.where(vd, lg, 0.0), axis=(2, 4))
            stats = jnp.where(cnt > 0, s / jnp.maximum(cnt, 1), NEG_INF)
        else:
            stats = jnp.zeros((b, h, 0), jnp.float32)
        return None, (jnp.asarray(ob, q.dtype), stats)

    _, (blocks, stats) = jax.lax.scan(body, None, jnp.arange(nbq))
    out = jnp.moveaxis(blocks, 0, 2).reshape(b, h, n, -1)
    if pad_q:
        out = out[:, :, :n_orig]
    if collect_stats:
        a_tilde = jnp.moveaxis(stats, 0, 2)                   # (B,H,NBq,NBkv)
        return out, a_tilde
    return out, None


def chunked_attention_fn(*, block_size: int, causal: bool = True):
    """AttentionFn adapter for repro.core.share_attention (single sample,
    (H, N, D) q and un-expanded (Hkv, N, D) k/v, always collects Ã)."""
    def fn(q, k, v, masks):
        from repro.kernels.ops import expand_kv
        k, v = expand_kv(k, v, q.shape[0])
        out, a_tilde = chunked_attention(
            q[None], k[None], v[None], block_size=block_size,
            causal=causal, block_mask=masks[None], collect_stats=True)
        return out[0], a_tilde[0]
    return fn
