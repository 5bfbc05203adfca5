"""Pallas TPU flash-decode kernels: query tokens vs a long KV cache.

Decode is memory-bound (EXPERIMENTS.md §Roofline: every decode_32k /
long_500k pair), so these kernels stream the grouped KV cache HBM→VMEM at
most once, keep the GQA query block resident, and support:

  * grouped-query attention without cache expansion (q reshaped to
    (Hkv, G, D); the cache is read once, not ×G);
  * block-skipping via scalar-prefetched block tables — the decode-phase
    pattern-sharing extension: kv blocks outside the keep-set are never
    streamed (same splash machinery as the prefill kernel);
  * running-max online softmax over sequential kv blocks.

Three entry points, from validation to production:

  ``flash_decode``          single-sample (Hkv, S/bs) grid, dense streaming,
                            per-head token ``keep`` mask (validation kernel).
  ``flash_decode_sparse``   single-sample block-skipping variant; rebuilds
                            its block table from the token mask on every call
                            (validation of the skipping machinery only).
  ``flash_decode_plan``     the serving path: batched (B, Hkv, W) grid
                            consuming a prebuilt :class:`DecodePlan` layer
                            slice — tables are built **once per batch**
                            (``repro.serving.decode_plan``), not per decode
                            step, and the backend auto-dispatches: compiled
                            Pallas kernel on TPU, grouped-einsum fallback
                            elsewhere (mirroring ``sparse_attention_fn``).

Validated against :func:`repro.kernels.ref.decode_attention_ref` / the
grouped einsum in interpret mode.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")

DECODE_IMPLS = ("auto", "kernel", "einsum")


class DecodePlan(NamedTuple):
    """Splash block tables for sparse decode — the kernel-side contract.

    Built once per served batch (``repro.serving.decode_plan``) from the
    post-prefill pattern dictionary; leaves may carry a leading layer axis
    (``(L, B, …)``, sliced per layer by the decode scan) or be a single
    layer's slice (``(B, …)``).

      indices:    (…, B, Hkv, W)  int32 — per-(batch, kv-head) active block
                  ids, ascending, padded by repeating the last kept id (the
                  Pallas pipeline elides the repeated DMA).
      counts:     (…, B, Hkv)     int32 — kept entries per table row.
      keep_heads: (…, B, Hkv, NB, G) bool — per-*query-head* block keep bits
                  refining the union table within each GQA group (a visited
                  block can still be masked for some of the group's heads).

    Everything is O(B·Hkv·NB) per layer — the O(B·H·S) token keep-mask the
    engine used to thread through every decode step is gone.

    The batch axis is a set of *slots* under the continuous-batching
    scheduler: the ``valid`` mask the kernels consume is per-row (each slot
    is at its own decode position), table rows are spliced in-flight when a
    slot is refilled (``repro.serving.decode_plan.update_plan_slot``), and
    an unoccupied slot's empty table (``counts == 0``, keep bits all False)
    makes it inert — the kernel's empty-keep contract emits exact zeros and
    the einsum fallback masks everything, so occupied rows are bitwise
    independent of slot churn.
    """

    indices: jnp.ndarray
    counts: jnp.ndarray
    keep_heads: jnp.ndarray


def _auto_interpret(interpret: Optional[bool]) -> bool:
    """Backend-auto: compile the kernel on TPU, interpret elsewhere."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def resolve_decode_impl(impl: str) -> str:
    """Map a decode ``impl`` name to a concrete backend.

    ``auto`` is the serving-safe policy: the compiled block-skipping kernel
    on TPU, the grouped-einsum fallback elsewhere — jitting the Pallas
    *interpreter* at serving cache lengths unrolls its grid into the HLO, so
    interpret mode stays a validation tool unless asked for via ``kernel``.
    """
    if impl == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "einsum"
    if impl not in DECODE_IMPLS:
        raise ValueError(f"unknown decode impl {impl!r}; "
                         f"expected one of {DECODE_IMPLS}")
    return impl


def _kernel(q_ref, k_ref, v_ref, mask_ref,      # VMEM tiles
            out_ref,                             # output
            acc_ref, m_ref, l_ref,               # scratch
            *, block_kv: int, scale: float, kv_steps: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)             # (G, D)
    k = k_ref[0].astype(jnp.float32)             # (bs, D)
    v = v_ref[0].astype(jnp.float32)             # (bs, Dv)
    valid = mask_ref[0]                          # (G, bs) bool

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                          # (G, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # rows with no valid key yet keep m = -inf; guard the rescale
    alpha = jnp.where(jnp.isfinite(m_prev),
                      jnp.exp(m_prev - jnp.where(jnp.isfinite(m_new),
                                                 m_new, 0.0)), 0.0)
    p = jnp.where(valid, jnp.exp(s - jnp.where(jnp.isfinite(m_new),
                                               m_new, 0.0)), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == kv_steps - 1)
    def _finalize():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def flash_decode(
    q: jnp.ndarray,             # (H, D) one token's queries
    cache_k: jnp.ndarray,       # (Hkv, S, D)
    cache_v: jnp.ndarray,       # (Hkv, S, Dv)
    mask: jnp.ndarray,          # (H, S) bool — length ∧ window ∧ keep
    *,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Returns (H, Dv)."""
    h, d = q.shape
    hkv, s, dv = cache_v.shape
    g = h // hkv
    kv_steps = s // block_kv
    scale = 1.0 / (d ** 0.5)

    qg = q.reshape(hkv, g, d)
    maskg = mask.reshape(hkv, g, s)

    kernel = functools.partial(_kernel, block_kv=block_kv, scale=scale,
                               kv_steps=kv_steps)
    out = pl.pallas_call(
        kernel,
        grid=(hkv, kv_steps),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda h_, j: (h_, 0, 0)),
            pl.BlockSpec((1, block_kv, d), lambda h_, j: (h_, j, 0)),
            pl.BlockSpec((1, block_kv, dv), lambda h_, j: (h_, j, 0)),
            pl.BlockSpec((1, g, block_kv), lambda h_, j: (h_, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, g, dv), lambda h_, j: (h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((hkv, g, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, dv), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
        interpret=_auto_interpret(interpret),
    )(qg, cache_k, cache_v, maskg)
    return out.reshape(h, dv)


def _sparse_kernel(idx_ref, cnt_ref,
                   q_ref, k_ref, v_ref, mask_ref,
                   out_ref, acc_ref, m_ref, l_ref,
                   *, block_kv: int, scale: float, w_steps: int):
    h = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid_step = w < cnt_ref[h]

    @pl.when(valid_step)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        valid = mask_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe), 0.0)
        p = jnp.where(valid, jnp.exp(s - safe), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, 1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(w == w_steps - 1)
    def _finalize():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def flash_decode_sparse(
    q: jnp.ndarray,             # (H, D)
    cache_k: jnp.ndarray,       # (Hkv, S, D)
    cache_v: jnp.ndarray,       # (Hkv, S, Dv)
    mask: jnp.ndarray,          # (H, S) bool — already includes keep-set
    *,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Block-skipping variant: kv blocks whose keep-mask is all-False for a
    kv-head group are never streamed (scalar-prefetched block tables — the
    decode analogue of the prefill splash kernel).

    NOTE: rebuilds the block-table argsort from the token mask on every call
    — fine for validation, wrong for serving.  The serving path is
    :func:`flash_decode_plan`, which consumes tables built once per batch.
    """
    h, d = q.shape
    hkv, s, dv = cache_v.shape
    g = h // hkv
    nb = s // block_kv
    scale = 1.0 / (d ** 0.5)

    qg = q.reshape(hkv, g, d)
    maskg = mask.reshape(hkv, g, s)
    # per-kv-head active block table (union over the group's heads)
    blk_any = jnp.any(maskg.reshape(hkv, g, nb, block_kv), axis=(1, 3))
    cols = jnp.arange(nb, dtype=jnp.int32)
    key = jnp.where(blk_any, cols, cols + nb)
    order = jnp.argsort(key, axis=-1).astype(jnp.int32)
    counts = jnp.sum(blk_any, axis=-1).astype(jnp.int32)
    last = jnp.take_along_axis(order,
                               jnp.maximum(counts - 1, 0)[:, None], -1)
    widx = jnp.arange(nb, dtype=jnp.int32)
    indices = jnp.where(widx[None, :] < counts[:, None], order, last)

    kernel = functools.partial(_sparse_kernel, block_kv=block_kv,
                               scale=scale, w_steps=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(hkv, nb),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda h_, w, idx, cnt: (h_, 0, 0)),
            pl.BlockSpec((1, block_kv, d),
                         lambda h_, w, idx, cnt: (h_, idx[h_, w], 0)),
            pl.BlockSpec((1, block_kv, dv),
                         lambda h_, w, idx, cnt: (h_, idx[h_, w], 0)),
            pl.BlockSpec((1, g, block_kv),
                         lambda h_, w, idx, cnt: (h_, 0, idx[h_, w])),
        ],
        out_specs=pl.BlockSpec((1, g, dv),
                               lambda h_, w, idx, cnt: (h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, dv), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hkv, g, dv), q.dtype),
        interpret=_auto_interpret(interpret),
    )(indices, counts, qg, cache_k, cache_v, maskg)
    return out.reshape(h, dv)


# --------------------------------------------------------------------------
# Batched serving kernel: (B, Hkv, W) grid over prebuilt DecodePlan tables
# --------------------------------------------------------------------------

def _tile_keep_valid(keep_heads: jnp.ndarray, valid: jnp.ndarray,
                     block_kv: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Kernel layout of the keep bits and slot validity: int32 with the
    group and block axes last, so each grid step reads a ``(G, 1)`` and a
    ``(1, bs)`` tile.  These equal the array's last two dims, which the TPU
    tiling rule accepts, and the body compares against 0 rather than
    broadcasting i1 vectors, which Mosaic cannot reshape."""
    b, s = valid.shape
    keep = keep_heads.astype(jnp.int32)[..., None]          # (B, Hkv, NB, G, 1)
    val = valid.astype(jnp.int32).reshape(b, s // block_kv, 1, block_kv)
    return keep, val


def _batched_kernel(idx_ref, cnt_ref,             # scalar prefetch (SMEM)
                    q_ref, k_ref, v_ref, keep_ref, val_ref,   # VMEM tiles
                    out_ref, acc_ref, m_ref, l_ref,
                    *, scale: float, w_steps: int):
    b = pl.program_id(0)
    h = pl.program_id(1)
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(w < cnt_ref[b, h])
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)      # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)      # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)      # (bs, Dv)
        keep = keep_ref[0, 0, 0]                 # (G, 1) per-head block keep
        tok = val_ref[0, 0]                      # (1, bs) slot validity
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = (keep != 0) & (tok != 0)            # (G, bs)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe), 0.0)
        p = jnp.where(ok, jnp.exp(s - safe), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, 1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(w == w_steps - 1)
    def _finalize():
        # kv-heads with an empty keep-set (counts == 0) emit zeros: l stays 0
        out_ref[0, 0] = (acc_ref[...] /
                         jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def flash_decode_sparse_batched(
    q: jnp.ndarray,             # (B, H, D) one token per sequence
    cache_k: jnp.ndarray,       # (B, Hkv, S, D)
    cache_v: jnp.ndarray,       # (B, Hkv, S, Dv)
    indices: jnp.ndarray,       # (B, Hkv, W) int32 block table
    counts: jnp.ndarray,        # (B, Hkv) int32
    keep_heads: jnp.ndarray,    # (B, Hkv, NB, G) bool per-head block keep
    valid: jnp.ndarray,         # (B, S) bool slot validity (length ∧ ragged)
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Batched GQA block-skipping flash decode over prebuilt tables.

    Grid ``(B, Hkv, W)`` with the W axis sequential; the block tables are
    scalar-prefetched to SMEM so the K/V BlockSpec index_map skips
    masked-out kv blocks — they are never streamed HBM→VMEM — and padded
    steps repeat the previous block id (DMA elided).  The table argsort is
    NOT rebuilt here: tables come from :func:`repro.serving.decode_plan.
    build_decode_plan`, once per batch.

    A kv-head whose table is empty (``counts == 0``) emits zeros for its
    whole query group — the caller guarantees non-empty keep-sets (the plan
    always keeps the dense recent tail).

    Returns (B, H, Dv).
    """
    b, h, d = q.shape
    _, hkv, s, dv = cache_v.shape
    g = h // hkv
    nb = keep_heads.shape[2]
    block_kv = s // nb
    w_steps = indices.shape[-1]
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, g, d)

    kernel = functools.partial(_batched_kernel, scale=scale, w_steps=w_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, w_steps),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda b_, h_, w, idx, cnt: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h_, w, idx, cnt:
                         (b_, h_, idx[b_, h_, w], 0)),
            pl.BlockSpec((1, 1, block_kv, dv),
                         lambda b_, h_, w, idx, cnt:
                         (b_, h_, idx[b_, h_, w], 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda b_, h_, w, idx, cnt:
                         (b_, h_, idx[b_, h_, w], 0, 0)),
            pl.BlockSpec((1, 1, 1, block_kv),
                         lambda b_, h_, w, idx, cnt:
                         (b_, idx[b_, h_, w], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda b_, h_, w, idx, cnt: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, dv), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dv), q.dtype),
        interpret=_auto_interpret(interpret),
    )(indices, counts, qg, cache_k, cache_v,
      *_tile_keep_valid(keep_heads, valid, block_kv))
    return out.reshape(b, h, dv)


def decode_plan_einsum(
    q: jnp.ndarray,             # (B, H, D)
    cache_k: jnp.ndarray,       # (B, Hkv, S, D)
    cache_v: jnp.ndarray,       # (B, Hkv, S, Dv)
    keep_heads: jnp.ndarray,    # (B, Hkv, NB, G) bool
    valid: jnp.ndarray,         # (B, S) bool
) -> jnp.ndarray:
    """Grouped-einsum fallback consuming the same DecodePlan semantics.

    Contracts the full cache (no block skipping — CPU is a correctness
    path), masking with the per-head block keep bits expanded to token
    granularity *transiently, per layer* — nothing O(L·B·H·S) is ever
    threaded between steps.  Rows with no visible key emit zeros, matching
    the kernel's empty-table behavior.
    """
    b, h, d = q.shape
    _, hkv, s, dv = cache_v.shape
    g = h // hkv
    nb = keep_heads.shape[2]
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, g, d)
    logits = jnp.einsum("bkgd,bksd->bkgs", qg, cache_k,
                        preferred_element_type=jnp.float32) * scale
    km = jnp.repeat(jnp.moveaxis(keep_heads, -1, -2), s // nb, axis=-1)
    ok = km & valid[:, None, None, :]            # (B, Hkv, G, S)
    logits = jnp.where(ok, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(ok, jnp.exp(logits - m), 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgs,bksd->bkgd",
                     jnp.asarray(p / denom, cache_v.dtype), cache_v,
                     preferred_element_type=jnp.float32)
    return jnp.asarray(out, q.dtype).reshape(b, h, dv)


def _plan_einsum_sliced(
    qg: jnp.ndarray,            # (B, Hkv, G, D)
    kg: jnp.ndarray,            # (B, Hkv, W, bs, D) gathered table blocks
    vg: jnp.ndarray,            # (B, Hkv, W, bs, Dv)
    keep_g: jnp.ndarray,        # (B, Hkv, W, G) gathered keep bits
    valid_g: jnp.ndarray,       # (B, Hkv, W, bs) gathered slot validity
    counts: jnp.ndarray,        # (B, Hkv)
    scale: float,
    out_dtype,
) -> jnp.ndarray:
    """Shared masked-softmax core of the width-sliced einsum fallbacks.

    Operates on *gathered* table blocks only — O(B·Hkv·W·bs) FLOPs and
    bytes instead of the full-cache O(B·Hkv·S).  Table entries at ranks
    ≥ ``counts`` are repeat-last padding (the kernel's ``w < counts``
    guard); the ``live`` mask kills them here so the padded copies of the
    last block are not double-counted.
    """
    b, hkv, w, bs, dv = vg.shape
    live = (jnp.arange(w, dtype=jnp.int32)[None, None, :]
            < counts[..., None])                       # (B, Hkv, W)
    logits = jnp.einsum("bkgd,bkwsd->bkgws", qg, kg,
                        preferred_element_type=jnp.float32) * scale
    ok = (jnp.moveaxis(keep_g, -1, 2)[..., None]       # (B, Hkv, G, W, 1)
          & valid_g[:, :, None]                        # (B, Hkv, 1, W, bs)
          & live[:, :, None, :, None])
    logits = jnp.where(ok, logits, NEG_INF)
    flat = logits.reshape(b, hkv, -1, w * bs)
    ok_f = ok.reshape(b, hkv, -1, w * bs)
    m = jnp.max(flat, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(ok_f, jnp.exp(flat - m), 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    pv = jnp.asarray(p / denom, vg.dtype).reshape(b, hkv, -1, w, bs)
    out = jnp.einsum("bkgws,bkwsd->bkgd", pv, vg,
                     preferred_element_type=jnp.float32)
    return jnp.asarray(out, out_dtype).reshape(b, hkv * out.shape[2], dv)


def decode_plan_einsum_sliced(
    q: jnp.ndarray,             # (B, H, D)
    cache_k: jnp.ndarray,       # (B, Hkv, S, D)
    cache_v: jnp.ndarray,       # (B, Hkv, S, Dv)
    plan: DecodePlan,           # one layer's slice
    valid: jnp.ndarray,         # (B, S) bool
) -> jnp.ndarray:
    """Width-sliced einsum fallback: gather only the plan's W table blocks
    and contract those, so a narrow plan (W < NB, e.g. after a pattern
    refresh) does proportionally less work on non-TPU backends — the
    einsum analogue of the kernel's block skipping.  Padding-safe via the
    ``counts`` guard; same masked-softmax math as :func:`decode_plan_
    einsum` but a different reduction *order* (per-block gather), so it is
    dispatched only for W < NB plans — full-width plans keep the bitwise
    legacy path.
    """
    b, h, d = q.shape
    _, hkv, s, dv = cache_v.shape
    nb = plan.keep_heads.shape[2]
    bs = s // nb
    idx = plan.indices                                 # (B, Hkv, W)
    exp = idx[..., None, None]
    kg = jnp.take_along_axis(cache_k.reshape(b, hkv, nb, bs, d), exp, axis=2)
    vg = jnp.take_along_axis(cache_v.reshape(b, hkv, nb, bs, dv), exp, axis=2)
    keep_g = jnp.take_along_axis(plan.keep_heads, idx[..., None], axis=2)
    valid_b = jnp.broadcast_to(valid.reshape(b, 1, nb, bs), (b, hkv, nb, bs))
    valid_g = jnp.take_along_axis(valid_b, idx[..., None], axis=2)
    return _plan_einsum_sliced(q.reshape(b, hkv, h // hkv, d), kg, vg,
                               keep_g, valid_g, plan.counts,
                               1.0 / (d ** 0.5), q.dtype)


def flash_decode_plan(
    q: jnp.ndarray,             # (B, H, D)
    cache_k: jnp.ndarray,       # (B, Hkv, S, D)
    cache_v: jnp.ndarray,       # (B, Hkv, S, Dv)
    plan: DecodePlan,           # one layer's slice — (B, …) leaves
    valid: jnp.ndarray,         # (B, S) bool
    *,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Backend-auto sparse decode over a prebuilt plan (see
    :func:`resolve_decode_impl`).  Returns (B, H, Dv).

    The einsum fallback dispatches on the plan's static width: W == NB
    (every plan the scheduler builds without refresh) takes the legacy
    full-cache contraction bitwise-unchanged; W < NB (refresh-narrowed
    plans) takes :func:`decode_plan_einsum_sliced`, which only touches
    the W gathered blocks.
    """
    impl = resolve_decode_impl(impl)
    if impl == "kernel":
        return flash_decode_sparse_batched(
            q, cache_k, cache_v, plan.indices, plan.counts, plan.keep_heads,
            valid, interpret=interpret)
    if plan.indices.shape[-1] < plan.keep_heads.shape[-2]:
        return decode_plan_einsum_sliced(q, cache_k, cache_v, plan, valid)
    return decode_plan_einsum(q, cache_k, cache_v, plan.keep_heads, valid)


# --------------------------------------------------------------------------
# Block-paged variants: K/V live in a shared page pool, one page per block
# --------------------------------------------------------------------------

def gather_pages(pool: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """Materialize a contiguous per-slot cache view from a page pool.

    pool ``(P, Hkv, ps, D)``, page_table ``(B, NB)`` int32 →
    ``(B, Hkv, NB·ps, D)``.  A pure gather: the returned values at every
    resident position are bitwise the page contents, so any contiguous
    attention path run on the gathered view matches the paged kernels
    exactly.
    """
    b, nb = page_table.shape
    _, hkv, ps, d = pool.shape
    g = jnp.take(pool, page_table.reshape(-1), axis=0)   # (B·NB, Hkv, ps, D)
    g = g.reshape(b, nb, hkv, ps, d)
    return jnp.moveaxis(g, 1, 2).reshape(b, hkv, nb * ps, d)


def _paged_kernel(pt_ref, idx_ref, cnt_ref,
                  q_ref, k_ref, v_ref, keep_ref, val_ref,
                  out_ref, acc_ref, m_ref, l_ref,
                  *, scale: float, w_steps: int):
    # pt_ref is consumed by the K/V BlockSpec index maps only — the kernel
    # body is the contiguous batched kernel verbatim.
    del pt_ref
    _batched_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, keep_ref,
                    val_ref, out_ref, acc_ref, m_ref, l_ref,
                    scale=scale, w_steps=w_steps)


def flash_decode_sparse_batched_paged(
    q: jnp.ndarray,             # (B, H, D) one token per slot
    pool_k: jnp.ndarray,        # (P, Hkv, ps, D) shared page pool
    pool_v: jnp.ndarray,        # (P, Hkv, ps, Dv)
    page_table: jnp.ndarray,    # (B, NB) int32 logical block → page id
    indices: jnp.ndarray,       # (B, Hkv, W) int32 logical block table
    counts: jnp.ndarray,        # (B, Hkv) int32
    keep_heads: jnp.ndarray,    # (B, Hkv, NB, G) bool
    valid: jnp.ndarray,         # (B, NB·ps) bool logical slot validity
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """:func:`flash_decode_sparse_batched` over a block-paged KV cache.

    The DecodePlan stays logical — block ids, keep bits and validity are
    indexed exactly as in the contiguous kernel — and only the K/V DMA
    address is translated through the scalar-prefetched page table:
    ``page = page_table[b, indices[b, h, w]]``.  Since
    ``page_size == block_size``, a sparse block table row *is* a walk of
    the slot's resident pages, and the online-softmax body is shared with
    the contiguous kernel, so outputs are bitwise-identical to running it
    on the gathered contiguous view.

    Returns (B, H, Dv).
    """
    b, h, d = q.shape
    _, hkv, ps, dv = pool_v.shape
    g = h // hkv
    w_steps = indices.shape[-1]
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, g, d)

    kernel = functools.partial(_paged_kernel, scale=scale, w_steps=w_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, w_steps),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda b_, h_, w, pt, idx, cnt: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda b_, h_, w, pt, idx, cnt:
                         (pt[b_, idx[b_, h_, w]], h_, 0, 0)),
            pl.BlockSpec((1, 1, ps, dv),
                         lambda b_, h_, w, pt, idx, cnt:
                         (pt[b_, idx[b_, h_, w]], h_, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda b_, h_, w, pt, idx, cnt:
                         (b_, h_, idx[b_, h_, w], 0, 0)),
            pl.BlockSpec((1, 1, 1, ps),
                         lambda b_, h_, w, pt, idx, cnt:
                         (b_, idx[b_, h_, w], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda b_, h_, w, pt, idx, cnt:
                               (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, dv), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    # The pool's K/V tiles carry their page axis as a singleton block dim,
    # so k_ref/v_ref arrive as (1, 1, ps, D) — same shape the contiguous
    # kernel sees.
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dv), q.dtype),
        interpret=_auto_interpret(interpret),
    )(page_table, indices, counts, qg, pool_k, pool_v,
      *_tile_keep_valid(keep_heads, valid, ps))
    return out.reshape(b, h, dv)


def decode_plan_einsum_paged(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,        # (P, Hkv, ps, D)
    pool_v: jnp.ndarray,
    page_table: jnp.ndarray,    # (B, NB)
    keep_heads: jnp.ndarray,
    valid: jnp.ndarray,
) -> jnp.ndarray:
    """Einsum fallback for the paged cache: gather the resident pages into
    the contiguous view (``jnp.take``) and reuse the contiguous fallback —
    bitwise-equal by construction."""
    return decode_plan_einsum(q, gather_pages(pool_k, page_table),
                              gather_pages(pool_v, page_table),
                              keep_heads, valid)


def decode_plan_einsum_sliced_paged(
    q: jnp.ndarray,             # (B, H, D)
    pool_k: jnp.ndarray,        # (P, Hkv, ps, D)
    pool_v: jnp.ndarray,        # (P, Hkv, ps, Dv)
    page_table: jnp.ndarray,    # (B, NB) int32
    plan: DecodePlan,
    valid: jnp.ndarray,         # (B, NB·ps) bool
) -> jnp.ndarray:
    """:func:`decode_plan_einsum_sliced` over the block-paged pool: the
    logical block table is translated through the page table first
    (``page = page_table[b, indices[b, h, w]]``), then only those W pages
    are gathered from the pool — the full-cache ``gather_pages``
    materialization is skipped entirely, which is where the paged
    fallback's traffic actually goes.
    """
    b, h, d = q.shape
    _, hkv, ps, dv = pool_v.shape
    nb = page_table.shape[1]
    idx = plan.indices                                 # (B, Hkv, W)
    pages = jnp.take_along_axis(
        jnp.broadcast_to(page_table[:, None, :], (b, hkv, nb)), idx, axis=-1)

    def _per_head(pool_h, pages_h):                    # (P, ps, D), (B, W)
        return jnp.take(pool_h, pages_h, axis=0)       # (B, W, ps, D)

    gather = jax.vmap(_per_head, in_axes=(1, 1), out_axes=1)
    kg = gather(pool_k, pages)                         # (B, Hkv, W, ps, D)
    vg = gather(pool_v, pages)
    keep_g = jnp.take_along_axis(plan.keep_heads, idx[..., None], axis=2)
    valid_b = jnp.broadcast_to(valid.reshape(b, 1, nb, ps), (b, hkv, nb, ps))
    valid_g = jnp.take_along_axis(valid_b, idx[..., None], axis=2)
    return _plan_einsum_sliced(q.reshape(b, hkv, h // hkv, d), kg, vg,
                               keep_g, valid_g, plan.counts,
                               1.0 / (d ** 0.5), q.dtype)


def flash_decode_plan_paged(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    page_table: jnp.ndarray,
    plan: DecodePlan,           # one layer's slice, logical block ids
    valid: jnp.ndarray,         # (B, NB·ps)
    *,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Backend-auto sparse decode over a block-paged cache.

    Same width dispatch as :func:`flash_decode_plan`: full-width plans
    (W == NB) keep the legacy gather-then-contract fallback bitwise;
    refresh-narrowed plans (W < NB) gather only their table pages.
    """
    impl = resolve_decode_impl(impl)
    if impl == "kernel":
        return flash_decode_sparse_batched_paged(
            q, pool_k, pool_v, page_table, plan.indices, plan.counts,
            plan.keep_heads, valid, interpret=interpret)
    if plan.indices.shape[-1] < plan.keep_heads.shape[-2]:
        return decode_plan_einsum_sliced_paged(q, pool_k, pool_v,
                                               page_table, plan, valid)
    return decode_plan_einsum_paged(q, pool_k, pool_v, page_table,
                                    plan.keep_heads, valid)
