"""Pallas strip-score kernel for the Algorithm-3 estimation pass.

SharePrefill estimates each head's block pattern from the *last query block
strip* — softmax(Q̂ Kᵀ/√d) for Q̂ = Q[-block_size:].  The pure-jnp
:func:`strip_scores` oracle materializes the full (block_size, N) logits,
the causal ``where`` mask, and the softmax temporaries in HBM before
producing the strip.  The Pallas version streams K through VMEM in
``block_size`` tiles with a flash-style online-softmax scan:

  * pass 1 (``_strip_ml_kernel``) — FA-2 running max / running denominator
    over kv tiles; only the final per-row (m, l) leaves the kernel;
  * pass 2 (``_strip_norm_kernel``) — re-scores each tile and writes the
    exactly-normalized probabilities ``exp(s − m)/l`` straight to the output,
    so the strip is the *only* (block_size, N) array that ever touches HBM.

Both kernels are GQA-native: query head ``h`` reads kv head ``h // group``
through the BlockSpec index_map, so grouped K is never repeated.

Causality comes cheap: strip rows are the globally-last queries, so every kv
tile except the final one is fully visible — only tile ``NB−1`` is masked.

``compute_strips`` is the dispatcher used by the orchestration: the pure-jnp
oracle on CPU hosts (where Pallas only interprets), the kernel on TPU.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


# --------------------------------------------------------------------------
# Pure-jnp oracle (also the CPU execution path)
# --------------------------------------------------------------------------

def strip_scores(q: jnp.ndarray, k: jnp.ndarray,
                 block_size: int) -> jnp.ndarray:
    """softmax(Q̂ Kᵀ/√d) for the last query block; (block_size, N)."""
    n, d = k.shape
    q_hat = q[-block_size:, :]
    logits = (q_hat @ k.T) / jnp.sqrt(jnp.asarray(d, q.dtype))
    # causal: row r of the strip is global query N - block_size + r
    rows = jnp.arange(block_size) + (n - block_size)
    cols = jnp.arange(n)
    logits = jnp.where(cols[None, :] <= rows[:, None], logits, -jnp.inf)
    logits = jnp.asarray(logits, jnp.float32)
    p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    return p / jnp.sum(p, axis=-1, keepdims=True)


# --------------------------------------------------------------------------
# Pallas kernels
# --------------------------------------------------------------------------

def _tile_logits(q_ref, k_ref, j, *, block_size, tile, n, scale):
    """(bs, tile) scaled QK logits of kv tile j, −inf outside causality."""
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    q_pos = (n - block_size) + jax.lax.broadcasted_iota(
        jnp.int32, (block_size, tile), 0)
    k_pos = j * tile + jax.lax.broadcasted_iota(
        jnp.int32, (block_size, tile), 1)
    valid = k_pos <= q_pos
    return jnp.where(valid, s, NEG_INF), valid


def _strip_ml_kernel(q_ref, k_ref, m_out, l_out, m_ref, l_ref,
                     *, block_size: int, n: int, scale: float):
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    s, valid = _tile_logits(q_ref, k_ref, j, block_size=block_size,
                            tile=block_size, n=n, scale=scale)
    m_prev = m_ref[...]                              # (bs, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        m_out[0] = m_ref[...]
        l_out[0] = l_ref[...]


def _strip_norm_kernel(q_ref, k_ref, m_ref, l_ref, out_ref,
                       *, block_size: int, tile: int, n: int, scale: float):
    j = pl.program_id(1)
    s, valid = _tile_logits(q_ref, k_ref, j, block_size=block_size,
                            tile=tile, n=n, scale=scale)
    m = m_ref[0]                                     # (bs, 1)
    l = jnp.maximum(l_ref[0], 1e-30)
    out_ref[0] = jnp.where(valid, jnp.exp(s - m), 0.0) / l


def strip_scores_pallas(
    q: jnp.ndarray,             # (H, N, D)
    k: jnp.ndarray,             # (Hkv, N, D)
    *,
    block_size: int,
    interpret: bool = True,
) -> jnp.ndarray:
    """Fused last-query-block strips for all heads; (H, block_size, N) f32.

    ``q`` may be shorter than ``k`` along the sequence axis (e.g. just the
    captured last-block query window during a decode-time refresh) — the
    key length ``N``, and with it the causal row offsets, always come from
    ``k``; only ``q``'s last ``block_size`` rows are read.
    """
    h, _, d = q.shape
    h_kv, n = k.shape[:2]
    group = h // h_kv
    nb = n // block_size
    scale = 1.0 / (d ** 0.5)
    q_hat = q[:, q.shape[1] - block_size:, :]

    q_spec = pl.BlockSpec((1, block_size, d), lambda hh, jj: (hh, 0, 0))
    k_spec = pl.BlockSpec((1, block_size, d),
                          lambda hh, jj: (hh // group, jj, 0))
    # per-row (m, l) keep the scratch's (bs, 1) column layout: a (1, bs)
    # row block of an (H, bs) array breaks the TPU (8, 128) tiling rule
    ml_spec = pl.BlockSpec((1, block_size, 1), lambda hh, jj: (hh, 0, 0))

    ml_kernel = functools.partial(_strip_ml_kernel, block_size=block_size,
                                  n=n, scale=scale)
    m, l = pl.pallas_call(
        ml_kernel,
        grid=(h, nb),
        in_specs=[q_spec, k_spec],
        out_specs=[ml_spec, ml_spec],
        out_shape=[
            jax.ShapeDtypeStruct((h, block_size, 1), jnp.float32),
            jax.ShapeDtypeStruct((h, block_size, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_size, 1), jnp.float32),
            pltpu.VMEM((block_size, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q_hat, k)

    # the strip tile's last dim is a lane dim, so it must be a multiple of
    # 128: blocks under 128 write several kv blocks per grid step (each
    # element's math is unchanged — the tile only sets how many are written)
    tile = math.lcm(block_size, 128)
    if n % tile:
        tile = block_size
    norm_kernel = functools.partial(_strip_norm_kernel, block_size=block_size,
                                    tile=tile, n=n, scale=scale)
    strip = pl.pallas_call(
        norm_kernel,
        grid=(h, n // tile),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, tile, d), lambda hh, jj: (hh // group, jj, 0)),
            ml_spec,
            ml_spec,
        ],
        out_specs=pl.BlockSpec((1, block_size, tile),
                               lambda hh, jj: (hh, 0, jj)),
        out_shape=jax.ShapeDtypeStruct((h, block_size, n), jnp.float32),
        interpret=interpret,
    )(q_hat, k, m, l)
    return strip


# --------------------------------------------------------------------------
# Dispatcher
# --------------------------------------------------------------------------

def compute_strips(
    q: jnp.ndarray,             # (H, N, D)
    k: jnp.ndarray,             # (Hkv, N, D)
    *,
    block_size: int,
    impl: str = "auto",         # auto | pallas | jnp
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """GQA-native strips for all query heads, (H, block_size, N) f32.

    ``auto`` runs the Pallas kernel compiled on TPU and the pure-jnp oracle
    elsewhere (interpret mode is a validation tool, not an execution path).
    Neither path repeats K across the GQA group.
    """
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        impl = "pallas" if on_tpu else "jnp"
    if impl == "pallas" and (k.shape[1] % block_size
                             or q.shape[1] < block_size):
        # the kernel grid covers whole kv tiles only — a ragged tail would
        # silently drop keys from the softmax denominator
        impl = "jnp"
    if impl == "pallas":
        # under a serving mesh the kernel runs per head shard (the compiler
        # cannot partition a Mosaic kernel); heads that do not shard take
        # the oracle
        from repro.distributed.sharding import (
            active_model_mesh,
            sharded_compute_strips,
            shardable_model_mesh,
        )
        it = interpret if interpret is not None else not on_tpu
        mesh = shardable_model_mesh(q.shape[0], k.shape[0])
        if mesh is not None:
            return sharded_compute_strips(q, k, mesh=mesh,
                                          block_size=block_size,
                                          interpret=it)
        if active_model_mesh() is None:
            return strip_scores_pallas(q, k, block_size=block_size,
                                       interpret=it)
        impl = "jnp"
    if impl != "jnp":
        raise ValueError(f"unknown strip impl {impl!r}")
    from repro.kernels.ops import gqa_head_vmap
    return gqa_head_vmap(
        lambda qh, kh: strip_scores(qh, kh, block_size), q, k)


def compute_strips_paged(
    q_hat: jnp.ndarray,         # (H, block_size, D) recent-query window
    pool_k: jnp.ndarray,        # (P, Hkv, ps, D) shared page pool
    page_table: jnp.ndarray,    # (NB,) int32 one slot's logical→page map
    *,
    block_size: int,
    num_blocks: int,            # static: live (block-aligned) block count
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """:func:`compute_strips` over one slot's live paged KV.

    The decode-time re-estimation entry point (``serving/refresh.py``):
    ``q_hat`` is the slot's captured last-``block_size`` decode queries
    (positions ``[n − block_size, n)`` for ``n = num_blocks ·
    block_size``), and K is gathered from the page pool through the
    slot's page-table prefix — a pure gather (bitwise page contents, same
    argument as :func:`repro.kernels.decode_attn.gather_pages`), so the
    strip equals running the contiguous kernel on the slot's cache.  The
    strip rows being the globally-last queries is exactly the kernels'
    causal assumption, which is why refresh only fires at block-aligned
    positions.

    Returns (H, block_size, num_blocks · ps) f32.
    """
    _, hkv, ps, d = pool_k.shape
    kg = jnp.take(pool_k, page_table[:num_blocks], axis=0)
    k = jnp.moveaxis(kg, 0, 1).reshape(hkv, num_blocks * ps, d)
    return compute_strips(q_hat, k, block_size=block_size, impl=impl,
                          interpret=interpret)
