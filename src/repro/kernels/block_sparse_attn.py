"""Pallas TPU block-sparse flash attention with fused block-stats (Ã).

The paper's Triton kernel (FlashAttention-2 blockwise, mask-directed block
skipping, fused block-avg QK emission) adapted to TPU (DESIGN.md §3):

  * 128×128 blocks — MXU-shaped matmuls, VMEM-resident tiles;
  * "splash"-style scalar prefetch: per (head, q-block) *active kv-block
    index lists* + counts are prefetched to SMEM; the K/V ``BlockSpec``
    index_map reads them, so skipped blocks are never touched by the MXU and
    padded steps repeat the previous index (the Pallas TPU pipeline elides
    the DMA when the block index does not change between steps);
  * online softmax (running max / sum, accumulator rescale) — FA-2 math;
  * fused block-averaged QK logits emitted compactly per *visited* step; the
    wrapper scatters them into the full (…, NBq, NBkv) Ã with −inf
    background (skipped blocks).

Two kernels share that machinery:

``block_sparse_attention_kernel`` — the single-sample validation oracle:
  grid ``(H, NBq, W)``, one sample, W sequential steps for **every** row.

``block_sparse_attention_batched`` — the production prefill kernel:
  batch-native ``(B, T, H)`` grid over a **ragged causal schedule**
  (:func:`ragged_schedule`).  The (q-block, slot) rectangle is flattened
  into one sequential axis of ``T = Σ_i min(causal_bound_i, W)`` steps, so
  the kernel's sequential work tracks the *kept* blocks instead of the
  ``NBq·NBkv`` rectangle (a uniform grid wastes ~2× even on a fully causal
  mask: row 0 has one causal block but still gets NBkv steps).  Heads are
  the **innermost** grid axis: at a fixed (t) step the kernel sweeps heads,
  so heads whose index rows are identical — e.g. heads sharing a pivotal
  pattern, made adjacent by the schedule-level permutation in
  :func:`repro.core.share_attention.pattern_sharing_head_perm` — re-address
  the same ``(kv_head, j)`` K/V block and the Pallas TPU pipeline elides
  their DMAs entirely.  Per-(batch, head) tables are scalar-prefetched, and
  the fused Ã stats are gated per head (``stats_gate``) so shared/VS heads
  — whose Ã is never consumed by Algorithm 2 — skip the stats reductions.

Validated against :mod:`repro.kernels.ref` (and the batched kernel
bit-for-bit against ``vmap`` of the single-sample oracle) in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _kernel(idx_ref, cnt_ref,                 # scalar prefetch (SMEM)
            q_ref, k_ref, v_ref,              # VMEM tiles
            out_ref, stats_ref,               # outputs
            acc_ref, m_ref, l_ref,            # VMEM scratch
            *, block_q: int, block_kv: int, scale: float,
            causal: bool, w_steps: int):
    h = pl.program_id(0)
    i = pl.program_id(1)
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    count = cnt_ref[h, i]
    j = idx_ref[h, i, w]
    valid = w < count

    @pl.when(valid)
    def _compute():
        q = q_ref[0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0].astype(jnp.float32)           # (bk, d)
        v = v_ref[0].astype(jnp.float32)           # (bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            tok_valid = k_pos <= q_pos
        else:
            tok_valid = jnp.ones((block_q, block_kv), dtype=bool)

        # fused block stats: mean of QK logits over valid entries
        n_valid = jnp.sum(tok_valid.astype(jnp.float32))
        s_sum = jnp.sum(jnp.where(tok_valid, s, 0.0))
        stats_ref[0, 0, 0] = jnp.where(
            n_valid > 0, s_sum / jnp.maximum(n_valid, 1.0), NEG_INF)

        s = jnp.where(tok_valid, s, NEG_INF)
        m_prev = m_ref[...]                         # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
        p = jnp.where(tok_valid, jnp.exp(s - m_new), 0.0)

        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(jnp.logical_not(valid))
    def _skip():
        stats_ref[0, 0, 0] = NEG_INF

    @pl.when(w == w_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        out_ref[0] = (acc_ref[...] / denom).astype(out_ref.dtype)


def block_sparse_attention_kernel(
    q: jnp.ndarray,             # (H, N, Dqk)
    k: jnp.ndarray,             # (Hkv, N, Dqk)
    v: jnp.ndarray,             # (Hkv, N, Dv)
    indices: jnp.ndarray,       # (H, NBq, W) int32 active kv-block ids
    counts: jnp.ndarray,        # (H, NBq) int32
    *,
    block_size: int,
    causal: bool = True,
    interpret: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out (H, N, Dv), stats_compact (H, NBq, W) f32)."""
    h, n, d = q.shape
    h_kv, _, dv = v.shape
    group = h // h_kv
    nbq = n // block_size
    w_steps = indices.shape[-1]
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _kernel, block_q=block_size, block_kv=block_size, scale=scale,
        causal=causal, w_steps=w_steps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(h, nbq, w_steps),
        in_specs=[
            pl.BlockSpec((1, block_size, d),
                         lambda hh, ii, ww, idx, cnt: (hh, ii, 0)),
            pl.BlockSpec((1, block_size, d),
                         lambda hh, ii, ww, idx, cnt:
                         (hh // group, idx[hh, ii, ww], 0)),
            pl.BlockSpec((1, block_size, dv),
                         lambda hh, ii, ww, idx, cnt:
                         (hh // group, idx[hh, ii, ww], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_size, dv),
                         lambda hh, ii, ww, idx, cnt: (hh, ii, 0)),
            pl.BlockSpec((1, 1, 1),
                         lambda hh, ii, ww, idx, cnt: (hh, ii, ww)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_size, dv), jnp.float32),
            pltpu.VMEM((block_size, 1), jnp.float32),
            pltpu.VMEM((block_size, 1), jnp.float32),
        ],
    )

    out, stats = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((h, n, dv), q.dtype),
            jax.ShapeDtypeStruct((h, nbq, w_steps), jnp.float32),
        ],
        interpret=interpret,
    )(indices, counts, q, k, v)
    return out, stats


# --------------------------------------------------------------------------
# Batched count-aware kernel: (B, T, H) grid over a ragged causal schedule
# --------------------------------------------------------------------------

def ragged_schedule(nbq: int, nbkv: int, *, width: Optional[int] = None,
                    causal: bool = True,
                    q_block_offset: Optional[int] = None,
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Static flattened step schedule for the batched kernel.

    Row ``i`` of a causal mask can keep at most ``q_block_offset + i + 1``
    blocks, so it gets ``w_i = min(causal_bound_i, W)`` sequential steps
    (``W`` = the static per-row block budget, see
    :mod:`repro.kernels.indices`); non-causal rows get ``min(NBkv, W)``.
    The (row, slot) pairs are flattened row-major into one axis of
    ``T = Σ_i w_i`` steps — the kernel's per-(batch, head) sequential work.

    ``q_block_offset`` places the q rows inside the kv block grid: q-block
    ``i`` covers global positions starting at block ``q_block_offset + i``.
    The default ``NBkv − NBq`` keeps the legacy "rows at the end" layout
    (decode-style suffix queries; ``NBq == NBkv`` ⇒ offset 0).  Chunked
    prefill passes the chunk's block cursor so an interior Q-chunk gets the
    causal bounds of its own rows rather than the full rectangle.

    Returns ``(row_map, slot_map)``:
      * ``row_map`` — ``(T + 1,)`` int32, the q-block of each step, with a
        ``-1`` sentinel appended so ``row_map[t+1] != row_map[t]`` marks the
        final step of every row (the kernel's finalize condition);
      * ``slot_map`` — ``(T,)`` int32, the index-table slot of each step
        (``slot_map[t] == 0`` marks the first step of a row).
    """
    w = nbkv if width is None else max(1, min(int(width), nbkv))
    rows, slots = [], []
    shift = (nbkv - nbq) if q_block_offset is None else int(q_block_offset)
    for i in range(nbq):
        wi = min(i + 1 + shift, w) if causal else w
        wi = max(1, min(wi, nbkv))
        rows.extend([i] * wi)
        slots.extend(range(wi))
    row_map = np.asarray(rows + [-1], np.int32)
    slot_map = np.asarray(slots, np.int32)
    return row_map, slot_map


def ragged_grid_steps(nbq: int, nbkv: int, *, width: Optional[int] = None,
                      causal: bool = True,
                      q_block_offset: Optional[int] = None) -> int:
    """Sequential steps per (batch, head) under :func:`ragged_schedule` —
    the ``grid_steps`` counter benchmarks compare against the uniform
    ``NBq·NBkv`` rectangle."""
    return int(ragged_schedule(nbq, nbkv, width=width, causal=causal,
                               q_block_offset=q_block_offset)[1]
               .shape[0])


def _schedule_indices(indices: jnp.ndarray, row_map: np.ndarray,
                      slot_map: np.ndarray) -> jnp.ndarray:
    """(B, H, NBq, W) index tables → (B, H, T), the kv block of every
    schedule step.  The scalar-prefetched table then holds only the steps
    the ragged schedule visits: about half the rectangle under a causal
    mask, which keeps an 8k-token block-64 table for 16 heads inside the
    1 MiB of SMEM."""
    return indices[:, :, row_map[:-1], slot_map]


def _store_head_stat(stats_ref, h, val):
    """Write head ``h``'s (1, 1) stat into lane ``h`` of the step's (1, H)
    stats row.  A masked vector store: Mosaic cannot store a scalar to
    VMEM.  Heads are the innermost grid axis, so the row stays resident
    across the head sweep and every lane is written before writeback."""
    row = stats_ref[0, 0]                                  # (1, H)
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    stats_ref[0, 0] = jnp.where(lane == h, val, row)


def _kernel_batched(row_ref, slot_ref, idx_ref, cnt_ref, gate_ref,  # SMEM
                    q_ref, k_ref, v_ref,          # VMEM tiles
                    out_ref, stats_ref,           # outputs
                    acc_ref, m_ref, l_ref,        # VMEM scratch (H-indexed)
                    *, block_q: int, block_kv: int, scale: float,
                    causal: bool, q_block_offset: int):
    b = pl.program_id(0)
    t = pl.program_id(1)
    h = pl.program_id(2)
    row = row_ref[t]
    slot = slot_ref[t]

    @pl.when(slot == 0)
    def _init():
        acc_ref[h] = jnp.zeros(acc_ref.shape[1:], acc_ref.dtype)
        m_ref[h] = jnp.full(m_ref.shape[1:], NEG_INF, m_ref.dtype)
        l_ref[h] = jnp.zeros(l_ref.shape[1:], l_ref.dtype)

    count = cnt_ref[b, h, row]
    j = idx_ref[b, h, t]
    valid = slot < count
    emit_stats = valid & (gate_ref[b, h] != 0)

    @pl.when(valid)
    def _compute():
        q = q_ref[0, h].astype(jnp.float32)        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)        # (bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        if causal:
            q_pos = (q_block_offset + row) * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            tok_valid = k_pos <= q_pos
        else:
            tok_valid = jnp.ones((block_q, block_kv), dtype=bool)

        # fused block stats, gated to the heads whose Ã is consumed
        # (Algorithm-2 construction heads) — shared/VS heads skip the
        # reductions entirely
        @pl.when(emit_stats)
        def _stats():
            n_valid = jnp.sum(tok_valid.astype(jnp.float32), keepdims=True)
            s_sum = jnp.sum(jnp.where(tok_valid, s, 0.0), keepdims=True)
            _store_head_stat(stats_ref, h, jnp.where(
                n_valid > 0, s_sum / jnp.maximum(n_valid, 1.0), NEG_INF))

        s = jnp.where(tok_valid, s, NEG_INF)
        m_prev = m_ref[h]                           # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
        p = jnp.where(tok_valid, jnp.exp(s - m_new), 0.0)

        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(jnp.logical_not(emit_stats))
    def _no_stats():
        _store_head_stat(stats_ref, h, jnp.full((1, 1), NEG_INF, jnp.float32))

    @pl.when(row_ref[t + 1] != row)
    def _finalize():
        denom = jnp.maximum(l_ref[h], 1e-30)
        out_ref[0, h] = (acc_ref[h] / denom).astype(out_ref.dtype)


def block_sparse_attention_batched(
    q: jnp.ndarray,             # (B, H, N, Dqk)
    k: jnp.ndarray,             # (B, Hkv, N, Dqk)
    v: jnp.ndarray,             # (B, Hkv, N, Dv)
    indices: jnp.ndarray,       # (B, H, NBq, W) int32 active kv-block ids
    counts: jnp.ndarray,        # (B, H, NBq) int32
    *,
    block_size: int,
    causal: bool = True,
    stats_gate: Optional[jnp.ndarray] = None,   # (B, H) — emit Ã stats
    q_block_offset: Optional[int] = None,
    interpret: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batch-native count-aware block-sparse attention (module docstring).

    Grid ``(B, T, H)`` with heads innermost; ``T`` comes from
    :func:`ragged_schedule` at ``W = indices.shape[-1]``.  Per-(batch, head)
    ``(indices, counts)`` tables and the static (row, slot) maps are
    scalar-prefetched to SMEM.  The q and out tiles carry the *full* head
    axis and are re-addressed only on row transitions, so the head sweep
    costs no extra q/out DMA; K/V tiles are per-(kv_head, block) and their
    DMA is elided whenever adjacent heads address the same block (identical
    shared-pattern rows, padded slots repeating the last kept id).

    ``stats_gate`` (None = all heads) selects the heads whose fused Ã stats
    are computed; gated-off heads emit −inf, which the scatter maps to the
    "never visited" background.

    ``NBq`` may be smaller than ``NBkv`` (a Q-chunk against the full
    prefix); ``q_block_offset`` then names the chunk's first q block in the
    kv grid (default ``NBkv − NBq``, the legacy suffix layout) and flows
    into both the ragged schedule and the kernel's causal mask.

    Returns ``(out (B, H, N, Dv), stats_compact (B, T, H) f32)``; scatter
    the stats with :func:`repro.kernels.indices.scatter_schedule_stats`.

    VMEM note: accumulator scratch is O(H·block²) because every head's
    online-softmax state lives across the head sweep — intended for use
    with a heads-sharded mesh (H = local heads) at production scale; see
    :func:`repro.distributed.sharding.sharded_batched_block_sparse_attention`.
    """
    b, h, n, d = q.shape
    _, h_kv, _, dv = v.shape
    group = h // h_kv
    nbq = n // block_size
    nbkv = k.shape[2] // block_size
    w = indices.shape[-1]
    scale = 1.0 / (d ** 0.5)
    if q_block_offset is None:
        q_block_offset = nbkv - nbq

    row_map, slot_map = ragged_schedule(nbq, nbkv, width=w, causal=causal,
                                        q_block_offset=q_block_offset)
    t_steps = int(slot_map.shape[0])
    if stats_gate is None:
        stats_gate = jnp.ones((b, h), jnp.int32)
    stats_gate = stats_gate.astype(jnp.int32)

    kernel = functools.partial(
        _kernel_batched, block_q=block_size, block_kv=block_size,
        scale=scale, causal=causal, q_block_offset=int(q_block_offset))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, t_steps, h),
        in_specs=[
            pl.BlockSpec((1, h, block_size, d),
                         lambda bb, tt, hh, row, slot, idx, cnt, gate:
                         (bb, 0, row[tt], 0)),
            pl.BlockSpec((1, 1, block_size, d),
                         lambda bb, tt, hh, row, slot, idx, cnt, gate:
                         (bb, hh // group, idx[bb, hh, tt], 0)),
            pl.BlockSpec((1, 1, block_size, dv),
                         lambda bb, tt, hh, row, slot, idx, cnt, gate:
                         (bb, hh // group, idx[bb, hh, tt], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, block_size, dv),
                         lambda bb, tt, hh, row, slot, idx, cnt, gate:
                         (bb, 0, row[tt], 0)),
            pl.BlockSpec((1, 1, 1, h),
                         lambda bb, tt, hh, row, slot, idx, cnt, gate:
                         (bb, tt, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, block_size, dv), jnp.float32),
            pltpu.VMEM((h, block_size, 1), jnp.float32),
            pltpu.VMEM((h, block_size, 1), jnp.float32),
        ],
    )

    out, stats = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, n, dv), q.dtype),
            jax.ShapeDtypeStruct((b, t_steps, 1, h), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(row_map), jnp.asarray(slot_map),
      _schedule_indices(indices, row_map, slot_map), counts,
      stats_gate, q, k, v)
    return out, stats.reshape(b, t_steps, h)


def _kernel_batched_paged(row_ref, slot_ref, idx_ref, cnt_ref, gate_ref,
                          pt_ref, *rest, **kw):
    # pt_ref feeds the K/V BlockSpec index maps only; the body (and hence
    # the math, causal masking by *logical* block id, stats) is the
    # contiguous kernel verbatim.
    del pt_ref
    _kernel_batched(row_ref, slot_ref, idx_ref, cnt_ref, gate_ref,
                    *rest, **kw)


def block_sparse_attention_batched_paged(
    q: jnp.ndarray,             # (B, H, N, Dqk) query chunk
    pool_k: jnp.ndarray,        # (P, Hkv, ps, Dqk) shared page pool
    pool_v: jnp.ndarray,        # (P, Hkv, ps, Dv)
    page_table: jnp.ndarray,    # (B, NBkv) int32 logical block → page id
    indices: jnp.ndarray,       # (B, H, NBq, W) int32 logical kv-block ids
    counts: jnp.ndarray,        # (B, H, NBq) int32
    *,
    block_size: int,
    causal: bool = True,
    stats_gate: Optional[jnp.ndarray] = None,
    q_block_offset: Optional[int] = None,
    interpret: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`block_sparse_attention_batched` against a block-paged KV.

    The prefill counterpart of the paged decode kernel: a Q-chunk attends
    to prefix KV that lives in the shared page pool (chunked prefill over
    an admitted slot, prefix sharing later).  The schedule, the causal
    mask, and the index tables all stay *logical* — only the K/V DMA
    address is translated through the scalar-prefetched page table, so the
    output is bitwise the contiguous kernel run on the gathered view
    (``repro.kernels.decode_attn.gather_pages``, also the CPU fallback).

    Requires ``page_size == block_size``; the pool has no batch axis —
    batch rows resolve their own pages via their page-table row.
    """
    b, h, n, d = q.shape
    _, h_kv, ps, dv = pool_v.shape
    if ps != block_size:
        raise ValueError(f"page_size {ps} != block_size {block_size}")
    group = h // h_kv
    nbq = n // block_size
    nbkv = page_table.shape[1]
    w = indices.shape[-1]
    scale = 1.0 / (d ** 0.5)
    if q_block_offset is None:
        q_block_offset = nbkv - nbq

    row_map, slot_map = ragged_schedule(nbq, nbkv, width=w, causal=causal,
                                        q_block_offset=q_block_offset)
    t_steps = int(slot_map.shape[0])
    if stats_gate is None:
        stats_gate = jnp.ones((b, h), jnp.int32)
    stats_gate = stats_gate.astype(jnp.int32)

    kernel = functools.partial(
        _kernel_batched_paged, block_q=block_size, block_kv=block_size,
        scale=scale, causal=causal, q_block_offset=int(q_block_offset))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b, t_steps, h),
        in_specs=[
            pl.BlockSpec((1, h, block_size, d),
                         lambda bb, tt, hh, row, slot, idx, cnt, gate, pt:
                         (bb, 0, row[tt], 0)),
            pl.BlockSpec((1, 1, block_size, d),
                         lambda bb, tt, hh, row, slot, idx, cnt, gate, pt:
                         (pt[bb, idx[bb, hh, tt]], hh // group, 0, 0)),
            pl.BlockSpec((1, 1, block_size, dv),
                         lambda bb, tt, hh, row, slot, idx, cnt, gate, pt:
                         (pt[bb, idx[bb, hh, tt]], hh // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, block_size, dv),
                         lambda bb, tt, hh, row, slot, idx, cnt, gate, pt:
                         (bb, 0, row[tt], 0)),
            pl.BlockSpec((1, 1, 1, h),
                         lambda bb, tt, hh, row, slot, idx, cnt, gate, pt:
                         (bb, tt, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, block_size, dv), jnp.float32),
            pltpu.VMEM((h, block_size, 1), jnp.float32),
            pltpu.VMEM((h, block_size, 1), jnp.float32),
        ],
    )

    out, stats = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, n, dv), q.dtype),
            jax.ShapeDtypeStruct((b, t_steps, 1, h), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(row_map), jnp.asarray(slot_map),
      _schedule_indices(indices, row_map, slot_map), counts,
      stats_gate, page_table, q, pool_k, pool_v)
    return out, stats.reshape(b, t_steps, h)
