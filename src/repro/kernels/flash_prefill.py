"""Causal flash attention of one dense prefill chunk (Pallas TPU kernel).

The dense chunked-admission quantum (``models/chunked_prefill.py``
``chunk_prefill_attn``) attends ``cn`` query rows at global positions
``[q_offset, q_offset + cn)`` to the keys of their causal frontier
``[0, q_offset + cn)``.  The grid is ``(B, H, query tiles, key tiles)``
with the key axis ending at the frontier, so keys past it are never read.
Each query tile walks the key tiles up to its own last row: the K/V index
map clamps later tiles to the last one needed, so the pipeline fetches
nothing past it, and the body skips them.  Scores never leave VMEM: bf16
Q, K, V and P enter the MXU, the running max, sum and accumulator are
float32 (online softmax), and the causal mask is applied only on the
tiles that cross the diagonal.

GQA is native: query head ``h`` reads kv head ``h // group`` through the
index map, so K/V are never repeated in HBM.  Q/K and V may have different
widths (MLA: 192 / 128).

Semantics match :func:`repro.kernels.chunked.chunked_attention` with
``causal=True``, ``window=0`` and ``q_offset``: scale ``1/sqrt(Dqk)`` on
the float32 scores, output in ``q.dtype``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# large finite, so exp(mask - max) is 0 without inf arithmetic
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)

# tile edges tried from the largest down; 128 is the lane width
TILES = (1024, 512, 256, 128)


def flash_tiles(cn: int, front: int, block_q: int = 512,
                block_k: int = 512) -> Optional[Tuple[int, int]]:
    """``(bq, bk)``: the largest tiles up to ``block_q`` / ``block_k`` that
    divide the chunk's ``cn`` rows and its ``front`` keys, or ``None`` when
    no lane-aligned tile does (the caller then takes the pure-JAX path)."""
    bq = next((t for t in TILES if t <= block_q and cn % t == 0), None)
    bk = next((t for t in TILES if t <= block_k and front % t == 0), None)
    return None if bq is None or bk is None else (bq, bk)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, q_offset: int, bq: int, bk: int, k_t: bool):
    i = pl.program_id(2)
    j = pl.program_id(3)
    row0 = q_offset + i * bq                # global position of row 0
    col0 = j * bk                           # global position of key 0

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(masked: bool):
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (0 if k_t else 1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, MASK_VALUE)
        m_prev = m_ref[...]                                  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    visible = col0 <= row0 + bq - 1         # some row sees some key
    crosses = col0 + bk - 1 > row0          # some row misses some key

    @pl.when(visible & jnp.logical_not(crosses))
    def _full():
        step(False)

    @pl.when(visible & crosses)
    def _diagonal():
        step(True)

    @pl.when(j == pl.num_programs(3) - 1)
    def _store():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_chunk_attention(
    q: jnp.ndarray,             # (B, H, cn, Dqk), the chunk's rows
    k: jnp.ndarray,             # (B, Hkv, S, Dqk), S >= q_offset + cn
    v: jnp.ndarray,             # (B, Hkv, S, Dv)
    *,
    q_offset: int,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Causal attention of the chunk's rows over the keys of its frontier
    ``[0, q_offset + cn)`` → ``(B, H, cn, Dv)`` in ``q.dtype``.  Keys past
    the frontier may be present and are never read, so the caller need
    not copy a slice.  ``interpret=None`` compiles on TPU and interprets
    elsewhere."""
    b, h, cn, d = q.shape
    h_kv, s_kv, dv = k.shape[1], k.shape[2], v.shape[-1]
    front = q_offset + cn
    if front > s_kv:
        raise ValueError(f"the chunk's frontier {front} lies past the "
                         f"{s_kv} keys")
    tiles = flash_tiles(cn, front, block_q, block_k)
    if tiles is None:
        raise ValueError(f"no lane-aligned tiles for a {cn}-row chunk over "
                         f"{front} keys")
    bq, bk = tiles
    group = h // h_kv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # a key width off the lane multiple (MLA's 192) is laid out with the
    # sequence minor; K^T is then the same bytes, read as (d, bk) tiles
    k_t = d % 128 != 0
    if k_t:
        k = jnp.swapaxes(k, 2, 3)

    def kv_tile(ii, jj):
        # the last key tile this query tile needs; later steps re-map to
        # it, so the pipeline issues no copy for them
        return jnp.minimum(jj, (q_offset + (ii + 1) * bq - 1) // bk)

    k_spec = (pl.BlockSpec((None, None, d, bk), lambda bb, hh, ii, jj:
                           (bb, hh // group, 0, kv_tile(ii, jj)))
              if k_t else
              pl.BlockSpec((None, None, bk, d), lambda bb, hh, ii, jj:
                           (bb, hh // group, kv_tile(ii, jj), 0)))
    kernel = functools.partial(_kernel, scale=1.0 / (d ** 0.5),
                               q_offset=q_offset, bq=bq, bk=bk, k_t=k_t)
    return pl.pallas_call(
        kernel,
        grid=(b, h, cn // bq, front // bk),
        in_specs=[
            pl.BlockSpec((None, None, bq, d),
                         lambda bb, hh, ii, jj: (bb, hh, ii, 0)),
            k_spec,
            pl.BlockSpec((None, None, bk, dv), lambda bb, hh, ii, jj:
                         (bb, hh // group, kv_tile(ii, jj), 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, dv),
                               lambda bb, hh, ii, jj: (bb, hh, ii, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, cn, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_prefill_chunk",
    )(q, k, v)
