"""One-shot prefill decomposed into step-cadence quanta (chunked admission).

The serving scheduler cannot afford ``transformer.prefill``'s monolithic
launch: every occupied decode slot stalls for the whole admission.  This
module re-expresses the SAME computation as a sequence of small quanta the
engine can interleave with decode steps:

    begin                                   (embed)
    for each layer l:
        layer_begin(l)                      (ln1 + qkv + rope + mask staging)
        attn(l, chunk_0) … attn(l, chunk_C) (rectangular Q-chunk × full-KV)
        layer_end(l)                        (o-proj + residual + ln2 + FFN,
                                             dictionary update, stats)
    finish                                  (last-token gather + lm head)

The decomposition is **layer-major**, not chunk-major, because SharePrefill's
masks at every layer depend on the full-sequence last-query-block strip
(Algorithm 3): pattern estimation, the decision, and the dictionary update
all run at full sequence length in ``layer_begin``/``layer_end`` — exactly
the ops the one-shot path runs — while only the attention *output rows* are
split across chunk quanta.  Each chunk launch reuses the batched
block-sparse kernel with ``q_block_offset`` (rectangular ``NBq × NBkv``
schedule), so per-row accumulation order is identical to the one-shot launch
and the assembled outputs match it bit for bit.

Every function takes the full stacked ``params`` plus a *traced* layer
index (sliced in-graph via ``dynamic_index_in_dim``), so a jitted quantum
compiles ONCE per shape and is replayed for every layer — the engine's
program cache stays O(chunks), not O(layers × chunks).

Packing: ``seg_blocks`` isolates concatenated prompts of a packed launch by
ANDing a block-diagonal segment mask into the share/vs/flex masks (positions
restart per segment on the caller side).  Attention-wise each segment is
independent; the pattern dictionary and the strip estimate still see the
packed row jointly, which is why packing is an opt-in for short-prompt
buckets (``serving/chunked_prefill.py``).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import baselines
from repro.core import share_attention as sa
from repro.core.api import SharePrefill
from repro.core.patterns import (
    block_mask_density,
    causal_block_mask,
    segment_block_mask,
    sliding_window_block_mask,
)
from repro.distributed.sharding import shard
from repro.kernels import batched_sparse_attention_fn
from repro.kernels.chunked import chunked_attention
from repro.kernels.indices import cap_block_mask
from repro.kernels.ops import expand_kv
from repro.models import common, mla
from repro.models.attention import AttnStats, resolved_attn_impl, rope_qk
from repro.models.transformer import (
    _ffn_serve,
    _uses_moe,
    embed_tokens,
    logits_from_hidden,
    num_prefix_layers,
)

CHUNK_ATTN_IMPLS = ("sparse", "chunked")


class ChunkPrefillApi(NamedTuple):
    """Model-family entry points for chunked admission (``Model.prefill_chunk``).

    ``None`` on families without a stacked-cache layout (ssm, hybrid,
    encdec) — the scheduler falls back to one-shot admission there.

    ``layer_begin`` returns ``(q, k, v, masks, decision, gate, perm)``;
    ``decision`` is what it hands ``layer_end`` beside the masks
    (SharePrefill's decision under GQA, the layer's latent rows under
    MLA).  ``layer_end`` returns ``(x, kv, sp_state, stats, rows)``:
    ``kv`` is what the cache keeps of the layer (``(k, v)`` under GQA,
    the latent rows ``(B, S, latent_dim)`` under MLA) and ``rows`` the
    rows each held expert took ``(B, held)``.
    """
    begin: Any
    layer_begin: Any
    attn: Any
    layer_end: Any
    finish: Any


def _layer_params(params, layer_idx):
    """Slice layer ``layer_idx`` out of the stacked params with a traced
    index — one compiled program serves every layer."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer_idx, 0,
                                               keepdims=False),
        params["stack"])


def _resolve_bs(sp: SharePrefill, n: int) -> int:
    return min(sp.cfg.block_size if sp.cfg.enabled else 128, n)


def _layer_cluster_ids(cluster_arr, layer_idx):
    return jax.lax.dynamic_index_in_dim(cluster_arr, layer_idx, 0,
                                        keepdims=False)


def chunk_prefill_begin(params, cfg: ModelConfig,
                        tokens: jnp.ndarray) -> jnp.ndarray:
    """Quantum 0: token embedding for the full (packed) row."""
    return embed_tokens(params, cfg, tokens)


def chunk_prefill_layer_begin(
    params, cfg: ModelConfig, layer_idx, x: jnp.ndarray,
    positions: jnp.ndarray, sp: SharePrefill, sp_state,
    cluster_arr: Optional[jnp.ndarray],
    *,
    method: str,
    attn_impl: str,
    seg_blocks: Optional[int] = None,
):
    """Per-layer quantum A: ln1 + QKV + rope for ALL rows, plus the full-
    sequence mask staging (strips, decision, pattern lookup) — the ops whose
    inputs cannot be chunked without changing the masks.

    Returns ``(q, k, v, masks, decision, gate, perm)``; the mask pack is
    ``None`` on the dense path.
    """
    layer = _layer_params(params, layer_idx)
    h = common.rmsnorm(layer["ln1"], x, cfg.rms_norm_eps)
    q, k, v = common.gqa_qkv(layer["attn"], h)
    q, k = rope_qk(q, k, positions, cfg)

    n = x.shape[1]
    bs = _resolve_bs(sp, n)
    use_sparse = method != "dense" and sp.applicable(n)
    nb = n // bs if n % bs == 0 else 0

    extra = None
    if cfg.sliding_window and nb:
        extra = sliding_window_block_mask(
            nb, max(cfg.sliding_window // bs, 1))
    if seg_blocks is not None and nb:
        seg = segment_block_mask(nb, seg_blocks)
        extra = seg if extra is None else (extra & seg)

    if not use_sparse:
        return q, k, v, None, None, None, None

    if method == "share":
        cluster_ids = _layer_cluster_ids(cluster_arr, layer_idx)
        masks, decision = jax.vmap(
            lambda qb, kb, st: sa.build_share_masks(qb, kb, st, cluster_ids,
                                                    sp.cfg, extra)
        )(q, k, sp_state)
        perm = None
        if resolved_attn_impl(attn_impl) == "sparse":
            group = q.shape[1] // k.shape[1]
            perm = jax.vmap(
                lambda d: sa.pattern_sharing_head_perm(d, cluster_ids, group)
            )(decision)
        return q, k, v, masks, decision, decision.use_dense, perm

    gamma = sp.cfg.gamma
    if method == "vertical_slash":
        head_mask_fn = lambda qh, kh: baselines.minference_head_mask(
            qh, kh, gamma=gamma, block_size=bs)
    elif method == "flex":
        head_mask_fn = lambda qh, kh: baselines.flexprefill_head_mask(
            qh, kh, gamma=gamma, block_size=bs)
    else:
        raise ValueError(f"unknown prefill method {method!r}")
    masks = jax.vmap(lambda qs, ks: sa.gqa_head_vmap(head_mask_fn, qs, ks)
                     )(q, k)
    masks = masks & causal_block_mask(nb)[None, None]
    if extra is not None:
        masks = masks & extra[None, None]
    return q, k, v, masks, None, None, None


def chunk_prefill_attn(
    cfg: ModelConfig, sp: SharePrefill,
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    masks, gate, perm,
    *,
    method: str,
    attn_impl: str,
    attn_width: Optional[int],
    chunk_start: int,               # first q block of this chunk (static)
    chunk_blocks: int,              # q blocks in this chunk (static)
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Per-layer chunk quantum: attention output rows for q blocks
    ``[chunk_start, chunk_start + chunk_blocks)`` against the FULL K/V.

    Mirrors ``attention_prefill``'s backend dispatch structure op for op
    (same vmap nesting, same head permutation, same stats gating) so the
    concatenated chunk outputs are bitwise the one-shot launch's rows.
    Returns ``(out_rows (B,H,cn,Dv), a_rows (B,H,cnb,NB) | None)``.
    """
    impl = resolved_attn_impl(attn_impl)
    if impl not in CHUNK_ATTN_IMPLS:
        raise ValueError(
            f"chunked admission supports attn_impl {CHUNK_ATTN_IMPLS}, "
            f"got {impl!r} — serve this config through one-shot admission")
    n = q.shape[2]
    bs = _resolve_bs(sp, n)
    off = chunk_start * bs
    cn = chunk_blocks * bs
    q_c = jax.lax.slice_in_dim(q, off, off + cn, axis=2)

    if masks is None:
        kx = common.repeat_kv(k, cfg.gqa_groups)
        vx = common.repeat_kv(v, cfg.gqa_groups)
        out, _ = chunked_attention(
            q_c, kx, vx, block_size=bs, causal=True,
            window=cfg.sliding_window, q_offset=off)
        return out, None

    m_c = jax.lax.slice_in_dim(masks, chunk_start,
                               chunk_start + chunk_blocks, axis=2)

    if impl == "sparse":
        fn = batched_sparse_attention_fn(block_size=bs, width=attn_width,
                                         q_block_offset=chunk_start)
        if perm is not None:            # share: grid-adjacent shared heads
            take = lambda x_, p: jnp.take_along_axis(
                x_, p.reshape(p.shape + (1,) * (x_.ndim - 2)), axis=1)
            out_p, a_p = fn(take(q_c, perm), k, v, take(m_c, perm),
                            stats_gate=take(gate, perm))
            inv = jnp.argsort(perm, axis=1)
            return take(out_p, inv), take(a_p, inv)
        sg = gate if gate is not None \
            else jnp.zeros(m_c.shape[:2], jnp.int32)
        out, a = fn(q_c, k, v, m_c, stats_gate=sg)
        return out, (a if method == "share" else None)

    # "chunked": the dense pure-JAX path, per-sample under vmap exactly like
    # chunked_attention_fn inside the legacy per-sample wrapper
    if attn_width is not None:
        m_c = cap_block_mask(m_c, attn_width)

    def one(qs, ks, vs, ms):
        ks, vs = expand_kv(ks, vs, qs.shape[0])
        o, at = chunked_attention(
            qs[None], ks[None], vs[None], block_size=bs, causal=True,
            block_mask=ms[None], collect_stats=True, q_offset=off)
        return o[0], at[0]

    out, a = jax.vmap(one)(q_c, k, v, m_c)
    return out, (a if method == "share" else None)


def chunk_prefill_layer_end(
    params, cfg: ModelConfig, layer_idx, x: jnp.ndarray,
    out: jnp.ndarray,               # (B, H, S, Dv) assembled chunk rows
    k: jnp.ndarray, v: jnp.ndarray,
    a_tilde,                        # (B, H, NB, NB) assembled Ã | None
    masks, decision,
    sp: SharePrefill, sp_state, cluster_arr,
    *,
    method: str,
):
    """Per-layer quantum B: everything downstream of attention at FULL
    sequence length — o-proj, residuals, ln2, FFN (identical gemm shapes to
    the one-shot path), the vmapped dictionary update, and layer stats.

    Returns ``(x, (k, v), sp_state, AttnStats, rows)`` — the
    ``layer_prefill`` contract; the caller inserts ``(k, v)`` into its
    slot of the serving cache.
    """
    layer = _layer_params(params, layer_idx)
    out = shard(out, "batch", "heads")
    x = x + common.gqa_out(layer["attn"], out)
    h = common.rmsnorm(layer["ln2"], x, cfg.rms_norm_eps)
    f, rows = _ffn_serve(layer, h, cfg, _uses_moe(cfg))
    x = x + f

    if masks is None:
        return x, (k, v), sp_state, AttnStats.zero(), rows

    if method == "share":
        cluster_ids = _layer_cluster_ids(cluster_arr, layer_idx)
        sp_state = jax.vmap(
            lambda a, st, d: sa.update_share_state(a, st, cluster_ids, d,
                                                   sp.cfg)
        )(a_tilde, sp_state, decision)
        ls = sa.layer_pattern_stats(masks, decision)
        stats = AttnStats(ls.num_shared, ls.num_dense, ls.num_vs,
                          ls.block_density, ls.max_row_pop)
        return x, (k, v), sp_state, stats, rows

    h_q = masks.shape[1]
    stats = AttnStats(jnp.zeros(()), jnp.zeros(()),
                      jnp.asarray(float(h_q)),
                      jnp.mean(block_mask_density(masks)),
                      jnp.max(jnp.sum(masks.astype(jnp.float32), axis=-1)))
    return x, (k, v), sp_state, stats, rows


def _mla_layer(params, cfg: ModelConfig, layer_idx, fn):
    """``fn(layer_params, moe_ffn)`` at a traced layer index over the
    whole depth: the prefix layers (``prefix_<i>``, dense FFN) first, then
    the scanned stack, chosen on the device so one program serves every
    layer."""
    n_prefix = num_prefix_layers(cfg)
    stack = lambda: fn(_layer_params(params, layer_idx - n_prefix),
                       _uses_moe(cfg))
    if not n_prefix:
        return stack()
    branches = [lambda i=i: fn(params[f"prefix_{i}"], False)
                for i in range(n_prefix)] + [stack]
    return jax.lax.switch(jnp.minimum(layer_idx, n_prefix), branches)


def mla_chunk_layer_begin(params, cfg: ModelConfig, layer_idx, x, positions,
                          sp, sp_state, cluster_arr, *, method: str,
                          attn_impl: str, seg_blocks=None):
    """MLA quantum A: ln1, the decompressed per-head q/k (qk_nope +
    qk_rope) and v for the dense ``attn`` quanta, and the layer's latent
    rows for the page insert (handed on in the ``decision`` slot)."""
    del sp, sp_state, cluster_arr, attn_impl, seg_blocks
    if method != "dense":
        raise ValueError(
            f"chunked admission of MLA layers is dense only: method "
            f"{method!r} (chunked share prefill is not implemented for "
            "latent attention)")

    def begin(layer, moe_ffn):
        del moe_ffn
        h = common.rmsnorm(layer["ln1"], x, cfg.rms_norm_eps)
        return mla.prefill_qkv(layer["attn"], h, cfg, positions)

    q, k, v, latent = _mla_layer(params, cfg, layer_idx, begin)
    return q, k, v, None, latent, None, None


def mla_chunk_layer_end(params, cfg: ModelConfig, layer_idx, x, out, k, v,
                        a_tilde, masks, latent, sp, sp_state, cluster_arr,
                        *, method: str):
    """MLA quantum B: o-proj, residual, ln2 and the layer's FFN (dense on
    the prefix layers, the held-expert layer on the stack)."""
    del k, v, a_tilde, masks, sp, cluster_arr, method

    def end(layer, moe_ffn):
        y = x + mla.out_proj(layer["attn"], out)
        h = common.rmsnorm(layer["ln2"], y, cfg.rms_norm_eps)
        f, rows = _ffn_serve(layer, h, cfg, moe_ffn)
        return y + f, rows

    x, rows = _mla_layer(params, cfg, layer_idx, end)
    return x, latent, sp_state, AttnStats.zero(), rows


def chunk_prefill_finish(params, cfg: ModelConfig, x: jnp.ndarray,
                         batch_idx: jnp.ndarray,    # (P,) int32
                         rows: jnp.ndarray          # (P,) int32
                         ) -> jnp.ndarray:
    """Final quantum: per-segment last-token gather + LM head → (P, V).

    ``rows`` are absolute positions in the packed row — segment j's real
    last token ``j * seg + clip(plen, 1, seg) - 1`` — so each admitted
    request's first sampled token is conditioned on its own text, matching
    the one-shot path's ``prompt_lens`` gather.
    """
    last = x[batch_idx, rows, :]
    return logits_from_hidden(params, cfg, last)


def make_chunk_prefill(cfg: ModelConfig) -> Optional[ChunkPrefillApi]:
    """Bind the quantum entry points for a transformer-family config.

    GQA configs index the scanned stack; MLA configs run every layer,
    the dense prefix layers included, through the same quanta (the
    latent cache takes one row per token and layer).  Returns ``None``
    for a GQA stack behind prefix layers, which the quanta cannot index.
    """
    latent = cfg.mla.enabled
    if not latent and num_prefix_layers(cfg) > 0:
        return None
    begin_fn = mla_chunk_layer_begin if latent else chunk_prefill_layer_begin
    end_fn = mla_chunk_layer_end if latent else chunk_prefill_layer_end
    return ChunkPrefillApi(
        begin=lambda params, tokens: chunk_prefill_begin(params, cfg, tokens),
        layer_begin=lambda params, layer_idx, x, positions, sp, sp_state, \
            cluster_arr, **kw: begin_fn(
                params, cfg, layer_idx, x, positions, sp, sp_state,
                cluster_arr, **kw),
        attn=lambda sp, q, k, v, masks, gate, perm, **kw: chunk_prefill_attn(
            cfg, sp, q, k, v, masks, gate, perm, **kw),
        layer_end=lambda params, layer_idx, x, out, k, v, a_tilde, masks, \
            decision, sp, sp_state, cluster_arr, **kw: end_fn(
                params, cfg, layer_idx, x, out, k, v, a_tilde, masks,
                decision, sp, sp_state, cluster_arr, **kw),
        finish=lambda params, x, batch_idx, rows: chunk_prefill_finish(
            params, cfg, x, batch_idx, rows),
    )
