"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

K/V are compressed into a small latent c_kv (kv_lora_rank) plus one shared
rotary key stream; per-head keys/values are up-projections of the latent.
The KV cache stores only (c_kv, k_rope) — the memory win that makes 128-head
attention affordable.  Decode uses the **absorbed** formulation (q_nope is
pushed through W_uk so scores are taken directly against the latent cache);
prefill decompresses so SharePrefill's per-head pattern logic sees ordinary
per-head Q·K blocks (DESIGN.md §5).

Rope is YaRN where the config scales positions (``MLAConfig.rope_factor``
> 1, DeepSeek-V2's formulas): interpolated inverse frequencies, cos/sin
times mscale(factor, mscale) / mscale(factor, mscale_all_dim), and a
softmax scale of mscale(factor, mscale_all_dim)² / √(qk_nope + qk_rope).
Prefill folds that temperature into q, so the attention kernels keep
their 1/√d.

Serving keeps one latent row per token, ``c_kv ‖ k_rope``
(``MLAConfig.latent_dim`` wide), in the paged pool; the absorbed decode
(:func:`mla_decode_paged`) is the dense paged decode of
``models.attention`` as MQA over that row, whose first ``kv_lora_rank``
columns are the value.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.api import SharePrefill
from repro.core import share_attention as sa
from repro.distributed.sharding import shard
from repro.kernels.chunked import chunked_attention
from repro.models import common
from repro.models.attention import (AttnStats, dense_decode,
                                    paged_append, paged_pages,
                                    resolve_attention_fn)


def init_mla_layer(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    params = {
        "w_kv_down": common.dense_init(
            ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": common.init_rmsnorm(m.kv_lora_rank, dtype),
        "w_uk": common.dense_init(
            ks[2], (m.kv_lora_rank, h, m.qk_nope_head_dim), dtype),
        "w_uv": common.dense_init(
            ks[3], (m.kv_lora_rank, h, m.v_head_dim), dtype),
        "wo": common.dense_init(ks[4], (h, m.v_head_dim, d), dtype),
    }
    if m.q_lora_rank:
        params["w_q_down"] = common.dense_init(
            ks[5], (d, m.q_lora_rank), dtype)
        params["q_norm"] = common.init_rmsnorm(m.q_lora_rank, dtype)
        params["w_q_up"] = common.dense_init(
            ks[6], (m.q_lora_rank, h, qk_dim), dtype)
    else:
        params["w_q"] = common.dense_init(ks[0], (d, h, qk_dim), dtype)
    return params


def _rope(x, positions, cfg: ModelConfig):
    """Rotate the rotary stream (YaRN where the config scales positions)."""
    m = cfg.mla
    if m.rope_factor <= 1:
        return common.apply_rope(x, positions, cfg.rope_theta)
    inv = common.yarn_frequencies(
        x.shape[-1], cfg.rope_theta, m.rope_factor,
        m.rope_original_max_positions, m.beta_fast, m.beta_slow)
    scale = (common.yarn_mscale(m.rope_factor, m.mscale)
             / common.yarn_mscale(m.rope_factor, m.mscale_all_dim))
    return common.apply_rope(x, positions, cfg.rope_theta, inv=inv,
                             scale=scale)


def q_temperature(cfg: ModelConfig) -> float:
    """YaRN's softmax temperature, mscale(factor, mscale_all_dim)²: the
    published softmax scale over 1/√(qk_nope + qk_rope)."""
    m = cfg.mla
    return common.yarn_mscale(m.rope_factor, m.mscale_all_dim) ** 2


def softmax_scale(cfg: ModelConfig) -> float:
    m = cfg.mla
    return q_temperature(cfg) / math.sqrt(m.qk_nope_head_dim
                                          + m.qk_rope_head_dim)


def _project_q(params, x, cfg: ModelConfig):
    m = cfg.mla
    if m.q_lora_rank:
        cq = common.rmsnorm(params["q_norm"], x @ params["w_q_down"],
                            cfg.rms_norm_eps)
        q = jnp.einsum("bsr,rhk->bhsk", cq, params["w_q_up"])
    else:
        q = jnp.einsum("bsd,dhk->bhsk", x, params["w_q"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = q[..., m.qk_nope_head_dim:]
    return shard(q_nope, "batch", "heads"), shard(q_rope, "batch", "heads")


def _project_kv_latent(params, x, cfg: ModelConfig, positions):
    """x → (c_kv (B,S,R), k_rope (B,1,S,rope_dim)) with RoPE applied."""
    m = cfg.mla
    down = x @ params["w_kv_down"]
    c_kv = common.rmsnorm(params["kv_norm"], down[..., : m.kv_lora_rank],
                          cfg.rms_norm_eps)
    k_rope = down[..., m.kv_lora_rank:][:, None, :, :]   # (B,1,S,rope)
    k_rope = _rope(k_rope, positions[:, None, :], cfg)
    return c_kv, k_rope


def _decompress(params, c_kv, cfg: ModelConfig):
    m = cfg.mla
    k_nope = jnp.einsum("bsr,rhk->bhsk", c_kv, params["w_uk"])
    v = jnp.einsum("bsr,rhk->bhsk", c_kv, params["w_uv"])
    return shard(k_nope, "batch", "heads"), shard(v, "batch", "heads")


def prefill_qkv(params, x, cfg: ModelConfig, positions):
    """Decompressed per-head ``q``/``k`` (qk_nope + qk_rope wide, q times
    YaRN's temperature) and ``v``, and the latent rows ``(B, S,
    latent_dim)`` the cache keeps."""
    m = cfg.mla
    q_nope, q_rope = _project_q(params, x, cfg)
    q_rope = _rope(q_rope, positions[:, None, :], cfg)
    c_kv, k_rope = _project_kv_latent(params, x, cfg, positions)
    k_nope, v = _decompress(params, c_kv, cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    t = q_temperature(cfg)
    if t != 1.0:
        q = jnp.asarray(jnp.asarray(q, jnp.float32) * t, q.dtype)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1]
                                  + (m.qk_rope_head_dim,))], axis=-1)
    return q, k, v, jnp.concatenate([c_kv, k_rope[:, 0]], axis=-1)


def out_proj(params, out):
    """Attention output (B, H, S, v_head_dim) → (B, S, D)."""
    out = shard(out, "batch", "heads")
    return jnp.einsum("bhsk,hkd->bsd", out, params["wo"])


def mla_train(params, x, cfg: ModelConfig, positions,
              block_size: int = 128) -> jnp.ndarray:
    s = x.shape[1]
    q, k, v, _ = prefill_qkv(params, x, cfg, positions)
    out, _ = chunked_attention(q, k, v, block_size=min(block_size, s),
                               causal=True)
    return out_proj(params, out)


def mla_prefill(params, x, cfg: ModelConfig, positions, *,
                method: str, sp: SharePrefill, sp_state,
                cluster_ids: Optional[jnp.ndarray],
                attn_impl: str = "auto",
                attn_width: Optional[int] = None):
    """Returns (y, cache=(c_kv, k_rope), new_state, stats)."""
    m = cfg.mla
    s = x.shape[1]
    q, k, v, latent = prefill_qkv(params, x, cfg, positions)

    use_sparse = method == "share" and sp.applicable(s)
    if use_sparse:
        bs = min(sp.cfg.block_size, s)
        attention_fn = resolve_attention_fn(attn_impl, bs, width=attn_width)
        out, new_state, lstats = sa.batched_share_prefill_attention_layer(
            q, k, v, sp_state, cluster_ids, sp.cfg, attention_fn)
        stats = AttnStats(lstats.num_shared, lstats.num_dense,
                          lstats.num_vs, lstats.block_density,
                          lstats.max_row_pop)
    else:
        out, _ = chunked_attention(q, k, v, block_size=min(128, s),
                                   causal=True)
        new_state, stats = sp_state, AttnStats.zero()
    y = out_proj(params, out)
    r = m.kv_lora_rank
    return y, (latent[..., :r], latent[..., r:]), new_state, stats


def mla_decode(params, x, cfg: ModelConfig,
               cache_ckv: jnp.ndarray,          # (B, S, R)
               cache_krope: jnp.ndarray,        # (B, S, rope_dim)
               pos: jnp.ndarray, positions):
    """Absorbed decode: score latent cache directly (perf note in DESIGN.md)."""
    s = cache_ckv.shape[1]
    q_nope, q_rope = _project_q(params, x, cfg)          # (B,H,1,·)
    q_rope = _rope(q_rope, positions[:, None, :], cfg)
    c_new, k_rope_new = _project_kv_latent(params, x, cfg, positions)
    cache_ckv = jax.lax.dynamic_update_slice_in_dim(
        cache_ckv, c_new, pos, axis=1)
    cache_krope = jax.lax.dynamic_update_slice_in_dim(
        cache_krope, k_rope_new[:, 0], pos, axis=1)
    cache_ckv = shard(cache_ckv, "batch", "seq")

    # absorb W_uk into q: (B,H,1,R) scores against latent directly
    q_lat = jnp.einsum("bhqk,rhk->bhqr", q_nope, params["w_uk"])
    logits = (jnp.einsum("bhqr,bsr->bhqs", q_lat, cache_ckv)
              + jnp.einsum("bhqk,bsk->bhqs", q_rope, cache_krope)
              ) * softmax_scale(cfg)
    length_mask = jnp.arange(s) <= pos
    logits = jnp.where(length_mask[None, None, None, :], logits, -jnp.inf)
    p = jax.nn.softmax(jnp.asarray(logits, jnp.float32), axis=-1)
    # attend in latent space, then decompress through W_uv (absorbed)
    lat = jnp.einsum("bhqs,bsr->bhqr", p, cache_ckv)
    out = jnp.einsum("bhqr,rhk->bhqk", lat, params["w_uv"])
    y = jnp.einsum("bhqk,hkd->bqd", jnp.asarray(out, x.dtype), params["wo"])
    return y, (cache_ckv, cache_krope)


def mla_decode_paged(params, x, cfg: ModelConfig, pool: jnp.ndarray,
                     layer, pos: jnp.ndarray, positions, page_table,
                     mask: Optional[jnp.ndarray] = None):
    """Absorbed decode of one layer at per-slot positions against the
    latent page pool ``(L, P, 1, page_size, latent_dim)``.

    The step's latent row is written into each slot's current page at
    ``[layer]`` (``attention.paged_append``), q_nope is taken through
    W_uk, and the query ``q_lat ‖ q_rope`` attends the resident pages as
    one key head (MQA) whose first ``kv_lora_rank`` columns are the value,
    at the YaRN softmax scale; the latent result goes through W_uv and
    W_o.  ``mask`` is ``(B, NB·page_size)`` in logical slot coordinates
    (default: every slot up to ``pos``).  Returns ``(y (B, 1, D), pool)``."""
    r = cfg.mla.kv_lora_rank
    if mask is None:
        sv = page_table.shape[1] * pool.shape[-2]
        mask = jnp.arange(sv)[None, :] <= pos[:, None]
    q_nope, q_rope = _project_q(params, x, cfg)          # (B,H,1,·)
    q_rope = _rope(q_rope, positions[:, None, :], cfg)
    c_new, k_rope_new = _project_kv_latent(params, x, cfg, positions)
    row = jnp.concatenate([c_new, k_rope_new[:, 0]], axis=-1)  # (B,1,W)
    pool = paged_append(pool, row[:, None], page_table, pos, (layer,))
    q_lat = jnp.einsum("bhqk,rhk->bhqr", q_nope, params["w_uk"])
    q = jnp.concatenate([q_lat, q_rope], axis=-1)[:, :, 0]    # (B,H,W)
    # the whole row serves as the value and the result is cut to its
    # first r columns: a sliced value operand is a second copy of the
    # slots' pages (twice the step's temporaries on a v5e)
    pages = paged_pages(pool, page_table, (layer,))
    lat = dense_decode(q, pages, pages, mask, scale=softmax_scale(cfg))
    out = jnp.einsum("bhr,rhk->bhk", jnp.asarray(lat[..., :r], x.dtype),
                     params["w_uv"])
    return jnp.einsum("bhk,hkd->bd", out, params["wo"])[:, None], pool
