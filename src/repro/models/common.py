"""Shared neural building blocks (module-free functional style).

Parameters are plain pytrees (nested dicts of jnp arrays); every block is an
``init_*(key, ...) -> params`` / ``apply(params, x, ...) -> y`` pair.  Layer
stacks are built by stacking params along a leading "stack" dim and scanning.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard


# --------------------------------------------------------------------------
# Init helpers
# --------------------------------------------------------------------------

def dense_init(key: jax.Array, shape: Tuple[int, ...],
               dtype=jnp.float32) -> jnp.ndarray:
    """Truncated-normal fan-in init."""
    fan_in = shape[0] if len(shape) > 1 else 1
    std = 1.0 / (fan_in ** 0.5)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std
            ).astype(dtype)


def embed_init(key: jax.Array, vocab: int, dim: int,
               dtype=jnp.float32) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


def stack_init(init_fn, key: jax.Array, num: int):
    """Stack ``num`` independent inits along a leading scan dim."""
    keys = jax.random.split(key, num)
    return jax.vmap(init_fn)(keys)


# --------------------------------------------------------------------------
# Normalization
# --------------------------------------------------------------------------

def init_rmsnorm(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x = jnp.asarray(x, jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return jnp.asarray(y * params["scale"], dtype)


# --------------------------------------------------------------------------
# Rotary embeddings (standard + M-RoPE)
# --------------------------------------------------------------------------

def rope_frequencies(dim: int, theta: float) -> jnp.ndarray:
    """(dim/2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature 0.1·mscale·ln(factor) + 1 (1 when the
    positions are not scaled)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float,
                     beta_slow: float) -> jnp.ndarray:
    """(dim/2,) YaRN inverse frequencies (DeepSeek-V2's formulas): the
    rope frequencies, interpolated by ``factor`` past the correction dims
    of ``beta_slow`` rotations, kept below those of ``beta_fast``, with a
    linear ramp between."""
    def corr(rot):
        return (dim * math.log(original_max / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = rope_frequencies(dim, theta)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float, inv: Optional[jnp.ndarray] = None,
               scale: float = 1.0) -> jnp.ndarray:
    """Rotate (…, S, D) by per-token positions (…, S); ``inv`` overrides
    the plain inverse frequencies (YaRN) and ``scale`` multiplies cos and
    sin."""
    if theta <= 0:
        return x
    d = x.shape[-1]
    if inv is None:
        inv = rope_frequencies(d, theta)
    ang = positions[..., None].astype(jnp.float32) * inv      # (…, S, D/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if scale != 1.0:
        sin, cos = sin * scale, cos * scale
    x1, x2 = jnp.split(jnp.asarray(x, jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return jnp.asarray(out, x.dtype)


def apply_mrope(x: jnp.ndarray, positions: jnp.ndarray,
                theta: float, sections: Tuple[int, int, int]) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE.

    ``positions``: (3, …, S) — temporal / height / width position ids.
    ``sections``: rotary half-dim split across the three id streams
    (t, h, w); Σ sections = D/2.
    """
    d = x.shape[-1]
    inv = rope_frequencies(d, theta)                           # (D/2,)
    # choose which position stream drives each frequency slot
    sec = jnp.concatenate([
        jnp.full((sections[0],), 0), jnp.full((sections[1],), 1),
        jnp.full((sections[2],), 2)]).astype(jnp.int32)        # (D/2,)
    pos = jnp.take_along_axis(
        jnp.moveaxis(positions, 0, -1),                        # (…, S, 3)
        jnp.broadcast_to(sec, positions.shape[1:] + (d // 2,)),
        axis=-1).astype(jnp.float32)                           # (…, S, D/2)
    ang = pos * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(jnp.asarray(x, jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return jnp.asarray(out, x.dtype)


def sinusoidal_positions(num: int, dim: int) -> jnp.ndarray:
    """Whisper-style fixed sinusoidal embeddings (num, dim)."""
    pos = jnp.arange(num, dtype=jnp.float32)[:, None]
    inv = jnp.exp(-jnp.log(10000.0) *
                  jnp.arange(dim // 2, dtype=jnp.float32) / (dim // 2 - 1))
    ang = pos * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------

def init_mlp(key: jax.Array, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype),
    }


def mlp(params, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    h = shard(h, "batch", None, "mlp")
    return h @ params["w_down"]


# --------------------------------------------------------------------------
# QKV projections (GQA)
# --------------------------------------------------------------------------

def init_gqa_proj(key: jax.Array, d_model: int, num_heads: int,
                  num_kv_heads: int, head_dim: int, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d_model, num_heads, head_dim), dtype),
        "wk": dense_init(k2, (d_model, num_kv_heads, head_dim), dtype),
        "wv": dense_init(k3, (d_model, num_kv_heads, head_dim), dtype),
        "wo": dense_init(k4, (num_heads, head_dim, d_model), dtype),
    }


def gqa_qkv(params, x: jnp.ndarray):
    """x (B, S, D) → q (B, H, S, hd), k/v (B, Hkv, S, hd)."""
    q = jnp.einsum("bsd,dhk->bhsk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, params["wv"])
    q = shard(q, "batch", "heads")
    k = shard(k, "batch", "kv_heads")
    v = shard(v, "batch", "kv_heads")
    return q, k, v


def gqa_out(params, attn: jnp.ndarray) -> jnp.ndarray:
    """attn (B, H, S, hd) → (B, S, D)."""
    return jnp.einsum("bhsk,hkd->bsd", attn, params["wo"])


def repeat_kv(x: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(B, Hkv, S, D) → (B, H, S, D)."""
    if groups == 1:
        return x
    return jnp.repeat(x, groups, axis=1)


def maybe_remat(fn, policy: str):
    """Wrap a scan layer body in jax.checkpoint per the config policy.

    ``full`` saves nothing (recompute everything in backward); ``dots``
    saves matmul outputs that have no batch dims (weight-stationary
    activations) — the standard large-model trade-off (§Perf iteration 2).
    """
    if policy == "full":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.nothing_saveable)
    if policy == "dots":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn
