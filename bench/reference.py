"""Building blocks of the plain references and of their low-precision
control.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``.  Each
family's ``logits`` (``bench/families/<family>.py``) composes them into
the published architecture; they import nothing of the program and take
nothing the program made.

``fp8=True`` is the control's step: both operands of a projection rounded
to float8_e4m3fn under one scale per tensor, the step below the bfloat16
the configurations serve in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F8_MAX = 448.0            # largest finite float8_e4m3fn


def q8(x):
    """Round to float8_e4m3fn under one per-tensor scale, back to f32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(spec, x, w, fp8):
    if fp8:
        x, w = q8(x), q8(w)
    return jnp.einsum(spec, x, w, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (heads, T, hd) rotated by positions pos (T,), halves convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, block):
    """Causal attention; q (H, T, hd), k/v (Hkv, T, hd) -> (H, T, hd).

    Query block i scans key blocks 0..i with a running max and sum."""
    h, t, hd = q.shape
    hkv = k.shape[0]
    g = h // hkv
    nb = t // block
    qb = q.reshape(hkv, g, nb, block, hd) * hd ** -0.5
    hi = jax.lax.Precision.HIGHEST

    def row(i):
        qi = jax.lax.dynamic_index_in_dim(qb, i, 2, keepdims=False)
        rows = i * block + jnp.arange(block)

        def body(j, carry):
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * block, block, 1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * block, block, 1)
            s = jnp.einsum("kgqd,ksd->kgqs", qi, kj, precision=hi)
            cols = j * block + jnp.arange(block)
            s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
            m2 = jnp.maximum(m, jnp.max(s, -1))
            p = jnp.exp(s - m2[..., None])
            c = jnp.exp(m - m2)
            return (m2, l * c + jnp.sum(p, -1),
                    acc * c[..., None] + jnp.einsum("kgqs,ksd->kgqd", p, vj,
                                                    precision=hi))

        init = (jnp.full((hkv, g, block), -jnp.inf),
                jnp.zeros((hkv, g, block)), jnp.zeros((hkv, g, block, hd)))
        _, l, acc = jax.lax.fori_loop(0, i + 1, body, init)
        return acc / l[..., None]

    out = jax.lax.map(row, jnp.arange(nb))          # (nb, Hkv, g, B, hd)
    return jnp.moveaxis(out, 0, 2).reshape(h, t, hd)
