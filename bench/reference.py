"""Plain reference of a dense GQA decoder, and its low-precision control.

Straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``, following the published
architecture: token embedding; per layer RMSNorm, query/key/value
projections, rotary embedding on the two halves of each head, causal
softmax attention scaled by ``head_dim ** -0.5`` with each key/value head
serving ``heads / kv_heads`` query heads, output projection and residual,
RMSNorm, SwiGLU feed-forward and residual; final RMSNorm and the output
head.  It imports nothing of the program and takes nothing the program
made: its weights come from ``weights.py`` and the seed.

It runs layer by layer (one jitted layer program per padded length) and
attends in blocks of query rows, each scanning only the key blocks at or
before it, so a 32k-token sequence fits next to the weights.  Sequences
are right-padded to a multiple of ``pad``; attention is causal, so padding
changes no earlier position.

``precision="fp8"`` is the control: the same computation with the operands
of every projection and of the output head (weights and activations)
rounded to float8_e4m3fn under one scale per tensor, the step below the
bfloat16 the configurations serve in.  Attention itself stays float32.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0            # largest finite float8_e4m3fn


def _q8(x):
    """Round to float8_e4m3fn under one per-tensor scale, back to f32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, x, w, fp8):
    if fp8:
        x, w = _q8(x), _q8(w)
    return jnp.einsum(spec, x, w, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (heads, T, hd) rotated by positions pos (T,), halves convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, block):
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


@functools.partial(jax.jit, static_argnames=("theta", "eps", "fp8", "block"))
def _layer(p, x, pos, *, theta, eps, fp8, block):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    h = _rmsnorm(x, p["ln1"], eps)
    q = _rope(_mm("td,dhk->htk", h, p["wq"], fp8), pos, theta)
    k = _rope(_mm("td,dhk->htk", h, p["wk"], fp8), pos, theta)
    v = _mm("td,dhk->htk", h, p["wv"], fp8)
    a = _attention(q, k, v, block)
    x = x + _mm("htk,hkd->td", a, p["wo"], fp8)
    h = _rmsnorm(x, p["ln2"], eps)
    f = jax.nn.silu(_mm("td,df->tf", h, p["w_gate"], fp8)) \
        * _mm("td,df->tf", h, p["w_up"], fp8)
    return x + _mm("tf,fd->td", f, p["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8", "tied"))
def _head(w, embed, norm, x, *, eps, fp8, tied):
    h = _rmsnorm(x, norm.astype(jnp.float32), eps)
    w = (embed.T if tied else w).astype(jnp.float32)
    return _mm("kd,dv->kv", h, w, fp8)


_LAYER_KEYS = {"ln1": "stack/ln1/scale", "ln2": "stack/ln2/scale",
               "wq": "stack/attn/wq", "wk": "stack/attn/wk",
               "wv": "stack/attn/wv", "wo": "stack/attn/wo",
               "w_gate": "stack/ffn/w_gate", "w_up": "stack/ffn/w_up",
               "w_down": "stack/ffn/w_down"}


def logits(params: Dict[str, jax.Array], sizes: Dict, tokens: np.ndarray,
           rows: np.ndarray, *, precision: str = "f32", pad: int = 1024,
           block: int = 512) -> np.ndarray:
    """Float32 logits ``(len(rows), vocab)`` of the model over ``tokens``,
    at sequence rows ``rows``.  ``params`` is ``path -> array`` (see
    ``weights.layout``)."""
    fp8 = {"f32": False, "fp8": True}[precision]
    t = len(tokens)
    tp = -(-t // pad) * pad
    ids = np.zeros((tp,), np.int32)
    ids[:t] = tokens
    pos = np.arange(tp, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(ids), axis=0
                     ).astype(jnp.float32)
        for li in range(sizes["num_hidden_layers"]):
            p = {k: params[path][li] for k, path in _LAYER_KEYS.items()}
            x = _layer(p, x, jnp.asarray(pos),
                       theta=float(sizes["rope_theta"]),
                       eps=float(sizes["rms_norm_eps"]), fp8=fp8,
                       block=min(block, tp))
        out = _head(params.get("lm_head"), params["embed"],
                    params["final_norm/scale"], x[jnp.asarray(rows)],
                    eps=float(sizes["rms_norm_eps"]), fp8=fp8,
                    tied=bool(sizes["tie_word_embeddings"]))
    return np.asarray(out)
