"""The dense chunked-prefill ``attn`` quantum over its causal frontier.

``chunk_prefill_attn`` without masks reads only the keys ``[0, off + cn)``
of its chunk: through the Pallas flash kernel (``kernels/flash_prefill.py``,
interpret mode here) where the backend is a TPU, through the sliced
``chunked_attention`` elsewhere.  For every chunk start of a 512-token
bucket, the assembled chunk outputs must match ``chunked_attention`` and
``kernels/ref.py`` over the full keys, for GQA (group 2, head dim 128) and
MLA (qk 192, v 128, q scaled by a temperature), with a ragged last chunk
and with chunk == seq.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.api import SharePrefill
from repro.kernels.chunked import chunked_attention
from repro.kernels.flash_prefill import flash_chunk_attention, flash_tiles
from repro.kernels.ref import dense_attention_ref
from repro.models import chunked_prefill as cp
from repro.models.common import repeat_kv

SEQ, BS = 512, 128
# (config, q heads, kv heads, qk width, v width, q temperature)
SHAPES = {
    "gqa": ("internlm2-1.8b", 4, 2, 128, 128, 1.0),
    "mla": ("deepseek-v2-lite", 2, 2, 192, 128, 1.37),
}
CHUNKS = {"chunk=1blk": 1, "chunk=3blk_ragged_tail": 3, "chunk=seq": 4}


def _inputs(kind, seed=0):
    _, h, hkv, d, dv, temp = SHAPES[kind]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, h, SEQ, d), jnp.float32) * temp
    k = jax.random.normal(ks[1], (1, hkv, SEQ, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, hkv, SEQ, dv), jnp.float32)
    return tuple(x.astype(jnp.bfloat16) for x in (q, k, v))


def _full_keys(q, k, v):
    g = q.shape[1] // k.shape[1]
    out, _ = chunked_attention(q, repeat_kv(k, g), repeat_kv(v, g),
                               block_size=BS, causal=True)
    return out


def _quanta(kind, chunk_blocks, monkeypatch, flash):
    """The dense quantum's rows for every chunk start, concatenated."""
    name, h = SHAPES[kind][:2]
    cfg = get_config(name)
    assert not cfg.sliding_window
    assert cfg.gqa_groups == SHAPES[kind][1] // SHAPES[kind][2]
    sp = SharePrefill.trivial(cfg.share_prefill, 1, h)
    assert sp.cfg.block_size == BS
    monkeypatch.setattr(cp, "_flash_backend", lambda: flash)
    q, k, v = _inputs(kind)
    rows = []
    nb = SEQ // BS
    for start in range(0, nb, chunk_blocks):
        cb = min(chunk_blocks, nb - start)
        out, a = cp.chunk_prefill_attn(
            cfg, sp, q, k, v, None, None, None, method="dense",
            attn_impl="chunked", attn_width=None, chunk_start=start,
            chunk_blocks=cb)
        assert a is None and out.dtype == q.dtype
        rows.append(out)
    return jnp.concatenate(rows, axis=2), (q, k, v)


@pytest.mark.parametrize("chunk", list(CHUNKS), ids=list(CHUNKS))
@pytest.mark.parametrize("kind", list(SHAPES))
@pytest.mark.parametrize("flash", [False, True], ids=["sliced", "pallas"])
def test_dense_quantum_matches_full_keys(kind, chunk, flash, monkeypatch):
    out, (q, k, v) = _quanta(kind, CHUNKS[chunk], monkeypatch, flash)
    full = _full_keys(q, k, v)
    g = q.shape[1] // k.shape[1]
    ref = dense_attention_ref(q, repeat_kv(k, g), repeat_kv(v, g))
    got = np.asarray(out, np.float32)
    if not flash:
        # the same float32 arithmetic: keys past the frontier only ever
        # added exact zeros
        np.testing.assert_array_equal(got, np.asarray(full, np.float32))
    # the kernel's P enters the PV matmul at bf16: within bf16 rounding of
    # the float32-P paths
    for want in (full, ref):
        np.testing.assert_allclose(got, np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)


def test_sliding_window_keeps_pure_jax_path(monkeypatch):
    """A sliding window never takes the kernel, even where it could run."""
    cfg = dataclasses.replace(get_config("internlm2-1.8b"),
                              sliding_window=128)
    sp = SharePrefill.trivial(cfg.share_prefill, 1, 4)
    monkeypatch.setattr(cp, "_flash_backend", lambda: True)
    monkeypatch.setattr(cp, "flash_chunk_attention", _refuse)
    q, k, v = _inputs("gqa")
    out, _ = cp.chunk_prefill_attn(
        cfg, sp, q, k, v, None, None, None, method="dense",
        attn_impl="chunked", attn_width=None, chunk_start=2, chunk_blocks=2)
    want, _ = chunked_attention(q[:, :, 256:], repeat_kv(k, 2),
                                repeat_kv(v, 2), block_size=BS,
                                window=128, q_offset=256)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=8e-3)


def _refuse(*a, **k):
    raise AssertionError("the flash kernel ran under a sliding window")


@pytest.mark.parametrize("cn,front,want", [
    (1024, 32768, (512, 512)), (1024, 1024, (512, 512)),
    (384, 384, (128, 128)), (128, 512, (128, 512)), (64, 256, None),
    (192, 256, None),
])
def test_flash_tiles(cn, front, want):
    assert flash_tiles(cn, front) == want


def test_flash_kernel_ignores_keys_past_the_frontier():
    """Keys past the chunk's frontier are never read: poisoning them with
    NaN leaves the output unchanged."""
    q, k, v = _inputs("mla")
    off, cn = 128, 256
    base = flash_chunk_attention(q[:, :, off:off + cn], k, v, q_offset=off)
    bad_k = k.at[:, :, off + cn:].set(jnp.nan)
    bad_v = v.at[:, :, off + cn:].set(jnp.nan)
    got = flash_chunk_attention(q[:, :, off:off + cn], bad_k, bad_v,
                                q_offset=off)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    with pytest.raises(ValueError, match="frontier"):
        flash_chunk_attention(q[:, :, off:off + cn], k[:, :, :off],
                              v[:, :, :off], q_offset=off)
