"""Chip-compiles of the serving path's Pallas kernels, with no chip attached.

Each test lowers one kernel at internlm2-1.8b widths (16 query / 8 kv
heads, head_dim 128, 8,192 tokens, block 64 and 128) for one chip of a
described TPU v5e topology, and asserts that the compiled program carries
the Mosaic kernel (``tpu_custom_call``).  The TPU compiler refuses here
what interpret mode accepts: block shapes that break the (8, 128) tiling
rule, scalar stores to VMEM, unsupported vector reshapes, and kernels
that want more VMEM than the chip has.  Nothing runs, so these say
nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and the
test workers import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H, HKV, D, N = 16, 8, 128, 8192
CHUNK = 1024
SLOTS = 8
BLOCKS = (64, 128)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on the described chip; the persistent compilation cache
    is off meanwhile (a chip compile written to it cannot be read back
    without the chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bs", BLOCKS)
def test_prefill_full_launch(one_chip, bs):
    from repro.kernels.block_sparse_attn import block_sparse_attention_batched
    nb = N // bs
    _compile(lambda q, k, v, i, c: block_sparse_attention_batched(
                 q, k, v, i, c, block_size=bs, interpret=False),
             [((1, H, N, D), jnp.bfloat16), ((1, HKV, N, D), jnp.bfloat16),
              ((1, HKV, N, D), jnp.bfloat16), ((1, H, nb, nb), jnp.int32),
              ((1, H, nb), jnp.int32)], one_chip)


@pytest.mark.parametrize("bs", BLOCKS)
def test_prefill_chunk_launch(one_chip, bs):
    from repro.kernels.block_sparse_attn import block_sparse_attention_batched
    nb, cq = N // bs, CHUNK // bs
    _compile(lambda q, k, v, i, c: block_sparse_attention_batched(
                 q, k, v, i, c, block_size=bs, q_block_offset=nb // 2,
                 interpret=False),
             [((1, H, CHUNK, D), jnp.bfloat16),
              ((1, HKV, N, D), jnp.bfloat16), ((1, HKV, N, D), jnp.bfloat16),
              ((1, H, cq, nb), jnp.int32), ((1, H, cq), jnp.int32)],
             one_chip)


@pytest.mark.parametrize("bs", BLOCKS)
def test_prefill_paged_chunk_launch(one_chip, bs):
    from repro.kernels.block_sparse_attn import (
        block_sparse_attention_batched_paged)
    nb, cq = N // bs, CHUNK // bs
    pages = nb + 1
    _compile(lambda q, pk, pv, pt, i, c: block_sparse_attention_batched_paged(
                 q, pk, pv, pt, i, c, block_size=bs, q_block_offset=nb // 2,
                 interpret=False),
             [((1, H, CHUNK, D), jnp.bfloat16),
              ((pages, HKV, bs, D), jnp.bfloat16),
              ((pages, HKV, bs, D), jnp.bfloat16), ((1, nb), jnp.int32),
              ((1, H, cq, nb), jnp.int32), ((1, H, cq), jnp.int32)],
             one_chip)


@pytest.mark.parametrize("bs", BLOCKS)
def test_strip(one_chip, bs):
    from repro.kernels.strip import strip_scores_pallas
    _compile(lambda q, k: strip_scores_pallas(q, k, block_size=bs,
                                              interpret=False),
             [((H, N, D), jnp.bfloat16), ((HKV, N, D), jnp.bfloat16)],
             one_chip)


@pytest.mark.parametrize("bs", BLOCKS)
def test_paged_decode(one_chip, bs):
    from repro.kernels.decode_attn import flash_decode_sparse_batched_paged
    nb = N // bs
    pages = SLOTS * nb + 1
    _compile(lambda q, pk, pv, pt, i, c, kh, va:
             flash_decode_sparse_batched_paged(q, pk, pv, pt, i, c, kh, va,
                                               interpret=False),
             [((SLOTS, H, D), jnp.bfloat16),
              ((pages, HKV, bs, D), jnp.bfloat16),
              ((pages, HKV, bs, D), jnp.bfloat16),
              ((SLOTS, nb), jnp.int32), ((SLOTS, HKV, nb), jnp.int32),
              ((SLOTS, HKV), jnp.int32),
              ((SLOTS, HKV, nb, H // HKV), jnp.bool_),
              ((SLOTS, N), jnp.bool_)], one_chip)


def test_paged_decode_step_updates_pool_in_place(one_chip):
    """The engine's dense paged decode program at internlm2-1.8b widths
    (24 layers, 8 slots, 68-page tables, a 360-page bf16 pool) takes the
    pool it is given and writes it in place: the output aliases the whole
    pool, no temporary reaches one layer's K+V pool slice, and the only
    pool-shaped values of the entry computation are its parameters, the
    layer loop, and the loop's results."""
    import re

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import EngineConfig, ServingEngine

    layers, pages, ps, nb = 24, 360, 128, (N + 512) // 128
    model = build_model(get_config("internlm2-1.8b"))
    eng = ServingEngine(model, None, model.default_share_prefill(),
                        EngineConfig(method="dense", paged=True,
                                     max_batch=SLOTS))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = jax.ShapeDtypeStruct((layers, pages, HKV, ps, D), jnp.bfloat16,
                                sharding=one_chip)
    vec = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = eng._decode_fn_paged(SLOTS, nb).lower(
        params, vec(SLOTS, 1), {"prefix": [], "stack": (pool, pool)},
        vec(SLOTS, nb), vec(SLOTS), vec(SLOTS), vec(SLOTS)).compile()

    pool_bytes = 2 * layers * pages * HKV * ps * D * 2
    layer_bytes = pool_bytes // layers
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < layer_bytes

    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    shape = f"bf16[{layers},{pages},{HKV},{ps},{D}]"
    ops = set()
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][\w-]*)\(", line)
        if m and shape in m.group(1):
            ops.add(m.group(2))
    assert "while" in ops
    assert ops <= {"parameter", "while", "get-tuple-element", "tuple"}, ops


def test_latent_paged_decode_step_updates_pool_in_place(one_chip):
    """The engine's latent paged decode program at DeepSeek-V2-Lite widths
    (27 layers, the dense prefix layer's pages at layer 0 of the pool,
    8 held of 64 routed experts, 8 slots, 260-page tables, a 521-page
    bf16 pool of 576-wide latent rows) writes the pool in place: the
    output aliases the whole pool, the largest temporary is one layer's
    read of the slots' resident pages, and the only pool-shaped values of
    the entry computation are its parameters, the layer loop and its
    results (and bitcasts of them).  The held experts run as the grouped
    matmul's kernel."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import EngineConfig, ServingEngine

    cfg = get_config("deepseek-v2-lite")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                           num_held=8))
    layers, pages, ps, nb, w = 27, 521, 128, (32768 + 512) // 128, 576
    model = build_model(cfg)
    eng = ServingEngine(model, None, model.default_share_prefill(),
                        EngineConfig(method="dense", paged=True,
                                     max_batch=SLOTS))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = jax.ShapeDtypeStruct((layers, pages, 1, ps, w), jnp.bfloat16,
                                sharding=one_chip)
    vec = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = eng._decode_fn_paged(SLOTS, nb).lower(
        params, vec(SLOTS, 1), {"prefix": [], "stack": (pool,)},
        vec(SLOTS, nb), vec(SLOTS), vec(SLOTS), vec(SLOTS)).compile()

    pool_bytes = layers * pages * ps * w * 2
    read_bytes = SLOTS * nb * ps * w * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < read_bytes + (8 << 20)

    text = compiled.as_text()
    assert "ragged-dot" in text
    entry = text[text.index("\nENTRY "):]
    shape = f"bf16[{layers},{pages},1,{ps},{w}]"
    ops = set()
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][\w-]*)\(", line)
        if m and shape in m.group(1):
            ops.add(m.group(2))
    assert "while" in ops
    # a bitcast reinterprets the buffer in place; it is no copy
    assert ops <= {"parameter", "while", "get-tuple-element", "tuple",
                   "bitcast"}, ops


@pytest.mark.parametrize("shape", [
    ("internlm2-1.8b", 8192, 7), ("deepseek-v2-lite", 32768, 0),
    ("deepseek-v2-lite", 32768, 31)], ids=["gqa-8k-c7", "mla-32k-c0",
                                           "mla-32k-c31"])
def test_dense_chunk_attn_quantum(one_chip, monkeypatch, shape):
    """The dense ``attn`` quantum as the TPU runs it (the flash kernel over
    the chunk's causal frontier) at internlm2-1.8b (16 / 8 heads, 128) and
    DeepSeek-V2-Lite (16 heads, qk 192, v 128) widths: the kernel is there,
    and the program holds no copy of K or V (at 32k one is 201 MB; MLA's
    192-wide K is read in the layout XLA gives it) and no float32 scores
    (a 1,024 x 32,768 tile per head is 2 GB over 16 heads)."""
    import functools

    from repro.configs import get_config
    from repro.core.api import SharePrefill
    from repro.kernels.flash_prefill import flash_chunk_attention
    from repro.models import chunked_prefill as cp

    name, n, chunk = shape
    cfg = get_config(name)
    h, hkv = cfg.num_heads, cfg.num_kv_heads
    d, dv = ((cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim,
              cfg.mla.v_head_dim) if cfg.mla.enabled else (D, D))
    sp = SharePrefill.trivial(cfg.share_prefill, 1, h)
    monkeypatch.setattr(cp, "_flash_backend", lambda: True)
    monkeypatch.setattr(cp, "flash_chunk_attention", functools.partial(
        flash_chunk_attention, interpret=False))
    cb = CHUNK // sp.cfg.block_size
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in ((1, h, n, d), (1, hkv, n, d), (1, hkv, n, dv))]
    compiled = jax.jit(lambda q, k, v: cp.chunk_prefill_attn(
        cfg, sp, q, k, v, None, None, None, method="dense",
        attn_impl="chunked", attn_width=None, chunk_start=chunk * cb,
        chunk_blocks=cb)[0]).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20
