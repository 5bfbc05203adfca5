"""Block-paged KV cache: allocator, cache-op helpers, kernel conformance.

The load-bearing invariant is **paged == contiguous, bitwise**: every
decode conformance :class:`Case` replayed with its cache scattered into a
shuffled page pool must reproduce the contiguous plan path's output
exactly (the paged kernels translate only the K/V DMA address — same
program otherwise), page recycling must leave no stale reads, and the
cross-bucket paged scheduler must keep the greedy-token guarantees of the
contiguous scheduler (bit-equal in-bucket; token-equal to the legacy batch
path across buckets) while an undersized pool defers admissions instead of
crashing.  The cache-op helper edge cases (trailing feature axes colliding
with the cache length, MLA latent layouts) are pinned here too.

The subprocess tier replays the paged plan path Hkv-sharded under a forced
2-device CPU mesh (``sharded_flash_decode_paged``) and asserts bitwise
equality with both the single-device paged path and the contiguous path.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import DataConfig, sample
from repro.kernels.block_sparse_attn import (
    block_sparse_attention_batched,
    block_sparse_attention_batched_paged,
)
from repro.kernels.decode_attn import flash_decode_plan_paged, gather_pages
from repro.kernels.indices import compact_block_mask
from repro.models import build_model
from repro.models.attention import dense_decode
from repro.serving import (
    EngineConfig,
    NULL_PAGE,
    PageAllocator,
    Request,
    ServingEngine,
)
from repro.serving import cache_ops, decode_plan, paged_cache
from test_decode_conformance import (CASES, SHARDABLE, CaseData, build_case,
                                     _run, _tol)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.dirname(os.path.abspath(__file__))


# --------------------------------------------------------------------------
# PageAllocator: free-list bookkeeping
# --------------------------------------------------------------------------

def test_allocator_reserves_null_page():
    a = PageAllocator(6)
    ids = a.alloc(5)
    assert ids is not None and len(ids) == 5
    assert NULL_PAGE not in ids              # page 0 is never handed out
    assert sorted(ids.tolist()) == [1, 2, 3, 4, 5]
    assert a.free_pages == 0


def test_allocator_exhaustion_is_none_not_partial():
    a = PageAllocator(4)
    assert a.alloc(4) is None                # only 3 allocatable pages
    assert a.free_pages == 3                 # a failed grant takes nothing
    got = a.alloc(2)
    assert a.alloc(2) is None
    a.free(got)
    assert a.alloc(3) is not None


def test_allocator_recycle_and_peak():
    a = PageAllocator(8)
    first = a.alloc(4)
    a.free(first)
    second = a.alloc(6)
    assert set(first.tolist()) <= set(second.tolist())   # ids recycled
    assert a.peak_in_use == 6                # peak survives the free
    assert a.utilization() == pytest.approx(6 / 7)


def test_allocator_invalid_free_raises():
    a = PageAllocator(4)
    with pytest.raises(ValueError):
        a.free([NULL_PAGE])
    with pytest.raises(ValueError):
        a.free([4])
    with pytest.raises(ValueError):
        PageAllocator(1)                     # room for null page only


# --------------------------------------------------------------------------
# cache_ops: the shared slice/copy conventions (satellite edge cases)
# --------------------------------------------------------------------------

def test_grow_leaf_trailing_axis_collision():
    """A trailing feature axis whose size equals the cache length must NOT
    be grown — only true sequence axes extend."""
    x = jnp.ones((2, 8, 8))                  # (B, S, D) with D == S == 8
    out = cache_ops.grow_leaf(x, 8, 4)
    assert out.shape == (2, 12, 8)
    np.testing.assert_array_equal(np.asarray(out[:, 8:]), 0.0)


def test_grow_leaf_mla_latent_layout():
    """MLA latent caches carry (B, S, rank): the middle axis grows."""
    x = jnp.arange(2 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 3)
    out = cache_ops.grow_leaf(x, 8, 8)
    assert out.shape == (2, 16, 3)
    np.testing.assert_array_equal(np.asarray(out[:, :8]), np.asarray(x))


def test_grow_leaf_no_seq_axis_passthrough():
    """Leaves without a sequence axis (RG-LRU conv state, scalars) pass
    through untouched."""
    x = jnp.ones((2, 4, 3))
    assert cache_ops.grow_leaf(x, 8, 4) is x
    assert cache_ops.grow_leaf("marker", 8, 4) == "marker"


def test_grow_cache_parity_on_mixed_pytree():
    """engine.grow_cache over a pytree mixing GQA stacks, MLA-style latent
    leaves, and no-seq-axis state grows exactly the sequence axes."""
    old, extra = 8, 8
    cache = {"prefix": [(jnp.ones((2, 3, old, 4)), jnp.ones((2, old, 3)))],
             "stack": (jnp.ones((2, 2, 2, old, 4)), jnp.ones((2, 4, 4)))}
    out = ServingEngine.grow_cache(cache, old, extra)
    assert out["prefix"][0][0].shape == (2, 3, old + extra, 4)
    assert out["prefix"][0][1].shape == (2, old + extra, 3)
    assert out["stack"][0].shape == (2, 2, 2, old + extra, 4)
    assert out["stack"][1].shape == (2, 4, 4)     # conv-like: untouched


def test_write_slot_multi_axis():
    """write_slot with {layer, slot} starts touches only that block."""
    dst = jnp.zeros((3, 4, 2, 8, 5))
    src = jnp.ones((1, 1, 2, 6, 5))
    out = cache_ops.write_slot(dst, src, {0: 2, 1: 1})
    assert float(out.sum()) == src.size
    np.testing.assert_array_equal(np.asarray(out[2, 1, :, :6]), 1.0)
    np.testing.assert_array_equal(np.asarray(out[2, 1, :, 6:]), 0.0)
    assert not np.asarray(out[2, 0]).any() and not np.asarray(out[1]).any()


def test_init_paged_pool_rejects_mla():
    """MLA gets no GQA K/V pool: its pool is the one latent pool over every
    layer (the dense prefix layer included), one ``c_kv ‖ k_rope`` row per
    token and no separate V."""
    cfg = get_smoke_config("deepseek-v2-236b")
    assert cfg.mla.enabled
    cache = paged_cache.init_paged_pool(cfg, num_pages=4, page_size=64)
    (pool,) = cache["stack"]
    assert cache["prefix"] == []
    assert pool.shape == (cfg.num_layers, 4, 1, 64, cfg.mla.latent_dim)
    assert paged_cache.page_bytes(cfg, 64, 2) == pool[:, 0].size * 2


# --------------------------------------------------------------------------
# Paged kernel conformance: every decode Case, bitwise vs contiguous
# --------------------------------------------------------------------------

def _page_in(cache_k, cache_v, page_size, seed=0, slack=3):
    """Scatter contiguous (B, Hkv, S, D) caches into a shuffled page pool;
    returns (pool_k, pool_v, page_table) with non-trivial page ids."""
    b, hkv, s, d = cache_k.shape
    nb = s // page_size
    num_pages = 1 + b * nb + slack
    rng = np.random.default_rng(seed)
    table = (1 + rng.permutation(num_pages - 1)[: b * nb]
             ).reshape(b, nb).astype(np.int32)

    def scatter(cache):
        pool = jnp.zeros((num_pages, hkv, page_size, d), cache.dtype)
        tiles = jnp.moveaxis(
            cache.reshape(b, hkv, nb, page_size, d), 1, 2)
        return pool.at[table.reshape(-1)].set(
            tiles.reshape(b * nb, hkv, page_size, d))

    return scatter(cache_k), scatter(cache_v), jnp.asarray(table)


def _run_paged(data: CaseData, page_size: int, impl: str) -> jnp.ndarray:
    pk, pv, table = _page_in(data.cache_k, data.cache_v, page_size)
    return flash_decode_plan_paged(
        data.q, pk, pv, table, data.plan, data.valid, impl=impl,
        interpret=True if impl == "kernel" else None)


@pytest.mark.parametrize("impl", ["kernel", "einsum"])
@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_paged_decode_bitmatches_contiguous(case, impl):
    """The full conformance sweep with the cache scattered into a shuffled
    pool: the page-aware path must be bitwise the contiguous path — the
    address translation is the ONLY difference."""
    data = build_case(case)
    out_c = _run(data, impl)
    out_p = _run_paged(data, case.bs, impl)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_c))


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_dense_paged_read_matches_contiguous(case):
    """The dense paged step's attention reads the resident pages in pool
    layout ``(B, NB, Hkv, ps, hd)`` and contracts over (page, within-page);
    against the contiguous dense decode's single key axis it is the same
    sum, whose order the backend may pick per shape (the CPU's differs by
    an ulp for MHA), so the dtype's conformance tolerance holds it."""
    data = build_case(case)
    pk, pv, table = _page_in(data.cache_k, data.cache_v, case.bs)
    pages = lambda pool: pool.at[table].get(mode="promise_in_bounds")
    out_p = dense_decode(data.q, pages(pk), pages(pv), data.valid)
    out_c = dense_decode(data.q, data.cache_k, data.cache_v, data.valid)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_c),
                               atol=_tol(case), rtol=_tol(case))


def test_gather_pages_roundtrip():
    data = build_case(CASES[0])
    pk, _, table = _page_in(data.cache_k, data.cache_v, CASES[0].bs)
    np.testing.assert_array_equal(np.asarray(gather_pages(pk, table)),
                                  np.asarray(data.cache_k))


def test_page_recycling_no_stale_reads():
    """Free → realloc → decode: pages recycled from request A to request B
    must read back pure-B content (bitwise the contiguous decode of B)."""
    import dataclasses as _dc
    case_a = CASES[0]
    data_a = build_case(case_a)
    # request B: same geometry, different seed → different cache content
    data_b = build_case(_dc.replace(case_a, seed=99))

    b, hkv, s, d = data_a.cache_k.shape
    ps = case_a.bs
    nb = s // ps
    alloc = PageAllocator(1 + b * nb)
    pages_a = alloc.alloc(b * nb)
    pool_k = jnp.zeros((1 + b * nb, hkv, ps, d), data_a.cache_k.dtype)
    pool_v = jnp.zeros_like(pool_k)

    def scatter(pool, cache, table):
        tiles = jnp.moveaxis(cache.reshape(b, hkv, nb, ps, d), 1, 2)
        return pool.at[table.reshape(-1)].set(
            tiles.reshape(b * nb, hkv, ps, d))

    table_a = pages_a.reshape(b, nb)
    pool_k = scatter(pool_k, data_a.cache_k, table_a)
    pool_v = scatter(pool_v, data_a.cache_v, table_a)

    alloc.free(pages_a)
    pages_b = alloc.alloc(b * nb)
    assert set(pages_b.tolist()) == set(pages_a.tolist())   # recycled
    table_b = jnp.asarray(pages_b.reshape(b, nb))
    pool_k = scatter(pool_k, data_b.cache_k, table_b)
    pool_v = scatter(pool_v, data_b.cache_v, table_b)

    out_p = flash_decode_plan_paged(data_b.q, pool_k, pool_v, table_b,
                                    data_b.plan, data_b.valid, impl="einsum")
    out_c = _run(data_b, "einsum")
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_c))


def test_paged_prefill_kernel_bitmatches_contiguous():
    """The batched block-sparse prefill kernel through a page table:
    outputs AND per-block stats bitwise-match the contiguous kernel."""
    b, h, hkv, n, s, d, bs = 2, 4, 2, 128, 256, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (b, h, n, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    nbq, nbkv = n // bs, s // bs
    keep = jax.random.bernoulli(ks[3], 0.6, (b, h, nbq, nbkv))
    keep = keep.at[..., 0].set(True)
    indices, counts = compact_block_mask(keep)

    out_c, st_c = block_sparse_attention_batched(
        q, k, v, indices, counts, block_size=bs, causal=True,
        q_block_offset=nbkv - nbq, interpret=True)
    pk, pv, table = _page_in(k, v, bs)
    out_p, st_p = block_sparse_attention_batched_paged(
        q, pk, pv, table, indices, counts, block_size=bs, causal=True,
        q_block_offset=nbkv - nbq, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_c))
    np.testing.assert_array_equal(np.asarray(st_p), np.asarray(st_c))


# --------------------------------------------------------------------------
# Paged scheduler: cross-bucket serving on the shared pool
# --------------------------------------------------------------------------

CFG = get_smoke_config("granite-3-2b")
SEQ = 256


@pytest.fixture(scope="module")
def setup():
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    sp = model.default_share_prefill()
    engines = {}

    def get_engine(**kw) -> ServingEngine:
        k = tuple(sorted(kw.items()))
        if k not in engines:
            engines[k] = ServingEngine(model, params, sp, EngineConfig(
                method="share", max_batch=2, **kw))
        return engines[k]

    return get_engine


def _requests(max_new, seq=SEQ, base=0):
    dcfg = DataConfig(vocab_size=CFG.vocab_size, seq_len=seq,
                      global_batch=1, task="retrieval")
    return [Request(uid=base + i, prompt=sample(dcfg, base + i)["tokens"],
                    max_new_tokens=m) for i, m in enumerate(max_new)]


def _mixed_requests():
    """Two former buckets' worth of prompts (64 and 256)."""
    return (_requests((5, 4), seq=64, base=10)
            + _requests((3, 5), seq=SEQ, base=20))


# (sparse decode, max_new per request).  The page-boundary mix runs slot 0
# past its first decode page (64 tokens) while slot 1 serves a short
# request and refills, then slot 0 finishes and sits inert (its appends in
# the null page) while slot 1 crosses its own page boundary.
SERVES = {"dense_decode": (False, (5, 2, 4, 3)),
          "sparse_decode": (True, (5, 2, 4, 3)),
          "dense_decode_page_boundary": (False, (66, 3, 66)),
          "sparse_decode_page_boundary": (True, (66, 3, 66))}


@pytest.mark.parametrize("serve", SERVES)
def test_paged_scheduler_bitmatches_contiguous(setup, serve):
    """Single bucket: the paged scheduler's greedy tokens bit-match the
    contiguous scheduler's (which itself bit-matches the legacy path)."""
    get_engine = setup
    sparse, max_new = SERVES[serve]
    eng_c = get_engine(seq_buckets=(SEQ,), decode_sparse=sparse,
                       scheduler=True)
    reqs_c = _requests(max_new)
    eng_c.serve(reqs_c, seed=0)

    eng_p = get_engine(seq_buckets=(SEQ,), decode_sparse=sparse, paged=True)
    reqs_p = _requests(max_new)
    eng_p.serve(reqs_p, seed=0)

    for a, b in zip(reqs_c, reqs_p):
        assert len(b.output_tokens) == b.max_new_tokens
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)
    stats = eng_p.page_pool_stats
    assert stats["page_size"] == max(eng_p.sp.cfg.block_size, 1)
    assert 0 < stats["peak_pages"] < stats["num_pages"]
    assert eng_p.pages_exhausted_steps == 0    # auto-sized pool never defers


@pytest.mark.parametrize("sparse", [False, True],
                         ids=["dense_decode", "sparse_decode"])
def test_paged_decode_donates_pool(setup, sparse):
    """One step of the engine's paged program consumes the pool it was
    given (the input pool arrays are deleted) and returns the pool that
    replaces it, so the step never holds two pools."""
    eng = setup(seq_buckets=(SEQ,), decode_sparse=sparse, paged=True)
    ps = eng.sp.cfg.block_size
    b, nb = eng.ecfg.max_batch, (SEQ + eng.ecfg.decode_extra) // ps
    cache = paged_cache.init_paged_pool(CFG, num_pages=1 + b * nb,
                                        page_size=ps)
    table = jnp.arange(1, 1 + b * nb, dtype=jnp.int32).reshape(b, nb)
    vec = jnp.full((b,), SEQ, jnp.int32)
    args = (eng.params, jnp.zeros((b, 1), jnp.int32), cache, table, vec,
            vec, vec)
    if sparse:
        args += (decode_plan.empty_decode_plan(
            CFG, batch=b, cache_len=nb * ps, block_size=ps),)
    logits, new = eng._decode_fn_paged(b, nb, sparse)(*args)
    assert logits.shape == (b, CFG.vocab_size)
    assert all(a.is_deleted() for a in cache["stack"])
    assert not any(a.is_deleted() for a in new["stack"])
    assert [a.shape for a in new["stack"]] == [a.shape for a in
                                               cache["stack"]]


def test_paged_decode_step_across_page_boundary(setup):
    """Drive the engine's dense paged program and its contiguous twin step
    by step: slot 0 decodes across its page boundary while slot 1 sits
    inert on the null page.  Slot 0's pages hold what the contiguous cache
    holds (layer 0 bitwise: its K/V depend on the token alone; deeper
    layers and the logits within f32 tolerance, since the paged read sums
    over (page, within-page) in an order the backend picks), and no page
    outside slot 0's run and the null page is ever written."""
    eng = setup(seq_buckets=(SEQ,), paged=True)
    ps = eng.sp.cfg.block_size
    b, nb = 2, (SEQ + eng.ecfg.decode_extra) // ps
    cl = nb * ps
    params = eng.params
    pool = paged_cache.init_paged_pool(CFG, num_pages=1 + 2 * nb,
                                       page_size=ps)
    cont = eng.model.init_cache(b, cl, dtype=pool["stack"][0].dtype)
    table = np.zeros((b, nb), np.int32)
    table[0] = 1 + nb + np.arange(nb)[::-1]     # pages nb+1..2nb, reversed
    pos = np.array([ps - 3, 2 * ps + 5], np.int32)
    full = jnp.full((b,), cl, jnp.int32)        # every slot <= pos valid
    paged_step = eng._decode_fn_paged(b, nb)
    cont_step = eng._decode_fn(b, cl, cl)
    toks = np.random.default_rng(0).integers(0, CFG.vocab_size, (6, b, 1))
    for tok in jnp.asarray(toks, jnp.int32):
        lp, pool = paged_step(params, tok, pool, jnp.asarray(table),
                              jnp.asarray(pos), full, full)
        lc, cont = cont_step(params, tok, cont, jnp.asarray(pos), full)
        np.testing.assert_allclose(np.asarray(lp[0]), np.asarray(lc[0]),
                                   rtol=2e-5, atol=2e-5)
        pos[0] += 1
    assert pos[0] > ps + 1                      # crossed into a new page
    for pk, ck in zip(pool["stack"], cont["stack"]):
        pk, ck = np.asarray(pk), np.asarray(ck)
        run = pk[:, table[0]]                   # (L, NB, Hkv, ps, hd)
        run = run.transpose(0, 2, 1, 3, 4).reshape(ck[:, 0].shape)
        np.testing.assert_array_equal(run[0], ck[0, 0])
        np.testing.assert_allclose(run, ck[:, 0], rtol=2e-5, atol=2e-5)
        assert not pk[:, 1:1 + nb].any()        # nobody's pages: untouched


def test_paged_mixed_buckets_one_batch(setup):
    """Mixed former buckets coexist in ONE paged decode batch and every
    request's greedy tokens match the legacy per-bucket batch serve."""
    get_engine = setup
    eng_l = get_engine(seq_buckets=(64, SEQ), decode_sparse=True)
    reqs_l = _mixed_requests()
    eng_l.serve(reqs_l, seed=0)

    eng_p = get_engine(seq_buckets=(64, SEQ), decode_sparse=True, paged=True)
    reqs_p = _mixed_requests()
    eng_p.serve(reqs_p, seed=0)

    for a, b in zip(reqs_l, reqs_p):
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)

    # one short + one long co-resident: the pool's peak footprint is
    # strictly below two max-length allocations (the contiguous scheduler's
    # fixed cost) — the memory win paging exists for
    pair = (_requests((5,), seq=64, base=10)
            + _requests((3,), seq=SEQ, base=20))
    eng_p.serve(pair, seed=0)
    stats = eng_p.page_pool_stats
    assert 0 < stats["peak_pages"] < 2 * stats["table_blocks"]


def test_paged_pool_exhaustion_defers_not_crashes(setup):
    """An undersized pool keeps requests WAITING (pages_exhausted_steps
    counts the deferrals) and still completes with identical tokens."""
    get_engine = setup
    eng_a = get_engine(seq_buckets=(64, SEQ), decode_sparse=True, paged=True)
    reqs_a = _mixed_requests()
    eng_a.serve(reqs_a, seed=0)
    assert eng_a.pages_exhausted_steps == 0

    eng_t = get_engine(seq_buckets=(64, SEQ), decode_sparse=True, paged=True,
                       num_pages=8)
    reqs_t = _mixed_requests()
    eng_t.serve(reqs_t, seed=0)
    assert eng_t.pages_exhausted_steps > 0
    for a, b in zip(reqs_a, reqs_t):
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)


def test_paged_pool_too_small_for_one_request_raises(setup):
    get_engine = setup
    eng = get_engine(seq_buckets=(SEQ,), decode_sparse=True, paged=True,
                     num_pages=3)
    with pytest.raises(ValueError, match="deadlock"):
        eng.serve(_requests((2,)), seed=0)


def test_paged_chunked_admission_bitmatches(setup):
    """Chunked (step-cadence) admission over the paged pool: per-layer KV
    lands page-at-a-time and tokens still bit-match the contiguous chunked
    scheduler."""
    get_engine = setup
    eng_c = get_engine(seq_buckets=(SEQ,), decode_sparse=True,
                       scheduler=True, prefill_chunk=64)
    reqs_c = _requests((5, 2, 4, 3))
    eng_c.serve(reqs_c, seed=0)

    eng_p = get_engine(seq_buckets=(SEQ,), decode_sparse=True, paged=True,
                       prefill_chunk=64)
    reqs_p = _requests((5, 2, 4, 3))
    eng_p.serve(reqs_p, seed=0)
    for a, b in zip(reqs_c, reqs_p):
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)


# --------------------------------------------------------------------------
# Sharded tier: paged decode under a forced 2-device mesh (subprocess)
# --------------------------------------------------------------------------

def _run_subprocess(code: str) -> subprocess.CompletedProcess:
    # the child stays on the CPU: a parent holding a chip would make a
    # child that asks for it fail or hang
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (os.path.join(REPO, "src") + os.pathsep + TESTS
                         + os.pathsep + env.get("PYTHONPATH", ""))
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.subprocess
def test_sharded_paged_decode_bitmatches():
    """Every shardable conformance case through the Hkv-sharded paged
    decode (pool sharded on its head axis, page table replicated):
    bitwise-equal to BOTH the single-device paged path and the contiguous
    plan path."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, numpy as np
        from repro.distributed.sharding import sharded_flash_decode_paged
        from repro.kernels.decode_attn import flash_decode_plan_paged
        from test_decode_conformance import SHARDABLE, build_case, _run
        from test_paged_cache import _page_in

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("model",))
        for case in SHARDABLE:
            data = build_case(case)
            pk, pv, table = _page_in(data.cache_k, data.cache_v, case.bs)
            impls = ("einsum", "kernel") if case.name == "gqa4" \\
                else ("einsum",)
            for impl in impls:
                it = True if impl == "kernel" else None
                out_s = sharded_flash_decode_paged(
                    data.q, pk, pv, table, data.plan, data.valid,
                    mesh=mesh, impl=impl, interpret=it)
                out_1 = flash_decode_plan_paged(
                    data.q, pk, pv, table, data.plan, data.valid,
                    impl=impl, interpret=it)
                np.testing.assert_array_equal(
                    np.asarray(out_s), np.asarray(out_1),
                    err_msg=f"case {case.name} impl {impl} (vs paged)")
                np.testing.assert_array_equal(
                    np.asarray(out_s), np.asarray(_run(data, impl)),
                    err_msg=f"case {case.name} impl {impl} (vs contiguous)")
            print(f"case {case.name}: bitwise OK ({', '.join(impls)})")
        print("SHARDED-PAGED-DECODE-OK")
    """)
    res = _run_subprocess(code)
    assert res.returncode == 0, res.stderr
    assert "SHARDED-PAGED-DECODE-OK" in res.stdout
