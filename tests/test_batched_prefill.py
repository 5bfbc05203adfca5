"""Batch-native count-aware prefill kernel: the PR-3 contract.

  * the ragged causal schedule (grid steps ∝ kept blocks, not NBq·NBkv);
  * the batched (B, T, H) kernel bit-matching ``jax.vmap`` of the
    single-sample oracle kernel, incl. width caps, GQA and stats;
  * head-permutation invariance of the fused share layer under the
    pattern-sharing schedule reorder;
  * stats-gating equivalence: gating Ã to dense-construction heads leaves
    outputs and the pivotal dictionary bit-identical;
  * shard_map over a forced multi-device CPU mesh with per-shard index
    tables == single-device outputs (subprocess);
  * count-aware width policy resolution + ragged prefill last-logits.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SharePrefillConfig
from repro.core.patterns import causal_block_mask
from repro.core.share_attention import (
    batched_share_prefill_attention_layer,
    init_batched_state,
    pattern_sharing_head_perm,
)
from repro.kernels import (
    batched_block_sparse_attention,
    batched_sparse_attention_fn,
    block_sparse_attention,
    compact_block_mask,
    ragged_grid_steps,
    ragged_schedule,
    scatter_block_stats,
)
from repro.kernels.chunked import chunked_attention_fn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEYS = jax.random.split(jax.random.PRNGKey(21), 8)
B, H, HKV, N, D, BS = 2, 4, 2, 256, 32, 64
NB = N // BS


def _qkv(dtype=jnp.float32):
    q = jax.random.normal(KEYS[0], (B, H, N, D), jnp.float32).astype(dtype)
    k = jax.random.normal(KEYS[1], (B, HKV, N, D), jnp.float32).astype(dtype)
    v = jax.random.normal(KEYS[2], (B, HKV, N, D), jnp.float32).astype(dtype)
    return q, k, v


def _mask(density=0.5, causal=True):
    m = jax.random.bernoulli(KEYS[3], density, (B, H, NB, NB))
    m = m | jnp.eye(NB, dtype=bool)[None, None]
    if causal:
        m = m & causal_block_mask(NB)[None, None]
    return m


# --------------------------------------------------------------------------
# Ragged schedule
# --------------------------------------------------------------------------

def test_ragged_schedule_counts_and_maps():
    row_map, slot_map = ragged_schedule(4, 4)
    # causal: row i gets i+1 slots -> 1+2+3+4 = 10 steps
    assert slot_map.shape == (10,)
    assert row_map.shape == (11,) and row_map[-1] == -1
    assert row_map[:-1].tolist() == [0, 1, 1, 2, 2, 2, 3, 3, 3, 3]
    assert slot_map.tolist() == [0, 0, 1, 0, 1, 2, 0, 1, 2, 3]
    assert ragged_grid_steps(4, 4) == 10
    # width cap: row i gets min(i+1, W)
    assert ragged_grid_steps(4, 4, width=2) == 1 + 2 + 2 + 2
    # non-causal: full rectangle at W
    assert ragged_grid_steps(4, 4, causal=False) == 16
    assert ragged_grid_steps(4, 4, width=3, causal=False) == 12


def test_ragged_schedule_beats_uniform_grid_2x_when_sparse():
    """With any width cap ≤ NB/2 the ragged grid is ≥ 2x below NBq·NBkv —
    the count-aware win the regenerated BENCH_prefill.json records."""
    nb = 32
    assert nb * nb / ragged_grid_steps(nb, nb, width=nb // 2) >= 2.0


# --------------------------------------------------------------------------
# Batched kernel vs per-sample vmap oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("width", [None, 2])
@pytest.mark.parametrize("causal", [True, False])
def test_batched_kernel_bitmatches_vmap_oracle(width, causal):
    q, k, v = _qkv()
    m = _mask(causal=causal)
    m = m.at[:, :, 2, :].set(False)          # a fully-skipped row
    out_b, a_b = batched_block_sparse_attention(
        q, k, v, m, block_size=BS, causal=causal, width=width)
    oracle = lambda qs, ks, vs, ms: block_sparse_attention(
        qs, ks, vs, ms, block_size=BS, impl="kernel", interpret=True,
        causal=causal, width=width)
    out_o, a_o = jax.vmap(oracle)(q, k, v, m)
    np.testing.assert_array_equal(np.asarray(out_b), np.asarray(out_o))
    fin_b = np.isfinite(np.asarray(a_b))
    fin_o = np.isfinite(np.asarray(a_o))
    assert (fin_b == fin_o).all()
    np.testing.assert_array_equal(np.asarray(a_b)[fin_b],
                                  np.asarray(a_o)[fin_o])


def test_batched_kernel_bf16_and_stats_scatter():
    q, k, v = _qkv(jnp.bfloat16)
    m = _mask()
    out_b, a_b = batched_block_sparse_attention(q, k, v, m, block_size=BS)
    oracle = lambda qs, ks, vs, ms: block_sparse_attention(
        qs, ks, vs, ms, block_size=BS, impl="kernel", interpret=True)
    out_o, a_o = jax.vmap(oracle)(q, k, v, m)
    np.testing.assert_array_equal(
        np.asarray(out_b, np.float32), np.asarray(out_o, np.float32))
    # the ragged-schedule scatter reconstructs the same Ã footprint and
    # values as the oracle's rectangular compact scatter
    assert (np.isfinite(np.asarray(a_b)) == np.asarray(m)).all()
    fin = np.isfinite(np.asarray(a_o))
    np.testing.assert_array_equal(np.asarray(a_b)[fin],
                                  np.asarray(a_o)[fin])


def test_batched_fn_gates_stats_and_falls_back():
    q, k, v = _qkv()
    fn = batched_sparse_attention_fn(block_size=BS)
    assert fn.batched
    m = _mask()
    gate = jnp.asarray([[1, 0, 0, 1], [0, 0, 0, 0]], jnp.int32)
    out_g, a_g = fn(q, k, v, m, stats_gate=gate)
    out_u, a_u = fn(q, k, v, m)
    np.testing.assert_array_equal(np.asarray(out_g), np.asarray(out_u))
    gated = np.isfinite(np.asarray(a_g))
    assert not gated[0, 1].any() and not gated[1].any()
    np.testing.assert_array_equal(np.asarray(a_g)[gated],
                                  np.asarray(a_u)[gated])
    # misaligned mask grid -> per-sample chunked fallback
    m32 = jax.random.bernoulli(KEYS[4], 0.5, (B, H, N // 32, N // 32))
    m32 = m32 | jnp.eye(N // 32, dtype=bool)[None, None]
    out_f, _ = fn(q, k, v, m32)
    out_c, _ = jax.vmap(chunked_attention_fn(block_size=32))(q, k, v, m32)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_c),
                               atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------
# Fused share layer: permutation invariance + stats gating
# --------------------------------------------------------------------------

def _share_inputs():
    cfg = SharePrefillConfig(block_size=BS, min_seq_blocks=2, tau=0.9,
                             delta=0.99)
    q, k, v = _qkv()
    ids = jnp.asarray([0, 0, 1, 1])
    st = init_batched_state(B, 2, NB)
    return cfg, q, k, v, ids, st


def test_head_perm_stays_within_gqa_groups():
    from repro.core.determine import PatternDecision
    use_shared = jnp.asarray([True, True, False, True])
    d = PatternDecision(use_shared, ~use_shared, jnp.zeros(4, bool),
                        jnp.zeros((4, NB)), jnp.zeros(4), jnp.zeros(4))
    ids = jnp.asarray([3, 3, 7, 3])
    perm = pattern_sharing_head_perm(d, ids, group=2)
    p = np.asarray(perm)
    assert sorted(p.tolist()) == [0, 1, 2, 3]
    # group membership preserved: position p's kv head == original's
    assert (p // 2 == np.arange(4) // 2).all()
    # shared heads of group 1 sort ahead, keeping cluster-3 heads adjacent
    assert p.tolist() == [0, 1, 3, 2]


def test_fused_layer_invariant_to_schedule_reorder():
    cfg, q, k, v, ids, st = _share_inputs()
    out_r, st_r, stats_r = batched_share_prefill_attention_layer(
        q, k, v, st, ids, cfg, reorder_heads=True)
    out_n, st_n, stats_n = batched_share_prefill_attention_layer(
        q, k, v, st, ids, cfg, reorder_heads=False)
    np.testing.assert_array_equal(np.asarray(out_r), np.asarray(out_n))
    np.testing.assert_array_equal(np.asarray(st_r.masks),
                                  np.asarray(st_n.masks))
    np.testing.assert_array_equal(np.asarray(st_r.reps),
                                  np.asarray(st_n.reps))
    assert float(stats_r.max_row_pop) == float(stats_n.max_row_pop)


def test_fused_layer_matches_per_sample_vmap_path():
    """The fused batched path (one kernel call, gated stats, reordered
    schedule) must reproduce the legacy vmap-the-whole-layer path — outputs
    and the pivotal dictionary state built from ungated Ã."""
    from repro.kernels import sparse_attention_fn

    cfg, q, k, v, ids, st = _share_inputs()
    out_f, st_f, stats_f = batched_share_prefill_attention_layer(
        q, k, v, st, ids, cfg)                       # default: fused
    out_v, st_v, stats_v = batched_share_prefill_attention_layer(
        q, k, v, st, ids, cfg, sparse_attention_fn(block_size=BS))
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_v),
                               atol=2e-6, rtol=2e-6)
    np.testing.assert_array_equal(np.asarray(st_f.masks),
                                  np.asarray(st_v.masks))
    np.testing.assert_allclose(np.asarray(st_f.reps), np.asarray(st_v.reps),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(st_f.valid),
                                  np.asarray(st_v.valid))
    for f in ("num_shared", "num_dense", "num_vs", "max_row_pop"):
        assert float(getattr(stats_f, f)) == pytest.approx(
            float(getattr(stats_v, f)))


# --------------------------------------------------------------------------
# Sharded tables (forced 2-device CPU mesh, subprocess)
# --------------------------------------------------------------------------

@pytest.mark.subprocess
def test_shard_map_matches_single_device():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core.patterns import causal_block_mask
        from repro.distributed.sharding import (
            head_shard_count, sharded_batched_block_sparse_attention)
        from repro.kernels import (batched_block_sparse_attention,
                                   batched_sparse_attention_fn)

        B, H, HKV, N, D, BS = 2, 4, 2, 256, 32, 64
        NB = N // BS
        ks = jax.random.split(jax.random.PRNGKey(5), 4)
        q = jax.random.normal(ks[0], (B, H, N, D))
        k = jax.random.normal(ks[1], (B, HKV, N, D))
        v = jax.random.normal(ks[2], (B, HKV, N, D))
        m = jax.random.bernoulli(ks[3], 0.5, (B, H, NB, NB))
        m = (m | jnp.eye(NB, dtype=bool)[None, None]) \\
            & causal_block_mask(NB)[None, None]

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("model",))
        assert head_shard_count(mesh, "model", H, HKV) == 2
        assert head_shard_count(mesh, "model", 3, HKV) == 1   # indivisible
        out_s, a_s = sharded_batched_block_sparse_attention(
            q, k, v, m, mesh=mesh, block_size=BS)
        out_1, a_1 = batched_block_sparse_attention(q, k, v, m,
                                                    block_size=BS)
        np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_1))
        fs, f1 = np.isfinite(np.asarray(a_s)), np.isfinite(np.asarray(a_1))
        assert (fs == f1).all()
        np.testing.assert_array_equal(np.asarray(a_s)[fs],
                                      np.asarray(a_1)[f1])

        # the batched AttentionFn auto-routes through shard_map
        fn = batched_sparse_attention_fn(block_size=BS, mesh=mesh)
        out_f, _ = fn(q, k, v, m)
        np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_1))
        print("SHARDED-OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr
    assert "SHARDED-OK" in res.stdout


@pytest.mark.subprocess
def test_strip_kernel_under_mesh_matches_single_device():
    """Under a serving mesh the strip kernel runs per head shard (a Mosaic
    kernel cannot be partitioned by the compiler); heads that do not shard
    take the oracle.  Both equal the single-device strips."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.distributed import sharding as dsh
        from repro.kernels.strip import compute_strips
        from repro.launch.mesh import make_mesh

        H, HKV, N, D, BS = 4, 2, 256, 32, 64
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (2, H, N, D))
        k = jax.random.normal(ks[1], (2, HKV, N, D))
        strips = lambda q, k: jax.vmap(lambda a, b: compute_strips(
            a, b, block_size=BS, impl="pallas"))(q, k)
        want = strips(q, k)
        calls = []
        real = dsh.sharded_compute_strips
        dsh.sharded_compute_strips = lambda *a, **kw: (
            calls.append(1), real(*a, **kw))[1]
        mesh = make_mesh((1, 2), ("data", "model"))
        with dsh.use_rules(dsh.ShardingRules(mesh)), mesh:
            got = jax.jit(strips)(q, k)
            assert calls, "strip kernel did not route through shard_map"
            odd = jax.jit(strips)(q[:, :3], k[:, :1])     # 3/1 heads
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_allclose(np.asarray(odd),
                                   np.asarray(strips(q[:, :3], k[:, :1])),
                                   rtol=1e-5, atol=1e-6)
        print("STRIP-SHARDED-OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr
    assert "STRIP-SHARDED-OK" in res.stdout


def test_decode_plan_kv_head_range_matches_global_slice():
    from repro.configs import get_smoke_config
    from repro.core.api import SharePrefill
    from repro.serving.decode_plan import build_decode_plan
    import dataclasses

    cfg = get_smoke_config("internlm2-1.8b")
    cfg = dataclasses.replace(cfg, num_layers=2, num_heads=4, num_kv_heads=2)
    spc = SharePrefillConfig(block_size=BS, min_seq_blocks=2)
    sp = SharePrefill.trivial(spc, cfg.num_layers, cfg.num_heads)
    st = init_batched_state(2, sp.num_clusters, NB)
    # give some clusters non-trivial pivots
    masks = st.masks.at[:, 0].set(
        jnp.tril(jnp.ones((NB, NB), bool))[None])
    st = st._replace(masks=masks,
                     valid=st.valid.at[:, 0].set(True))
    full = build_decode_plan(sp, st, cfg, prefill_len=N, cache_len=N + BS)
    for start, count in ((0, 1), (1, 1), (0, 2)):
        local = build_decode_plan(sp, st, cfg, prefill_len=N,
                                  cache_len=N + BS,
                                  kv_head_range=(start, count))
        sl = slice(start, start + count)
        np.testing.assert_array_equal(np.asarray(local.indices),
                                      np.asarray(full.indices[:, :, sl]))
        np.testing.assert_array_equal(np.asarray(local.counts),
                                      np.asarray(full.counts[:, :, sl]))
        np.testing.assert_array_equal(np.asarray(local.keep_heads),
                                      np.asarray(full.keep_heads[:, :, sl]))
    with pytest.raises(ValueError):
        build_decode_plan(sp, st, cfg, prefill_len=N, cache_len=N + BS,
                          kv_head_range=(1, 2))


# --------------------------------------------------------------------------
# Count-aware width policy + ragged prefill logits
# --------------------------------------------------------------------------

def test_population_width_cap():
    from repro.serving import population_width_cap
    # percentile 100 covers the max (lossless), safety rounds up
    assert population_width_cap([3, 7, 2], 16, safety=1.0) == 7
    assert population_width_cap([3, 7, 2], 16) == 8          # ceil(7·1.1)
    assert population_width_cap([40], 16) == 16              # clamp to NB
    pops = list(range(1, 33))
    assert population_width_cap(pops, 32, percentile=50.0,
                                safety=1.0) == 17
    with pytest.raises(ValueError):
        population_width_cap([], 8)


def test_prefill_ragged_last_logits():
    """transformer.prefill(prompt_lens=...) gathers each row's logits at
    prompt_len - 1, matching the full-logits row at that position."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_smoke_config("internlm2-1.8b"),
                              num_layers=2, num_heads=4, num_kv_heads=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sp = model.default_share_prefill()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
                              cfg.vocab_size)
    plens = jnp.asarray([50, 128], jnp.int32)
    res = model.prefill(params, toks, sp, method="dense",
                        prompt_lens=plens)
    res_pad = model.prefill(params, toks, sp, method="dense")
    # row 1 is full-length: identical to the padded gather; row 0 must
    # come from position 49, not 127
    np.testing.assert_allclose(np.asarray(res.last_logits[1]),
                               np.asarray(res_pad.last_logits[1]),
                               atol=1e-5, rtol=1e-5)
    from repro.core.profile import run_prefill_traced
    tr = run_prefill_traced(params, cfg, toks[:1], sp, method="dense",
                            want_full_logits=True)
    np.testing.assert_allclose(np.asarray(res.last_logits[0]),
                               np.asarray(tr.full_logits[0, 49]),
                               atol=1e-4, rtol=1e-4)
