"""Adaptive pattern refresh during long decode.

Unit tier: the score-mass → ragged-keep-set pipeline
(``score_mass_budgets`` / ``ragged_top_mask``), plan-width management
(``set_plan_width`` / ``bucket_plan_width``), and the refreshed-row
builders (``build_refresh_plan_row`` / ``extend_plan_row_horizon``) —
geometry, horizon force-keep, and per-head raggedness.

Serve tier (slow): refresh fires on cadence through the paged scheduler
and lowers the plan's traffic fraction; a slot whose pages are still
prefix-shared (refcount > 1) defers its refresh until the index pin is
gone; chunked admission never sees a mid-prefill refresh; a preempt →
resume cycle rebuilds refresh state cold and re-refreshes after the
window re-warms.  The refresh-OFF default stays bitwise — that guarantee
is pinned by the pre-existing paged-vs-contiguous conformance tests,
which run with the refresh knobs at their defaults.

The subprocess tier splices a refreshed ragged row through
``update_plan_slot_auto`` under a forced 2-device mesh and asserts the
result is bitwise the unsharded splice.
"""
import dataclasses
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
from repro.kernels.indices import ragged_top_mask
from repro.models import build_model
from repro.serving import EngineConfig, Request, ServingEngine
from repro.serving import decode_plan as dplan
from repro.serving.width_policy import score_mass_budgets

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.dirname(os.path.abspath(__file__))

CFG = get_smoke_config("granite-3-2b")
S64 = 64


# --------------------------------------------------------------------------
# Unit tier: score-mass budgets, ragged masks, width management
# --------------------------------------------------------------------------

def test_score_mass_budgets():
    scores = jnp.asarray([[0.5, 0.3, 0.1, 0.1],
                          [0.0, 0.0, 0.0, 0.0]])
    k = score_mass_budgets(scores, mass=0.7)
    # row 0: top-2 blocks hold 0.8 >= 0.7; all-zero row floors at min_width
    np.testing.assert_array_equal(np.asarray(k), [2, 1])
    k = score_mass_budgets(scores, mass=0.95)
    np.testing.assert_array_equal(np.asarray(k), [4, 1])
    k = score_mass_budgets(scores, mass=0.95, min_width=2, max_width=3)
    np.testing.assert_array_equal(np.asarray(k), [3, 2])


def test_ragged_top_mask_widths_and_ties():
    scores = jnp.asarray([[0.1, 0.4, 0.2, 0.3],
                          [0.5, 0.5, 0.0, 0.5]])
    keep = np.asarray(ragged_top_mask(scores, jnp.asarray([1, 2])))
    np.testing.assert_array_equal(keep[0], [False, True, False, False])
    # ties break toward the HIGHER block index (recency)
    np.testing.assert_array_equal(keep[1], [False, True, False, True])
    assert keep.sum(-1).tolist() == [1, 2]


def test_bucket_and_set_plan_width():
    assert dplan.bucket_plan_width(3, 16) == 4
    assert dplan.bucket_plan_width(5, 16) == 8
    assert dplan.bucket_plan_width(9, 12) == 12     # clamped to NB
    assert dplan.bucket_plan_width(0, 16) == 1
    keep = jnp.zeros((2, 1, 2, 8, 2), bool).at[..., :3, :].set(True)
    union = jnp.any(keep, axis=-1)
    from repro.kernels.indices import compact_block_mask
    indices, counts = compact_block_mask(union, width=None)
    row = dplan.DecodePlan(indices=indices, counts=counts, keep_heads=keep)
    narrow = dplan.set_plan_width(row, 4)
    assert narrow.indices.shape[-1] == 4
    wide = dplan.set_plan_width(narrow, 8)
    # widening pads with repeat-last (DMA elision) — counts unchanged
    np.testing.assert_array_equal(np.asarray(wide.counts),
                                  np.asarray(row.counts))
    with pytest.raises(ValueError):
        dplan.set_plan_width(row, 2)    # narrower than max count


def _refresh_row_inputs(seed=0, *, L=2, H=4, Hkv=2, D=8, bs=16,
                        table_blocks=8, num_blocks=5):
    cfg = dataclasses.replace(CFG, num_heads=H, num_kv_heads=Hkv)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = jax.random.normal(ks[0], (L, H, bs, D))
    pool_k = jax.random.normal(ks[1], (L, table_blocks + 1, Hkv, bs, D))
    # shuffled page map: block b of the slot lives on page b + 1
    table = jnp.arange(1, table_blocks + 1, dtype=jnp.int32)
    return cfg, q, pool_k, table


def test_build_refresh_plan_row_geometry_and_horizon():
    nb, nblk, horizon = 8, 5, 2
    cfg, q, pool_k, table = _refresh_row_inputs(table_blocks=nb,
                                                num_blocks=nblk)
    row = dplan.build_refresh_plan_row(
        q, pool_k, table, cfg, block_size=16, num_blocks=nblk,
        table_blocks=nb, horizon_blocks=horizon, mass=0.5,
        strip_impl="jnp")
    L, Hkv = q.shape[0], pool_k.shape[2]
    assert row.keep_heads.shape == (L, 1, Hkv, nb, cfg.num_heads // Hkv)
    assert row.indices.shape[-1] == nb
    kh = np.asarray(row.keep_heads)
    # the local band + dense horizon [nblk-1, nblk+horizon) is force-kept
    # for every head; blocks past the horizon stay unkept
    assert kh[..., nblk - 1:nblk + horizon, :].all()
    assert not kh[..., nblk + horizon:, :].any()
    # indices ascend and counts bound the table
    idx, cnt = np.asarray(row.indices), np.asarray(row.counts)
    assert (np.diff(idx, axis=-1) >= 0).all()
    assert (cnt >= horizon + 1).all() and (cnt <= nblk + horizon).all()

    # mass=1.0 keeps every live block: the union row is exactly
    # [0, nblk + horizon)
    full = dplan.build_refresh_plan_row(
        q, pool_k, table, cfg, block_size=16, num_blocks=nblk,
        table_blocks=nb, horizon_blocks=horizon, mass=1.0,
        strip_impl="jnp")
    np.testing.assert_array_equal(np.asarray(full.counts),
                                  np.full_like(np.asarray(full.counts),
                                               nblk + horizon))
    # a tighter budget is genuinely ragged across kv heads or layers
    tight = dplan.build_refresh_plan_row(
        q, pool_k, table, cfg, block_size=16, num_blocks=nblk,
        table_blocks=nb, horizon_blocks=0, mass=0.3,
        strip_impl="jnp")
    per_head = np.asarray(tight.keep_heads).sum(axis=-2)
    assert per_head.min() < per_head.max() or per_head.max() < nblk


def test_extend_plan_row_horizon():
    nb, nblk = 8, 5
    cfg, q, pool_k, table = _refresh_row_inputs(table_blocks=nb,
                                                num_blocks=nblk)
    row = dplan.build_refresh_plan_row(
        q, pool_k, table, cfg, block_size=16, num_blocks=nblk,
        table_blocks=nb, horizon_blocks=1, mass=0.5, strip_impl="jnp")
    ext = dplan.extend_plan_row_horizon(row, nblk + 1, nb)
    kh, ke = np.asarray(row.keep_heads), np.asarray(ext.keep_heads)
    # everything kept before stays kept; the new horizon appears for all
    np.testing.assert_array_equal(ke | kh, ke)
    assert ke[..., nblk + 1:nb, :].all()
    assert (np.asarray(ext.counts) >= np.asarray(row.counts)).all()


# --------------------------------------------------------------------------
# Serve tier (slow): refresh through the paged scheduler
# --------------------------------------------------------------------------

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
                method="share", **kw))
        return engines[k]

    return get_engine


def _requests(max_new, seq=S64, base=0, **kw):
    dcfg = DataConfig(vocab_size=CFG.vocab_size, seq_len=seq,
                      global_batch=1, task="retrieval")
    return [Request(uid=base + i, prompt=sample(dcfg, base + i)["tokens"],
                    max_new_tokens=m, **kw) for i, m in enumerate(max_new)]


LONG = 4 * S64 + 3      # decode length that outgrows the refresh horizon


@pytest.mark.slow
def test_refresh_fires_and_lowers_traffic(setup):
    """Cadence refresh on a long decode: re-estimation fires, the plan's
    traffic fraction drops below the frozen serve's (which reports the
    tail telemetry too), and the pool still drains."""
    get_engine = setup
    base = dict(max_batch=2, seq_buckets=(S64,), paged=True,
                decode_sparse=True)
    frozen = get_engine(**base)
    f_reqs = _requests((LONG, LONG))
    frozen.serve(f_reqs, seed=0)
    assert frozen.refresh_stats["refreshes"] == 0
    # tail/traffic telemetry is visible with refresh OFF too
    assert all(r.plan_traffic_fraction > 0 for r in f_reqs)
    assert all(r.metrics()["tail_fraction"] >= 0 for r in f_reqs)

    eng = get_engine(**base, refresh_every=S64, refresh_mass=0.5)
    reqs = _requests((LONG, LONG))
    eng.serve(reqs, seed=0)
    assert eng.refresh_stats["refreshes"] > 0
    for r, f in zip(reqs, f_reqs):
        assert r.refreshes >= 1
        assert len(r.output_tokens) == LONG
        # the re-estimated row keeps less of the allocation than the
        # frozen row's sparse-prefill + unbounded dense tail
        assert r.plan_traffic_fraction < f.plan_traffic_fraction
    assert eng.page_pool_stats["pages_in_use_at_end"] == 0


@pytest.mark.slow
def test_refresh_defers_while_prefix_shared(setup):
    """The COW fence: a slot whose pages the prefix index still pins
    (refcount > 1) defers refresh — counted, never spliced — while a slot
    whose index entry was evicted refreshes normally in the same serve."""
    get_engine = setup
    eng = get_engine(max_batch=2, seq_buckets=(S64,), paged=True,
                     decode_sparse=True, prefix_sharing=True,
                     prefix_max_entries=1, refresh_every=S64,
                     refresh_mass=0.5)
    # two DISTINCT prompts: both publish at admission, and the 1-entry
    # index evicts r0's entry when r1 publishes — r0's pages go private
    # (refresh resumes), r1's stay pinned for the whole serve (fenced)
    reqs = _requests((LONG, LONG), base=30)
    eng.serve(reqs, seed=0)
    assert reqs[0].refreshes > 0          # unpinned by eviction
    assert reqs[1].refreshes == 0         # fenced: entry pins its run
    assert eng.refresh_stats["deferred_cow"] > 0
    assert all(len(r.output_tokens) == LONG for r in reqs)
    assert eng.page_pool_stats["pages_in_use_at_end"] == 0


@pytest.mark.slow
def test_refresh_skips_mid_prefill_chunked_admission(setup):
    """Chunked admission: refresh ticks fire while another request's
    quantum run is in flight, but only DECODE slots are ever re-estimated
    — a mid-prefill slot is unoccupied until its final quantum lands, and
    a short decode never outlives the query-window warm-up."""
    get_engine = setup
    eng = get_engine(max_batch=2, seq_buckets=(256,), paged=True,
                     decode_sparse=True, prefill_chunk=64,
                     refresh_every=S64, refresh_mass=0.5)
    # r0 decodes long (its cadence points land while r1's 4-quantum
    # admission is in flight); r1's 6-token decode never warms a window
    reqs = _requests((LONG, 6), seq=256, base=50)
    eng.serve(reqs, seed=0)
    assert reqs[0].refreshes > 0
    assert reqs[1].refreshes == 0
    assert all(r.finish_reason == "length" for r in reqs)
    assert eng.page_pool_stats["pages_in_use_at_end"] == 0


@pytest.mark.slow
def test_preempt_resume_rebuilds_refresh_state(setup):
    """Preemption discards a slot's refresh state with its pages; the
    resumed request re-warms a cold query window and refreshes again
    after replay — and every terminal path still drains the pool."""
    get_engine = setup
    eng = get_engine(max_batch=3, seq_buckets=(S64,), paged=True,
                     decode_sparse=True, refresh_every=S64,
                     refresh_mass=0.5, num_pages=10,
                     preempt_after_steps=2)
    # extra = max(max_new) = 192, so each admission holds
    # (64 + 192) / 64 = 4 pages; 9 allocatable admit two and the short
    # third starves into the preemption window.  Pin the LONG request as
    # the victim via priority (victim order is priority first), so the
    # resumed stream still has ~185 decode steps — enough to re-warm the
    # cold query ring (64) and cross a refresh cadence point
    reqs = _requests((3 * S64, 3 * S64 - 10, 12), base=70)
    reqs[0].priority = -1
    eng.serve(reqs, seed=0)
    assert eng.preemptions > 0
    assert reqs[0].preempted_count > 0
    assert reqs[0].state == "done" and reqs[0].finish_reason == "length"
    # the rebuilt refresh state fired on the resumed stream
    assert reqs[0].refreshes >= 1
    assert eng.page_pool_stats["pages_in_use_at_end"] == 0


# --------------------------------------------------------------------------
# Sharded tier: refreshed ragged rows through the auto splice
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
def test_refreshed_row_splices_bitwise_under_mesh():
    """A refreshed per-head ragged row round-trips update_plan_slot_auto
    under a forced 2-device model mesh bitwise: the sharded splice
    re-places the same tables, it may not re-derive them."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.distributed.sharding import ShardingRules, use_rules
        from repro.launch.mesh import make_serving_mesh
        from repro.serving import decode_plan as dplan

        cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                                  num_heads=4, num_kv_heads=2)
        L, H, Hkv, D, bs, nb, nblk = (cfg.num_layers, 4, 2, 8, 16, 8, 5)
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        q = jax.random.normal(ks[0], (L, H, bs, D))
        pool_k = jax.random.normal(ks[1], (L, nb + 1, Hkv, bs, D))
        table = jnp.arange(1, nb + 1, dtype=jnp.int32)
        row = dplan.build_refresh_plan_row(
            q, pool_k, table, cfg, block_size=bs, num_blocks=nblk,
            table_blocks=nb, horizon_blocks=2, mass=0.5,
            strip_impl="jnp")
        assert int(jnp.max(row.counts)) < nb   # genuinely ragged

        plan = dplan.empty_decode_plan(cfg, batch=2, cache_len=nb * bs,
                                       block_size=bs)
        ref = dplan.update_plan_slot(plan, row, 1)
        mesh = make_serving_mesh(2)
        with use_rules(ShardingRules(mesh)), mesh:
            got = dplan.update_plan_slot_auto(plan, row, 1, cfg)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK")
    """)
    res = _run_subprocess(code)
    assert res.returncode == 0, res.stderr
    assert "OK" in res.stdout
