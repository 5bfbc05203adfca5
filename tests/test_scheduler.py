"""Continuous-batching scheduler: conformance, in-flight splicing, metrics.

The load-bearing invariant is **greedy conformance**: with greedy sampling
and a fixed seed the slot-based scheduler must produce bitwise-identical
output tokens to the legacy batch-at-a-time serve for the same request set
— slot churn (insertion, early exit, refill) must never perturb an
occupied row's numerics.  The splice primitives are additionally checked
directly: ``cache_insert`` / ``update_plan_slot`` touch only their slot's
row, a plan spliced from single-request builds bit-matches the batched
build, and per-slot (vector) decode positions reproduce the lockstep
scalar path.  The subprocess tier replays the scheduler under a forced
2-device CPU mesh (Hkv-sharded plan splicing) and bit-matches the unmeshed
serve.
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
from repro.models import build_model
from repro.serving import (
    EngineConfig,
    Request,
    SamplingConfig,
    ServingEngine,
    empty_decode_plan,
    update_plan_slot,
)
from repro.serving import decode_plan as dplan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.dirname(os.path.abspath(__file__))

CFG = get_smoke_config("granite-3-2b")
KEY = jax.random.PRNGKey(0)
SEQ = 256
MAX_NEW = (5, 2, 4, 3)      # mixed lengths over 2 slots: forces early exit
                            # + mid-decode refill in the scheduler


@pytest.fixture(scope="module")
def setup():
    model = build_model(CFG)
    params = model.init(KEY)
    sp = model.default_share_prefill()
    engines = {}

    def get_engine(scheduler: bool, sparse: bool) -> ServingEngine:
        """Engines are memoized so compiled programs are reused across
        tests (the scheduler and batch paths each compile once)."""
        k = (scheduler, sparse)
        if k not in engines:
            engines[k] = ServingEngine(model, params, sp, EngineConfig(
                method="share", max_batch=2, seq_buckets=(SEQ,),
                decode_sparse=sparse, scheduler=scheduler))
        return engines[k]

    return model, params, sp, get_engine


def _requests(max_new=MAX_NEW, **kw):
    dcfg = DataConfig(vocab_size=CFG.vocab_size, seq_len=SEQ,
                      global_batch=1, task="retrieval")
    return [Request(uid=i, prompt=sample(dcfg, i)["tokens"],
                    max_new_tokens=m, **kw) for i, m in enumerate(max_new)]


# --------------------------------------------------------------------------
# Greedy conformance: scheduler == batch-at-a-time, bitwise
# --------------------------------------------------------------------------

@pytest.mark.parametrize("sparse", [False, True],
                         ids=["dense_decode", "sparse_decode"])
def test_scheduler_bitmatches_batch_serve(setup, sparse):
    """Mixed max_new_tokens over fewer slots than requests: the scheduler
    exits short rows early and refills mid-decode (cache_insert +
    update_plan_slot), yet every request's greedy tokens bit-match the
    legacy batch-at-a-time serve — and slots are measurably busier."""
    _, _, _, get_engine = setup
    outs, occ = {}, {}
    for sched in (False, True):
        eng = get_engine(sched, sparse)
        reqs = _requests()
        eng.serve(reqs, seed=0)
        outs[sched] = [r.output_tokens for r in reqs]
        occ[sched] = eng.slot_occupancy()
        for r in reqs:
            assert r.finish_reason == "length"
            assert len(r.output_tokens) == r.max_new_tokens
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(a, b)
    assert occ[True] > occ[False]       # refill keeps slots busy


def test_scheduler_stop_tokens_both_paths(setup):
    """SamplingConfig.stop_tokens ends a request at the stop token in BOTH
    serving paths, with the stop token kept as the final output token."""
    _, _, _, get_engine = setup
    # find a token the greedy decode actually emits mid-stream
    probe = _requests(max_new=(6,))
    get_engine(False, False).serve(probe, seed=0)
    full = probe[0].output_tokens
    stop = int(full[2])
    first = int(np.argmax(full == stop))
    for sched in (False, True):
        reqs = _requests(max_new=(6,),
                         sampling=SamplingConfig(stop_tokens=(stop,)))
        get_engine(sched, False).serve(reqs, seed=0)
        np.testing.assert_array_equal(reqs[0].output_tokens,
                                      full[: first + 1])
        assert reqs[0].finish_reason == "stop"


def test_scheduler_arrival_simulation(setup):
    """Requests arriving over time are admitted in arrival order once a
    slot frees; greedy tokens are arrival-independent."""
    _, _, _, get_engine = setup
    eng = get_engine(True, False)
    base = _requests()
    eng.serve(base, seed=0)
    reqs = _requests()
    for i, r in enumerate(reqs):
        r.arrival_s = 0.02 * i
    eng.serve(reqs, seed=0)
    for a, b in zip(base, reqs):
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)
        assert b.queue_s >= 0.0 and b.ttft_s > 0.0


def test_scheduler_per_request_metrics(setup):
    """Metrics are real per-request values, not batch-wide copies: every
    request records its own queue time, TTFT, and decode tokens/s."""
    _, _, _, get_engine = setup
    eng = get_engine(True, False)
    reqs = _requests()
    eng.serve(reqs, seed=0)
    for r in reqs:
        assert r.ttft_s > 0.0
        assert r.ttft_s >= r.prefill_s        # TTFT includes the prefill
        assert r.queue_s >= 0.0
        if r.max_new_tokens > 1:
            assert r.decode_tokens_per_s > 0.0
    # later-admitted requests queued behind the initial slot fill
    assert max(r.queue_s for r in reqs) > min(r.queue_s for r in reqs)
    assert 0.0 < eng.slot_occupancy() <= 1.0


def test_truncated_prompt_flagged(setup, caplog):
    """A prompt longer than the largest bucket is clipped to its tail —
    flagged on the Request and logged, in both serving paths."""
    _, _, _, get_engine = setup
    for sched in (False, True):
        reqs = _requests(max_new=(2,))
        reqs[0].prompt = np.concatenate([reqs[0].prompt] * 2)
        with caplog.at_level("WARNING", logger="repro.serving.engine"):
            get_engine(sched, False).serve(reqs, seed=0)
        assert reqs[0].truncated
        assert any("clipping" in rec.message for rec in caplog.records)
        caplog.clear()


def test_prefill_only_request_emits_no_tokens(setup):
    """max_new_tokens=0 is prefill-only: no token is emitted in either
    serving path (the legacy path used to truncate post-hoc; the token
    must not be generated at all)."""
    _, _, _, get_engine = setup
    for sched in (False, True):
        reqs = _requests(max_new=(0, 3))
        get_engine(sched, False).serve(reqs, seed=0)
        assert len(reqs[0].output_tokens) == 0
        assert reqs[0].finish_reason == "length"
        assert len(reqs[1].output_tokens) == 3


def test_vacated_slot_plan_row_emptied(setup):
    """Freeing a slot splices the empty row back: a finished request's
    keep-set must not keep streaming kv blocks from an inert slot."""
    from repro.serving import SlotScheduler

    _, _, _, get_engine = setup
    eng = get_engine(True, True)
    sched = SlotScheduler(eng, _requests(max_new=(4, 2)), SEQ, seed=0)
    sched.run()
    assert all(s is None for s in sched.slots)
    np.testing.assert_array_equal(np.asarray(sched.plan.counts), 0)
    assert not np.asarray(sched.plan.keep_heads).any()


# --------------------------------------------------------------------------
# Splice primitives: slot-local by construction
# --------------------------------------------------------------------------

def test_cache_insert_touches_only_its_slot():
    """cache_insert writes one row's prefill region and nothing else —
    other rows and the slot's own decode tail are bitwise untouched."""
    L, B, HKV, S, HD, SRC = 2, 3, 2, 80, 8, 64
    k = jax.random.PRNGKey(1)
    dst = {"prefix": [(jax.random.normal(k, (B, HKV, S, HD)),
                       jax.random.normal(k, (B, HKV, S, HD)))],
           "stack": (jax.random.normal(k, (L, B, HKV, S, HD)),
                     jax.random.normal(k, (L, B, HKV, S, HD)))}
    src = {"prefix": [(jnp.ones((1, HKV, SRC, HD)),
                       2 * jnp.ones((1, HKV, SRC, HD)))],
           "stack": (3 * jnp.ones((L, 1, HKV, SRC, HD)),
                     4 * jnp.ones((L, 1, HKV, SRC, HD)))}
    out = ServingEngine.cache_insert(dst, src, 1)
    # spliced slot: prefill region replaced, decode tail preserved
    np.testing.assert_array_equal(out["stack"][0][:, 1, :, :SRC],
                                  np.asarray(src["stack"][0][:, 0]))
    np.testing.assert_array_equal(out["stack"][0][:, 1, :, SRC:],
                                  np.asarray(dst["stack"][0][:, 1, :, SRC:]))
    np.testing.assert_array_equal(out["prefix"][0][1][1, :, :SRC],
                                  np.asarray(src["prefix"][0][1][0]))
    # other slots bitwise untouched
    for row in (0, 2):
        np.testing.assert_array_equal(out["stack"][1][:, row],
                                      np.asarray(dst["stack"][1][:, row]))
        np.testing.assert_array_equal(out["prefix"][0][0][row],
                                      np.asarray(dst["prefix"][0][0][row]))


def test_spliced_plan_matches_batched_build(setup):
    """An empty plan with each request's single-row plan spliced in equals
    the plan built from the batched prefill, leaf-for-leaf bitwise — the
    invariant that makes in-flight splicing safe."""
    model, params, sp, get_engine = setup
    eng = get_engine(False, True)
    dcfg = DataConfig(vocab_size=CFG.vocab_size, seq_len=SEQ,
                      global_batch=1, task="retrieval")
    toks = np.stack([sample(dcfg, 30 + i)["tokens"] for i in range(2)])
    plens = jnp.asarray([SEQ, SEQ], jnp.int32)
    cache_len = SEQ + 2 * sp.cfg.block_size

    batched = eng._prefill_fn(2, SEQ)(params, jnp.asarray(toks), plens)
    plan_b = dplan.build_decode_plan(sp, batched.sp_state, CFG,
                                     prefill_len=SEQ, cache_len=cache_len)

    plan_s = empty_decode_plan(CFG, batch=2, cache_len=cache_len,
                               block_size=sp.cfg.block_size)
    prefill1 = eng._prefill_fn(1, SEQ)
    for slot in range(2):
        solo = prefill1(params, jnp.asarray(toks[slot: slot + 1]),
                        plens[slot: slot + 1])
        rplan = dplan.build_decode_plan(sp, solo.sp_state, CFG,
                                        prefill_len=SEQ,
                                        cache_len=cache_len)
        plan_s = update_plan_slot(plan_s, rplan, slot)
    for a, b in zip(plan_b, plan_s):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_update_plan_slot_width_mismatch_raises():
    plan = empty_decode_plan(CFG, batch=2, cache_len=256, block_size=64)
    other = empty_decode_plan(CFG, batch=1, cache_len=512, block_size=64)
    with pytest.raises(ValueError, match="width mismatch"):
        update_plan_slot(plan, other, 0)


def test_slot_insertion_leaves_other_rows_bitwise(setup):
    """Mid-decode slot replacement: decoding a 2-slot state where slot 1
    holds request B vs request C yields bitwise-identical slot-0 logits —
    the row independence the scheduler's refill relies on."""
    model, params, sp, get_engine = setup
    eng = get_engine(False, True)
    dcfg = DataConfig(vocab_size=CFG.vocab_size, seq_len=SEQ,
                      global_batch=1, task="retrieval")
    cache_len = SEQ + 2 * sp.cfg.block_size
    prefill1 = eng._prefill_fn(1, SEQ)
    solos = []
    for i in range(3):                   # A, B, C
        toks = sample(dcfg, 50 + i)["tokens"][None]
        solos.append(prefill1(params, jnp.asarray(toks),
                              jnp.asarray([SEQ], jnp.int32)))

    decode = eng._decode_fn(2, SEQ, cache_len, True)
    pos = jnp.asarray([SEQ, SEQ], jnp.int32)
    plens = jnp.asarray([SEQ, SEQ], jnp.int32)
    tok = jnp.asarray([[7], [9]], jnp.int32)

    logits_by_mate = []
    for mate in (1, 2):                  # slot 1 ← B, then slot 1 ← C
        cache = model.init_cache(2, cache_len)
        plan = empty_decode_plan(CFG, batch=2, cache_len=cache_len,
                                 block_size=sp.cfg.block_size)
        for slot, idx in ((0, 0), (1, mate)):
            cache = ServingEngine.cache_insert(cache, solos[idx].cache,
                                               slot)
            rplan = dplan.build_decode_plan(sp, solos[idx].sp_state, CFG,
                                            prefill_len=SEQ,
                                            cache_len=cache_len)
            plan = update_plan_slot(plan, rplan, slot)
        logits, _ = decode(params, tok, cache, pos, plens, plan)
        logits_by_mate.append(np.asarray(logits))
    np.testing.assert_array_equal(logits_by_mate[0][0],
                                  logits_by_mate[1][0])
    assert not np.array_equal(logits_by_mate[0][1], logits_by_mate[1][1])


# --------------------------------------------------------------------------
# Per-slot (vector) decode positions == lockstep scalar path
# --------------------------------------------------------------------------

def test_vector_pos_matches_scalar_decode(setup):
    """decode_step with pos as a (B,) vector of identical values is
    bitwise the scalar path; with per-row values each row matches its own
    solo scalar decode."""
    model, params, sp, get_engine = setup
    eng = get_engine(False, False)
    dcfg = DataConfig(vocab_size=CFG.vocab_size, seq_len=SEQ,
                      global_batch=1, task="retrieval")
    toks = np.stack([sample(dcfg, 60 + i)["tokens"] for i in range(2)])
    plens = jnp.asarray([SEQ, SEQ], jnp.int32)
    cache_len = SEQ + 64
    res = eng._prefill_fn(2, SEQ)(params, jnp.asarray(toks), plens)
    cache = ServingEngine.grow_cache(res.cache, SEQ, 64)
    tok = jnp.asarray([[3], [5]], jnp.int32)

    l_scalar, c_scalar = model.decode(params, tok, cache, jnp.int32(SEQ),
                                      prompt_lens=plens, prefill_len=SEQ)
    l_vec, c_vec = model.decode(params, tok, cache,
                                jnp.asarray([SEQ, SEQ], jnp.int32),
                                prompt_lens=plens, prefill_len=SEQ)
    np.testing.assert_array_equal(np.asarray(l_scalar), np.asarray(l_vec))
    for a, b in zip(jax.tree.leaves(c_scalar), jax.tree.leaves(c_vec)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # staggered per-row positions: row i bit-matches a lockstep scalar
    # decode of the whole batch at row i's position (same batch shape, so
    # XLA's batched matmuls are reassociated identically)
    stag = jnp.asarray([SEQ, SEQ + 3], jnp.int32)
    l_stag, _ = model.decode(params, tok, cache, stag,
                             prompt_lens=plens, prefill_len=SEQ)
    for row in range(2):
        l_lock, _ = model.decode(params, tok, cache, stag[row],
                                 prompt_lens=plens, prefill_len=SEQ)
        np.testing.assert_array_equal(np.asarray(l_stag[row]),
                                      np.asarray(l_lock[row]))


def test_vector_pos_mla_raises():
    """MLA latent caches keep the scalar lockstep contract — vector pos is
    the dense carve-out's hard error, not silent misbehavior."""
    from repro.models import transformer

    cfg = get_smoke_config("deepseek-v2-236b")
    assert cfg.mla.enabled
    model = build_model(cfg)
    params = model.init(KEY)
    cache = model.init_cache(2, 64)
    tok = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(ValueError, match="per-slot"):
        transformer.decode_step(params, cfg, tok, cache,
                                jnp.asarray([8, 9], jnp.int32))


# --------------------------------------------------------------------------
# Sharded tier: scheduler under a forced 2-device mesh (subprocess)
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
@pytest.mark.slow
def test_scheduler_serve_under_mesh_bitmatches():
    """Continuous-batching serve on a forced 2-device CPU mesh: slot
    refill splices Hkv-sharded plan rows (update_sharded_plan_slot,
    asserted via call counter) and the output tokens bit-match the
    unmeshed scheduler serve."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, numpy as np
        from repro.configs import get_smoke_config
        from repro.data import DataConfig, sample
        from repro.distributed import sharding as dsh
        from repro.models import build_model
        from repro.serving import EngineConfig, Request, ServingEngine
        from repro.serving import decode_plan as dplan

        calls = {"splice": 0, "plan": 0}
        orig_splice = dplan.update_sharded_plan_slot
        orig_plan = dplan.build_sharded_decode_plan

        def count_splice(*a, **kw):
            calls["splice"] += 1
            return orig_splice(*a, **kw)

        def count_plan(*a, **kw):
            calls["plan"] += 1
            return orig_plan(*a, **kw)

        dplan.update_sharded_plan_slot = count_splice
        dplan.build_sharded_decode_plan = count_plan

        cfg = get_smoke_config("granite-3-2b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        sp = model.default_share_prefill()
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=256,
                          global_batch=1, task="retrieval")

        def serve(meshed):
            engine = ServingEngine(model, params, sp, EngineConfig(
                method="share", attn_impl="sparse", seq_buckets=(256,),
                decode_sparse=True, scheduler=True, max_batch=2))
            reqs = [Request(uid=i, prompt=sample(dcfg, 7 + i)["tokens"],
                            max_new_tokens=m)
                    for i, m in enumerate((4, 2, 3))]
            if meshed:
                from repro.launch.mesh import make_mesh
                mesh = make_mesh((1, 2), ("data", "model"))
                with dsh.use_rules(dsh.ShardingRules(mesh)), mesh:
                    engine.serve(reqs)
            else:
                engine.serve(reqs)
            return [r.output_tokens for r in reqs]

        t_plain = serve(False)
        assert calls == {"splice": 0, "plan": 0}, calls
        t_mesh = serve(True)
        # one splice per admitted slot (3) + one empty-row splice per slot
        # that stayed vacated (2: the dead keep-set must stop streaming;
        # B's slot is refilled by C before a decode step, so its vacate
        # costs no splice)
        assert calls["splice"] == 5, calls
        assert calls["plan"] == 3, calls     # per-shard single-row builds
        for a, b in zip(t_plain, t_mesh):
            np.testing.assert_array_equal(a, b)
        print("SCHEDULER-UNDER-MESH-OK", calls)
    """)
    res = _run_subprocess(code)
    assert res.returncode == 0, res.stderr
    assert "SCHEDULER-UNDER-MESH-OK" in res.stdout
