"""Request-lifecycle hardening: validation, cancellation, deadlines,
preemption with page reclaim, and per-request fault quarantine.

The load-bearing invariant is **fault isolation under greedy
conformance**: whatever happens to one request — rejected at submit,
cancelled, timed out, NaN-poisoned mid-decode, failed in prefill, or
preempted and resumed — every OTHER request's tokens must stay bitwise
equal to a clean serve of the same workload, and a preempted request's
own resumed stream must reproduce its unpreempted stream bitwise (the
resume re-prefills the original prompt at its original bucket and
replays the carry through decode as forced tokens).  Page accounting is
pinned too: every terminal path returns its pages, so
``page_pool_stats["pages_in_use_at_end"]`` is 0 after a drained serve.

The subprocess tier replays cancellation + quarantine under a forced
2-device CPU mesh: the hardened lifecycle must not perturb the sharded
decode path's healthy rows either.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import DataConfig, sample
from repro.models import build_model
from repro.serving import (
    CancelAt,
    EngineConfig,
    FaultInjector,
    NaNLogits,
    PrefillError,
    Request,
    RequestError,
    SamplingConfig,
    SchedulerHandle,
    ServingEngine,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.dirname(os.path.abspath(__file__))

CFG = get_smoke_config("granite-3-2b")
S64, S256 = 64, 256


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


def _sched(get_engine):
    """The small contiguous scheduler most lifecycle tests run on."""
    return get_engine(max_batch=2, seq_buckets=(S64,), scheduler=True)


# --------------------------------------------------------------------------
# Submit-time validation → typed RequestError, finish_reason="rejected"
# --------------------------------------------------------------------------

def _bad_requests():
    ok = _requests((2,))[0].prompt
    return [
        ("empty prompt", Request(uid=7, prompt=np.zeros((0,), np.int32))),
        ("2-D prompt", Request(uid=7, prompt=np.zeros((2, 4), np.int32))),
        ("float prompt", Request(uid=7, prompt=np.zeros((4,), np.float32))),
        ("negative max_new", Request(uid=7, prompt=ok, max_new_tokens=-1)),
        ("negative deadline", Request(uid=7, prompt=ok, deadline_s=-1.0)),
        ("oversize, no truncation",
         Request(uid=7, prompt=np.zeros((S64 * 8,), np.int32),
                 allow_truncation=False)),
        ("negative stop token",
         Request(uid=7, prompt=ok,
                 sampling=SamplingConfig(stop_tokens=(-3,)))),
        ("bool stop token",
         Request(uid=7, prompt=ok,
                 sampling=SamplingConfig(stop_tokens=(True,)))),
        ("non-iterable stop_tokens",
         Request(uid=7, prompt=ok, sampling=SamplingConfig(stop_tokens=5))),
    ]


def test_validate_request_raises_typed(setup):
    eng = _sched(setup)
    for label, r in _bad_requests():
        with pytest.raises(RequestError) as ei:
            eng.validate_request(r)
        assert ei.value.uid == 7, label
        assert ei.value.kind == "invalid", label
    # the documented contracts stay valid: max_new_tokens=0 is
    # prefill-only, an oversize prompt with truncation allowed clips
    eng.validate_request(Request(uid=1, prompt=_bad_requests()[3][1].prompt,
                                 max_new_tokens=0))
    eng.validate_request(Request(uid=1,
                                 prompt=np.zeros((S64 * 8,), np.int32)))


@pytest.mark.parametrize("scheduler", [False, True],
                         ids=["batch_path", "scheduler"])
def test_rejected_requests_finish_terminally(setup, scheduler):
    """Both serving paths mark malformed submissions rejected/failed with
    the typed error and empty output — they never reach the fused batch."""
    eng = setup(max_batch=2, seq_buckets=(S64,), scheduler=scheduler)
    bad = [r for _, r in _bad_requests()]
    eng.serve(bad, seed=0)
    for r in bad:
        assert r.finish_reason == "rejected"
        assert r.state == "failed"
        assert isinstance(r.error, RequestError) and r.error.uid == 7
        assert r.output_tokens.size == 0


def test_rejection_isolates_healthy_requests(setup):
    """A malformed co-submission must not perturb valid requests: their
    greedy tokens bit-match a clean serve without the bad request."""
    eng = _sched(setup)
    clean = _requests((5, 4), base=1)
    eng.serve(clean, seed=0)

    bad = Request(uid=7, prompt=np.zeros((0,), np.int32))
    mixed = [_requests((5, 4), base=1)[0], bad,
             _requests((5, 4), base=1)[1]]
    eng.serve(mixed, seed=0)
    assert mixed[1].finish_reason == "rejected"
    np.testing.assert_array_equal(mixed[0].output_tokens,
                                  clean[0].output_tokens)
    np.testing.assert_array_equal(mixed[2].output_tokens,
                                  clean[1].output_tokens)


# --------------------------------------------------------------------------
# Cancellation + deadlines
# --------------------------------------------------------------------------

def test_cancel_waiting_request(setup):
    """A request cancelled through the SchedulerHandle before admission
    finishes inert (no tokens) and the others bit-match a clean serve."""
    eng = _sched(setup)
    clean = _requests((5, 4, 3))
    eng.serve(clean, seed=0)

    handle = SchedulerHandle()
    handle.cancel(1)
    reqs = _requests((5, 4, 3))
    eng.serve(reqs, seed=0, handle=handle)
    assert reqs[1].finish_reason == "cancelled"
    assert reqs[1].state == "cancelled"
    assert reqs[1].output_tokens.size == 0
    for i in (0, 2):
        assert reqs[i].finish_reason == "length"
        np.testing.assert_array_equal(reqs[i].output_tokens,
                                      clean[i].output_tokens)


def test_cancel_mid_decode_via_fault(setup):
    """A mid-decode cancellation (injected at a deterministic step)
    vacates only its slot: partial output, finish_reason="cancelled",
    the surviving request bitwise-unaffected."""
    eng = _sched(setup)
    clean = _requests((10, 6))
    eng.serve(clean, seed=0)

    reqs = _requests((10, 6))
    eng.serve(reqs, seed=0, faults=FaultInjector(CancelAt(uid=0, step=4)))
    assert reqs[0].finish_reason == "cancelled"
    assert reqs[0].state == "cancelled"
    assert 0 < len(reqs[0].output_tokens) < 10
    np.testing.assert_array_equal(
        reqs[0].output_tokens,
        clean[0].output_tokens[: len(reqs[0].output_tokens)])
    np.testing.assert_array_equal(reqs[1].output_tokens,
                                  clean[1].output_tokens)


def test_deadline_expires_waiting_request(setup):
    """deadline_s is a wall budget from arrival: an expired WAITING
    request times out at the next reap instead of being admitted."""
    eng = _sched(setup)
    reqs = _requests((4, 4))
    reqs[1].deadline_s = 1e-6
    eng.serve(reqs, seed=0)
    assert reqs[0].finish_reason == "length"
    assert reqs[1].finish_reason == "timeout"
    assert reqs[1].state == "cancelled"
    assert reqs[1].output_tokens.size == 0


# --------------------------------------------------------------------------
# Per-request fault quarantine
# --------------------------------------------------------------------------

def test_nan_decode_logits_quarantines_one_slot(setup):
    """NaN logits on one decode row fail ONLY that request (typed error,
    kind="decode", tokens up to the poisoned step kept); the other slot's
    stream is bitwise-unaffected."""
    eng = _sched(setup)
    clean = _requests((8, 6))
    eng.serve(clean, seed=0)

    reqs = _requests((8, 6))
    eng.serve(reqs, seed=0,
              faults=FaultInjector(NaNLogits(uid=0, at_token=2)))
    assert reqs[0].finish_reason == "failed"
    assert reqs[0].state == "failed"
    assert isinstance(reqs[0].error, RequestError)
    assert reqs[0].error.kind == "decode" and reqs[0].error.uid == 0
    assert len(reqs[0].output_tokens) == 2
    np.testing.assert_array_equal(reqs[0].output_tokens,
                                  clean[0].output_tokens[:2])
    np.testing.assert_array_equal(reqs[1].output_tokens,
                                  clean[1].output_tokens)


def test_prefill_fault_quarantines_one_request(setup):
    """An exception inside one request's admission prefill fails only
    that request (kind="prefill"); the co-served request completes with
    bitwise-identical tokens."""
    eng = _sched(setup)
    clean = _requests((4, 6))
    eng.serve(clean, seed=0)

    reqs = _requests((4, 6))
    eng.serve(reqs, seed=0, faults=FaultInjector(PrefillError(uid=0)))
    assert reqs[0].finish_reason == "failed"
    assert isinstance(reqs[0].error, RequestError)
    assert reqs[0].error.kind == "prefill"
    assert reqs[0].output_tokens.size == 0
    np.testing.assert_array_equal(reqs[1].output_tokens,
                                  clean[1].output_tokens)


# --------------------------------------------------------------------------
# Preemption with page reclaim (paged mode)
# --------------------------------------------------------------------------

def test_preempt_resume_bitwise_and_pages_reclaimed(setup):
    """Pool starvation past preempt_after_steps evicts a decoding victim
    and re-queues it; the resumed stream — original-prompt re-prefill +
    decode replay of the carry — reproduces the unpreempted serve
    bitwise, and the reclaimed pages are what admit the starved request.
    No page leaks: the pool drains to zero."""
    get_engine = setup
    base = dict(max_batch=3, seq_buckets=(S64,), paged=True,
                decode_sparse=True, decode_extra=S64)
    eng_a = get_engine(**base)                      # auto-sized ample pool
    clean = _requests((20, 18, 12))
    eng_a.serve(clean, seed=0)
    assert eng_a.preemptions == 0

    # each admission holds (64 + 64) / 64 = 2 pages; num_pages=6 leaves 5
    # allocatable, so two requests admit and the third starves with a
    # free slot — exactly the preemption trigger
    eng_t = get_engine(**base, num_pages=6, preempt_after_steps=2)
    reqs = _requests((20, 18, 12))
    eng_t.serve(reqs, seed=0)
    assert eng_t.preemptions > 0
    assert eng_t.pages_exhausted_steps > 0
    assert any(r.preempted_count > 0 for r in reqs)
    assert any(r.waiting_deferred_steps > 0 for r in reqs)
    for a, b in zip(clean, reqs):
        assert b.finish_reason == "length"
        assert b.state == "done"
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)
    stats = eng_t.page_pool_stats
    assert stats["pages_in_use_at_end"] == 0
    # the preempted victim's pages were genuinely recycled: peak usage
    # never exceeded the 5 allocatable pages of the tight pool
    assert stats["peak_pages"] <= 5


def test_priority_selects_preemption_victim(setup):
    """Victim order is (priority, generated tokens): the low-priority
    request is evicted, the high-priority ones are never preempted."""
    get_engine = setup
    eng = get_engine(max_batch=3, seq_buckets=(S64,), paged=True,
                     decode_sparse=True, decode_extra=S64, num_pages=6,
                     preempt_after_steps=2)
    reqs = _requests((20, 18, 12))
    reqs[0].priority = 1                # admitted first, but protected
    eng.serve(reqs, seed=0)
    assert eng.preemptions > 0
    assert reqs[0].preempted_count == 0
    assert reqs[1].preempted_count > 0
    assert all(r.finish_reason == "length" for r in reqs)


# --------------------------------------------------------------------------
# Chunked admission: cancellation between quanta, mid-admission eviction
# --------------------------------------------------------------------------

def test_chunked_cancel_aborts_between_quanta(setup):
    """Cancelling a request whose chunked prefill is in flight aborts the
    run between quanta: the request is cancelled with no tokens, its
    pages return, and the following request still serves bitwise."""
    get_engine = setup
    eng = get_engine(max_batch=2, seq_buckets=(S256,), paged=True,
                     prefill_chunk=64)
    clean = _requests((6,), seq=S256, base=1)
    eng.serve(clean, seed=0)

    # r0's 4-quantum prefill is cancelled at step 2 (mid-run); r1 admits
    # afterwards and must see a clean pool and plan
    reqs = _requests((4, 6), seq=S256)
    eng.serve(reqs, seed=0, faults=FaultInjector(CancelAt(uid=0, step=2)))
    assert reqs[0].finish_reason == "cancelled"
    assert reqs[0].output_tokens.size == 0
    assert reqs[1].finish_reason == "length"
    np.testing.assert_array_equal(reqs[1].output_tokens,
                                  clean[0].output_tokens)
    assert eng.page_pool_stats["pages_in_use_at_end"] == 0


def test_preemption_during_chunked_admission(setup):
    """The starvation clock keeps ticking while a chunked run is in
    flight: a queue head that would stay starved even after the run lands
    evicts a decoding victim mid-admission, and every stream still
    bit-matches the ample-pool serve."""
    get_engine = setup
    base = dict(max_batch=3, seq_buckets=(S256,), paged=True,
                prefill_chunk=64, decode_extra=S64)
    eng_a = get_engine(**base)
    clean = _requests((16, 5, 4), seq=S256)
    eng_a.serve(clean, seed=0)
    assert eng_a.preemptions == 0

    # each admission holds (256 + 64) / 64 = 5 pages; 10 allocatable →
    # r0 and r1 hold the whole pool, the third slot stays FREE, and r2
    # starves on pages while r1's 4-quantum run is still in flight — the
    # mid-run tick preempts r0 (the only progressed decoder) before the
    # run even lands
    eng_t = get_engine(**base, num_pages=11, preempt_after_steps=1)
    reqs = _requests((16, 5, 4), seq=S256)
    eng_t.serve(reqs, seed=0)
    assert eng_t.preemptions > 0
    assert reqs[0].preempted_count > 0
    for a, b in zip(clean, reqs):
        assert b.finish_reason == "length"
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)
    assert eng_t.page_pool_stats["pages_in_use_at_end"] == 0


# --------------------------------------------------------------------------
# Sharded tier: cancel + quarantine under a forced 2-device mesh
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
def test_sharded_cancel_and_quarantine_replay():
    """The hardened lifecycle under a heads-sharded 2-device mesh: one
    request cancelled mid-decode, one NaN-quarantined — the surviving
    requests' tokens stay bitwise equal to the clean mesh serve."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, numpy as np
        from repro.configs import get_smoke_config
        from repro.data import DataConfig, sample
        from repro.distributed.sharding import ShardingRules, use_rules
        from repro.launch.mesh import make_serving_mesh
        from repro.models import build_model
        from repro.serving import (CancelAt, EngineConfig, FaultInjector,
                                   NaNLogits, Request, RequestError,
                                   ServingEngine)

        cfg = get_smoke_config("granite-3-2b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        sp = model.default_share_prefill()
        eng = ServingEngine(model, params, sp, EngineConfig(
            method="share", max_batch=2, seq_buckets=(64,),
            scheduler=True))

        def reqs():
            d = DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                           global_batch=1, task="retrieval")
            return [Request(uid=i, prompt=sample(d, i)["tokens"],
                            max_new_tokens=m)
                    for i, m in enumerate((10, 8, 6))]

        mesh = make_serving_mesh(2)
        with use_rules(ShardingRules(mesh)), mesh:
            clean = reqs()
            eng.serve(clean, seed=0)
            faulty = reqs()
            eng.serve(faulty, seed=0,
                      faults=FaultInjector(CancelAt(uid=0, step=5),
                                           NaNLogits(uid=1, at_token=3)))
        assert faulty[0].finish_reason == "cancelled", faulty[0]
        assert faulty[1].finish_reason == "failed"
        assert isinstance(faulty[1].error, RequestError)
        assert faulty[1].error.kind == "decode"
        np.testing.assert_array_equal(
            faulty[0].output_tokens,
            clean[0].output_tokens[: len(faulty[0].output_tokens)])
        np.testing.assert_array_equal(faulty[1].output_tokens,
                                      clean[1].output_tokens[:3])
        np.testing.assert_array_equal(faulty[2].output_tokens,
                                      clean[2].output_tokens)
        print("SHARDED-LIFECYCLE-OK")
    """)
    res = _run_subprocess(code)
    assert res.returncode == 0, res.stderr
    assert "SHARDED-LIFECYCLE-OK" in res.stdout
