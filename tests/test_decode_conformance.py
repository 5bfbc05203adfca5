"""Decode conformance harness: the DecodePlan decode contract, table-driven.

One seeded :class:`Case` table sweeps the axes the serving path must
survive — GQA ratios (incl. MHA and single-kv-head), ragged prompt
lengths, empty keep-set kv-heads, bf16, post-``grow_cache`` decode
positions, and width-capped tables — and every backend of
:func:`repro.kernels.decode_attn.flash_decode_plan` is checked against the
dense token-level reference, with exact zeros for empty keep-sets and
bitwise kv-head-slice decomposability (the invariant the heads-sharded
execution path relies on).

The forced-2-device-mesh subprocess tier replays the same ``CASES``
through :func:`repro.distributed.sharding.sharded_flash_decode` and
asserts bitwise equality with the single-device plan path, then runs a
full :class:`ServingEngine` serve-under-mesh smoke test (prefill and
decode both under ``shard_map``, tokens bit-matching the unmeshed serve).

Consolidates the ad-hoc batched-decode oracle cases previously scattered
across ``test_decode_kernel.py`` / ``test_sparse_decode.py``.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn import (
    DecodePlan,
    flash_decode,
    flash_decode_plan,
)
from repro.kernels.indices import cap_block_mask, compact_block_mask

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.dirname(os.path.abspath(__file__))


# --------------------------------------------------------------------------
# Case table
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Case:
    """One decode conformance scenario (seeded, fully reproducible)."""
    name: str
    b: int = 2                  # batch
    h: int = 8                  # query heads
    hkv: int = 2                # kv heads
    s: int = 256                # prefill cache length
    d: int = 32                 # head dim
    bs: int = 64                # pattern block size
    keep_p: float = 0.5         # per-(kv-head, block, head) keep density
    dtype: str = "float32"
    ragged: bool = False        # row 0 stops at ~s/2 (right-pad invalid)
    empty_head: bool = False    # kv-head 0's keep-set emptied entirely
    grow: int = 0               # dense-tail blocks appended post-prefill
    width: Optional[int] = None  # static table width cap W
    seed: int = 0


CASES: Tuple[Case, ...] = (
    Case("gqa2", h=8, hkv=4, seed=1),
    Case("gqa4", h=8, hkv=2, seed=2),
    Case("gqa8_single_kv_head", h=8, hkv=1, seed=3),
    Case("mha", h=4, hkv=4, seed=4),
    Case("ragged_prompts", ragged=True, seed=5),
    Case("empty_keep_head", empty_head=True, seed=6),
    Case("bf16", dtype="bfloat16", seed=7),
    Case("grow_cache_ragged", grow=2, ragged=True, seed=8),
    Case("width_capped", width=2, seed=9),
    Case("dense_keep", keep_p=1.0, seed=10),
)

# cases whose kv heads split into 2 whole-GQA-group shards (the subprocess
# mesh tier skips the rest — head_shard_count falls back to 1 there)
SHARDABLE = tuple(c for c in CASES if c.hkv % 2 == 0 and c.h % 2 == 0)


class CaseData(NamedTuple):
    q: jnp.ndarray              # (B, H, D)
    cache_k: jnp.ndarray        # (B, Hkv, S, D)
    cache_v: jnp.ndarray        # (B, Hkv, S, D)
    plan: DecodePlan            # one layer's (B, Hkv, …) slice
    valid: jnp.ndarray          # (B, S) bool


def build_case(case: Case) -> CaseData:
    ks = jax.random.split(jax.random.PRNGKey(case.seed), 4)
    dtype = jnp.dtype(case.dtype)
    g, nb = case.h // case.hkv, case.s // case.bs
    q = jax.random.normal(ks[0], (case.b, case.h, case.d),
                          jnp.float32).astype(dtype)
    ck = jax.random.normal(ks[1], (case.b, case.hkv, case.s, case.d),
                           jnp.float32).astype(dtype)
    cv = jax.random.normal(ks[2], (case.b, case.hkv, case.s, case.d),
                           jnp.float32).astype(dtype)
    keep = jax.random.bernoulli(ks[3], case.keep_p,
                                (case.b, case.hkv, nb, g))
    keep = keep.at[:, :, -1, :].set(True)        # final block always kept
    if case.empty_head:
        keep = keep.at[:, 0].set(False)
    if case.width is not None:
        union = cap_block_mask(jnp.any(keep, axis=-1), case.width)
        keep = keep & union[..., None]

    s = case.s
    if case.grow:                                # post-prefill dense tail
        extra = case.grow * case.bs
        ck = jnp.pad(ck, ((0, 0), (0, 0), (0, extra), (0, 0)))
        cv = jnp.pad(cv, ((0, 0), (0, 0), (0, extra), (0, 0)))
        keep = jnp.concatenate(
            [keep, jnp.ones((case.b, case.hkv, case.grow, g), bool)], axis=2)
        s = case.s + extra

    # decode position: last slot, or inside the grown tail
    pos = s - 2 if case.grow else s - 1
    slots = jnp.arange(s)[None, :]
    if case.ragged:
        plens = jnp.asarray([case.s // 2 + 3] + [case.s] * (case.b - 1))
        valid = ((slots <= pos)
                 & ((slots < plens[:, None]) | (slots >= case.s)))
    else:
        valid = jnp.broadcast_to(slots <= pos, (case.b, s))

    indices, counts = compact_block_mask(jnp.any(keep, axis=-1),
                                         width=case.width)
    return CaseData(q, ck, cv, DecodePlan(indices, counts, keep), valid)


def dense_reference(q, cache_k, cache_v, keep_heads, valid) -> jnp.ndarray:
    """Token-level masked-softmax oracle for the DecodePlan semantics.
    Query rows with no visible key emit zeros (the kernel contract)."""
    b, h, d = q.shape
    hkv, s = cache_k.shape[1], cache_k.shape[2]
    g = h // hkv
    nb = keep_heads.shape[2]
    kx = jnp.repeat(cache_k, g, axis=1)
    vx = jnp.repeat(cache_v, g, axis=1)
    logits = jnp.einsum("bhd,bhsd->bhs", jnp.asarray(q, jnp.float32),
                        jnp.asarray(kx, jnp.float32)) / (d ** 0.5)
    km = jnp.repeat(jnp.moveaxis(keep_heads, -1, -2), s // nb,
                    axis=-1).reshape(b, h, s)
    ok = km & valid[:, None, :]
    logits = jnp.where(ok, logits, -jnp.inf)
    m = jnp.max(logits, -1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(ok, jnp.exp(logits - m), 0.0)
    denom = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhs,bhsd->bhd", p / denom,
                      jnp.asarray(vx, jnp.float32))


def _tol(case: Case) -> float:
    return 2e-2 if case.dtype == "bfloat16" else 2e-5


def _run(data: CaseData, impl: str) -> jnp.ndarray:
    # the Pallas kernel runs through the interpreter on CPU (same program
    # the TPU compiles); einsum is the off-TPU serving fallback
    return flash_decode_plan(data.q, data.cache_k, data.cache_v, data.plan,
                             data.valid, impl=impl,
                             interpret=True if impl == "kernel" else None)


# --------------------------------------------------------------------------
# Conformance: every backend vs the dense reference, per case
# --------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["kernel", "einsum"])
@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_flash_decode_plan_matches_reference(case, impl):
    data = build_case(case)
    out = _run(data, impl)
    ref = dense_reference(data.q, data.cache_k, data.cache_v,
                          data.plan.keep_heads, data.valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(case), rtol=_tol(case))
    if case.empty_head:
        g = case.h // case.hkv
        og = np.asarray(out, np.float32).reshape(case.b, case.hkv, g, case.d)
        assert int(data.plan.counts[0, 0]) == 0
        assert (og[:, 0] == 0).all()            # exact-zero contract


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_backends_agree(case):
    data = build_case(case)
    out_k = _run(data, "kernel")
    out_e = _run(data, "einsum")
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_e, np.float32),
                               atol=_tol(case), rtol=_tol(case))
    out_a = _run(data, "auto")
    assert np.asarray(out_a).shape == np.asarray(out_k).shape


def test_full_keep_matches_dense_flash_decode():
    """With a full keep-set the plan path equals the dense-grid
    single-sample kernel (fp tolerance)."""
    data = build_case(Case("dense", keep_p=1.0, seed=10))
    keep = jnp.ones_like(data.plan.keep_heads)
    idx, cnt = compact_block_mask(jnp.any(keep, axis=-1))
    out = flash_decode_plan(data.q, data.cache_k, data.cache_v,
                            DecodePlan(idx, cnt, keep), data.valid,
                            impl="kernel", interpret=True)
    b, h = data.q.shape[:2]
    s = data.cache_k.shape[2]
    for i in range(b):
        dense = flash_decode(data.q[i], data.cache_k[i], data.cache_v[i],
                             jnp.ones((h, s), bool), block_kv=64)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(dense),
                                   atol=2e-6, rtol=2e-6)


# --------------------------------------------------------------------------
# kv-head-slice decomposability — the invariant sharded execution relies on
# --------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["kernel", "einsum"])
@pytest.mark.parametrize("case", SHARDABLE, ids=lambda c: c.name)
def test_kv_head_range_slices_match_global(case, impl):
    """Running the plan path on a kv-head slice (per-shard tables + the
    matching cache/query slice) must reproduce the global output's head
    slice **bitwise** — per-kv-head work shares nothing across heads, which
    is exactly why ``sharded_flash_decode`` equals the single-device path."""
    data = build_case(case)
    out_g = _run(data, impl)
    g = case.h // case.hkv
    half = case.hkv // 2
    for start in (0, half):
        sl = slice(start, start + half)
        qsl = slice(start * g, (start + half) * g)
        local = CaseData(
            data.q[:, qsl], data.cache_k[:, sl], data.cache_v[:, sl],
            DecodePlan(data.plan.indices[:, sl], data.plan.counts[:, sl],
                       data.plan.keep_heads[:, sl]),
            data.valid)
        out_l = _run(local, impl)
        np.testing.assert_array_equal(np.asarray(out_l),
                                      np.asarray(out_g[:, qsl]))
        ref_l = dense_reference(local.q, local.cache_k, local.cache_v,
                                local.plan.keep_heads, local.valid)
        np.testing.assert_allclose(np.asarray(out_l, np.float32),
                                   np.asarray(ref_l, np.float32),
                                   atol=_tol(case), rtol=_tol(case))


# --------------------------------------------------------------------------
# Sharded execution (forced 2-device CPU mesh, subprocess tier)
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
def test_sharded_flash_decode_bitmatches_single_device():
    """Every shardable conformance case, replayed under shard_map on a
    forced 2-device CPU mesh, bit-matches the single-device plan path —
    einsum for all cases, the interpreted Pallas kernel for one."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, numpy as np
        from repro.distributed.sharding import (head_shard_count,
                                                sharded_flash_decode)
        from repro.kernels.decode_attn import flash_decode_plan
        from test_decode_conformance import SHARDABLE, build_case

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("model",))
        for case in SHARDABLE:
            assert head_shard_count(mesh, "model", case.h, case.hkv) == 2
            data = build_case(case)
            impls = ("einsum", "kernel") if case.name == "gqa4" \\
                else ("einsum",)
            for impl in impls:
                it = True if impl == "kernel" else None
                out_s = sharded_flash_decode(
                    data.q, data.cache_k, data.cache_v, data.plan,
                    data.valid, mesh=mesh, impl=impl, interpret=it)
                out_1 = flash_decode_plan(
                    data.q, data.cache_k, data.cache_v, data.plan,
                    data.valid, impl=impl, interpret=it)
                np.testing.assert_array_equal(
                    np.asarray(out_s), np.asarray(out_1),
                    err_msg=f"case {case.name} impl {impl}")
            print(f"case {case.name}: bitwise OK ({', '.join(impls)})")
        print("SHARDED-DECODE-OK")
    """)
    res = _run_subprocess(code)
    assert res.returncode == 0, res.stderr
    assert "SHARDED-DECODE-OK" in res.stdout


@pytest.mark.subprocess
@pytest.mark.slow
def test_serving_engine_serve_under_mesh():
    """Full ServingEngine smoke on a forced 2-device CPU mesh: prefill runs
    through the shard_map'd batched prefill kernel, decode through
    sharded_flash_decode with per-shard tables (both routings asserted via
    call counters), and output tokens bit-match the unmeshed serve."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, numpy as np
        from repro.configs import get_smoke_config
        from repro.data import DataConfig, sample
        from repro.distributed import sharding as dsh
        from repro.models import attention as attn_mod
        from repro.models import build_model
        from repro.serving import EngineConfig, Request, ServingEngine
        from repro.serving import decode_plan as dplan

        calls = {"prefill": 0, "decode": 0, "plan": 0}
        orig_prefill = dsh.sharded_batched_block_sparse_attention
        orig_decode = attn_mod.sharded_flash_decode
        orig_plan = dplan.build_sharded_decode_plan

        def count_prefill(*a, **kw):
            calls["prefill"] += 1
            return orig_prefill(*a, **kw)

        def count_decode(*a, **kw):
            calls["decode"] += 1
            return orig_decode(*a, **kw)

        def count_plan(*a, **kw):
            calls["plan"] += 1
            return orig_plan(*a, **kw)

        dsh.sharded_batched_block_sparse_attention = count_prefill
        attn_mod.sharded_flash_decode = count_decode
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
                decode_sparse=True))
            reqs = [Request(uid=i, prompt=sample(dcfg, 7 + i)["tokens"],
                            max_new_tokens=5) for i in range(2)]
            if meshed:
                from repro.launch.mesh import make_mesh
                mesh = make_mesh((1, 2), ("data", "model"))
                with dsh.use_rules(dsh.ShardingRules(mesh)), mesh:
                    engine.serve(reqs)
            else:
                engine.serve(reqs)
            return np.stack([r.output_tokens for r in reqs])

        t_plain = serve(False)
        assert calls == {"prefill": 0, "decode": 0, "plan": 0}, calls
        t_mesh = serve(True)
        assert calls["prefill"] >= 1, calls     # prefill under shard_map
        assert calls["decode"] >= 1, calls      # decode under shard_map
        assert calls["plan"] == 1, calls        # per-shard tables, once
        np.testing.assert_array_equal(t_mesh, t_plain)
        print("SERVE-UNDER-MESH-OK", calls)
    """)
    res = _run_subprocess(code)
    assert res.returncode == 0, res.stderr
    assert "SERVE-UNDER-MESH-OK" in res.stdout
