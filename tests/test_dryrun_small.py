"""Dry-run machinery on a small forced-device mesh (subprocess so the main
pytest process keeps its single real device), plus HLO collective parsing."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.hlo_analysis import (
    collective_bytes,
    dominant_term,
    roofline_terms,
    _shape_bytes,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_shape_bytes():
    assert _shape_bytes("f32[128,256]") == 128 * 256 * 4
    assert _shape_bytes("bf16[2,3]") == 12
    assert _shape_bytes("(f32[4], s32[2])") == 24
    assert _shape_bytes("pred[]") == 1


def test_collective_parse():
    hlo = textwrap.dedent("""\
        %ag = f32[64,128] all-gather(f32[4,128] %x), replica_groups={}
        %ar.1 = bf16[32] all-reduce(bf16[32] %y), to_apply=%add
        ROOT %out = (f32[8], f32[8]) all-to-all(f32[8] %a, f32[8] %b)
        %copy = f32[9] copy(f32[9] %z)
    """)
    c = collective_bytes(hlo)
    assert c["all-gather"]["count"] == 1
    assert c["all-gather"]["bytes"] == 64 * 128 * 4
    assert c["all-reduce"]["count"] == 1
    assert c["all-reduce"]["bytes"] == 64
    assert c["all-to-all"]["count"] == 1
    assert c["all-to-all"]["bytes"] == 64
    assert c["reduce-scatter"]["count"] == 0


def test_roofline_terms_dominance():
    coll = {"all-reduce": {"count": 1, "bytes": 1e9}}
    t = roofline_terms(flops=1e12, bytes_accessed=1e9, coll=coll, chips=4,
                       peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9)
    assert t["compute_s"] == pytest.approx(1e12 / 197e12)
    assert dominant_term(t) == "collective_s"


def test_attn_impl_parity_flags_cpu_divergence():
    """The AOT dry-run lowers on forced host-CPU devices, where
    ``attn_impl="auto"`` resolves to the chunked path — its report must flag
    that the analyzed program diverges from the sparse Pallas kernel
    production TPUs run."""
    import jax
    jax.devices()           # lock the backend before dryrun touches XLA_FLAGS
    from repro.launch.dryrun import attn_impl_parity
    from repro.models.attention import resolved_attn_impl

    assert resolved_attn_impl("auto", backend="tpu") == "sparse"
    assert resolved_attn_impl("auto", backend="cpu") == "chunked"
    assert resolved_attn_impl("chunked", backend="tpu") == "chunked"

    rec = attn_impl_parity("auto")
    assert rec["tpu_resolved"] == "sparse"
    if jax.default_backend() != "tpu":
        assert rec["resolved"] == "chunked"
        assert rec["divergent_from_tpu"] is True
    else:                                        # pragma: no cover
        assert rec["divergent_from_tpu"] is False

    # an explicitly pinned impl never diverges
    pinned = attn_impl_parity("chunked")
    assert pinned["divergent_from_tpu"] is False


@pytest.mark.slow
@pytest.mark.subprocess
def test_dryrun_pair_in_subprocess_8dev():
    """Full lower+compile of a smoke-scale arch on an 8-device forced-host
    mesh — validates the whole steps/param-spec/mesh pipeline without the
    cost of the 512-device production run."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.distributed.param_specs import param_shardings, batch_pspec
        from repro.optim import init_adamw, AdamWState
        from repro.training import TrainConfig, make_train_step
        from jax.sharding import NamedSharding

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_smoke_config("granite-3-2b")
        model = build_model(cfg)
        params_avals = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        p_shard = param_shardings(params_avals, mesh)
        params = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            params_avals, p_shard)
        opt_avals = jax.eval_shape(init_adamw, params_avals)
        o_shard = AdamWState(step=NamedSharding(mesh, P()), mu=p_shard, nu=p_shard)
        opt = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            opt_avals, o_shard)
        bspec = NamedSharding(mesh, batch_pspec(mesh, 8))
        batch = {
            "tokens": jax.ShapeDtypeStruct((8, 256), jnp.int32, sharding=bspec),
            "labels": jax.ShapeDtypeStruct((8, 256), jnp.int32, sharding=bspec),
        }
        step = make_train_step(model, TrainConfig(num_steps=10))
        with mesh:
            compiled = jax.jit(step).lower(params, opt, batch).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        assert cost.get("flops", 0) > 0
        print("SUBPROCESS_OK", int(cost.get("flops", 0)))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert "SUBPROCESS_OK" in out.stdout, out.stderr[-2000:]
