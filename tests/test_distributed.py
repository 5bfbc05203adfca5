"""Sharding rules + param specs (single-device semantics; multi-device
lowering is exercised in test_dryrun_small.py via a subprocess)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.distributed.param_specs import (
    batch_pspec,
    cache_pspec,
    leaf_pspec,
    param_pspecs,
)
from repro.distributed.sharding import ShardingRules, shard, use_rules
from repro.launch.mesh import make_mesh
from repro.models import build_model


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


def test_shard_noop_without_rules():
    x = jnp.ones((4, 4))
    y = shard(x, "batch", "mlp")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_rules_drop_missing_axes():
    mesh = _mesh11()
    rules = ShardingRules(mesh)
    # "pod" not in the mesh → batch maps to data only
    assert rules.spec("batch") == P("data")


def test_leaf_pspec_rules():
    mesh = _mesh11()
    # divisible everywhere on a 1x1 mesh → named axes still assigned
    assert leaf_pspec(("stack", "attn", "wq"), (4, 256, 8, 64), mesh) \
        == P(None, "data", "model", None)
    assert leaf_pspec(("embed",), (512, 128), mesh) == P("model", "data")
    assert leaf_pspec(("ffn", "w_gate"), (4, 256, 512), mesh) \
        == P("model", "data", None)          # MoE expert stack
    assert leaf_pspec(("mlp", "w_gate"), (256, 512), mesh) \
        == P("data", "model")
    assert leaf_pspec(("ln1", "scale"), (256,), mesh) == P()


def test_leaf_pspec_divisibility_fallback():
    mesh = make_mesh((1, 1), ("data", "model"))
    big_mesh_shape = {"data": 16, "model": 16}

    class FakeMesh:
        axis_names = ("data", "model")
        shape = big_mesh_shape
    # kv heads = 8 on a 16-way model axis → replicated dim
    spec = leaf_pspec(("attn", "wk"), (256, 8, 64), FakeMesh())
    assert spec == P("data", None, None)


def test_param_pspecs_cover_all_leaves():
    cfg = get_smoke_config("deepseek-v2-236b")
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    mesh = _mesh11()
    specs = param_pspecs(shapes, mesh)
    n = len(jax.tree.leaves(shapes))
    assert len(jax.tree.leaves(specs,
                               is_leaf=lambda x: isinstance(x, P))) == n


def test_batch_pspec():
    mesh = _mesh11()
    assert batch_pspec(mesh, 4) == P("data")
    assert batch_pspec(mesh, 3) == P("data")   # 3 % 1 == 0 on 1-dev mesh

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}
    assert batch_pspec(FakeMesh(), 256) == P(("pod", "data"))
    assert batch_pspec(FakeMesh(), 1) == P()


def test_cache_pspec_long_decode_context_parallel():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    # batch=1 dense KV cache (L, B, Hkv, S, hd): seq gets the data axis
    spec = cache_pspec((40, 1, 8, 524288, 128), FakeMesh(), batch=1,
                       stacked=True)
    assert spec[3] == "data"                   # context parallel
    assert spec[4] == "model"                  # head_dim (Hkv=8 % 16 != 0)


def test_cache_pspec_batched_decode():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    spec = cache_pspec((40, 128, 16, 32768, 128), FakeMesh(), batch=128,
                       stacked=True)
    assert spec[1] == "data"
    assert spec[2] == "model"                  # kv heads divisible here


def test_end_to_end_sharded_forward_single_device():
    """Rules context + constraints must be no-ops semantically."""
    cfg = get_smoke_config("granite-3-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg.vocab_size)
    plain, _ = model.train_logits(params, tokens)
    mesh = _mesh11()
    with use_rules(ShardingRules(mesh)):
        with mesh:
            sharded, _ = jax.jit(
                lambda p, t: model.train_logits(p, t))(params, tokens)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(sharded),
                               atol=1e-5, rtol=1e-5)
