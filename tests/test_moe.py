"""MoE dispatch invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # container may lack it; skip, don't error
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.models.moe import _capacity, _group_size, init_moe_layer, moe_apply

CFG = get_smoke_config("mixtral-8x22b")
KEY = jax.random.PRNGKey(0)


def test_moe_output_shape_finite():
    params = init_moe_layer(KEY, CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, CFG.d_model))
    y, aux = moe_apply(params, x, CFG)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux.load_balance_loss) >= 1.0 - 1e-5   # ≥ 1 by Cauchy-Schwarz
    np.testing.assert_allclose(float(aux.expert_load.sum()),
                               np.asarray(aux.expert_load).sum())


def test_moe_capacity_drops_tokens_gracefully():
    """With capacity_factor → tiny, most tokens are dropped but outputs stay
    finite (dropped tokens pass through the residual stream)."""
    cfg = dataclasses.replace(
        CFG, moe=dataclasses.replace(CFG.moe, capacity_factor=0.01))
    params = init_moe_layer(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 64, cfg.d_model))
    y, _ = moe_apply(params, x, cfg)
    assert np.isfinite(np.asarray(y)).all()
    # with C = top_k minimum, output magnitude is much smaller than input
    assert float(jnp.mean(jnp.abs(y))) < float(jnp.mean(jnp.abs(x)))


def test_moe_router_determinism():
    params = init_moe_layer(KEY, CFG)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 32, CFG.d_model))
    y1, _ = moe_apply(params, x, CFG)
    y2, _ = moe_apply(params, x, CFG)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_deepseek_shared_experts_always_active():
    """Zeroing the router must leave the shared-expert path intact."""
    cfg = get_smoke_config("deepseek-v2-236b")
    params = init_moe_layer(KEY, cfg)
    assert "shared" in params
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 32, cfg.d_model))
    params_zero = dict(params)
    params_zero["router"] = jnp.full_like(params["router"], -1e9)
    y, _ = moe_apply(params_zero, x, cfg)
    # router logits all equal → top-k still routes; instead compare against
    # shared-only output by zeroing expert weights
    params_noexp = dict(params)
    for k in ("w_gate", "w_up", "w_down"):
        params_noexp[k] = jnp.zeros_like(params[k])
    y_shared, _ = moe_apply(params_noexp, x, cfg)
    assert float(jnp.mean(jnp.abs(y_shared))) > 0.0


@given(st.integers(1, 5000))
@settings(max_examples=20, deadline=None)
def test_group_size_divides(s):
    g = _group_size(s)
    assert s % g == 0 and 1 <= g <= 2048


def test_capacity_formula():
    assert _capacity(2048, CFG) == int(
        2048 * CFG.moe.top_k * CFG.moe.capacity_factor / CFG.moe.num_experts)
    assert _capacity(1, CFG) == CFG.moe.top_k     # floor at top_k


def _held_cfg(num_held, first_held):
    from repro.configs import get_config
    from repro.configs.base import reduced_config
    cfg = reduced_config(get_config("deepseek-v2-lite"))
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=16, top_k=3, num_held=num_held,
        first_held=first_held))


def test_held_shares_sum_to_the_uncut_layer():
    """EP8 at a small size: 16 routed experts (top-3, softmax, no
    renormalization), 8 shares of 2 held experts each.  The shares'
    outputs less the shared experts, plus the shared experts once, are
    the uncut layer's output, and the uncut layer is the plain per-expert
    sum over every token's top-3 (so no assignment is dropped)."""
    from repro.models import common
    from repro.models.moe import moe_held_apply

    full_cfg = _held_cfg(0, 0)
    params = init_moe_layer(KEY, full_cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 256))
    full, rows = moe_held_apply(params, x, full_cfg)
    shared = common.mlp(params["shared"], x)

    total, taken = shared, 0
    for first in range(0, 16, 2):
        cut = {k: v[first:first + 2] if k in ("w_gate", "w_up", "w_down")
               else v for k, v in params.items()}
        y, r = moe_held_apply(cut, x, _held_cfg(2, first))
        total, taken = total + (y - shared), taken + r
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(taken).sum(-1),
                                  np.asarray(rows).sum(-1))
    assert int(np.asarray(rows).sum()) == 2 * 64 * 3       # dropless

    probs = jax.nn.softmax(x @ params["router"], -1)
    gate, idx = jax.lax.top_k(probs, 3)                     # no renorm
    plain = shared
    for e in range(16):
        w = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)[..., None]
        plain = plain + w * common.mlp(
            {k: params[k][e] for k in ("w_gate", "w_up", "w_down")}, x)
    np.testing.assert_allclose(np.asarray(full), np.asarray(plain),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("arch", ["mixtral-8x22b", "deepseek-v2-236b"])
def test_scheduler_counts_prefill_and_decode_rows(arch):
    """The paged slot scheduler counts every row routed to a held expert,
    prefill and decode, for GQA and latent-attention MoE alike: with every
    expert held, a request whose prompt fills its bucket takes top_k rows
    per MoE layer for each prompt token and each decoded token but the
    last (the last is sampled, never fed back)."""
    from repro.models import build_model
    from repro.models.transformer import num_prefix_layers
    from repro.serving import EngineConfig, Request, ServingEngine

    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    eng = ServingEngine(model, model.init(KEY), model.default_share_prefill(),
                        EngineConfig(method="dense", paged=True, max_batch=2,
                                     seq_buckets=(64,)))
    rng = np.random.default_rng(0)
    reqs = [Request(uid=u, prompt=rng.integers(0, cfg.vocab_size, 64)
                    .astype(np.int32), max_new_tokens=n)
            for u, n in ((0, 5), (1, 3))]
    eng.serve(reqs)
    moe_layers = cfg.num_layers - num_prefix_layers(cfg)
    for r in reqs:
        assert r.state == "done" and len(r.output_tokens) == r.max_new_tokens
        assert r.expert_rows.shape == (cfg.moe.num_experts,)
        assert int(r.expert_rows.sum()) == (cfg.moe.top_k * moe_layers
                                            * (64 + r.max_new_tokens - 1))
