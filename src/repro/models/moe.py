"""Mixture-of-experts FFN: the serving path's dropless held-expert layer and
the training path's capacity-bucketed einsum dispatch.

:func:`moe_held_apply` (prefill and decode) routes every token over all
``num_experts`` (softmax, top-k, renormalized only where ``norm_topk``),
keeps the assignments that fall on the experts this device holds
(``MoEConfig.first_held`` onward, ``held`` of them), sorts them by expert
and runs one grouped matmul (``jax.lax.ragged_dot``) per projection over
exactly those rows: no token is dropped, and nothing stands in for the
experts other devices hold.  Shared experts are added once.

:func:`moe_apply` (training) is Mesh-TF-style dense dispatch: tokens are
routed to ``top_k`` experts, each expert has a fixed capacity, and
dispatch/combine are one-hot einsums — under GSPMD with experts sharded
over ``model`` this lowers to the all-to-all exchange (DESIGN.md §7).
Covers Mixtral (8e top-2) and DeepSeek-V2 (2 shared + 160 routed top-6).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import common


class MoEAux(NamedTuple):
    load_balance_loss: jnp.ndarray
    router_z_loss: jnp.ndarray
    expert_load: jnp.ndarray        # (E,) mean routed fraction per expert

    @staticmethod
    def zero(num_experts: int = 1) -> "MoEAux":
        return MoEAux(jnp.zeros(()), jnp.zeros(()),
                      jnp.zeros((num_experts,)))


def init_moe_layer(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32):
    mo = cfg.moe
    d = cfg.d_model
    f = mo.expert_d_ff or cfg.d_ff
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    params = {
        "router": common.dense_init(k1, (d, mo.num_experts), dtype),
        "w_gate": common.stack_init(
            lambda kk: common.dense_init(kk, (d, f), dtype), k2, mo.held),
        "w_up": common.stack_init(
            lambda kk: common.dense_init(kk, (d, f), dtype), k3, mo.held),
        "w_down": common.stack_init(
            lambda kk: common.dense_init(kk, (f, d), dtype), k4, mo.held),
    }
    if mo.num_shared_experts:
        params["shared"] = common.init_mlp(
            k5, d, f * mo.num_shared_experts, dtype)
    return params


GROUP_TOKENS = 2048     # routing-group size: dispatch memory is O(S·g·k·cf)


def _group_size(s: int) -> int:
    g = min(GROUP_TOKENS, s)
    while s % g:
        g -= 1
    return g


def _capacity(group: int, cfg: ModelConfig) -> int:
    mo = cfg.moe
    c = int(group * mo.top_k * mo.capacity_factor / mo.num_experts)
    return max(c, mo.top_k)


def moe_apply(params, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, MoEAux]:
    """x: (B, S, D) → (B, S, D), aux losses.

    Tokens are routed within fixed-size groups (Mesh-TF style) so the
    dispatch one-hots are O(groups · g · E · C) with C ∝ g/E, i.e. linear in
    sequence length — required for 32k-token prefill (DESIGN.md §7).
    """
    mo = cfg.moe
    b, s, d = x.shape
    e, k = mo.num_experts, mo.top_k
    g = _group_size(s)
    ng = (b * s) // g
    cap = _capacity(g, cfg)
    xg = x.reshape(ng, g, d)

    logits = jnp.einsum("ngd,de->nge", xg, params["router"])
    probs = jax.nn.softmax(jnp.asarray(logits, jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)             # (NG,g,K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)   # (NG,g,K,E)
    flat = onehot.reshape(ng, g * k, e)
    pos = jnp.cumsum(flat, axis=1) - flat                     # (NG,g*K,E)
    pos = jnp.einsum("nte,nte->nt", pos, flat)
    keep = pos < cap
    pos = jnp.asarray(pos, jnp.int32)

    slot_gate = gate_vals.reshape(ng, g * k) * keep
    expert_of_slot = gate_idx.reshape(ng, g * k)

    # dispatch/combine tensors live in the activation dtype: f32 one-hots
    # would promote the expert einsums and materialize an f32 copy of the
    # whole stacked expert weights (§Perf iteration 3 — 180 GB/tensor for
    # Mixtral at decode before this fix).
    adt = x.dtype
    dispatch = (jax.nn.one_hot(expert_of_slot, e, dtype=adt)[..., None]
                * jax.nn.one_hot(pos, cap, dtype=adt)[..., None, :]
                * jnp.asarray(keep, adt)[..., None, None])    # (NG,g*K,E,C)
    combine = dispatch * jnp.asarray(slot_gate, adt)[..., None, None]
    dispatch = dispatch.reshape(ng, g, k, e, cap).sum(axis=2)
    combine = combine.reshape(ng, g, k, e, cap).sum(axis=2)

    expert_in = jnp.einsum("ngec,ngd->encd", dispatch, xg)
    expert_in = shard(expert_in, "experts", "batch")
    h = (jax.nn.silu(jnp.einsum("encd,edf->encf", expert_in,
                                params["w_gate"],
                                preferred_element_type=jnp.float32))
         * jnp.einsum("encd,edf->encf", expert_in, params["w_up"],
                      preferred_element_type=jnp.float32)).astype(adt)
    # hidden sharded on experts when divisible, else on the FFN dim (the
    # dedupe in shard() keeps exactly one model-axis user)
    h = shard(h, "experts", "batch", None, "mlp")
    expert_out = jnp.einsum("encf,efd->encd", h, params["w_down"],
                            preferred_element_type=jnp.float32).astype(adt)
    y = jnp.einsum("ngec,encd->ngd", combine, expert_out).reshape(b, s, d)

    if "shared" in params:
        y = y + common.mlp(params["shared"], x)

    # aux losses (Switch-style load balance + router z-loss)
    me = jnp.mean(onehot.sum(axis=2).clip(0, 1), axis=(0, 1))  # routed frac
    ce = jnp.mean(probs, axis=(0, 1))
    lb = e * jnp.sum(me * ce)
    z = jnp.mean(jax.nn.logsumexp(jnp.asarray(logits, jnp.float32),
                                  axis=-1) ** 2)
    return jnp.asarray(y, x.dtype), MoEAux(lb, z, me)


HELD_TOKENS = 4096      # tokens routed per grouped-matmul pass: the sorted
                        # rows are O(HELD_TOKENS · top_k · d_model)


def _held_pass(params, x, cfg: ModelConfig):
    """One pass of :func:`moe_held_apply` over ``x`` (T, D): the held
    experts' weighted outputs (T, D) f32 and each token's rows per held
    expert (T, held)."""
    mo = cfg.moe
    t, d = x.shape
    k, held = mo.top_k, mo.held
    # the router scores in f32, as published
    logits = jnp.einsum("td,de->te", jnp.asarray(x, jnp.float32),
                        jnp.asarray(params["router"], jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                        # (T, K)
    if mo.norm_topk:
        gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    local = idx - mo.first_held
    mine = (local >= 0) & (local < held)
    # held assignments first, grouped by expert; the rest sort last and
    # fall outside every group
    order = jnp.argsort(jnp.where(mine, local, held).reshape(-1),
                        stable=True)
    hot = jax.nn.one_hot(jnp.where(mine, local, held), held,
                         dtype=jnp.int32)                       # (T, K, H)
    per_token = hot.sum(axis=1)
    sizes = per_token.sum(axis=0)
    tok = order // k
    rows = x[tok]                                               # (T·K, D)
    adt = x.dtype
    h = (jax.nn.silu(jax.lax.ragged_dot(rows, params["w_gate"], sizes,
                                        preferred_element_type=jnp.float32))
         * jax.lax.ragged_dot(rows, params["w_up"], sizes,
                              preferred_element_type=jnp.float32))
    out = jax.lax.ragged_dot(h.astype(adt), params["w_down"], sizes,
                             preferred_element_type=jnp.float32)
    live = jnp.arange(t * k) < jnp.sum(sizes)
    w = jnp.where(live, gate.reshape(-1)[order], 0.0)
    # rows past the groups are never written by the grouped matmul: select,
    # so whatever they hold cannot reach the sum
    y = jnp.zeros((t, d), jnp.float32).at[tok].add(
        jnp.where(live[:, None], out * w[:, None], 0.0))
    return y, per_token


def moe_held_apply(params, x: jnp.ndarray, cfg: ModelConfig
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) → (B, S, D), and the rows each held expert took per
    batch row ``(B, held)`` int32.

    Dropless: every assignment to a held expert is computed.  Long inputs
    run in passes of ``HELD_TOKENS`` tokens, so the sorted rows stay
    bounded at 32k-token prefill."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    n = b * s
    if n <= HELD_TOKENS or n % HELD_TOKENS:
        y, per_token = _held_pass(params, flat, cfg)
    else:
        y, per_token = jax.lax.map(
            lambda xc: _held_pass(params, xc, cfg),
            flat.reshape(n // HELD_TOKENS, HELD_TOKENS, d))
    y = jnp.asarray(y.reshape(b, s, d), x.dtype)
    if "shared" in params:
        y = y + common.mlp(params["shared"], x)
    return y, per_token.reshape(b, s, -1).sum(axis=1)
