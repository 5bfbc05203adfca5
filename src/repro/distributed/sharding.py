"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Models annotate activations/params with *logical* axis names via
:func:`shard`; a :class:`ShardingRules` context maps logical names to mesh
axes.  Outside a rules context the annotations are no-ops, so the same model
code runs unsharded on one CPU device (smoke tests) and fully sharded on the
(pod, data, model) production mesh (dry-run / launch).

The rules context also drives the **mesh-active routing rule**
(:func:`active_model_mesh`): when the context's "model" axis is non-trivial,
the serving hot paths resolve their ``shard_map`` twins automatically —
sparse prefill through :func:`sharded_batched_block_sparse_attention`,
sparse decode through :func:`sharded_flash_decode` — each building/consuming
its splash index tables per head shard, so SMEM stays O(local heads) and
outputs stay bitwise-equal to the single-device paths.

Logical axes:
  batch        DP over ("pod", "data") — training/prefill/decode batch
  seq          context parallelism — long-decode KV-cache sequence
  heads        TP over "model" — attention heads
  kv_heads     TP over "model" (GQA: may be smaller than the axis → replicate)
  embed        replicated activation feature dim
  mlp          TP over "model" — FFN hidden
  experts      expert parallelism over "model"
  vocab        TP over "model" — embedding/logits
  ssm_inner    TP over "model" — SSM/RG-LRU channel dim
  stack        layer-stack dim of scanned params (never sharded)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

DEFAULT_RULES: Dict[str, Optional[Tuple[str, ...]]] = {
    "batch": ("pod", "data"),
    "seq": ("data",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "embed": None,
    "mlp": ("model",),
    "experts": ("model",),
    "expert_cap": None,
    "vocab": ("model",),
    "ssm_inner": ("model",),
    "ssm_state": None,
    "stack": None,
    "blocks_q": None,
    "blocks_kv": None,
    "clusters": None,
}


class ShardingRules:
    def __init__(self, mesh: Mesh,
                 overrides: Optional[Dict[str, Optional[Tuple[str, ...]]]]
                 = None):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES)
        if overrides:
            self.rules.update(overrides)
        axes = set(mesh.axis_names)
        # drop mesh axes the current mesh does not have (e.g. "pod" single-pod)
        for k, v in list(self.rules.items()):
            if v is None:
                continue
            kept = tuple(a for a in v if a in axes)
            self.rules[k] = kept if kept else None

    def spec(self, *logical: Optional[str]) -> P:
        parts = []
        for name in logical:
            if name is None:
                parts.append(None)
                continue
            axes = self.rules.get(name)
            if axes is None:
                parts.append(None)
            elif len(axes) == 1:
                parts.append(axes[0])
            else:
                parts.append(axes)
        return P(*parts)

    def sharding(self, *logical: Optional[str]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical))


def current_rules() -> Optional[ShardingRules]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[ShardingRules]):
    prev = getattr(_state, "rules", None)
    _state.rules = rules
    try:
        yield rules
    finally:
        _state.rules = prev


def head_shard_count(mesh: Mesh, axis: str, num_heads: int,
                     num_kv_heads: int) -> int:
    """Usable shard count of ``axis`` for head-parallel attention: the mesh
    axis size when both head counts divide it (each shard gets whole GQA
    groups), else 1 (replicate — same fallback rule as :func:`shard`)."""
    if axis not in mesh.axis_names:
        return 1
    n = mesh.shape[axis]
    if n <= 1 or num_heads % n or num_kv_heads % n:
        return 1
    return n


def active_model_mesh(axis: str = "model") -> Optional[Mesh]:
    """The **mesh-active routing rule**, shared by sparse prefill and sparse
    decode: return the active rules context's mesh when its ``axis`` is
    non-trivial (size > 1), else None.

    Both hot paths resolve their sharded twin from this single predicate —
    :func:`repro.models.attention.resolve_attention_fn` routes the prefill
    kernel through :func:`sharded_batched_block_sparse_attention`, and
    :func:`repro.models.attention.attention_decode` routes a DecodePlan step
    through :func:`sharded_flash_decode` — so a served model runs prefill
    *and* decode under the same mesh with no per-call configuration.  Head
    counts that do not divide the axis still fall back to the single-device
    path (see :func:`head_shard_count`).
    """
    rules = current_rules()
    if rules is None or axis not in rules.mesh.axis_names:
        return None
    return rules.mesh if rules.mesh.shape[axis] > 1 else None


def shardable_model_mesh(num_heads: int, num_kv_heads: int,
                         axis: str = "model") -> Optional[Mesh]:
    """The mesh-active routing predicate with head divisibility folded in:
    the active rules context's mesh when its ``axis`` is non-trivial AND
    both head counts shard over it (whole GQA groups per shard —
    :func:`head_shard_count`), else None.

    Sparse-decode plan *construction* (``build_decode_plan_auto``) and plan
    *execution* (``attention_decode``) both resolve through this single
    helper, so a sharded-laid-out plan is always consumed by the sharded
    path and vice versa — the lockstep is structural, not copy-paste.
    """
    mesh = active_model_mesh(axis)
    if mesh is None or head_shard_count(mesh, axis, num_heads,
                                        num_kv_heads) <= 1:
        return None
    return mesh


def sharded_batched_block_sparse_attention(
    q: jax.Array,               # (B, H, N, Dqk)
    k: jax.Array,               # (B, Hkv, N, Dqk)
    v: jax.Array,               # (B, Hkv, N, Dv)
    block_mask: jax.Array,      # (B, H, NBq, NBkv) bool
    *,
    mesh: Mesh,
    axis: str = "model",
    block_size: int,
    causal: bool = True,
    width: Optional[int] = None,
    interpret: bool = True,
    stats_gate: Optional[jax.Array] = None,     # (B, H)
):
    """Heads-sharded batch-native block-sparse prefill attention.

    Runs :func:`repro.kernels.ops.batched_block_sparse_attention` under
    ``shard_map`` with every head-indexed operand partitioned over ``axis``.
    The splash ``(indices, counts)`` tables are built *inside* the shard
    body from the local mask slice, so the kernel's scalar-prefetch SMEM
    footprint is O(local heads) — a device never materializes another
    shard's tables (the multi-host table-size concern deferred since PR 1).
    Head-parallel attention has no cross-shard reductions, so outputs match
    the single-device path exactly.

    Requires ``head_shard_count(mesh, axis, H, Hkv) > 1``; callers (e.g.
    :func:`repro.kernels.batched_sparse_attention_fn`) are expected to fall
    back to the single-device path otherwise.
    """
    from repro.kernels.ops import batched_block_sparse_attention

    if head_shard_count(mesh, axis, q.shape[1], k.shape[1]) <= 1:
        raise ValueError(
            f"head counts {q.shape[1]}/{k.shape[1]} do not shard over mesh "
            f"axis {axis!r} of {mesh.shape}")
    if stats_gate is None:
        stats_gate = jnp.ones(q.shape[:2], jnp.int32)

    def body(q_l, k_l, v_l, m_l, g_l):
        return batched_block_sparse_attention(
            q_l, k_l, v_l, m_l, block_size=block_size, causal=causal,
            interpret=interpret, width=width, stats_gate=g_l)

    hs = P(None, axis)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(hs, hs, hs, hs, hs),
        out_specs=(hs, hs),
        check_vma=False,
    )(q, k, v, block_mask, stats_gate)


def sharded_compute_strips(
    q: jax.Array,               # (H, N, D)
    k: jax.Array,               # (Hkv, N, D)
    *,
    mesh: Mesh,
    axis: str = "model",
    block_size: int,
    interpret: bool,
) -> jax.Array:
    """Heads-sharded strip scores, (H, block_size, N) f32.

    Runs :func:`repro.kernels.strip.strip_scores_pallas` under
    ``shard_map`` with both head axes partitioned over ``axis``: a Mosaic
    kernel cannot be partitioned by the compiler, so under a mesh it must
    see only its local heads.  Strips are per head, so the output equals
    the single-device kernel's.
    """
    from repro.kernels.strip import strip_scores_pallas

    if head_shard_count(mesh, axis, q.shape[0], k.shape[0]) <= 1:
        raise ValueError(
            f"head counts {q.shape[0]}/{k.shape[0]} do not shard over mesh "
            f"axis {axis!r} of {mesh.shape}")
    hs = P(axis)
    return jax.shard_map(
        lambda q_l, k_l: strip_scores_pallas(q_l, k_l, block_size=block_size,
                                             interpret=interpret),
        mesh=mesh, in_specs=(hs, hs), out_specs=hs, check_vma=False,
    )(q, k)


def sharded_flash_decode(
    q: jax.Array,               # (B, H, D) one token per sequence
    cache_k: jax.Array,         # (B, Hkv, S, D)
    cache_v: jax.Array,         # (B, Hkv, S, Dv)
    plan,                       # DecodePlan, one layer's (B, Hkv, …) slice
    valid: jax.Array,           # (B, S) bool slot validity
    *,
    mesh: Mesh,
    axis: str = "model",
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Heads-sharded sparse decode over prebuilt DecodePlan tables.

    Runs :func:`repro.kernels.decode_attn.flash_decode_plan` under
    ``shard_map`` with every head-indexed operand — queries, the grouped KV
    cache, and the scalar-prefetched ``(indices, counts, keep_heads)``
    tables — partitioned over ``axis``; the slot-validity vector is
    replicated.  Each device's kernel invocation sees only its local
    kv-heads' tables, so the scalar-prefetch SMEM footprint stays O(local
    heads) — the decode analogue of
    :func:`sharded_batched_block_sparse_attention`, and the execution half
    of the per-shard tables that ``build_decode_plan(kv_head_range=...)``
    produces.  Head-parallel decode has no cross-shard reductions, so the
    output equals the single-device plan path bitwise.

    Requires ``head_shard_count(mesh, axis, H, Hkv) > 1``; callers (e.g.
    :func:`repro.models.attention.attention_decode`) fall back to the
    single-device :func:`flash_decode_plan` otherwise.  MLA latent caches
    and the hybrid ring-buffer layouts never reach this function — they
    decode densely (no DecodePlan is built for them), so the carve-out
    lives at the dispatch site, not here.

    Returns (B, H, Dv).
    """
    from repro.kernels.decode_attn import DecodePlan, flash_decode_plan

    if head_shard_count(mesh, axis, q.shape[1], cache_k.shape[1]) <= 1:
        raise ValueError(
            f"head counts {q.shape[1]}/{cache_k.shape[1]} do not shard over "
            f"mesh axis {axis!r} of {mesh.shape}")

    def body(q_l, k_l, v_l, idx_l, cnt_l, keep_l, valid_l):
        return flash_decode_plan(q_l, k_l, v_l,
                                 DecodePlan(idx_l, cnt_l, keep_l),
                                 valid_l, impl=impl, interpret=interpret)

    hs = P(None, axis)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(hs, hs, hs, hs, hs, hs, P(None, None)),
        out_specs=hs,
        check_vma=False,
    )(q, cache_k, cache_v, plan.indices, plan.counts, plan.keep_heads, valid)


def sharded_flash_decode_paged(
    q: jax.Array,               # (B, H, D) one token per slot
    pool_k: jax.Array,          # (P, Hkv, ps, D) shared page pool
    pool_v: jax.Array,          # (P, Hkv, ps, Dv)
    page_table: jax.Array,      # (B, NB) int32
    plan,                       # DecodePlan, one layer's (B, Hkv, …) slice
    valid: jax.Array,           # (B, NB·ps) bool
    *,
    mesh: Mesh,
    axis: str = "model",
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """:func:`sharded_flash_decode` over a block-paged KV cache.

    The page pool's heads axis (axis 1 of ``(P, Hkv, ps, D)``) shards over
    ``axis`` exactly like the contiguous cache's — the same ``P(None,
    axis)`` spec — while the page table and slot validity replicate: page
    residency is a per-slot property, not a per-head one.  Each device
    walks its local kv-heads' logical block tables through the (replicated)
    page table into its local pool shard; head-parallel decode has no
    cross-shard reductions, so the output equals the single-device
    :func:`repro.kernels.decode_attn.flash_decode_plan_paged` bitwise.
    """
    from repro.kernels.decode_attn import DecodePlan, flash_decode_plan_paged

    if head_shard_count(mesh, axis, q.shape[1], pool_k.shape[1]) <= 1:
        raise ValueError(
            f"head counts {q.shape[1]}/{pool_k.shape[1]} do not shard over "
            f"mesh axis {axis!r} of {mesh.shape}")

    def body(q_l, k_l, v_l, pt_l, idx_l, cnt_l, keep_l, valid_l):
        return flash_decode_plan_paged(q_l, k_l, v_l, pt_l,
                                       DecodePlan(idx_l, cnt_l, keep_l),
                                       valid_l, impl=impl,
                                       interpret=interpret)

    hs = P(None, axis)
    rep = P(None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(hs, hs, hs, rep, hs, hs, hs, rep),
        out_specs=hs,
        check_vma=False,
    )(q, pool_k, pool_v, page_table, plan.indices, plan.counts,
      plan.keep_heads, valid)


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Annotate with a sharding constraint if a rules context is active.

    ``len(logical)`` may be shorter than ``x.ndim``; missing trailing axes are
    treated as replicated.  Sizes not divisible by the mapped mesh axes fall
    back to replication for that dim (e.g. 8 kv heads on a 16-way model axis).
    """
    rules = current_rules()
    if rules is None:
        return x
    logical = tuple(logical) + (None,) * (x.ndim - len(logical))
    parts = []
    used: set = set()
    for dim, name in zip(x.shape, logical):
        if name is None:
            parts.append(None)
            continue
        axes = rules.rules.get(name)
        if axes:
            # a mesh axis may appear at most once per spec: first dim wins
            axes = tuple(a for a in axes if a not in used)
        if not axes:
            parts.append(None)
            continue
        size = 1
        for a in axes:
            size *= rules.mesh.shape[a]
        if dim % size != 0:
            parts.append(None)
        else:
            used.update(axes)
            parts.append(axes[0] if len(axes) == 1 else axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, P(*parts)))
