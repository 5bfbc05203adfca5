"""GQA attention layer: train / prefill / decode paths.

The prefill path is where the paper lives: ``method`` selects the pattern
policy — ``dense`` (FlashAttention-2 semantics), ``share`` (SharePrefill),
``vertical_slash`` (MInference default config) or ``flex`` (FlexPrefill) —
all consuming the same block-sparse attention implementation so comparisons
isolate the pattern policy (paper §6.1).  ``attn_impl`` selects that
implementation: ``auto`` (default — the block-skipping Pallas kernel
compiled on TPU, dense chunked elsewhere), ``sparse`` (the kernel
unconditionally, interpret mode off-TPU), ``chunked`` (dense pure-JAX),
``ref`` / ``kernel`` (validation pins).  Sparse prefill consumes K/V
un-expanded — ``(B, Hkv, N, D)`` — end to end.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import baselines
from repro.core import share_attention as sa
from repro.core.api import SharePrefill
from repro.core.patterns import (
    block_mask_density,
    causal_block_mask,
    sliding_window_block_mask,
)
from repro.distributed.sharding import (
    active_model_mesh,
    shard,
    shardable_model_mesh,
    sharded_flash_decode,
    sharded_flash_decode_paged,
)
from repro.kernels import batched_sparse_attention_fn, sparse_attention_fn
from repro.kernels.chunked import chunked_attention, chunked_attention_fn
from repro.kernels.decode_attn import (DecodePlan, flash_decode_plan,
                                       flash_decode_plan_paged)
from repro.kernels.indices import cap_block_mask
from repro.kernels.ops import make_attention_fn
from repro.kernels.ref import decode_attention_ref
from repro.models import common

PREFILL_METHODS = ("dense", "share", "vertical_slash", "flex")
PREFILL_ATTN_IMPLS = ("auto", "sparse", "chunked", "ref", "kernel")


def resolved_attn_impl(attn_impl: str, backend: Optional[str] = None) -> str:
    """Resolve ``auto`` to the concrete prefill backend for ``backend``
    (default: this process's ``jax.default_backend()``).

    The AOT dry-run uses the explicit ``backend`` form to compare what its
    forced-host-CPU lowering ran against what production TPUs run.
    """
    if attn_impl == "auto":
        backend = backend if backend is not None else jax.default_backend()
        return "sparse" if backend == "tpu" else "chunked"
    if attn_impl not in PREFILL_ATTN_IMPLS:
        raise ValueError(f"unknown attn_impl {attn_impl!r}; "
                         f"expected one of {PREFILL_ATTN_IMPLS}")
    return attn_impl


def resolve_attention_fn(attn_impl: str, block_size: int,
                         width: Optional[int] = None) -> sa.AttentionFn:
    """Map an ``attn_impl`` name to an AttentionFn backend.

    ``auto`` is the serving-safe policy: the compiled sparse kernel on TPU,
    dense chunked elsewhere — jitting the Pallas *interpreter* at large
    sequence lengths unrolls its grid into the HLO, so interpret mode stays
    a validation tool unless asked for explicitly via ``sparse``.

    ``sparse`` resolves to the **batch-native** count-aware kernel
    (:func:`repro.kernels.batched_sparse_attention_fn`): one ``(B, T, H)``
    grid for the whole batch instead of ``jax.vmap`` replaying B
    single-sample programs.  When a sharding-rules context with a non-trivial
    ``model`` mesh axis is active, the kernel additionally runs under
    ``shard_map`` with the index tables built per head-shard.

    ``width`` forwards the static per-row block budget W (see
    :mod:`repro.kernels.indices`).  The sparse kernel consumes it natively
    (table truncation); every other backend applies the numerically
    identical boolean cap so capped results agree across backends.
    """
    attn_impl = resolved_attn_impl(attn_impl)
    if attn_impl == "sparse":
        # mesh-active routing rule (shared with sparse decode — see
        # repro.distributed.sharding.active_model_mesh)
        return batched_sparse_attention_fn(block_size=block_size,
                                           width=width,
                                           mesh=active_model_mesh())
    if attn_impl == "kernel":
        base = make_attention_fn(block_size=block_size, impl="kernel")
    elif attn_impl == "ref":
        base = make_attention_fn(block_size=block_size, impl="ref")
    else:                                   # "chunked"
        base = chunked_attention_fn(block_size=block_size)
    if width is None:
        return base
    return lambda q, k, v, masks: base(q, k, v, cap_block_mask(masks, width))


class AttnStats(NamedTuple):
    num_shared: jnp.ndarray
    num_dense: jnp.ndarray
    num_vs: jnp.ndarray
    block_density: jnp.ndarray
    # max kept blocks in any (head, q-block) mask row — the observable the
    # count-aware width policy resolves W from (serving/width_policy.py)
    max_row_pop: jnp.ndarray

    @staticmethod
    def zero() -> "AttnStats":
        z = jnp.zeros(())
        return AttnStats(z, z, z, jnp.ones(()), z)

    @staticmethod
    def reduce_layers(stats: "AttnStats") -> "AttnStats":
        """Collapse a scanned (L, …) stats pytree: means, except
        ``max_row_pop`` (a bound — max over layers)."""
        means = AttnStats(*(jnp.mean(f) for f in stats))
        return means._replace(max_row_pop=jnp.max(stats.max_row_pop))


def init_attention_layer(key: jax.Array, cfg: ModelConfig,
                         dtype=jnp.float32):
    return common.init_gqa_proj(
        key, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
        cfg.resolved_head_dim, dtype)


def rope_qk(q, k, positions, cfg: ModelConfig):
    """Rotate q/k by (M-)RoPE. positions: (B, S) or (3, B, S) for M-RoPE."""
    if cfg.vlm.enabled and positions.ndim == 3:
        rot = lambda x: common.apply_mrope(
            x, positions[:, :, None, :], cfg.rope_theta,
            cfg.vlm.mrope_sections)
        # x is (B, H, S, D); positions stream (3, B, 1, S) broadcasts over H
        return rot(q), rot(k)
    pos = positions[:, None, :]          # (B, 1, S) broadcast over heads
    rot = lambda x: common.apply_rope(x, pos, cfg.rope_theta)
    return rot(q), rot(k)


# back-compat alias (callers should migrate to the public name)
_rope_qk = rope_qk


# --------------------------------------------------------------------------
# Train (dense or SWA, differentiable, O(N) memory)
# --------------------------------------------------------------------------

def attention_train(params, x: jnp.ndarray, cfg: ModelConfig,
                    positions: jnp.ndarray,
                    block_size: int = 128) -> jnp.ndarray:
    q, k, v = common.gqa_qkv(params, x)
    q, k = rope_qk(q, k, positions, cfg)
    kx = common.repeat_kv(k, cfg.gqa_groups)
    vx = common.repeat_kv(v, cfg.gqa_groups)
    n = x.shape[1]
    bs = min(block_size, n)
    out, _ = chunked_attention(
        q, kx, vx, block_size=bs, causal=True,
        window=cfg.sliding_window, sink=0)
    out = shard(out, "batch", "heads")
    return common.gqa_out(params, out)


# --------------------------------------------------------------------------
# Prefill (pattern policies; returns KV cache)
# --------------------------------------------------------------------------

def attention_prefill(
    params,
    x: jnp.ndarray,                     # (B, S, D)
    cfg: ModelConfig,
    positions: jnp.ndarray,
    *,
    method: str,
    sp: SharePrefill,
    sp_state,                           # batched PivotalState (or None)
    cluster_ids: Optional[jnp.ndarray],  # (H,) for this layer
    attn_impl: str = "auto",            # auto | sparse | chunked | ref | kernel
    attn_width: Optional[int] = None,   # static per-row block budget W
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray], object, AttnStats]:
    b, n, _ = x.shape
    q, k, v = common.gqa_qkv(params, x)
    q, k = rope_qk(q, k, positions, cfg)

    bs = sp.cfg.block_size if sp.cfg.enabled else 128
    bs = min(bs, n)
    use_sparse = method != "dense" and sp.applicable(n)
    nb = n // bs if n % bs == 0 else 0

    extra = None
    if cfg.sliding_window and nb:
        extra = sliding_window_block_mask(
            nb, max(cfg.sliding_window // bs, 1))

    if not use_sparse:
        kx = common.repeat_kv(k, cfg.gqa_groups)
        vx = common.repeat_kv(v, cfg.gqa_groups)
        out, _ = chunked_attention(
            q, kx, vx, block_size=bs, causal=True,
            window=cfg.sliding_window)
        out = shard(out, "batch", "heads")
        return common.gqa_out(params, out), (k, v), sp_state, AttnStats.zero()

    attention_fn = resolve_attention_fn(attn_impl, bs, width=attn_width)

    if method == "share":
        out, new_state, lstats = sa.batched_share_prefill_attention_layer(
            q, k, v, sp_state, cluster_ids, sp.cfg, attention_fn,
            extra_mask=extra)
        out = shard(out, "batch", "heads")
        stats = AttnStats(lstats.num_shared, lstats.num_dense,
                          lstats.num_vs, lstats.block_density,
                          lstats.max_row_pop)
        return common.gqa_out(params, out), (k, v), new_state, stats

    # baseline policies: build masks (GQA-grouped — K is never repeated),
    # run the same sparse attention on un-expanded K/V
    gamma = sp.cfg.gamma
    if method == "vertical_slash":
        head_mask_fn = lambda qh, kh: baselines.minference_head_mask(
            qh, kh, gamma=gamma, block_size=bs)
    elif method == "flex":
        head_mask_fn = lambda qh, kh: baselines.flexprefill_head_mask(
            qh, kh, gamma=gamma, block_size=bs)
    else:
        raise ValueError(f"unknown prefill method {method!r}")
    masks = jax.vmap(lambda qs, ks: sa.gqa_head_vmap(head_mask_fn, qs, ks)
                     )(q, k)                            # (B, H, NB, NB)
    masks = masks & causal_block_mask(nb)[None, None]
    if extra is not None:
        masks = masks & extra[None, None]
    if getattr(attention_fn, "batched", False):
        # batch-native kernel, no per-sample vmap; the baselines never
        # consume Ã, so the fused stats are gated off entirely
        out, _ = attention_fn(q, k, v, masks,
                              stats_gate=jnp.zeros(masks.shape[:2],
                                                   jnp.int32))
    else:
        out, _ = jax.vmap(attention_fn)(q, k, v, masks)
    out = shard(out, "batch", "heads")
    h = q.shape[1]
    stats = AttnStats(jnp.zeros(()), jnp.zeros(()),
                      jnp.asarray(float(h)),
                      jnp.mean(block_mask_density(masks)),
                      jnp.max(jnp.sum(masks.astype(jnp.float32), axis=-1)))
    return common.gqa_out(params, out), (k, v), sp_state, stats


# --------------------------------------------------------------------------
# Decode (1 token vs a KV cache)
# --------------------------------------------------------------------------

def attention_decode(
    params,
    x: jnp.ndarray,                     # (B, 1, D)
    cfg: ModelConfig,
    cache_k: jnp.ndarray,               # (B, Hkv, S, hd)
    cache_v: jnp.ndarray,
    pos: jnp.ndarray,                   # scalar int32 write index, or (B,)
                                        # per-slot indices (continuous
                                        # batching: every row has its own
                                        # decode position)
    positions: jnp.ndarray,             # (B, 1) or (3, B, 1) rope positions
    *,
    window: int = 0,
    sink: int = 0,
    valid_mask: Optional[jnp.ndarray] = None,   # (S,) or (B, S) slot validity
    plan: Optional[DecodePlan] = None,  # one layer's sparse-decode tables
    decode_impl: str = "auto",          # auto | kernel | einsum
    page_table: Optional[jnp.ndarray] = None,   # (B, NB) block-paged cache
    pool_layer: Optional[jnp.ndarray] = None,   # this layer's index into
                                        # whole (L, P, Hkv, ps, hd) pools
    return_q: bool = False,             # also return this step's (B, H, hd)
                                        # post-rope query vectors
) -> Tuple[jnp.ndarray, ...]:
    """One decode step against the KV cache.

    ``pos`` is the cache write index — a scalar for the batch-at-a-time
    path (every row decodes in lockstep) or a ``(B,)`` vector for the
    slot-based continuous-batching scheduler (each slot is at its own
    position, so the write and the slot-validity mask are per-row).
    ``valid_mask`` carries per-request cache-slot validity (length ∧ ragged
    right-pad); when None, every slot ≤ ``pos`` (per-row for vector pos) is
    visible.  ``plan`` enables decode-phase pattern sharing: the step
    consumes prebuilt O(B·Hkv·NB) splash tables (built once per batch by
    ``repro.serving.decode_plan`` and spliced per slot in-flight by the
    scheduler), dispatched by ``decode_impl`` — the compiled block-skipping
    Pallas kernel on TPU, the grouped einsum elsewhere.

    ``page_table`` switches the cache contract to the block-paged pool:
    ``cache_k``/``cache_v`` are then one layer's shared page-pool slice
    ``(P, Hkv, page_size, hd)`` — or, with ``pool_layer``, the whole
    ``(L, P, Hkv, page_size, hd)`` pools the dense decode loop carries,
    addressed at that layer — and the table maps each slot's logical
    block to its page.  The token append rewrites each slot's current
    page (no whole-row copies), and attention walks the pool via the
    page-aware kernel twins.  Paged decode is a continuous-batching
    contract: ``pos`` must be the per-slot vector.

    ``return_q`` appends this step's post-rope query vectors ``(B, H,
    hd)`` to the return tuple — the observable the decode-time pattern
    refresh accumulates into its recent-query window (the strip kernel
    re-scores the cache against exactly these vectors).  Default off: the
    2-tuple contract and its compiled programs are untouched.
    """
    b, _, _ = x.shape
    q, k, v = common.gqa_qkv(params, x)
    q, k = rope_qk(q, k, positions, cfg)
    ret = ((lambda o, c: (o, c, q[:, :, 0, :])) if return_q
           else (lambda o, c: (o, c)))

    if page_table is not None:
        return ret(*_attention_decode_paged(
            params, cfg, q, k, v, cache_k, cache_v, pos, page_table,
            window=window, sink=sink, valid_mask=valid_mask, plan=plan,
            decode_impl=decode_impl, layer=pool_layer))

    s = cache_k.shape[2]
    if jnp.ndim(pos):                   # per-slot positions: per-row writes
        upd = lambda c, u, p: jax.lax.dynamic_update_slice_in_dim(
            c, u, p, axis=1)            # row-local seq axis
        cache_k = jax.vmap(upd)(cache_k, k, pos)
        cache_v = jax.vmap(upd)(cache_v, v, pos)
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, pos,
                                                      axis=2)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, pos,
                                                      axis=2)
    # keep head_dim model-sharded when kv_heads cannot shard ("heads" is
    # skipped by the dedupe if "kv_heads" already took the model axis) —
    # forcing hd replication here costs a 30 GB/device cache all-gather
    # (§Perf iteration 3).
    cache_k = shard(cache_k, "batch", "kv_heads", "seq", "heads")
    cache_v = shard(cache_v, "batch", "kv_heads", "seq", "heads")

    # (B, 1) column view of pos: broadcasting makes every mask term below
    # per-row, whether pos is the lockstep scalar or the per-slot vector
    pcol = pos[:, None] if jnp.ndim(pos) else pos
    if valid_mask is None:
        mask = jnp.broadcast_to(jnp.arange(s)[None, :] <= pcol, (b, s))
    else:
        mask = (valid_mask[None] if valid_mask.ndim == 1
                else valid_mask)                 # (B, S)
    if window > 0:
        pos_idx = jnp.arange(s)[None, :]
        mask = mask & (((pos_idx > pcol - window) & (pos_idx <= pcol))
                       | (pos_idx < sink))
        mask = jnp.broadcast_to(mask, (b, s))

    hkv = cache_k.shape[1]

    if plan is not None:
        # decode-phase pattern sharing (beyond paper): stream only the
        # keep-set's kv blocks through the batched flash-decode kernel.
        # Mesh-active routing rule (same predicate as resolve_attention_fn's
        # prefill routing): under a sharding-rules context with a
        # non-trivial "model" axis that the head counts divide, run the
        # heads-sharded shard_map twin with per-shard tables.  Only the
        # dense/vlm/moe GQA caches ever carry a plan — MLA latent caches and
        # the hybrid ring-buffer layouts decode densely and never reach this
        # dispatch (the documented carve-out; see ServingEngine.
        # _supports_sparse_decode).
        mesh = shardable_model_mesh(q.shape[1], hkv)
        if mesh is not None:
            out = sharded_flash_decode(q.squeeze(2), cache_k, cache_v, plan,
                                       mask, mesh=mesh, impl=decode_impl)
        else:
            out = flash_decode_plan(q.squeeze(2), cache_k, cache_v, plan,
                                    mask, impl=decode_impl)
        out = out[:, :, None, :]                  # (B, H, 1, hd)
        return ret(common.gqa_out(params, out), (cache_k, cache_v))

    out = dense_decode(q.squeeze(2), cache_k, cache_v, mask)
    out = jnp.asarray(out, x.dtype)[:, :, None, :]      # (B, H, 1, hd)
    return ret(common.gqa_out(params, out), (cache_k, cache_v))


def _attention_decode_paged(params, cfg, q, k, v, pool_k, pool_v, pos,
                            page_table, *, window, sink, valid_mask, plan,
                            decode_impl, layer=None):
    """Block-paged half of :func:`attention_decode` (post-QKV/rope).

    The pools are one layer's ``(P, Hkv, ps, hd)`` slice, or with
    ``layer`` the whole ``(L, P, Hkv, ps, hd)`` pools that the dense
    decode loop carries (and the engine donates), so the step updates
    them in place.  The append is written per page: each slot's current
    page — its logical block resolved through the table — is read, the
    token's ``(Hkv, hd)`` K/V set at ``pos % page_size`` inside it, and
    the whole page written back.  A page write keeps the pool's storage
    layout, where a single-sliver scatter into the carried pool makes
    XLA relayout all of it.  Only the slot's own current page changes
    (inert slots: the null page or their frozen tail), so slots are
    bitwise independent; inert slots that share the null page may
    overwrite each other's page write there, and nothing reads the null
    page unmasked.  Attention then walks the pool through the
    page-aware kernel twins (sparse plan), or reads the resident pages
    as ``(B, NB, Hkv, ps, hd)`` and contracts over them (dense), with
    all masks/tables kept in *logical* slot coordinates over the virtual
    length ``NB·page_size``.
    """
    b = q.shape[0]
    ps = pool_k.shape[-2]
    sv = page_table.shape[1] * ps
    if not jnp.ndim(pos):
        raise ValueError("paged decode requires per-slot (vector) pos")
    lead = () if layer is None else (layer,)
    pool_k = paged_append(pool_k, k, page_table, pos, lead)
    pool_v = paged_append(pool_v, v, page_table, pos, lead)
    # pool layout ([L,] P, Hkv, ps, hd): heads axis shards exactly like the
    # contiguous cache's; pages replicate across the batch by construction
    axes = (None,) * len(lead) + (None, "kv_heads", None, "heads")
    pool_k = shard(pool_k, *axes)
    pool_v = shard(pool_v, *axes)

    pcol = pos[:, None]
    if valid_mask is None:
        mask = jnp.broadcast_to(jnp.arange(sv)[None, :] <= pcol, (b, sv))
    else:
        mask = (valid_mask[None] if valid_mask.ndim == 1 else valid_mask)
    if window > 0:
        pos_idx = jnp.arange(sv)[None, :]
        mask = mask & (((pos_idx > pcol - window) & (pos_idx <= pcol))
                       | (pos_idx < sink))
        mask = jnp.broadcast_to(mask, (b, sv))

    if plan is not None:
        mesh = shardable_model_mesh(q.shape[1], pool_k.shape[1])
        if mesh is not None:
            out = sharded_flash_decode_paged(
                q.squeeze(2), pool_k, pool_v, page_table, plan, mask,
                mesh=mesh, impl=decode_impl)
        else:
            out = flash_decode_plan_paged(
                q.squeeze(2), pool_k, pool_v, page_table, plan, mask,
                impl=decode_impl)
        out = out[:, :, None, :]                  # (B, H, 1, hd)
        return common.gqa_out(params, out), (pool_k, pool_v)

    # dense paged decode: read the resident pages in their storage layout
    out = dense_decode(q.squeeze(2), paged_pages(pool_k, page_table, lead),
                       paged_pages(pool_v, page_table, lead), mask)
    out = jnp.asarray(out, q.dtype)[:, :, None, :]      # (B, H, 1, hd)
    return common.gqa_out(params, out), (pool_k, pool_v)


def paged_append(pool, new, page_table, pos, lead=()):
    """Write one token per slot into its current page: ``new`` is ``(B,
    Hkv, 1, hd)``, ``pool`` ``(*lead_dims, P, Hkv, ps, hd)`` addressed at
    ``lead``.  Each slot's page (its logical block ``pos // ps`` through
    the table) is read, the token set at ``pos % ps`` inside it, and the
    whole page written back, which keeps the pool's storage layout."""
    rows = jnp.arange(new.shape[0])
    cur = tuple(lead) + (page_table[rows, pos // pool.shape[-2]],)
    page = pool[cur]                                # (B, Hkv, ps, hd)
    page = page.at[rows, :, pos % pool.shape[-2]].set(
        new[:, :, 0, :].astype(pool.dtype))
    return pool.at[cur].set(page)


def paged_pages(pool, page_table, lead=()):
    """The slots' resident pages ``(B, NB, Hkv, ps, hd)`` in their storage
    layout (the allocator only hands out valid page ids, so no bounds
    fill)."""
    return pool.at[tuple(lead) + (page_table,)].get(
        mode="promise_in_bounds")


def dense_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 mask: jnp.ndarray,
                 scale: Optional[float] = None) -> jnp.ndarray:
    """Dense attention of one query token per row against grouped K/V.

    ``q`` is ``(B, H, hd)``; ``k``/``v`` are a contiguous ``(B, Hkv, S,
    hd)`` cache, or resident pages ``(B, NB, Hkv, ps, hd)`` as the pool
    stores them, whose key axis is (page, within-page) with ``S = NB·ps``;
    ``mask`` is ``(B, S)``.  Query heads fold into (kv_head, group) and
    contract against the grouped K/V directly — HBM traffic is the cache
    once, not ×groups, and pages are never moved into a contiguous view —
    accumulating in f32 via preferred_element_type instead of casting the
    cache (an f32 cache copy would be hoisted to full stacked shape).
    ``scale`` defaults to ``hd ** -0.5``; ``v`` may be wider than the
    output needs (the latent MQA passes its key rows as values).
    Returns f32 ``(B, H, hd_v)``.
    """
    b, h, hd = q.shape
    paged = k.ndim == 5
    hkv = k.shape[2] if paged else k.shape[1]
    g = h // hkv
    kv, keys = ("bnkpd", "np") if paged else ("bksd", "s")
    key_shape = (k.shape[1], k.shape[3]) if paged else (k.shape[2],)
    qg = q.reshape(b, hkv, g, hd)
    logits = jnp.einsum(f"bkgd,{kv}->bkg{keys}", qg, k,
                        preferred_element_type=jnp.float32)
    scale = (1.0 / (hd ** 0.5)) if scale is None else scale
    logits = logits.reshape(b, hkv, g, -1) * scale
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.asarray(p, v.dtype).reshape((b, hkv, g) + key_shape)
    out = jnp.einsum(f"bkg{keys},{kv}->bkgd", p, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, v.shape[-1])
