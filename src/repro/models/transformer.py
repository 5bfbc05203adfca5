"""Generic decoder-only transformer stack (dense / VLM / MoE / MLA families).

Layers are scanned (``lax.scan`` over stacked params) so an 88-layer model
lowers to one compact HLO loop; heterogeneous prefixes (DeepSeek-V2's dense
first layer) are applied unscanned before the stack.  The prefill path
threads the SharePrefill pivotal-pattern state through the scan carry —
exactly the paper's layer-by-layer dictionary evolution (DESIGN.md §4).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.api import SharePrefill
from repro.distributed.sharding import shard
from repro.models import attention as attn
from repro.models import common, mla, moe


class PrefillResult(NamedTuple):
    last_logits: jnp.ndarray        # (B, V)
    cache: Any
    stats: attn.AttnStats
    sp_state: Any
    # rows each held expert took per batch row, summed over MoE layers
    # (B, held); None where no layer routes
    expert_rows: Any = None


def _uses_mla(cfg: ModelConfig) -> bool:
    return cfg.mla.enabled


def _uses_moe(cfg: ModelConfig) -> bool:
    return cfg.moe.enabled


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def init_layer(key: jax.Array, cfg: ModelConfig, *, moe_ffn: bool,
               dtype=jnp.float32) -> Dict:
    k1, k2 = jax.random.split(key)
    if _uses_mla(cfg):
        a = mla.init_mla_layer(k1, cfg, dtype)
    else:
        a = attn.init_attention_layer(k1, cfg, dtype)
    ffn = (moe.init_moe_layer(k2, cfg, dtype) if moe_ffn
           else common.init_mlp(k2, cfg.d_model, cfg.d_ff, dtype))
    return {
        "attn": a,
        "ffn": ffn,
        "ln1": common.init_rmsnorm(cfg.d_model, dtype),
        "ln2": common.init_rmsnorm(cfg.d_model, dtype),
    }


def num_prefix_layers(cfg: ModelConfig) -> int:
    """DeepSeek-V2: first layer uses a dense FFN; everything else scans."""
    return 1 if (_uses_moe(cfg) and cfg.mla.enabled) else 0


def init_decoder_params(key: jax.Array, cfg: ModelConfig,
                        dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 5)
    n_prefix = num_prefix_layers(cfg)
    n_stack = cfg.num_layers - n_prefix
    params = {
        "embed": common.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": common.init_rmsnorm(cfg.d_model, dtype),
        "stack": common.stack_init(
            lambda kk: init_layer(kk, cfg, moe_ffn=_uses_moe(cfg),
                                  dtype=dtype),
            ks[1], n_stack),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = common.dense_init(
            ks[2], (cfg.d_model, cfg.vocab_size), dtype)
    for i in range(n_prefix):
        params[f"prefix_{i}"] = init_layer(
            jax.random.fold_in(ks[3], i), cfg, moe_ffn=False, dtype=dtype)
    return params


def logits_from_hidden(params, cfg: ModelConfig, x: jnp.ndarray
                       ) -> jnp.ndarray:
    x = common.rmsnorm(params["final_norm"], x, cfg.rms_norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embed"])
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["lm_head"])
    return shard(logits, "batch", None, "vocab")


def embed_tokens(params, cfg: ModelConfig, tokens: jnp.ndarray):
    x = jnp.take(params["embed"], tokens, axis=0)
    return shard(x, "batch")


# --------------------------------------------------------------------------
# Per-layer bodies
# --------------------------------------------------------------------------

def _ffn_apply(layer, x, cfg: ModelConfig, moe_ffn: bool):
    if moe_ffn:
        y, aux = moe.moe_apply(layer["ffn"], x, cfg)
        return y, (aux.load_balance_loss, aux.router_z_loss)
    return common.mlp(layer["ffn"], x), (jnp.zeros(()), jnp.zeros(()))


def _ffn_serve(layer, x, cfg: ModelConfig, moe_ffn: bool):
    """The serving FFN: the dropless held-expert layer on MoE layers, with
    the rows each held expert took per batch row ``(B, held)`` (zeros on
    dense layers)."""
    if moe_ffn:
        return moe.moe_held_apply(layer["ffn"], x, cfg)
    return (common.mlp(layer["ffn"], x),
            jnp.zeros((x.shape[0], cfg.moe.held), jnp.int32))


def layer_train(layer, x, cfg: ModelConfig, positions, *, moe_ffn: bool):
    h = common.rmsnorm(layer["ln1"], x, cfg.rms_norm_eps)
    if _uses_mla(cfg):
        a = mla.mla_train(layer["attn"], h, cfg, positions)
    else:
        a = attn.attention_train(layer["attn"], h, cfg, positions)
    x = x + a
    h = common.rmsnorm(layer["ln2"], x, cfg.rms_norm_eps)
    f, aux = _ffn_apply(layer, h, cfg, moe_ffn)
    return x + f, aux


def layer_prefill(layer, x, cfg: ModelConfig, positions, sp: SharePrefill,
                  sp_state, cluster_ids, *, method: str, moe_ffn: bool,
                  attn_impl: str, attn_width: Optional[int] = None):
    h = common.rmsnorm(layer["ln1"], x, cfg.rms_norm_eps)
    if _uses_mla(cfg):
        a, cache, sp_state, stats = mla.mla_prefill(
            layer["attn"], h, cfg, positions, method=method, sp=sp,
            sp_state=sp_state, cluster_ids=cluster_ids, attn_impl=attn_impl,
            attn_width=attn_width)
    else:
        a, cache, sp_state, stats = attn.attention_prefill(
            layer["attn"], h, cfg, positions, method=method, sp=sp,
            sp_state=sp_state, cluster_ids=cluster_ids, attn_impl=attn_impl,
            attn_width=attn_width)
    x = x + a
    h = common.rmsnorm(layer["ln2"], x, cfg.rms_norm_eps)
    f, rows = _ffn_serve(layer, h, cfg, moe_ffn)
    return x + f, cache, sp_state, stats, rows


def layer_decode(layer, x, cfg: ModelConfig, cache, pos, positions, *,
                 moe_ffn: bool, window: int = 0, plan=None, valid=None,
                 decode_impl: str = "auto", page_table=None,
                 pool_layer=None, return_q: bool = False):
    """One decode layer: ``(x, cache, rows)``, with the refresh query
    window appended under ``return_q``; ``rows`` are the rows each held
    expert took per batch row ``(B, held)`` (zeros on dense layers)."""
    window = window or cfg.sliding_window      # native SWA (Mixtral)
    h = common.rmsnorm(layer["ln1"], x, cfg.rms_norm_eps)
    if _uses_mla(cfg):
        if return_q:
            raise ValueError("return_q is a GQA decode contract (the "
                             "refresh query window); MLA layers never "
                             "carry a DecodePlan")
        if page_table is not None:
            a, pool = mla.mla_decode_paged(
                layer["attn"], h, cfg, cache[0], pool_layer, pos, positions,
                page_table, valid)
            cache = (pool,)
        else:
            a, cache = mla.mla_decode(layer["attn"], h, cfg, cache[0],
                                      cache[1], pos, positions)
        a = a[:, None, :] if a.ndim == 2 else a
    else:
        res = attn.attention_decode(
            layer["attn"], h, cfg, cache[0], cache[1], pos, positions,
            window=window, valid_mask=valid, plan=plan,
            decode_impl=decode_impl, page_table=page_table,
            pool_layer=pool_layer, return_q=return_q)
        a, cache = res[0], res[1]
    x = x + a
    h = common.rmsnorm(layer["ln2"], x, cfg.rms_norm_eps)
    f, rows = _ffn_serve(layer, h, cfg, moe_ffn)
    if return_q:
        return x + f, cache, rows, res[2]
    return x + f, cache, rows


# --------------------------------------------------------------------------
# Full-model entry points
# --------------------------------------------------------------------------

def forward_train(params, cfg: ModelConfig, tokens: jnp.ndarray,
                  positions: Optional[jnp.ndarray] = None,
                  embeds: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """tokens (B, S) → logits (B, S, V); VLM passes ``embeds``/3D positions."""
    b, s = (embeds.shape[:2] if embeds is not None else tokens.shape)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = embeds if embeds is not None else embed_tokens(params, cfg, tokens)

    moe_ffn = _uses_moe(cfg)
    for i in range(num_prefix_layers(cfg)):
        x, _ = layer_train(params[f"prefix_{i}"], x, cfg, positions,
                           moe_ffn=False)

    def body(carry, layer):
        x, lb, zl = carry
        x, (l1, l2) = layer_train(layer, x, cfg, positions, moe_ffn=moe_ffn)
        return (x, lb + l1, zl + l2), None

    body = common.maybe_remat(body, cfg.remat_policy)
    (x, lb, zl), _ = jax.lax.scan(body, (x, jnp.zeros(()), jnp.zeros(())),
                                  params["stack"])
    n_stack = cfg.num_layers - num_prefix_layers(cfg)
    aux = {"load_balance_loss": lb / max(n_stack, 1),
           "router_z_loss": zl / max(n_stack, 1)}
    return logits_from_hidden(params, cfg, x), aux


def prefill(params, cfg: ModelConfig, tokens: Optional[jnp.ndarray],
            sp: SharePrefill, *, method: str = "share",
            attn_impl: str = "auto",
            attn_width: Optional[int] = None,
            prompt_lens: Optional[jnp.ndarray] = None,   # (B,) int32
            positions: Optional[jnp.ndarray] = None,
            embeds: Optional[jnp.ndarray] = None) -> PrefillResult:
    """Prefill the padded batch.  ``prompt_lens`` (optional) gathers each
    row's ``last_logits`` at its real last token (``prompt_len - 1``)
    instead of the padded final position, so a short prompt's first sampled
    token is conditioned on its own text rather than right-pad."""
    b, s = (embeds.shape[:2] if embeds is not None else tokens.shape)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = embeds if embeds is not None else embed_tokens(params, cfg, tokens)

    sp_state = (sp.init_state(b, s)
                if (sp.cfg.enabled and sp.applicable(s)) else None)
    cluster_arr = (sp.layer_cluster_ids()
                   if (sp.cfg.enabled and sp.applicable(s)) else None)
    moe_ffn = _uses_moe(cfg)
    n_prefix = num_prefix_layers(cfg)

    prefix_caches = []
    for i in range(n_prefix):
        ids = cluster_arr[i] if cluster_arr is not None else None
        x, cache, sp_state, _, _ = layer_prefill(
            params[f"prefix_{i}"], x, cfg, positions, sp, sp_state, ids,
            method=method, moe_ffn=False, attn_impl=attn_impl,
            attn_width=attn_width)
        prefix_caches.append(cache)

    def body(carry, xs):
        x, sp_state, rows = carry
        layer, ids = xs
        x, cache, sp_state, stats, r = layer_prefill(
            layer, x, cfg, positions, sp, sp_state, ids,
            method=method, moe_ffn=moe_ffn, attn_impl=attn_impl,
            attn_width=attn_width)
        return (x, sp_state, rows + r), (cache, stats)

    n_stack = cfg.num_layers - n_prefix
    ids_xs = (cluster_arr[n_prefix:] if cluster_arr is not None
              else jnp.zeros((n_stack, max(cfg.num_heads, 1)), jnp.int32))
    rows0 = jnp.zeros((b, cfg.moe.held), jnp.int32)
    (x, sp_state, rows), (caches, stats) = jax.lax.scan(
        body, (x, sp_state, rows0), (params["stack"], ids_xs))

    if prompt_lens is None:
        last = x[:, -1, :]
    else:
        last_row = jnp.clip(prompt_lens, 1, s) - 1
        last = x[jnp.arange(b), last_row, :]
    logits = logits_from_hidden(params, cfg, last)
    stats = attn.AttnStats.reduce_layers(stats)
    return PrefillResult(logits, {"prefix": prefix_caches, "stack": caches},
                         stats, sp_state, rows if moe_ffn else None)


def _cache_seq_len(cache) -> int:
    """Sequence-axis length of the KV cache pytree (dense GQA and MLA
    layouts both keep it second-to-last)."""
    if cache["prefix"]:
        return cache["prefix"][0][0].shape[-2]
    return cache["stack"][0].shape[-2]


def decode_step(params, cfg: ModelConfig, token: jnp.ndarray,
                cache, pos: jnp.ndarray,
                positions: Optional[jnp.ndarray] = None, *,
                window: int = 0,
                embeds: Optional[jnp.ndarray] = None,
                plan=None,                  # DecodePlan, (L, B, …) leaves
                prompt_lens: Optional[jnp.ndarray] = None,   # (B,) int32
                prefill_len=0,              # int, or (B,) per-slot lengths
                decode_impl: str = "auto",
                page_table: Optional[jnp.ndarray] = None,    # (B, NB) int32
                collect_queries: bool = False,
                expert_rows: bool = False,
                ):
    """One decode step. token (B, 1) → logits (B, V), updated cache.

    ``pos`` is either the lockstep scalar write index (batch-at-a-time
    serving) or a ``(B,)`` vector of per-slot positions (the continuous-
    batching scheduler: each slot decodes at its own position, so the rope
    position, the cache write, and the slot-validity mask are all per-row).
    Vector ``pos`` is a GQA-cache contract, or MLA's over the latent page
    pool (``page_table``); contiguous MLA latent caches keep the scalar
    lockstep path.

    ``plan`` enables decode-phase pattern sharing (beyond paper): prebuilt
    O(L·B·Hkv·NB) splash block tables derived once per batch from the
    prefill pattern dictionary (``repro.serving.decode_plan``); the scan
    slices one layer's tables per step — no O(L·B·H·S) token mask is ever
    materialized.  When traced inside a sharding-rules context with a
    non-trivial "model" axis, each plan-carrying attention layer resolves
    the heads-sharded ``shard_map`` decode path automatically
    (``repro.distributed.sharding.sharded_flash_decode``; MLA layers never
    carry a plan and keep dense latent-cache decode under any mesh).
    ``prompt_lens``/``prefill_len`` mark right-pad cache
    slots (positions in [prompt_len, prefill_len)) invalid so padded K/V is
    never attended (ignored by contiguous MLA layers, which keep the plain
    length mask); under the paged cache ``prefill_len`` is a ``(B,)``
    vector — slots of different former buckets coexist, each with its own
    prefill boundary.

    ``page_table`` switches the cache contract to the block-paged pool:
    ``cache["stack"]`` leaves are then the shared ``(L, P, Hkv, ps, hd)``
    page pools (GQA: the scanned stack only, no prefix layers) or the one
    latent pool ``(L, P, 1, ps, latent_dim)`` over every layer, prefix
    layers first (MLA), and each attention layer appends/reads through the
    table; the virtual cache length is ``page_table.shape[1] · page_size``.
    Dense paged decode carries the whole pools through the layer loop,
    each layer writing its pages in place at its layer index, so a donated
    pool is updated without a copy; the sparse plan path scans one
    layer's pool slice in and out.

    ``collect_queries`` additionally returns the step's per-layer
    post-rope query vectors ``(L_stack, B, H, hd)`` (the scan's ys) — the
    refresh query-window capture.  Plan-carrying stack-only decode only
    (the refresh path is paged + sparse).  ``expert_rows`` appends, last,
    the rows each held expert took per slot ``(B, held)``, summed over the
    MoE layers.  With neither, the 2-tuple contract is unchanged."""
    b = (embeds.shape[0] if embeds is not None else token.shape[0])
    pos = jnp.asarray(pos)
    if jnp.ndim(pos) and _uses_mla(cfg) and page_table is None:
        raise ValueError(
            "per-slot decode positions require the GQA cache layout or the "
            "latent page pool; contiguous MLA latent caches keep the "
            "lockstep scalar pos")
    if page_table is not None and (not jnp.ndim(pos) or cache["prefix"]
                                   or (_uses_mla(cfg) and plan is not None)):
        raise ValueError(
            "paged decode requires per-slot (vector) pos and a stack-only "
            "cache (the MLA latent pool holds the prefix layers and "
            "decodes densely, with no DecodePlan)")
    if positions is None:
        positions = (pos[:, None] if jnp.ndim(pos)
                     else jnp.broadcast_to(pos[None, None], (b, 1)))
    x = embeds if embeds is not None else embed_tokens(params, cfg, token)
    moe_ffn = _uses_moe(cfg)
    n_prefix = num_prefix_layers(cfg)
    rows = jnp.zeros((b, cfg.moe.held), jnp.int32)

    valid = None
    if prompt_lens is not None:
        if page_table is not None:
            sv = page_table.shape[1] * cache["stack"][0].shape[-2]
        else:
            sv = _cache_seq_len(cache)
        slots = jnp.arange(sv)[None, :]
        pcol = pos[:, None] if jnp.ndim(pos) else pos
        pf = jnp.asarray(prefill_len)
        pfcol = pf[:, None] if jnp.ndim(pf) else pf
        valid = ((slots <= pcol)
                 & ((slots < prompt_lens[:, None]) | (slots >= pfcol)))

    new_prefix = []
    for i, c in enumerate(cache["prefix"]):
        lp = (jax.tree.map(lambda a: a[i], plan)
              if plan is not None else None)
        x, c, _ = layer_decode(params[f"prefix_{i}"], x, cfg, c, pos,
                               positions, moe_ffn=False, window=window,
                               plan=lp, valid=valid, decode_impl=decode_impl)
        new_prefix.append(c)

    qs = None
    if plan is not None:
        plan_xs = jax.tree.map(lambda a: a[n_prefix:], plan)

        if collect_queries:
            if new_prefix:
                raise ValueError("collect_queries covers the scanned stack "
                                 "only (no prefix layers)")

            def body(carry, xs):
                x, rows = carry
                layer, c, lp = xs
                x, c, r, qv = layer_decode(layer, x, cfg, c, pos, positions,
                                           moe_ffn=moe_ffn, window=window,
                                           plan=lp, valid=valid,
                                           decode_impl=decode_impl,
                                           page_table=page_table,
                                           return_q=True)
                return (x, rows + r), (c, qv)

            (x, rows), (new_caches, qs) = jax.lax.scan(
                body, (x, rows), (params["stack"], cache["stack"], plan_xs))
        else:
            def body(carry, xs):
                x, rows = carry
                layer, c, lp = xs
                x, c, r = layer_decode(layer, x, cfg, c, pos, positions,
                                       moe_ffn=moe_ffn, window=window,
                                       plan=lp, valid=valid,
                                       decode_impl=decode_impl,
                                       page_table=page_table)
                return (x, rows + r), c

            (x, rows), new_caches = jax.lax.scan(
                body, (x, rows), (params["stack"], cache["stack"], plan_xs))
    elif collect_queries:
        raise ValueError("collect_queries requires a DecodePlan (the "
                         "refresh path is sparse paged decode)")
    elif page_table is not None:
        # the pools ride in the carry, indexed by layer: no per-layer slice
        # goes in and no stacked pool comes out (the latent pool holds the
        # prefix layers at its first layer indices)
        pools = cache["stack"]
        for i in range(n_prefix):
            x, pools, _ = layer_decode(
                params[f"prefix_{i}"], x, cfg, pools, pos, positions,
                moe_ffn=False, window=window, valid=valid,
                page_table=page_table, pool_layer=i)

        def body(carry, xs):
            x, pools, rows = carry
            layer, idx = xs
            x, pools, r = layer_decode(
                layer, x, cfg, pools, pos, positions,
                moe_ffn=moe_ffn, window=window, valid=valid,
                page_table=page_table, pool_layer=idx)
            return (x, pools, rows + r), None

        (x, new_caches, rows), _ = jax.lax.scan(
            body, (x, pools, rows),
            (params["stack"], jnp.arange(n_prefix, cfg.num_layers)))
    else:
        def body(carry, xs):
            x, rows = carry
            layer, c = xs
            x, c, r = layer_decode(layer, x, cfg, c, pos, positions,
                                   moe_ffn=moe_ffn, window=window,
                                   valid=valid)
            return (x, rows + r), c

        (x, rows), new_caches = jax.lax.scan(
            body, (x, rows), (params["stack"], cache["stack"]))
    logits = logits_from_hidden(params, cfg, x[:, -1, :])
    out = (logits, {"prefix": new_prefix, "stack": new_caches})
    if collect_queries:
        out += (qs,)
    if expert_rows:
        out += (rows,)
    return out


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.float32):
    """Empty KV cache pytree for decode-from-scratch / dry-run staging."""
    n_prefix = num_prefix_layers(cfg)
    n_stack = cfg.num_layers - n_prefix
    if cfg.mla.enabled:
        one = lambda: (jnp.zeros((batch, cache_len, cfg.mla.kv_lora_rank),
                                 dtype),
                       jnp.zeros((batch, cache_len,
                                  cfg.mla.qk_rope_head_dim), dtype))
    else:
        hd = cfg.resolved_head_dim
        one = lambda: (jnp.zeros((batch, cfg.num_kv_heads, cache_len, hd),
                                 dtype),
                       jnp.zeros((batch, cfg.num_kv_heads, cache_len, hd),
                                 dtype))
    stack = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_stack,) + x.shape), one())
    return {"prefix": [one() for _ in range(n_prefix)], "stack": stack}
