"""Unified model API: every architecture family behind the same five
callables, dispatched by config family.

    model = build_model(cfg)
    params = model.init(key)
    logits, aux = model.train_logits(params, tokens)
    result = model.prefill(params, tokens, sp, method="share")
    logits, cache = model.decode(params, token, cache, pos)
    cache = model.init_cache(batch, cache_len)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.api import SharePrefill
from repro.models import (chunked_prefill, hybrid, ssm_stack, transformer,
                          whisper)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[jax.Array], Any]
    train_logits: Callable[..., Any]
    prefill: Callable[..., Any]
    decode: Callable[..., Any]
    init_cache: Callable[..., Any]
    # step-cadence chunked admission (models.chunked_prefill.ChunkPrefillApi);
    # None on families/layouts that can only prefill one-shot
    prefill_chunk: Optional[Any] = None

    def default_share_prefill(self) -> SharePrefill:
        """Trivial clustering (per-head clusters) until an offline artifact
        is provided — sharing degrades to per-head pivots (DESIGN.md §4)."""
        if not self.cfg.share_prefill.enabled or not self.cfg.has_attention:
            return SharePrefill.disabled()
        return SharePrefill.trivial(self.cfg.share_prefill,
                                    self.cfg.num_layers,
                                    max(self.cfg.num_heads, 1))


_FAMILY_MODULES = {
    "dense": transformer,
    "vlm": transformer,
    "moe": transformer,
    "ssm": ssm_stack,
    "hybrid": hybrid,
    "encdec": whisper,
}

_INIT_FNS = {
    "dense": transformer.init_decoder_params,
    "vlm": transformer.init_decoder_params,
    "moe": transformer.init_decoder_params,
    "ssm": ssm_stack.init_ssm_params,
    "hybrid": hybrid.init_hybrid_params,
    "encdec": whisper.init_whisper_params,
}


def build_model(cfg: ModelConfig, dtype=jnp.float32) -> Model:
    if cfg.family not in _FAMILY_MODULES:
        raise ValueError(f"unknown family {cfg.family!r}")
    mod = _FAMILY_MODULES[cfg.family]
    init_fn = _INIT_FNS[cfg.family]

    if cfg.family in ("dense", "vlm", "moe"):
        fwd = lambda p, tokens, positions=None, embeds=None: \
            transformer.forward_train(p, cfg, tokens, positions, embeds)
        pf = lambda p, tokens, sp, method="share", attn_impl="auto", \
            attn_width=None, prompt_lens=None, positions=None, \
            embeds=None: transformer.prefill(
                p, cfg, tokens, sp, method=method, attn_impl=attn_impl,
                attn_width=attn_width, prompt_lens=prompt_lens,
                positions=positions, embeds=embeds)
        dec = lambda p, token, cache, pos, positions=None, window=0, \
            embeds=None, plan=None, prompt_lens=None, prefill_len=0, \
            decode_impl="auto", page_table=None, collect_queries=False, \
            expert_rows=False: transformer.decode_step(
                p, cfg, token, cache, pos, positions, window=window,
                embeds=embeds, plan=plan, prompt_lens=prompt_lens,
                prefill_len=prefill_len, decode_impl=decode_impl,
                page_table=page_table, collect_queries=collect_queries,
                expert_rows=expert_rows)
        ic = lambda batch, cache_len, dtype=jnp.float32: \
            transformer.init_cache(cfg, batch, cache_len, dtype)
        pc = chunked_prefill.make_chunk_prefill(cfg)
    else:
        fwd = lambda p, tokens, positions=None, embeds=None: \
            mod.forward_train(p, cfg, tokens, positions, embeds)
        pf = lambda p, tokens, sp, method="share", attn_impl="auto", \
            positions=None, embeds=None: mod.prefill(
                p, cfg, tokens, sp, method=method, attn_impl=attn_impl,
                positions=positions, embeds=embeds)
        dec = lambda p, token, cache, pos, positions=None, window=0, \
            embeds=None: mod.decode_step(
                p, cfg, token, cache, pos, positions, window=window,
                embeds=embeds)
        ic = lambda batch, cache_len, dtype=jnp.float32: \
            mod.init_cache(cfg, batch, cache_len, dtype)
        pc = None

    return Model(
        cfg=cfg,
        init=lambda key: init_fn(key, cfg, dtype),
        train_logits=fwd,
        prefill=pf,
        decode=dec,
        init_cache=ic,
        prefill_chunk=pc,
    )
