"""Work counts from shapes: the operations a served request needs.

Counted as multiply-adds times two.  What the layers, the head and
attention cost is the configuration's family's (``matmul_params``,
``attention_flops`` and ``key_flops`` in ``bench/families/<family>.py``);
this module sums them over a request.  Padding is never counted, so it
shows as lost utilization.  Attention is counted over the blocks the run
kept (block density from the program's own pattern counters), never as
dense, so skipping blocks can never push a share over 100%.
"""
from __future__ import annotations

import math
from typing import Dict


def kept_blocks(density: float, tokens: int, block: int) -> float:
    """Attention blocks of one head in one layer computed over ``tokens``
    at ``density`` (the share of causal blocks kept)."""
    nb = math.ceil(tokens / block)
    return density * nb * (nb + 1) / 2


def prefill_flops(prompt: int, density: float, block: int, family,
                  sizes: Dict) -> float:
    """Prefill of one prompt: every layer's projections per real token,
    attention over the kept blocks of the real tokens, and the output head
    once for the first token."""
    p = family.matmul_params(sizes)
    return (2.0 * p["layers"] * prompt + 2.0 * p["head"]
            + family.attention_flops(kept_blocks(density, prompt, block),
                                     block, sizes))


def decode_flops(prompt: int, n_out: int, traffic: float, family,
                 sizes: Dict) -> float:
    """The ``n_out - 1`` decode steps of one request: projections and head
    per token, attention over the share ``traffic`` of the context."""
    if n_out < 2:
        return 0.0
    p = family.matmul_params(sizes)
    steps = n_out - 1
    ctx = steps * prompt + steps * (steps + 1) / 2     # sum of context lengths
    per_key = family.key_flops(sizes)
    return (2.0 * (p["layers"] + p["head"]) * steps
            + per_key * ctx * (traffic if traffic > 0 else 1.0))


def served_flops(requests, block: int, family, sizes: Dict) -> float:
    """Prefill and decode operations of finished ``repro`` requests, at the
    block density and decode traffic each request's counters report."""
    total = 0.0
    for r in requests:
        density = (r.pattern_stats or {}).get("block_density", 1.0)
        total += prefill_flops(len(r.prompt), density, block, family, sizes)
        total += decode_flops(len(r.prompt), len(r.output_tokens),
                              r.plan_traffic_fraction, family, sizes)
    return total
