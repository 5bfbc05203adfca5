"""Work counts from shapes: the operations a served request needs.

Counted as multiply-adds times two.  Padding is never counted, so it shows
as lost utilization.  Attention is counted over the blocks the run kept
(block density from the program's own pattern counters), never as dense,
so skipping blocks can never push a share over 100%.
"""
from __future__ import annotations

import math
from typing import Dict


def matmul_params(sizes: Dict) -> Dict[str, int]:
    """Weights multiplied once per token: per layer, and the output head."""
    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    h, hkv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    layer = d * h * hd * 2 + d * hkv * hd * 2 + 3 * d * ff
    return {"layers": sizes["num_hidden_layers"] * layer,
            "head": d * sizes["vocab_size"]}


def kept_blocks(density: float, tokens: int, block: int, sizes: Dict) -> float:
    """Attention blocks computed over ``tokens`` at ``density`` (the share of
    causal blocks kept), summed over heads and layers."""
    nb = math.ceil(tokens / block)
    return (density * nb * (nb + 1) / 2 * sizes["num_attention_heads"]
            * sizes["num_hidden_layers"])


def block_flops(block: int, sizes: Dict) -> float:
    """QK^T and PV of one (block x block) tile of one head."""
    return 4.0 * sizes["head_dim"] * block * block


def prefill_flops(prompt: int, density: float, block: int,
                  sizes: Dict) -> float:
    """Prefill of one prompt: every layer's projections per real token,
    attention over the kept blocks of the real tokens, and the output head
    once for the first token."""
    p = matmul_params(sizes)
    return (2.0 * p["layers"] * prompt + 2.0 * p["head"]
            + kept_blocks(density, prompt, block, sizes)
            * block_flops(block, sizes))


def decode_flops(prompt: int, n_out: int, traffic: float,
                 sizes: Dict) -> float:
    """The ``n_out - 1`` decode steps of one request: projections and head
    per token, attention over the share ``traffic`` of the context."""
    if n_out < 2:
        return 0.0
    p = matmul_params(sizes)
    steps = n_out - 1
    ctx = steps * prompt + steps * (steps + 1) / 2     # sum of context lengths
    per_key = 4.0 * sizes["head_dim"] * sizes["num_attention_heads"] \
        * sizes["num_hidden_layers"]
    return (2.0 * (p["layers"] + p["head"]) * steps
            + per_key * ctx * (traffic if traffic > 0 else 1.0))


def served_flops(requests, block: int, sizes: Dict) -> float:
    """Prefill and decode operations of finished ``repro`` requests, at the
    block density and decode traffic each request's counters report."""
    total = 0.0
    for r in requests:
        density = (r.pattern_stats or {}).get("block_density", 1.0)
        total += prefill_flops(len(r.prompt), density, block, sizes)
        total += decode_flops(len(r.prompt), len(r.output_tokens),
                              r.plan_traffic_fraction, sizes)
    return total
