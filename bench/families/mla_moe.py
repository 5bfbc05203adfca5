"""The DeepSeek-V2 decoder family: multi-head latent attention with YaRN,
leading dense layers, then layers of routed and shared experts, held by
this chip in part (its parameter layout, fan-in rule, plain reference and
work counts; the interface: ``Bench.family``).

A configuration file gives the published keys at its top level, as the
published config names them, and the same keys again under ``model``,
the sizes the harness hands to this module; ``model_fields`` refuses a
file whose two copies differ.  ``n_routed_experts`` is the routed
experts this chip holds of each layer (experts
``0 .. n_routed_experts - 1``) and ``n_routed_experts_published`` the
router's width.

The reference follows the published architecture (arXiv:2405.04434 and
the model's ``modeling_deepseek.py``): token embedding; per layer
RMSNorm, then attention: q projected per head (no q-LoRA), a shared
down-projection to the latent ``c_kv`` (RMSNorm) and one rotary key
stream, per-head keys and values up-projected from ``c_kv``; rope on
q's and the key's rotary dims with YaRN's inverse frequencies (the
frequencies interpolated by ``factor`` past the correction dim of
``beta_slow`` rotations, kept below that of ``beta_fast``, a linear ramp
between) and cos/sin times mscale(factor, mscale) / mscale(factor,
mscale_all_dim); causal softmax at mscale(factor, mscale_all_dim)² /
√(qk_nope + qk_rope); output projection and residual; RMSNorm and the
feed-forward: SwiGLU of ``intermediate_size`` on the first
``first_k_dense_replace`` layers, else the router's softmax over all
routed experts, its top ``num_experts_per_tok``, renormalized only where
``norm_topk_prob``, times ``routed_scaling_factor``; the weighted outputs
of the held experts among them, plus the shared experts as one SwiGLU of
``n_shared_experts · moe_intermediate_size``; final RMSNorm and the
untied output head.  What the experts other chips hold would add is left
out, as in the program.  Rope pairs dims as halves, the program's
layout; the published code pairs them interleaved and permutes them to
halves before rotating, a fixed permutation of the rotary weight columns
that random weights do not see.

It runs layer by layer, upcasting one layer's bf16 weights at a time, and
attends in blocks of query rows (``reference.attention``, its values
zero-padded to the query width).  Every held expert runs over every
token, weighted by its router weight where the token chose it and zero
elsewhere.  ``precision="fp8"`` is the dense family's control: the
operands of every projection, router and output head rounded to
float8_e4m3fn under one scale per tensor; attention stays float32.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import attention, mm, rmsnorm

# published keys -> repro ModelConfig fields, outside the MLA and MoE groups
MODEL_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}

# what the program implements of the published options
SUPPORTED = {"first_k_dense_replace": 1, "moe_layer_freq": 1,
             "q_lora_rank": None, "scoring_func": "softmax",
             "topk_method": "greedy", "n_group": 1, "topk_group": 1,
             "routed_scaling_factor": 1, "attention_bias": False,
             "tie_word_embeddings": False, "hidden_act": "silu"}

# leaf name -> number of input (contracted) axes after the layer and
# expert axes
FAN_IN_AXES = {"w_q": 1, "w_kv_down": 1, "w_uk": 1, "w_uv": 1, "wo": 2,
               "router": 1, "w_gate": 1, "w_up": 1, "w_down": 1,
               "lm_head": 1}


def model_fields(conf: Dict) -> Dict:
    from repro.configs.base import MLAConfig, MoEConfig
    m = conf["model"]
    apart = {k: (conf[k], v) for k, v in m.items()
             if k in conf and conf[k] != v}
    if apart:
        raise ValueError(f"top-level keys differ from model's: {apart}")
    off = {k: m.get(k) for k, v in SUPPORTED.items() if m.get(k) != v}
    if off:
        raise ValueError(f"the program does not implement {off}")
    rs = m["rope_scaling"]
    out = {MODEL_FIELDS[k]: v for k, v in m.items() if k in MODEL_FIELDS}
    out["mla"] = MLAConfig(
        kv_lora_rank=m["kv_lora_rank"], q_lora_rank=0,
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"],
        v_head_dim=m["v_head_dim"], rope_factor=float(rs["factor"]),
        rope_original_max_positions=rs["original_max_position_embeddings"],
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]),
        mscale_all_dim=float(rs["mscale_all_dim"]))
    out["moe"] = MoEConfig(
        num_experts=m["n_routed_experts_published"],
        top_k=m["num_experts_per_tok"],
        num_shared_experts=m["n_shared_experts"],
        expert_d_ff=m["moe_intermediate_size"],
        norm_topk=bool(m["norm_topk_prob"]),
        num_held=m["n_routed_experts"])
    return out


def _attn_layout(p: str, lead: Tuple[int, ...], s: Dict) -> Dict:
    d, h, r = s["hidden_size"], s["num_attention_heads"], s["kv_lora_rank"]
    nope, rope, vd = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                      s["v_head_dim"])
    return {f"{p}/attn/w_q": lead + (d, h, nope + rope),
            f"{p}/attn/w_kv_down": lead + (d, r + rope),
            f"{p}/attn/kv_norm/scale": lead + (r,),
            f"{p}/attn/w_uk": lead + (r, h, nope),
            f"{p}/attn/w_uv": lead + (r, h, vd),
            f"{p}/attn/wo": lead + (h, vd, d),
            f"{p}/ln1/scale": lead + (d,), f"{p}/ln2/scale": lead + (d,)}


def _mlp_layout(p: str, lead: Tuple[int, ...], d: int, f: int) -> Dict:
    return {f"{p}/w_gate": lead + (d, f), f"{p}/w_up": lead + (d, f),
            f"{p}/w_down": lead + (f, d)}


def layout(sizes: Dict) -> Dict[str, Tuple[int, ...]]:
    """``path -> shape``: ``prefix_<i>/`` for the dense layers, then the
    MoE layers stacked, each expert leaf with its held-expert axis after
    the layer axis."""
    s = sizes
    d, v = s["hidden_size"], s["vocab_size"]
    k = s["first_k_dense_replace"]
    n = (s["num_hidden_layers"] - k,)
    held, f = s["n_routed_experts"], s["moe_intermediate_size"]
    out = {"embed": (v, d), "final_norm/scale": (d,), "lm_head": (d, v)}
    for i in range(k):
        out.update(_attn_layout(f"prefix_{i}", (), s))
        out.update(_mlp_layout(f"prefix_{i}/ffn", (), d,
                               s["intermediate_size"]))
    out.update(_attn_layout("stack", n, s))
    out["stack/ffn/router"] = n + (d, s["n_routed_experts_published"])
    out.update(_mlp_layout("stack/ffn", n + (held,), d, f))
    out.update(_mlp_layout("stack/ffn/shared", n, d,
                           s["n_shared_experts"] * f))
    return out


def fan_in(path: str, shape: Tuple[int, ...]) -> int:
    """The embedding is standard normal; a matrix's input axes follow the
    layer axis of a ``stack/`` leaf and the expert axis of an expert's."""
    parts = path.split("/")
    name = parts[-1]
    if name == "embed":
        return 1
    lead = 0
    if parts[0] == "stack":
        lead = 2 if parts[1] == "ffn" and len(parts) == 3 \
            and name != "router" else 1
    size = 1
    for x in shape[lead: lead + FAN_IN_AXES[name]]:
        size *= x
    return size


def yarn(sizes: Dict) -> Tuple[np.ndarray, float, float]:
    """YaRN's inverse frequencies (rotary dim / 2,), cos/sin multiplier
    and softmax scale for the published ``rope_scaling``."""
    s = sizes
    rs, dim, theta = s["rope_scaling"], s["qk_rope_head_dim"], s["rope_theta"]
    factor = rs["factor"]

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / dim)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    scale = (mscale(rs["mscale_all_dim"]) ** 2
             / math.sqrt(s["qk_nope_head_dim"] + dim))
    return (inv.astype(np.float32), mscale(rs["mscale"])
            / mscale(rs["mscale_all_dim"]), scale)


def _rope(x, pos, inv, mult):
    """x (heads, T, dim) rotated by positions pos (T,), halves."""
    ang = pos.astype(jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang) * mult, jnp.cos(ang) * mult
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "mult", "scale", "fp8",
                                             "block"))
def _attention(p, x, pos, inv, *, eps, mult, scale, fp8, block):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    r = p["w_uk"].shape[0]
    nope = p["w_uk"].shape[-1]
    h = rmsnorm(x, p["ln1"], eps)
    q = mm("td,dhk->htk", h, p["w_q"], fp8)
    down = mm("td,dr->tr", h, p["w_kv_down"], fp8)
    c = rmsnorm(down[:, :r], p["kv_norm"], eps)
    q_pe = _rope(q[..., nope:], pos, inv, mult)
    k_pe = _rope(down[None, :, r:], pos, inv, mult)
    k_nope = mm("tr,rhk->htk", c, p["w_uk"], fp8)
    v = mm("tr,rhk->htk", c, p["w_uv"], fp8)
    qk = q.shape[-1]
    # reference.attention scales by qk ** -0.5: q carries the rest
    q = jnp.concatenate([q[..., :nope], q_pe], -1) * (scale * qk ** 0.5)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, k_nope.shape[:-1] + (k_pe.shape[-1],))], -1)
    vd = v.shape[-1]
    a = attention(q, k, jnp.pad(v, ((0, 0), (0, 0), (0, qk - vd))),
                  block)[..., :vd]
    return x + mm("htk,hkd->td", a, p["wo"], fp8)


def _swiglu(p, h, fp8):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", h, p["w_gate"], fp8))
              * mm("td,df->tf", h, p["w_up"], fp8), p["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _dense_ffn(p, x, *, eps, fp8):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    return x + _swiglu(p, rmsnorm(x, p["ln2"], eps), fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8", "top_k", "norm"))
def _moe_ffn(p, x, *, eps, fp8, top_k, norm):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    h = rmsnorm(x, p["ln2"], eps)
    probs = jax.nn.softmax(mm("td,de->te", h, p["router"], fp8), -1)
    gate, idx = jax.lax.top_k(probs, top_k)
    if norm:
        gate = gate / jnp.sum(gate, -1, keepdims=True)
    y = _swiglu(p["shared"], h, fp8)
    for e in range(p["w_gate"].shape[0]):
        w = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)
        y = y + w[:, None] * _swiglu(
            {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}, h, fp8)
    return x + y


_ATTN = ("w_q", "w_kv_down", "w_uk", "w_uv", "wo")


def _layer_params(params, prefix: str, li=None) -> Dict:
    at = (lambda a: a) if li is None else (lambda a: a[li])
    p = {k: at(params[f"{prefix}/attn/{k}"]) for k in _ATTN}
    p["kv_norm"] = at(params[f"{prefix}/attn/kv_norm/scale"])
    p["ln1"] = at(params[f"{prefix}/ln1/scale"])
    p["ln2"] = at(params[f"{prefix}/ln2/scale"])
    for k in ("w_gate", "w_up", "w_down"):
        p[k] = at(params[f"{prefix}/ffn/{k}"])
    if li is not None:
        p["router"] = at(params[f"{prefix}/ffn/router"])
        p["shared"] = {k: at(params[f"{prefix}/ffn/shared/{k}"])
                       for k in ("w_gate", "w_up", "w_down")}
    return p


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(w, norm, x, *, eps, fp8):
    h = rmsnorm(x, norm.astype(jnp.float32), eps)
    return mm("kd,dv->kv", h, w.astype(jnp.float32), fp8)


def logits(params: Dict[str, jax.Array], sizes: Dict, tokens: np.ndarray,
           rows: np.ndarray, *, precision: str = "f32", pad: int = 1024,
           block: int = 512) -> np.ndarray:
    """Float32 logits ``(len(rows), vocab)`` of the model over ``tokens``,
    at sequence rows ``rows``.  ``params`` is ``path -> array`` (see
    :func:`layout`)."""
    fp8 = {"f32": False, "fp8": True}[precision]
    s = sizes
    eps = float(s["rms_norm_eps"])
    inv, mult, scale = yarn(s)
    t = len(tokens)
    tp = -(-t // pad) * pad
    ids = np.zeros((tp,), np.int32)
    ids[:t] = tokens
    pos = jnp.asarray(np.arange(tp, dtype=np.int32))
    k = s["first_k_dense_replace"]
    attn_kw = dict(eps=eps, mult=float(mult), scale=float(scale), fp8=fp8,
                   block=min(block, tp))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(ids), axis=0
                     ).astype(jnp.float32)
        for li in range(s["num_hidden_layers"]):
            if li < k:
                p = _layer_params(params, f"prefix_{li}")
                x = _attention(p, x, pos, inv, **attn_kw)
                x = _dense_ffn(p, x, eps=eps, fp8=fp8)
            else:
                p = _layer_params(params, "stack", li - k)
                x = _attention(p, x, pos, inv, **attn_kw)
                x = _moe_ffn(p, x, eps=eps, fp8=fp8,
                             top_k=s["num_experts_per_tok"],
                             norm=bool(s["norm_topk_prob"]))
        out = _head(params["lm_head"], params["final_norm/scale"],
                    x[jnp.asarray(rows)], eps=eps, fp8=fp8)
    return np.asarray(out)


def matmul_params(sizes: Dict) -> Dict[str, int]:
    """Weights multiplied once per token: per layer, and the output head.

    The routed experts count as ``num_experts_per_tok x n_routed_experts /
    n_routed_experts_published`` experts a token (0.75 at 6 of 64 with 8
    held): the expectation under uniform routing, which the program's
    held-expert row counter checks."""
    s = sizes
    d, h, r = s["hidden_size"], s["num_attention_heads"], s["kv_lora_rank"]
    nope, rope, vd = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                      s["v_head_dim"])
    f = s["moe_intermediate_size"]
    attn = (d * h * (nope + rope) + d * (r + rope) + r * h * nope
            + r * h * vd + h * vd * d)
    dense = attn + 3 * d * s["intermediate_size"]
    routed = (3 * d * f * s["num_experts_per_tok"] * s["n_routed_experts"]
              / s["n_routed_experts_published"])
    moe = (attn + d * s["n_routed_experts_published"]
           + 3 * d * f * s["n_shared_experts"] + routed)
    k = s["first_k_dense_replace"]
    return {"layers": k * dense + (s["num_hidden_layers"] - k) * moe,
            "head": d * s["vocab_size"]}


def attention_flops(blocks: float, block: int, sizes: Dict) -> float:
    """Prefill attention over ``blocks`` causal (block x block) tiles kept
    in each head of each layer: QK^T at qk_nope + qk_rope and PV at
    v_head_dim, summed over heads and layers."""
    s = sizes
    return (blocks * s["num_attention_heads"] * s["num_hidden_layers"]
            * (2.0 * block * block * (s["qk_nope_head_dim"]
                                      + s["qk_rope_head_dim"]
                                      + s["v_head_dim"])))


def key_flops(sizes: Dict) -> float:
    """Absorbed decode per key of context: scores over the latent row
    (kv_lora_rank + qk_rope) and the latent value (kv_lora_rank), summed
    over heads and layers."""
    s = sizes
    r = s["kv_lora_rank"]
    return (2.0 * (2 * r + s["qk_rope_head_dim"]) * s["num_attention_heads"]
            * s["num_hidden_layers"])
