"""The dense GQA decoder family: its parameter layout, fan-in rule, plain
reference and work counts (the interface: ``Bench.family``).  A
configuration file without a ``"family"`` key is of this family.

The reference follows the published architecture: token embedding; per
layer RMSNorm, query/key/value projections, rotary embedding on the two
halves of each head, causal softmax attention scaled by ``head_dim **
-0.5`` with each key/value head serving ``heads / kv_heads`` query heads,
output projection and residual, RMSNorm, SwiGLU feed-forward and residual;
final RMSNorm and the output head.  It runs layer by layer (one jitted
layer program per padded length) and attends in blocks of query rows
(``reference.attention``), so a 32k-token sequence fits next to the
weights.  Sequences are right-padded to a multiple of ``pad``; attention
is causal, so padding changes no earlier position.

``precision="fp8"`` is the control: the same computation with the operands
of every projection and of the output head (weights and activations)
rounded to float8_e4m3fn under one scale per tensor, the step below the
bfloat16 the configurations serve in.  Attention itself stays float32.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import attention, mm, rmsnorm, rope

# configuration-file keys (the published names) -> repro ModelConfig fields
MODEL_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps", "tie_word_embeddings": "tie_embeddings",
}

# leaf name -> number of input (contracted) axes after the layer axis
FAN_IN_AXES = {"wq": 1, "wk": 1, "wv": 1, "wo": 2, "w_gate": 1, "w_up": 1,
               "w_down": 1, "lm_head": 1}


def model_fields(conf: Dict) -> Dict:
    return {MODEL_FIELDS[k]: v for k, v in conf["model"].items()
            if k in MODEL_FIELDS}


def layout(sizes: Dict) -> Dict[str, Tuple[int, ...]]:
    """``path -> shape`` of a dense GQA decoder with stacked layers."""
    d, ff, v = sizes["hidden_size"], sizes["intermediate_size"], \
        sizes["vocab_size"]
    h, hkv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    n = sizes["num_hidden_layers"]
    out = {
        "embed": (v, d),
        "final_norm/scale": (d,),
        "stack/attn/wq": (n, d, h, hd),
        "stack/attn/wk": (n, d, hkv, hd),
        "stack/attn/wv": (n, d, hkv, hd),
        "stack/attn/wo": (n, h, hd, d),
        "stack/ffn/w_gate": (n, d, ff),
        "stack/ffn/w_up": (n, d, ff),
        "stack/ffn/w_down": (n, ff, d),
        "stack/ln1/scale": (n, d),
        "stack/ln2/scale": (n, d),
    }
    if not sizes["tie_word_embeddings"]:
        out["lm_head"] = (d, v)
    return out


def fan_in(path: str, shape: Tuple[int, ...]) -> int:
    """The embedding is standard normal; a matrix's input axes follow the
    layer axis of a ``stack/`` leaf."""
    name = path.rsplit("/", 1)[-1]
    if name == "embed":
        return 1
    lead = 1 if path.startswith("stack/") else 0
    size = 1
    for s in shape[lead: lead + FAN_IN_AXES[name]]:
        size *= s
    return size


@functools.partial(jax.jit, static_argnames=("theta", "eps", "fp8", "block"))
def _layer(p, x, pos, *, theta, eps, fp8, block):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    h = rmsnorm(x, p["ln1"], eps)
    q = rope(mm("td,dhk->htk", h, p["wq"], fp8), pos, theta)
    k = rope(mm("td,dhk->htk", h, p["wk"], fp8), pos, theta)
    v = mm("td,dhk->htk", h, p["wv"], fp8)
    a = attention(q, k, v, block)
    x = x + mm("htk,hkd->td", a, p["wo"], fp8)
    h = rmsnorm(x, p["ln2"], eps)
    f = jax.nn.silu(mm("td,df->tf", h, p["w_gate"], fp8)) \
        * mm("td,df->tf", h, p["w_up"], fp8)
    return x + mm("tf,fd->td", f, p["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8", "tied"))
def _head(w, embed, norm, x, *, eps, fp8, tied):
    h = rmsnorm(x, norm.astype(jnp.float32), eps)
    w = (embed.T if tied else w).astype(jnp.float32)
    return mm("kd,dv->kv", h, w, fp8)


_LAYER_KEYS = {"ln1": "stack/ln1/scale", "ln2": "stack/ln2/scale",
               "wq": "stack/attn/wq", "wk": "stack/attn/wk",
               "wv": "stack/attn/wv", "wo": "stack/attn/wo",
               "w_gate": "stack/ffn/w_gate", "w_up": "stack/ffn/w_up",
               "w_down": "stack/ffn/w_down"}


def logits(params: Dict[str, jax.Array], sizes: Dict, tokens: np.ndarray,
           rows: np.ndarray, *, precision: str = "f32", pad: int = 1024,
           block: int = 512) -> np.ndarray:
    """Float32 logits ``(len(rows), vocab)`` of the model over ``tokens``,
    at sequence rows ``rows``.  ``params`` is ``path -> array`` (see
    :func:`layout`)."""
    fp8 = {"f32": False, "fp8": True}[precision]
    t = len(tokens)
    tp = -(-t // pad) * pad
    ids = np.zeros((tp,), np.int32)
    ids[:t] = tokens
    pos = np.arange(tp, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(ids), axis=0
                     ).astype(jnp.float32)
        for li in range(sizes["num_hidden_layers"]):
            p = {k: params[path][li] for k, path in _LAYER_KEYS.items()}
            x = _layer(p, x, jnp.asarray(pos),
                       theta=float(sizes["rope_theta"]),
                       eps=float(sizes["rms_norm_eps"]), fp8=fp8,
                       block=min(block, tp))
        out = _head(params.get("lm_head"), params["embed"],
                    params["final_norm/scale"], x[jnp.asarray(rows)],
                    eps=float(sizes["rms_norm_eps"]), fp8=fp8,
                    tied=bool(sizes["tie_word_embeddings"]))
    return np.asarray(out)


def matmul_params(sizes: Dict) -> Dict[str, int]:
    """Weights multiplied once per token: per layer, and the output head."""
    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    h, hkv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    layer = d * h * hd * 2 + d * hkv * hd * 2 + 3 * d * ff
    return {"layers": sizes["num_hidden_layers"] * layer,
            "head": d * sizes["vocab_size"]}


def attention_flops(blocks: float, block: int, sizes: Dict) -> float:
    """Prefill attention over ``blocks`` causal (block x block) tiles kept
    in each head of each layer: QK^T and PV, summed over heads and
    layers."""
    return (blocks * sizes["num_attention_heads"] * sizes["num_hidden_layers"]
            * (4.0 * sizes["head_dim"] * block * block))


def key_flops(sizes: Dict) -> float:
    """Decode attention per key of context: QK^T and PV, summed over heads
    and layers."""
    return 4.0 * sizes["head_dim"] * sizes["num_attention_heads"] \
        * sizes["num_hidden_layers"]
