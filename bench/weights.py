"""The benchmark's own weights, made from the seed on the device.

The parameter layout of a dense GQA decoder is written here from the
configuration's sizes, path by path.  The harness checks that the
program's parameter tree (``jax.eval_shape`` of its init) has exactly
these paths and shapes, and fills that tree from this generator, so a
later change to the program's init cannot move a cell.  The reference
(``reference.py``) fills its own copy of the layout from the same
generator and the same seed; it takes no weight from the program.

Every matrix is normal with standard deviation ``fan_in ** -0.5``, the
embedding is standard normal, and every norm scale is one.  Each leaf has
its own key, folded from the seed and the leaf's path.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

# leaf name -> number of input (contracted) axes after the layer axis
FAN_IN_AXES = {"wq": 1, "wk": 1, "wv": 1, "wo": 2, "w_gate": 1, "w_up": 1,
               "w_down": 1, "lm_head": 1}


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


def seed_key(seed: int) -> jax.Array:
    s = abs(int(seed))
    key = jax.random.fold_in(jax.random.PRNGKey(0x5EED), s & 0xFFFFFFFF)
    return jax.random.fold_in(key, ((s >> 32) << 1 | (seed < 0)) & 0xFFFFFFFF)


def leaf(key: jax.Array, path: str, shape, dtype) -> jax.Array:
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    if name == "embed":
        std = 1.0
    else:
        lead = 1 if path.startswith("stack/") else 0
        fan_in = 1
        for s in shape[lead: lead + FAN_IN_AXES[name]]:
            fan_in *= s
        std = fan_in ** -0.5
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def make(shapes: Dict[str, Tuple[int, ...]], seed: int,
         dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """``path -> array`` for every path of ``shapes``, in one jitted call
    on the default device."""
    paths = sorted(shapes)

    @jax.jit
    def gen(key):
        return {p: leaf(key, p, shapes[p], dtype) for p in paths}

    return gen(seed_key(seed))


def _path(keys) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in keys)


def fill(tree_shape, sizes: Dict, seed: int, dtype=jnp.bfloat16):
    """The program's parameter tree, filled from this generator.

    ``tree_shape`` is ``jax.eval_shape`` of the program's init; its paths
    and shapes must be exactly :func:`layout`'s."""
    want = layout(sizes)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree_shape)
    got = {_path(kp): tuple(x.shape) for kp, x in flat}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"program parameter tree differs from the "
                         f"benchmark's layout: {diff}")
    made = make(want, seed, dtype)
    return jax.tree_util.tree_unflatten(treedef,
                                        [made[_path(kp)] for kp, _ in flat])
