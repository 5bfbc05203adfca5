"""The benchmark's own weights, made from the seed on the device.

The parameter layout is the configuration's family's (``layout`` in
``bench/families/<family>.py``), written there from the configuration's
sizes, path by path.  The harness checks that the program's parameter tree
(``jax.eval_shape`` of its init) has exactly those paths and shapes, and
fills that tree from this generator, so a later change to the program's
init cannot move a cell.  The reference fills its own copy of the layout
from the same generator and the same seed; it takes no weight from the
program.

Every norm scale (a leaf named ``scale``) is one; every other leaf is
normal with standard deviation ``fan_in ** -0.5``, its contracted size as
the family's ``fan_in`` gives it (1 for an embedding).  Each leaf has its
own key, folded from the seed and the leaf's path.
"""
from __future__ import annotations

import zlib
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    s = abs(int(seed))
    key = jax.random.fold_in(jax.random.PRNGKey(0x5EED), s & 0xFFFFFFFF)
    return jax.random.fold_in(key, ((s >> 32) << 1 | (seed < 0)) & 0xFFFFFFFF)


def leaf(key: jax.Array, path: str, shape, dtype,
         fan_in: Callable[[str, Tuple[int, ...]], int]) -> jax.Array:
    if path.rsplit("/", 1)[-1] == "scale":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    std = fan_in(path, shape) ** -0.5
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def make(family, sizes: Dict, seed: int,
         dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """``path -> array`` for every path of the ``family``'s
    ``layout(sizes)``, in one jitted call on the default device."""
    shapes = family.layout(sizes)
    paths = sorted(shapes)

    @jax.jit
    def gen(key):
        return {p: leaf(key, p, shapes[p], dtype, family.fan_in)
                for p in paths}

    return gen(seed_key(seed))


def _path(keys) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in keys)


def fill(tree_shape, family, sizes: Dict, seed: int, dtype=jnp.bfloat16):
    """The program's parameter tree, filled from this generator.

    ``tree_shape`` is ``jax.eval_shape`` of the program's init; its paths
    and shapes must be exactly the ``family``'s ``layout(sizes)``."""
    want = family.layout(sizes)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree_shape)
    got = {_path(kp): tuple(x.shape) for kp, x in flat}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"program parameter tree differs from the "
                         f"benchmark's layout: {diff}")
    made = make(family, sizes, seed, dtype)
    return jax.tree_util.tree_unflatten(treedef,
                                        [made[_path(kp)] for kp, _ in flat])
