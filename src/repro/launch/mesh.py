"""Production mesh factory.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis extends
data parallelism across the DCN/ICI boundary.

A FUNCTION, not a module constant — importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before any device query).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """Every mesh of this repo: ``jax.make_mesh`` with Auto axes.

    Since JAX 0.8 ``jax.make_mesh`` defaults to Explicit axes, which
    ``with_sharding_constraint`` (``repro.distributed.sharding.shard``)
    refuses; the sharding rules here annotate and let the compiler
    propagate, which needs Auto axes.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh for unit tests (requires ≥ prod(shape) local devices)."""
    return make_mesh(shape, axes)


def make_serving_mesh(model_parallel: int = 0,
                      data_parallel: int = 1) -> Mesh:
    """(data, model) mesh for the serving launcher over the local devices.

    ``model_parallel=0`` puts every device left over after ``data_parallel``
    on the model axis.  With the model axis non-trivial, a rules context
    built on this mesh makes the engine run sparse prefill *and* sparse
    decode under ``shard_map`` with per-shard index tables (the mesh-active
    routing rule — see ``repro.distributed.sharding.active_model_mesh``).
    """
    n = jax.device_count()
    dp = max(data_parallel, 1)
    mp = model_parallel or max(n // dp, 1)
    if dp * mp > n:
        raise ValueError(f"mesh (data={dp}, model={mp}) needs {dp * mp} "
                         f"devices, have {n}")
    return make_mesh((dp, mp), ("data", "model"))


# TPU v5e hardware constants for the roofline model (per chip).
PEAK_FLOPS_BF16 = 197e12        # 197 TFLOP/s
HBM_BW = 819e9                  # 819 GB/s
ICI_BW = 50e9                   # ~50 GB/s per link
