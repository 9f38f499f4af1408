"""Production meshes and per-arch logical views.

Physical meshes (TPU v5e):
  single-pod : (data=16, model=16)           = 256 chips
  multi-pod  : (pod=2, data=16, model=16)    = 512 chips

Logical view: every arch sees the same devices as (node, fsdp, model).
DFL nodes live on `node`; each node's replica is `model`-way tensor
parallel and `fsdp`-way weight-sharded.  `fsdp` grows (and `node` shrinks)
for archs whose per-node state (params + grads + PME buffer, ~3x params in
bf16) would not fit 16 chips x 16 GB.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.models.config import ModelConfig

__all__ = [
    "make_production_mesh", "make_logical_mesh", "fsdp_degree",
    "HBM_PER_CHIP",
]

HBM_PER_CHIP = 16e9          # v5e
PER_CHIP_PARAM_BUDGET = 8e9  # leave headroom for activations/caches
MODEL_AXIS = 16
STATE_MULTIPLier = 3.0       # params + grads + PME aggregate (no opt state)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def fsdp_degree(cfg: ModelConfig, total_chips: int, model_axis: int = MODEL_AXIS) -> int:
    """Smallest power-of-two fsdp that fits ~3x params in bf16 per node."""
    param_bytes = cfg.param_count() * 2  # bf16
    need = STATE_MULTIPLier * param_bytes / (model_axis * PER_CHIP_PARAM_BUDGET)
    fsdp = 1 if need <= 1 else 2 ** math.ceil(math.log2(need))
    max_fsdp = total_chips // (model_axis * 2)  # keep >= 2 DFL nodes
    return int(max(1, min(fsdp, max_fsdp)))


def make_logical_mesh(
    cfg: ModelConfig,
    *,
    multi_pod: bool = False,
    production: Optional[Mesh] = None,
) -> Mesh:
    """(node, fsdp, model) view over the production device set."""
    prod = production or make_production_mesh(multi_pod=multi_pod)
    devs = np.asarray(prod.devices).reshape(-1)
    total = devs.size
    fsdp = fsdp_degree(cfg, total)
    node = total // (fsdp * MODEL_AXIS)
    return Mesh(
        devs.reshape(node, fsdp, MODEL_AXIS),
        ("node", "fsdp", "model"),
        axis_types=(AxisType.Auto,) * 3,
    )
