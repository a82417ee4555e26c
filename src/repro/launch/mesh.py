"""Production mesh construction + sharding-policy factory.

``make_production_mesh`` is a FUNCTION (assignment requirement): importing
this module never touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh

from repro.models.config import ModelConfig
from repro.models.sharding import ShardingPolicy

FSDP_PARAM_THRESHOLD = 8e9  # shard weights over data axis above this


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_policy(cfg: ModelConfig, mesh: Mesh, *, rules=None) -> ShardingPolicy:
    pol = ShardingPolicy(mesh=mesh)
    pol.enable_fsdp = cfg.total_params >= FSDP_PARAM_THRESHOLD
    if rules:
        pol.rules.update(rules)
    return pol
