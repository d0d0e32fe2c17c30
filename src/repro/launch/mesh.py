"""Production mesh construction.

Functions only — importing this module never touches jax device state, so the
dry-run's XLA_FLAGS device-count override (set before any import) stays in
control of how many host devices exist.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

from repro.configs.base import MeshConfig, ModelConfig
from repro.sharding import ShardingRules, default_rules


def make_mesh(shape, axes, devices=None) -> Mesh:
    """jax.make_mesh with ``Auto`` axes: the sharding rules and the
    trainer's ``with_sharding_constraint`` calls assume the compiler
    propagates shardings (a bare ``jax.make_mesh`` gives ``Explicit``
    axes, which refuse them).  ``devices`` defaults to all of them."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) = 256 chips ("data", "model").
    Multi-pod: (2, 16, 16) = 512 chips ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_from_config(mc: MeshConfig) -> Mesh:
    return make_mesh(mc.shape, mc.axes)


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = jax.device_count()
    if data * model > n:
        data, model = n, 1
    return make_mesh((data, model), ("data", "model"))


def rules_for(cfg: ModelConfig, mesh: Mesh,
              shape_kind: str = "train") -> ShardingRules:
    """Arch/shape-aware sharding rules (the dry-run baseline policy).

    - kv_heads not divisible by the model axis -> shard the KV-cache's
      sequence dim over "model" instead (decode memory would otherwise
      replicate a multi-GB cache 16x).
    - decode/long shapes with batch smaller than the batch mesh axes ->
      nothing to do; divisibility fallback replicates automatically.
    """
    multi_pod = "pod" in mesh.shape
    rules = default_rules(multi_pod=multi_pod)
    tp = mesh.shape.get("model", 1)
    if shape_kind in ("decode", "prefill"):
        if cfg.num_kv_heads % tp == 0:
            rules = rules.with_overrides(cache_seq=None)
        else:
            rules = rules.with_overrides(cache_seq="model")
    return rules
