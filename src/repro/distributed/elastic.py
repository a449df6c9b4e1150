"""Elastic restart: resume a checkpoint on a DIFFERENT device count.

At 1000+ nodes the practical failure mode is losing a host (or a whole
pod) and restarting on the surviving fleet. Because checkpoints store
UNSHARDED host arrays (repro/checkpoint) and every sharding in this
framework is derived from (tree, mesh) by `repro.distributed.sharding`,
elasticity is: build the new mesh, re-derive specs, `device_put`.

`plan_elastic_mesh` picks the largest valid (data, model) factorization of
the surviving chip count, preferring to SHRINK the data axis first (model
parallel degree is a property of the model, data parallelism of the
fleet); `reshard_tree` moves a restored tree onto the new mesh.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding

from repro.distributed.sharding import param_specs, to_shardings


def plan_elastic_mesh(num_devices: int, *, model_parallel: int = 16,
                      devices=None) -> Mesh:
    """Largest usable (data, model) mesh from the surviving devices.
    Drops stragglers that don't fit the factorization (they rejoin as
    spares)."""
    devices = list(devices if devices is not None else jax.devices())
    num_devices = min(num_devices, len(devices))
    mp = model_parallel
    while mp > 1 and num_devices % mp:
        mp //= 2
    dp = num_devices // mp
    used = devices[:dp * mp]
    return jax.make_mesh((dp, mp), ("data", "model"), devices=used,
                         axis_types=(AxisType.Auto,) * 2)


def reshard_tree(tree, mesh: Mesh):
    """Re-shard a (restored, host-resident) tree for the new mesh."""
    sh = to_shardings(param_specs(tree, mesh), mesh)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, sh)


def elastic_restore(mgr, target_tree, *, model_parallel: int = 16,
                    step: Optional[int] = None):
    """CheckpointManager.restore + reshard onto a mesh built from whatever
    devices exist NOW. Returns (tree, extra_state, mesh)."""
    mesh = plan_elastic_mesh(len(jax.devices()),
                             model_parallel=model_parallel)
    tree, extra = mgr.restore(target_tree, step=step)
    with mesh:
        tree = reshard_tree(tree, mesh)
    return tree, extra, mesh
