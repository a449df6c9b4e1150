"""Production mesh construction.

Physical topology (TPU v5e target):
  single pod: 16 x 16 = 256 chips  -> axes (data, model)
  multi  pod:  2 x 16 x 16 = 512   -> axes (pod, data, model)

Logical mapping (see repro/distributed/sharding.py):
  batch/FSDP over (pod, data); TP + EP (+ sequence/KV sharding for long
  context) over model. The `pod` axis defaults to pure data parallelism so
  cross-pod traffic is one gradient reduce-scatter per step (DCI-friendly);
  gradient compression (repro/optim/compress.py) applies there.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        shape = (2, 16, 16)
        axes = ("pod", "data", "model")
    else:
        shape = (16, 16)
        axes = ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(see repro/launch/dryrun.py)")
    return jax.make_mesh(shape, axes, devices=devices[:need],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh(model_parallel: int = 1):
    """Small real-device mesh for tests / local runs."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def dp_axis_names(mesh) -> tuple[str, ...]:
    return tuple(n for n in mesh.axis_names if n in ("pod", "data"))
