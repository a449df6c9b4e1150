"""Sharding rules validity for every arch x mesh, and a REAL small-mesh
dry-run in a subprocess (8 host devices, DP x TP) proving lower+compile."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.config import SHAPES
from repro.configs import get_config, list_archs
from repro.distributed.sharding import batch_specs, cache_specs, param_specs
from repro.models import build_model
from repro.optim.adamw import adamw_init

MESHES = {
    "16x16": AbstractMesh((16, 16), ("data", "model")),
    "2x16x16": AbstractMesh((2, 16, 16), ("pod", "data", "model")),
}


def _check_divisible(tree_specs, tree_leaves, mesh):
    flat_s = jax.tree_util.tree_flatten(
        tree_specs, is_leaf=lambda x: isinstance(x, P))[0]
    flat_l = jax.tree_util.tree_leaves(tree_leaves)
    for spec, leaf in zip(flat_s, flat_l):
        for dim, axis in zip(leaf.shape, tuple(spec)):
            if axis is None:
                continue
            names = axis if isinstance(axis, tuple) else (axis,)
            size = 1
            for n in names:
                size *= dict(mesh.shape)[n]
            assert dim % size == 0, (leaf.shape, spec)


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("arch", list_archs())
def test_param_specs_divisible(arch, mesh_name):
    mesh = MESHES[mesh_name]
    model = build_model(get_config(arch))
    params = model.abstract_params()
    _check_divisible(param_specs(params, mesh), params, mesh)
    opt = jax.eval_shape(adamw_init, params)
    _check_divisible(param_specs(opt, mesh), opt, mesh)


@pytest.mark.parametrize("arch", list_archs())
def test_cache_and_batch_specs_divisible(arch):
    mesh = MESHES["2x16x16"]
    cfg = get_config(arch)
    model = build_model(cfg)
    for shape in SHAPES.values():
        if shape.name == "long_500k" and not cfg.sub_quadratic:
            continue
        specs = model.input_specs(shape)
        if shape.kind == "decode":
            _check_divisible(cache_specs(specs["cache"], mesh),
                             specs["cache"], mesh)
        else:
            _check_divisible(batch_specs(specs, mesh), specs, mesh)


@pytest.mark.slow
def test_small_mesh_dryrun_subprocess():
    """Real lower+compile on an 8-device host mesh (2 data x 4 model)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.config import override, ShapeConfig
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.optim.adamw import adamw_init
        from repro.launch.steps import make_train_step
        from repro.distributed.sharding import (param_specs, batch_specs,
                                                to_shardings)
        from repro.distributed.policy import activation_sharding

        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_smoke_config("qwen1.5-0.5b")
        model = build_model(cfg)
        shape = ShapeConfig("t", 64, 4, "train")
        specs = model.input_specs(shape)
        params = model.abstract_params()
        opt = jax.eval_shape(adamw_init, params)
        with mesh, activation_sharding(mesh, seq_shard=False):
            fn = jax.jit(make_train_step(model, remat=True),
                         in_shardings=(
                             to_shardings(param_specs(params, mesh), mesh),
                             to_shardings(param_specs(opt, mesh), mesh),
                             to_shardings(batch_specs(specs, mesh), mesh)),
                         donate_argnums=(0, 1))
            compiled = fn.lower(params, opt, specs).compile()
        assert compiled.cost_analysis().get("flops", 0) > 0
        print("SMALL-MESH-DRYRUN-OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert "SMALL-MESH-DRYRUN-OK" in out.stdout, out.stderr[-2000:]


def test_roofline_parser_loop_correction():
    """The HLO parser multiplies while-loop bodies by trip count (XLA's
    cost_analysis does not — the §Roofline methodology depends on this)."""
    import jax.numpy as jnp
    from repro.roofline import analyze

    def f(x, w):
        def body(x, wl):
            return jnp.tanh(x @ wl), None
        return jax.lax.scan(body, x, w)[0]

    xs = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 64, 64), jnp.float32)
    txt = jax.jit(f).lower(xs, ws).compile().as_text()
    res = analyze(txt)
    expect = 12 * 2 * 32 * 64 * 64
    assert abs(res["flops"] - expect) / expect < 0.01
    assert 12 in res["trip_counts"]
