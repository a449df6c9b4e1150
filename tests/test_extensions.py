"""Extended coverage: flash-decode kernel, elastic restart, MLA absorbed
decode, gemma3 local/global windows, conversion CLI."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from conftest import make_batch
from repro.config import override
from repro.configs import get_smoke_config
from repro.models import build_model


@pytest.mark.parametrize("bh,t,d,pos", [(4, 100, 32, 63), (2, 512, 64, 511),
                                        (3, 70, 16, 0), (1, 33, 8, 20)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_kernel(bh, t, d, pos, dtype):
    from repro.kernels import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (bh, 1, d), dtype)
    k = jax.random.normal(ks[1], (bh, t, d), dtype)
    v = jax.random.normal(ks[2], (bh, t, d), dtype)
    out = ops.flash_decode(q, k, v, jnp.int32(pos), block_k=64)
    exp = ref.flash_decode_ref(q, k, v, pos)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol,
                               rtol=tol)


def test_mla_absorbed_decode_matches_forward():
    """DeepSeek-v2 decode uses the ABSORBED latent form; it must agree with
    the expanded teacher-forced forward."""
    import dataclasses
    cfg = override(get_smoke_config("deepseek-v2-236b"), dtype="float32")
    # high capacity isolates the MLA property under test: the t=34 forward
    # must not drop MoE assignments the drop-free decode path computes
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=8.0))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 17, seed=3)
    full = model.forward(params, {"tokens": batch["tokens"]})
    _, cache = model.prefill(params, {"tokens": batch["tokens"][:, :16]},
                             max_len=18)
    logits, _ = model.decode_step(params, batch["tokens"][:, 16:17],
                                  cache, jnp.int32(16))
    np.testing.assert_allclose(np.asarray(full[:, 16]), np.asarray(logits),
                               atol=3e-4, rtol=3e-4)


def test_gemma3_window_pattern_and_parity():
    from repro.models.model import layer_windows
    cfg = override(get_smoke_config("gemma3-4b"), dtype="float32")
    w = np.asarray(layer_windows(cfg))
    assert (w == 0).sum() == cfg.num_layers // (cfg.local_global_ratio + 1)
    assert set(w.tolist()) == {0, cfg.sliding_window}
    # decode parity through the mixed local/global stack
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 25, seed=4)   # > sliding_window=16
    full = model.forward(params, {"tokens": batch["tokens"]})
    _, cache = model.prefill(params, {"tokens": batch["tokens"][:, :24]},
                             max_len=26)
    logits, _ = model.decode_step(params, batch["tokens"][:, 24:25],
                                  cache, jnp.int32(24))
    np.testing.assert_allclose(np.asarray(full[:, 24]), np.asarray(logits),
                               atol=3e-4, rtol=3e-4)


def test_elastic_mesh_planning():
    from repro.distributed.elastic import plan_elastic_mesh, reshard_tree
    # degenerate single-device case (this container)
    mesh = plan_elastic_mesh(1, model_parallel=16)
    assert mesh.devices.size == 1
    tree = {"w": jnp.ones((32, 64)), "b": jnp.zeros((64,))}
    out = reshard_tree(tree, mesh)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))


def test_elastic_restore_roundtrip(tmp_path, qwen_smoke):
    from repro.checkpoint import CheckpointManager
    from repro.distributed.elastic import elastic_restore
    cfg, model, params = qwen_smoke
    mgr = CheckpointManager(str(tmp_path), keep=1)
    mgr.save(3, {"params": params}, {"step": 3}, block=True)
    tree, extra, mesh = elastic_restore(mgr, {"params": params},
                                        model_parallel=4)
    assert extra["step"] == 3
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(
            {"params": params})):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_convert_cli_roundtrip(tmp_path):
    from repro.launch.convert import main as convert_main
    from repro.checkpoint import CheckpointManager
    out = str(tmp_path / "cmoe")
    rc = convert_main(["--arch", "qwen1.5-0.5b", "--smoke",
                       "--cmoe", "S3A3E8", "--calib-samples", "2",
                       "--calib-seq", "64", "--out", out])
    assert rc == 0
    mgr = CheckpointManager(out)
    assert mgr.latest_step() == 0
    # converted checkpoint loads into a converted-config model
    from repro.config import CMoEConfig
    cfg = override(get_smoke_config("qwen1.5-0.5b"), dtype="float32")
    k_act = max(2, cfg.d_ff // 32)
    cm = CMoEConfig(num_experts=8, num_shared=3, top_k=3,
                    k_activation=k_act)
    m2 = build_model(cfg.with_cmoe(cm))
    target = m2.init(jax.random.PRNGKey(0))
    (state, extra) = mgr.restore({"params": target})
    assert extra["cmoe"] == "S3A3E8"
    batch = make_batch(cfg, 2, 16, seed=5)
    loss, _ = m2.loss(state["params"], batch)
    assert np.isfinite(float(loss))


def test_moe_local_dispatch_matches_global_single_device():
    """shard_map local dispatch == global dispatch on the trivial mesh."""
    import dataclasses
    from repro.models.moe import init_moe_ffn, moe_ffn, moe_ffn_local
    cfg = override(get_smoke_config("deepseek-v2-236b"), dtype="float32")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=4.0, num_shared=0))
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    p = init_moe_ffn(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y1, _ = moe_ffn(x, p, cfg)
    with mesh:
        y2, _ = jax.jit(lambda x, p: moe_ffn_local(x, p, cfg, mesh))(x, p)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
