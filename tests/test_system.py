"""End-to-end behaviour tests for the CMoE system."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch
from repro.config import CMoEConfig, override
from repro.configs import get_smoke_config
from repro.core.convert import convert_dense_model, reconstruction_error
from repro.data import ShardedLoader
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim.adamw import adamw_init

CM_JV = CMoEConfig(num_experts=8, num_shared=3, top_k=3, k_activation=4,
                   assignment="jv")


def test_training_reduces_loss(qwen_smoke):
    cfg, model, params = qwen_smoke
    params = model.init(jax.random.PRNGKey(7))
    opt = adamw_init(params)
    loader = ShardedLoader(cfg.vocab_size, 4, 64, seed=0)
    step = jax.jit(make_train_step(model, lr=1e-3, warmup=3, total=30,
                                   remat=False))
    losses = []
    for _ in range(30):
        batch = {"tokens": jnp.asarray(next(loader)["tokens"])}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.05, (losses[0], losses[-1])


def test_conversion_exactness_all_active(qwen_smoke):
    """The core CMoE invariant: activating every routed expert reproduces
    the dense model exactly (partition is a permutation)."""
    cfg, model, params = qwen_smoke
    calib = make_batch(cfg, 4, 64, seed=3)
    cm_all = CMoEConfig(num_experts=8, num_shared=3, top_k=5,
                        k_activation=4, assignment="jv")
    m2, p2, _ = convert_dense_model(model, params, calib, cm_all)
    batch = make_batch(cfg, 2, 48, seed=4)
    h1 = model.hidden_states(params, batch)
    h2 = m2.hidden_states(p2, batch)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=5e-5, rtol=1e-4)


def test_conversion_sparse_quality(qwen_smoke):
    """S3A3E8 (25% sparsity) reconstruction error is small relative to the
    hidden-state scale."""
    cfg, model, params = qwen_smoke
    calib = make_batch(cfg, 4, 64, seed=3)
    m2, p2, rep = convert_dense_model(model, params, calib, CM_JV)
    batch = make_batch(cfg, 2, 48, seed=4)
    err = reconstruction_error(model, params, m2, p2, batch)
    scale = float(jnp.mean(jnp.sum(
        model.hidden_states(params, batch).astype(jnp.float32) ** 2, -1)))
    assert err < 0.5 * scale, (err, scale)
    assert rep.num_layers == cfg.num_layers


def test_prefill_decode_matches_forward(qwen_smoke):
    """Serving parity: prefill(S) + decode == teacher-forced forward."""
    cfg, model, params = qwen_smoke
    batch = make_batch(cfg, 2, 17, seed=9)
    full = model.forward(params, {"tokens": batch["tokens"]})
    logits_p, cache = model.prefill(
        params, {"tokens": batch["tokens"][:, :16]}, max_len=18)
    np.testing.assert_allclose(np.asarray(full[:, 15]),
                               np.asarray(logits_p), atol=2e-4, rtol=2e-4)
    logits_d, _ = model.decode_step(params, batch["tokens"][:, 16:17],
                                    cache, jnp.int32(16))
    np.testing.assert_allclose(np.asarray(full[:, 16]),
                               np.asarray(logits_d), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("arch", ["mamba2-370m", "zamba2-1.2b"])
def test_prefill_decode_matches_forward_ssm(arch):
    cfg = override(get_smoke_config(arch), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 17, seed=9)
    full = model.forward(params, {"tokens": batch["tokens"]})
    logits_p, cache = model.prefill(
        params, {"tokens": batch["tokens"][:, :16]}, max_len=18)
    np.testing.assert_allclose(np.asarray(full[:, 15]),
                               np.asarray(logits_p), atol=3e-4, rtol=3e-4)
    logits_d, _ = model.decode_step(params, batch["tokens"][:, 16:17],
                                    cache, jnp.int32(16))
    np.testing.assert_allclose(np.asarray(full[:, 16]),
                               np.asarray(logits_d), atol=3e-4, rtol=3e-4)


def test_converted_model_trains(qwen_smoke):
    """Post-conversion fine-tuning path: gradients flow through the sparse
    FFN (learnable scaling + LoRA-able weights)."""
    cfg, model, params = qwen_smoke
    calib = make_batch(cfg, 4, 64, seed=3)
    m2, p2, _ = convert_dense_model(model, params, calib, CM_JV)
    batch = make_batch(cfg, 2, 32, seed=5)
    g = jax.grad(lambda p: m2.loss(p, batch)[0])(p2)
    u_grad = g["blocks"]["cmoe"]["u"]
    assert jnp.any(u_grad != 0), "scaling params receive no gradient"
    gn = sum(float(jnp.sum(x.astype(jnp.float32) ** 2))
             for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


def test_compile_cache_placement(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and nothing is
    overridden; otherwise the cache is the one fixed, git-ignored
    directory in the checkout. Nothing is compiled, so nothing is
    written."""
    from pathlib import Path
    from repro.launch.compile_cache import enable_compile_cache
    repo = Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            repo / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()


def test_chip_smoke_refuses_without_tpu(capsys):
    """Off a TPU the chip smoke exits nonzero before doing any work and
    prints no result line."""
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert jax.default_backend() != "tpu"
    assert mod.main() != 0
    assert '"ok"' not in capsys.readouterr().out
