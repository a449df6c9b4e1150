"""Compile the served path's Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see the Mosaic lowering's
rules: block tiling, VMEM limits, supported ops. These tests compile each
hot-path kernel with ``interpret=False`` at real widths in bf16 against a
``v5e:2x2`` topology that is described, not attached, and check that the
result holds a ``tpu_custom_call``. Nothing runs.

- ``paged_attn_decode``, ``moe_gather``, ``moe_gmm_ragged``: qwen1.5-0.5b
  converted S3A3E8 (5 routed experts of width 352, top-3), at the fused
  widths the chip smoke serves (4 slots, 64-token prefill budget, 16-token
  blocks, 144-token lanes).
- ``mla_paged_decode``: deepseek-v2 latent widths (r=512, dr=64, 128
  heads).
- the whole fused paged serving step of the converted qwen1.5-0.5b with
  kernels on, at a decode-only width (gather) and at the widest fused
  width (grouped), from ``jax.eval_shape`` shapes.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.moe_gather import moe_gather
from repro.kernels.moe_gmm import moe_gmm_ragged
from repro.kernels.paged_attention import mla_paged_decode, paged_attn_decode

BF16 = jnp.bfloat16
SLOTS, BUDGET, BLOCK, MAX_LEN = 4, 64, 16, 144
FUSED_WIDTH = SLOTS + BUDGET          # widest fused step: lanes + a chunk
NBLK = MAX_LEN // BLOCK
NUM_BLOCKS = SLOTS * NBLK + 1         # + the trash block
NUM_EXPERTS, NUM_SHARED, TOP_K = 8, 3, 3
BLOCK_M = 128                         # expert width is padded to this
RAGGED_BLOCK_C = 128                  # ops.ragged_block_c() on a TPU


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """The first described chip, with the persistent compilation cache
    off: an entry compiled for a described chip cannot be read back
    without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _round_up(x, m):
    return -(-x // m) * m


def _qwen_cases():
    cfg = get_config("qwen1.5-0.5b")
    kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    grp = cfg.num_heads // kh
    d = cfg.d_model
    e = NUM_EXPERTS - NUM_SHARED
    m = _round_up(cfg.d_ff // NUM_EXPERTS, BLOCK_M)
    pool = (NUM_BLOCKS, BLOCK, kh, hd)
    gather_t = 8                     # widest width the policy sends to gather
    p_rows = _round_up(FUSED_WIDTH * TOP_K + e * (RAGGED_BLOCK_C - 1),
                       RAGGED_BLOCK_C)
    return {
        "paged_attn_decode": (
            lambda q, kp, vp, t, p, w: paged_attn_decode(
                q, kp, vp, t, p, w, scale=hd ** -0.5, interpret=False),
            [((FUSED_WIDTH, kh, grp, hd), BF16), (pool, BF16), (pool, BF16),
             ((FUSED_WIDTH * NBLK,), jnp.int32), ((FUSED_WIDTH,), jnp.int32),
             ((1,), jnp.int32)]),
        "moe_gather": (
            lambda x, ei, wg, wu, wd: moe_gather(
                x, ei, wg, wu, wd, top_k=TOP_K, block_m=BLOCK_M,
                interpret=False),
            [((gather_t, d), BF16), ((gather_t * TOP_K,), jnp.int32),
             ((e, d, m), BF16), ((e, d, m), BF16), ((e, m, d), BF16)]),
        "moe_gmm_ragged": (
            lambda x, own, wg, wu, wd: moe_gmm_ragged(
                x, own, wg, wu, wd, block_c=RAGGED_BLOCK_C, block_m=BLOCK_M,
                interpret=False),
            [((p_rows, d), BF16), ((p_rows // RAGGED_BLOCK_C,), jnp.int32),
             ((e, d, m), BF16), ((e, d, m), BF16), ((e, m, d), BF16)]),
    }


def _mla_case():
    cfg = get_config("deepseek-v2-236b")
    h, r, dr = cfg.num_heads, cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
    b = SLOTS
    return (
        lambda qa, qp, cc, cp, t, p: mla_paged_decode(
            qa, qp, cc, cp, t, p, scale=(r + dr) ** -0.5, interpret=False),
        [((b, h, r), BF16), ((b, h, dr), BF16),
         ((NUM_BLOCKS, BLOCK, r), BF16), ((NUM_BLOCKS, BLOCK, dr), BF16),
         ((b * NBLK,), jnp.int32), ((b,), jnp.int32)])


@pytest.mark.parametrize("kernel", ["paged_attn_decode", "moe_gather",
                                    "moe_gmm_ragged", "mla_paged_decode"])
def test_kernel_compiles_for_v5e(kernel, one_chip):
    fn, shapes = _mla_case() if kernel == "mla_paged_decode" \
        else _qwen_cases()[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem is not None and mem.argument_size_in_bytes > 0


@pytest.mark.parametrize("rows,kernels", [
    (SLOTS, ("paged_attn_decode", "moe_gather")),
    (FUSED_WIDTH, ("paged_attn_decode", "moe_gmm_ragged"))])
def test_fused_paged_step_compiles_for_v5e(rows, kernels, one_chip,
                                           monkeypatch):
    """The served step, not just its kernels: params and pool are shapes
    only. The kernel wrappers and the segment GEMM pick their TPU
    branches from the platform, which is the CPU here, so the test
    steers them to what they pick on a chip."""
    from repro.core import experts
    from repro.kernels import ops
    from repro.launch.serve import parse_sxayez
    from repro.models import build_model
    from repro.serving.executor import StepExecutor
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(experts, "_use_ragged_dot", lambda: True)
    cm = parse_sxayez(f"S{NUM_SHARED}A{TOP_K}E{NUM_EXPERTS}")
    model = build_model(get_config("qwen1.5-0.5b").with_cmoe(cm),
                        use_kernel=True)
    ex = StepExecutor(model)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    backend = ex._backend(rows, "mixed")
    compiled = ex._step_fused_paged.lower(
        place(model.abstract_params()),
        place(model.init_paged_cache(NUM_BLOCKS, BLOCK, abstract=True)),
        arg((rows,)), arg((rows,), jnp.bool_), arg((SLOTS,)), arg((rows,)),
        arg((rows, NBLK)), arg((rows,)), arg((rows,)), arg((rows,)),
        arg((rows,), jnp.bool_), None, backend=backend).compile()
    text = compiled.as_text()
    for name in kernels:
        assert f"jit({name})/pallas_call" in text, name
    assert "tpu_custom_call" in text
