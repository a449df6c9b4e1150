"""Gather decode MoE Pallas kernel (TPU target): per-assignment expert
FFN rows without materializing gathered weight copies.

The XLA lowering of the ``gather`` backend (`core.experts._gather`)
builds (T*k, d, m) / (T*k, m, d) gathered WEIGHT buffers via ``jnp.take``
before its batched einsums — fine at decode T, but the copies are pure
HBM traffic that grows with the batch and is why gather loses to grouped
past the measured crossover. Here the flat per-assignment expert ids ride
SCALAR PREFETCH (the same owner-id pattern as ``moe_gmm_ragged``), so
grid step (i, k)'s BlockSpec index_maps DMA expert ``eidx[i]``'s live
(d, bm)/(bm, d) slabs straight from the stacked banks — the only weight
bytes moved are the k live slabs each token actually routes through.

Grid (T*k, m/bm), bm innermost sequential: the fused glu body
(gate ⊙ up -> down) accumulates the down-projection over m-chunks in a
(1, d) VMEM scratch, mirroring ``moe_gmm``'s accumulation exactly. The
token row for assignment i is ``xf[i // top_k]`` (index_map arithmetic —
no repeated activation buffer either).

glu families only (gate/up/down), matching ``moe_gmm``; non-glu banks
stay on the XLA gather path. Inference only: no VJP.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(eidx_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *,
            activation: str, num_experts: int):
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # invalidated assignments (per-token k / padding) carry the sentinel
    # id E: their index_maps aim at slab 0 (dead runs coalesce to at most
    # one redundant fetch — consecutive identical block indices are not
    # re-DMA'd) and the FLOPs are skipped entirely; the output row stays
    # the zeroed accumulator, matching the zeroed gate downstream
    @pl.when(eidx_ref[i] < num_experts)
    def _():
        x = x_ref[0]                                 # (1, d)
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        if activation == "swiglu":
            h = g * jax.nn.sigmoid(g) * u
        else:
            h = jax.nn.gelu(g) * u
        acc_ref[...] += jnp.dot(h.astype(x.dtype), wd_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_gather(xf: jax.Array, eidx: jax.Array, wg: jax.Array, wu: jax.Array,
               wd: jax.Array, *, top_k: int, activation: str = "swiglu",
               block_m: int = 128, interpret: bool = True) -> jax.Array:
    """xf: (T, d) token activations; eidx: (T*k,) int32 flat expert id per
    assignment (row i serves token i // top_k), in [0, E] where the
    SENTINEL id E marks an invalidated assignment (a token routing fewer
    than K_max experts under per-row activation tiers, or padding):
    sentinel rows DMA no live weight slab (their index_maps collapse to
    slab 0, coalescing consecutive dead fetches), run no FLOPs, and
    output a zero row. wg/wu: (E, d, m); wd: (E, m, d) -> (T*k, d)
    per-assignment expert outputs (pre gate-weight combine). Caller pads
    m to a block_m multiple.

    Token rows and output rows move as (1, 1, d) blocks of (T, 1, d) /
    (T*k, 1, d) views: the TPU lowering needs a block's two minor dims to
    be (8, 128)-aligned or whole, which a (1, d) row of a (T, d) array is
    not (and a squeezed row dim is refused the same way)."""
    t, d = xf.shape
    n_e = wg.shape[0]
    m = wg.shape[2]
    assert m % block_m == 0, (m, block_m)
    n = eidx.shape[0]
    assert n == t * top_k, (n, t, top_k)

    def slab(e, i):
        # sentinel-safe slab index: dead rows all aim at slab 0, so a run
        # of them re-uses one resident block instead of E-1's slab
        return jnp.where(e[i] < n_e, e[i], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, m // block_m),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, k, e: (i // top_k, 0, 0)),
            pl.BlockSpec((1, d, block_m), lambda i, k, e: (slab(e, i), 0, k)),
            pl.BlockSpec((1, d, block_m), lambda i, k, e: (slab(e, i), 0, k)),
            pl.BlockSpec((1, block_m, d), lambda i, k, e: (slab(e, i), k, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, k, e: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation, num_experts=n_e),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), xf.dtype),
        interpret=interpret,
    )(eidx, xf.reshape(t, 1, d), wg, wu, wd)
    return out.reshape(n, d)
