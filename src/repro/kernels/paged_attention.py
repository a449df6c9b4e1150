"""Paged-attention decode Pallas kernels (TPU target): single-query
attention straight against the serving engine's BLOCK POOL.

The reference paged path (`models.attention.paged_view`) gathers every
lane's logical (B, T) cache view per layer before attending — correct,
and kept as the parity oracle, but it re-materializes the whole window
in HBM at exactly the full-slot-width decode scale the pool exists for.
These kernels never assemble a logical view: the per-lane block tables
ride SCALAR PREFETCH (the owner-id-prefetch pattern `moe_gmm_ragged`
established), so each grid step's BlockSpec index_map points the K/V DMA
at ONE live physical block — `table[b, j]` — and the body runs a running
online softmax over the blocks in VMEM scratch. HBM traffic per lane is
exactly its live blocks, once.

Masking is by per-slot logical length: positions > pos[b] (the token
being decoded, already written by `paged_cache_update`) are NEG_INF'd,
which also covers unallocated table entries (they sit past the valid
length and point at the trash block 0 anyway).

Two families share the pattern:

``paged_attn_decode`` — GQA. Grid (B, nblk), nblk innermost
    (sequential on TPU -> scratch carries). Each step DMAs one whole
    (bs, KH, hd) physical block and attends each kv head's slab with
    the `grp = H // KH` query heads that share it; supports the
    per-layer sliding window as a prefetched scalar (traced per-layer
    values allowed).

``mla_paged_decode`` — MLA absorbed decode. The pool holds the latent
    (bs, r) + rope-key (bs, dr) blocks; scores are
    (q_abs · c_t + q_pe · k_pe_t) * scale and the value accumulation
    stays in latent space (the caller expands through W_uv), so the
    kernel never touches per-head K/V at all.

Inference only: no VJP (decode kernels sit behind ``use_kernel``, which
autodiff callers must leave off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _gqa_kernel(tbl_ref, pos_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                m_ref, l_ref, acc_ref, *, scale: float, block_size: int,
                kv_heads: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kpos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)               # (1, bs) logical pos
    pos = pos_ref[b]
    win = win_ref[0]
    mask = kpos <= pos
    # (win <= 0) | ...: Mosaic cannot lower a select between bool vectors
    mask &= (win <= 0) | (kpos > pos - win)
    # one DMA brought the whole (bs, KH, hd) block; each kv head's
    # (bs, hd) slab is a strided VMEM read, attended by its grp queries
    for h in range(kv_heads):
        q = q_ref[0, h]                              # (grp, hd)
        k = k_ref[0, :, h, :]                        # (bs, hd)
        v = v_ref[0, :, h, :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attn_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      table: jax.Array, pos: jax.Array, window: jax.Array,
                      *, scale: float, interpret: bool = True) -> jax.Array:
    """q: (B, KH, grp, hd) grouped queries; k_pool/v_pool:
    (nblocks, bs, KH, hd) block pools; table: (B * nblk,) int32 flattened
    block tables; pos: (B,) int32 per-lane last valid logical index;
    window: (1,) int32 sliding window (0 = full). Returns (B, KH, grp,
    hd). The table/pos/window arrive as scalar prefetch so each block's
    DMA is issued from table[b * nblk + j] before the body runs. A K/V
    block spans all KV heads: the TPU lowering needs a block's two minor
    dims to be (8, 128)-aligned or whole, and a one-head (1, hd) slice
    of the (KH, hd) minor dims is neither."""
    b, kh, grp, hd = q.shape
    bs = k_pool.shape[1]
    nblk = table.shape[0] // b
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, kh, grp, hd),
                         lambda bb, j, tbl, ps, w: (bb, 0, 0, 0)),
            pl.BlockSpec((1, bs, kh, hd),
                         lambda bb, j, tbl, ps, w:
                         (tbl[bb * nblk + j], 0, 0, 0)),
            pl.BlockSpec((1, bs, kh, hd),
                         lambda bb, j, tbl, ps, w:
                         (tbl[bb * nblk + j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kh, grp, hd),
                               lambda bb, j, tbl, ps, w: (bb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kh, grp, 1), jnp.float32),
            pltpu.VMEM((kh, grp, 1), jnp.float32),
            pltpu.VMEM((kh, grp, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_gqa_kernel, scale=scale, block_size=bs,
                          kv_heads=kh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, grp, hd), q.dtype),
        interpret=interpret,
    )(table, pos, window, q, k_pool, v_pool)


def _mla_kernel(tbl_ref, pos_ref, qa_ref, qp_ref, cc_ref, cp_ref, o_ref,
                m_ref, l_ref, acc_ref, *, scale: float, block_size: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qa = qa_ref[0]                                   # (H, r)
    qp = qp_ref[0]                                   # (H, dr)
    cc = cc_ref[0]                                   # (bs, r)
    cp = cp_ref[0]                                   # (bs, dr)
    s = (jnp.dot(qa, cc.T, preferred_element_type=jnp.float32) +
         jnp.dot(qp, cp.T, preferred_element_type=jnp.float32)) * scale
    kpos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    s = jnp.where(kpos <= pos_ref[b], s, NEG_INF)    # (H, bs)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p.astype(cc.dtype), cc, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def mla_paged_decode(q_abs: jax.Array, q_pe: jax.Array, cc_pool: jax.Array,
                     cp_pool: jax.Array, table: jax.Array, pos: jax.Array,
                     *, scale: float, interpret: bool = True) -> jax.Array:
    """q_abs: (B, H, r) queries absorbed through W_uk; q_pe: (B, H, dr)
    rope queries; cc_pool: (nblocks, bs, r) latent pool; cp_pool:
    (nblocks, bs, dr) rope-key pool; table: (B * nblk,) int32; pos: (B,)
    int32. Returns o_lat (B, H, r) — the softmax-weighted latent (caller
    expands through W_uv)."""
    b, h, r = q_abs.shape
    dr = q_pe.shape[-1]
    bs = cc_pool.shape[1]
    nblk = table.shape[0] // b
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda bb, j, tbl, ps: (bb, 0, 0)),
            pl.BlockSpec((1, h, dr), lambda bb, j, tbl, ps: (bb, 0, 0)),
            pl.BlockSpec((1, bs, r),
                         lambda bb, j, tbl, ps: (tbl[bb * nblk + j], 0, 0)),
            pl.BlockSpec((1, bs, dr),
                         lambda bb, j, tbl, ps: (tbl[bb * nblk + j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, r), lambda bb, j, tbl, ps: (bb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, r), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, block_size=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), q_abs.dtype),
        interpret=interpret,
    )(table, pos, q_abs, q_pe, cc_pool, cp_pool)
