"""Attention: chunked (flash-style) causal attention in pure JAX, decode
attention against a KV cache, GQA, sliding windows, and MLA (DeepSeek-v2).

The chunked path never materializes the full (S, S) score matrix: it scans
over KV blocks with an online-softmax running (max, sum, acc). This is the
memory-safe reference; `repro.kernels.flash_attention` is the Pallas TPU
version validated against it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, matmul

Array = jax.Array

NEG_INF = -1e30


def _repeat_kv(k: Array, num_heads: int) -> Array:
    """(B, T, KH, D) -> (B, T, H, D) by repeating each kv head."""
    b, t, kh, d = k.shape
    if kh == num_heads:
        return k
    reps = num_heads // kh
    return jnp.repeat(k, reps, axis=2)


def _mask_block(qp: Array, kp: Array, *, causal: bool, window: Array,
                t_valid: int) -> Array:
    """(cq, ck) bool mask from float position vectors (float so the flash
    custom_vjp can treat window/offset as differentiable-dtype args with
    zero cotangents)."""
    mask = kp[None, :] < float(t_valid)
    if causal:
        mask = mask & (kp[None, :] <= qp[:, None])
    in_win = jnp.where(window > 0, kp[None, :] > qp[:, None] - window, True)
    return mask & in_win


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, window, q_offset,
           causal: bool, scale: float, chunk_q: int, chunk_kv: int,
           t_valid: int):
    """Blocked attention with flash-style forward AND backward (the
    backward recomputes score blocks per tile — no (S, T) residuals).

    q: (B, nq, cq, H, D); k: (B, nkv, ck, H, D); v: (..., Dv);
    window/q_offset: f32 scalars (traced per-layer values allowed).
    """
    out, _ = _flash_fwd_impl(q, k, v, window, q_offset, causal, scale,
                             chunk_q, chunk_kv, t_valid)
    return out


def _flash_fwd_impl(q, k, v, window, q_offset, causal, scale, chunk_q,
                    chunk_kv, t_valid):
    b, nq, cq, h, d = q.shape
    nkv, ck = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    kv_pos = jnp.arange(nkv * ck, dtype=jnp.float32).reshape(nkv, ck)
    q_pos = q_offset + jnp.arange(nq * cq, dtype=jnp.float32).reshape(
        nq, cq)

    def q_block(args):
        qb, qp = args                                   # (B,cq,H,D), (cq,)

        def kv_step(carry, inp):
            # named scope: everything here lives in VMEM inside the Pallas
            # flash kernel — the roofline analyzer skips its HBM bytes
            with jax.named_scope("flash_vmem"):
                m, l, acc = carry
                kb, vb, kp = inp
                s_blk = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                                   preferred_element_type=jnp.float32) * scale
                mask = _mask_block(qp, kp, causal=causal, window=window,
                                   t_valid=t_valid)
                s_blk = jnp.where(mask[None, None], s_blk, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
                p = jnp.exp(s_blk - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bhqk,bkhd->bhqd", p.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32)
                return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, cq), jnp.float32)
        a0 = jnp.zeros((b, h, cq, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (k.swapaxes(0, 1), v.swapaxes(0, 1), kv_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), 1e30)
        return out.swapaxes(1, 2).astype(v.dtype), lse  # (B,cq,H,Dv)

    out, lse = jax.lax.map(q_block, (q.swapaxes(0, 1), q_pos))
    return out.swapaxes(0, 1), lse.swapaxes(0, 1)       # lse: (B,nq,H,cq)


def _flash_fwd(q, k, v, window, q_offset, causal, scale, chunk_q, chunk_kv,
               t_valid):
    out, lse = _flash_fwd_impl(q, k, v, window, q_offset, causal, scale,
                               chunk_q, chunk_kv, t_valid)
    return out, (q, k, v, out, lse, window, q_offset)


def _flash_bwd(causal, scale, chunk_q, chunk_kv, t_valid, res, g):
    q, k, v, out, lse, window, q_offset = res
    b, nq, cq, h, d = q.shape
    nkv, ck = k.shape[1], k.shape[2]
    kv_pos = jnp.arange(nkv * ck, dtype=jnp.float32).reshape(nkv, ck)
    q_pos = q_offset + jnp.arange(nq * cq, dtype=jnp.float32).reshape(
        nq, cq)
    # delta: rowsum(g * out) per query
    delta = jnp.einsum("bnqhd,bnqhd->bnhq", g.astype(jnp.float32),
                       out.astype(jnp.float32))         # (B,nq,H,cq)

    def p_block(qb, qp, kb, kp, lse_b):
        with jax.named_scope("flash_vmem"):
            s_blk = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                               preferred_element_type=jnp.float32) * scale
            mask = _mask_block(qp, kp, causal=causal, window=window,
                               t_valid=t_valid)
            s_blk = jnp.where(mask[None, None], s_blk, NEG_INF)
            return jnp.exp(s_blk - lse_b[..., None])    # (B,H,cq,ck)

    # pass 1: dq — scan q blocks, inner scan kv blocks
    def dq_block(args):
        qb, qp, lse_b, gb, db = args

        def kv_step(dq, inp):
            with jax.named_scope("flash_vmem"):
                kb, vb, kp = inp
                p = p_block(qb, qp, kb, kp, lse_b)
                dp = jnp.einsum("bqhd,bkhd->bhqk", gb.astype(jnp.float32),
                                vb.astype(jnp.float32))
                ds = p * (dp - db[..., None])
                dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds,
                                     kb.astype(jnp.float32)) * scale
                return dq, None

        dq0 = jnp.zeros((b, cq, h, d), jnp.float32)
        dq, _ = jax.lax.scan(kv_step, dq0,
                             (k.swapaxes(0, 1), v.swapaxes(0, 1), kv_pos))
        return dq

    dq = jax.lax.map(dq_block, (q.swapaxes(0, 1), q_pos,
                                lse.swapaxes(0, 1), g.swapaxes(0, 1),
                                delta.swapaxes(0, 1)))
    dq = dq.swapaxes(0, 1).astype(q.dtype)              # (B,nq,cq,H,D)

    # pass 2: dk/dv — scan kv blocks, inner scan q blocks
    def dkv_block(args):
        kb, vb, kp = args

        def q_step(carry, inp):
            with jax.named_scope("flash_vmem"):
                dk, dvv = carry
                qb, qp, lse_b, gb, db = inp
                p = p_block(qb, qp, kb, kp, lse_b)
                dvv = dvv + jnp.einsum("bhqk,bqhd->bkhd", p,
                                       gb.astype(jnp.float32))
                dp = jnp.einsum("bqhd,bkhd->bhqk", gb.astype(jnp.float32),
                                vb.astype(jnp.float32))
                ds = p * (dp - db[..., None])
                dk = dk + jnp.einsum("bhqk,bqhd->bkhd", ds,
                                     qb.astype(jnp.float32)) * scale
                return (dk, dvv), None

        dk0 = jnp.zeros((b, ck, h, d), jnp.float32)
        dv0 = jnp.zeros((b, ck, h, v.shape[-1]), jnp.float32)
        (dk, dvv), _ = jax.lax.scan(
            q_step, (dk0, dv0),
            (q.swapaxes(0, 1), q_pos, lse.swapaxes(0, 1),
             g.swapaxes(0, 1), delta.swapaxes(0, 1)))
        return dk, dvv

    dk, dv = jax.lax.map(dkv_block,
                         (k.swapaxes(0, 1), v.swapaxes(0, 1), kv_pos))
    dk = dk.swapaxes(0, 1).astype(k.dtype)
    dv = dv.swapaxes(0, 1).astype(v.dtype)
    zero = jnp.zeros((), jnp.float32)
    return dq, dk, dv, zero, zero


_flash.defvjp(_flash_fwd, _flash_bwd)


def chunked_attention(q: Array, k: Array, v: Array, *,
                      causal: bool = True,
                      window: Array | int = 0,
                      q_offset: Array | int = 0,
                      chunk_q: int = 1024,
                      chunk_kv: int = 1024,
                      scale: Optional[float] = None) -> Array:
    """Flash-style attention (memory-safe forward AND backward).

    q: (B, S, H, D); k, v: (B, T, KH, D). Returns (B, S, H, D).
    ``window`` 0 means full attention; >0 is a sliding window (query attends
    to keys in (pos - window, pos]). May be a traced scalar (per-layer flag
    inside a scanned stack — masking only, no compute skip; see DESIGN.md).
    ``q_offset``: absolute position of q[0] (for prefill continuation).
    """
    from repro.distributed.policy import attn_chunk_hint
    b, s, h, d = q.shape
    t = k.shape[1]
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)

    chunk_q = min(attn_chunk_hint(s, chunk_q), s)
    chunk_kv = min(chunk_kv, t)
    pad_q = (-s) % chunk_q
    pad_kv = (-t) % chunk_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    nq = (s + pad_q) // chunk_q
    nkv = (t + pad_kv) // chunk_kv
    q = q.reshape(b, nq, chunk_q, h, d)
    k = k.reshape(b, nkv, chunk_kv, h, d)
    v = v.reshape(b, nkv, chunk_kv, h, dv)
    window_f = jnp.asarray(window).astype(jnp.float32)
    offset_f = jnp.asarray(q_offset).astype(jnp.float32)
    out = _flash(q, k, v, window_f, offset_f, causal, scale, chunk_q,
                 chunk_kv, t)
    out = out.reshape(b, nq * chunk_q, h, dv)
    return out[:, :s].astype(v.dtype)


def decode_attention(q: Array, k_cache: Array, v_cache: Array, *,
                     pos: Array, window: Array | int = 0,
                     scale: Optional[float] = None) -> Array:
    """Single-step attention against a cache.

    q: (B, 1, H, D); caches: (B, T, KH, D); pos: current position — a
    scalar, or per-slot (B,) so every batch lane can sit at its own depth
    (entries at index > pos are invalid). Returns (B, 1, H, D).

    The S=1 case of `ragged_attention` (query 0's absolute position IS
    pos) — one implementation of the mask/window/softmax math to keep in
    sync."""
    return ragged_attention(q, k_cache, v_cache, pos=pos, window=window,
                            scale=scale)


def ragged_attention(q: Array, k_cache: Array, v_cache: Array, *,
                     pos: Array, window: Array | int = 0,
                     scale: Optional[float] = None) -> Array:
    """Multi-token attention against a cache with PER-SLOT query offsets.

    The serving prefill path: each batch lane b holds a different request
    whose queries start at absolute position pos[b] — 0 for a freshly
    recycled slot, the prefill cursor for a CHUNKED prefill resuming
    mid-prompt (query i attends the already-filled cache prefix
    [0, pos[b] + i], so a chunk sees exactly what the whole prompt would
    have) — so one mask cannot be shared across the batch the way the
    flash kernel's block mask is. Scores are materialized as
    (B, H, S, T) — serving prefill micro-batches are short (a few chunks
    x a budget-bounded width vs the gathered prefix window), so this
    stays far below the flash crossover; long uniform-offset prefill
    keeps using `chunked_attention`.

    q: (B, S, H, D); caches: (B, T, KH, Dk/Dv); pos: (B,) or scalar offset
    of q[:, 0]. Query i of lane b attends cache entries <= pos[b] + i.
    Returns (B, S, H, Dv).
    """
    b, s, h, d = q.shape
    t = k_cache.shape[1]
    scale = scale if scale is not None else d ** -0.5
    k = _repeat_kv(k_cache, h)
    v = _repeat_kv(v_cache, h)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(t)
    q_abs = (jnp.broadcast_to(jnp.asarray(pos), (b,))[:, None]
             + jnp.arange(s))                         # (B, S)
    mask = kv_pos[None, None, None, :] <= q_abs[:, None, :, None]
    window = jnp.asarray(window)
    in_win = jnp.where(
        window > 0,
        kv_pos[None, None, None, :] > q_abs[:, None, :, None] - window, True)
    scores = jnp.where(mask & in_win, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def is_per_slot(pos) -> bool:
    """True when a cache position is a per-slot (B,) vector rather than a
    scalar shared by the whole batch."""
    return pos is not None and getattr(jnp.asarray(pos), "ndim", 0) == 1


# ------------------------------------------------------------ paged cache

def paged_view(pool: Array, table: Array) -> Array:
    """Assemble each lane's logical sequence view from a block pool.

    pool: (nblocks, bs, ...) — one layer's slice of a paged cache, fixed-
    size blocks of bs sequence positions. table: (B, nblk) int32 — lane
    b's logical block j lives in physical block table[b, j] (0 is the
    trash block, standing in for not-yet-allocated tail entries; whatever
    it holds sits at positions >= the lane's valid length, where the
    ragged/decode masks never look). Returns (B, nblk * bs, ...): the
    lane's tokens in logical order, as `gather_slots` copies them out of
    a contiguous lane, so the downstream mask/softmax math is shared
    verbatim with the contiguous path."""
    b, nblk = table.shape
    g = pool[table]                                 # (B, nblk, bs, ...)
    return g.reshape(b, nblk * pool.shape[1], *pool.shape[2:])


def paged_cache_update(pool: Array, vals: Array, pos: Array,
                       table: Array) -> Array:
    """Write vals (B, S, ...) into a block pool at per-lane offsets.

    Token i of lane b lands at logical position p = pos[b] + i, i.e.
    physical (table[b, p // bs], p % bs). Writes past the table width
    (a padded chunk tail spilling beyond the lane's allocation) are
    routed to the trash block 0 — the paged analogue of scatter_slots'
    mode="drop" — as are writes through unallocated table entries (which
    already hold 0). Trash contents are finite garbage no mask can
    reach."""
    bs = pool.shape[1]
    b, s = vals.shape[0], vals.shape[1]
    nblk = table.shape[1]
    p = jnp.broadcast_to(jnp.asarray(pos), (b,))[:, None] + jnp.arange(s)
    blk, off = p // bs, p % bs                      # (B, S) each
    phys = jnp.take_along_axis(table, jnp.clip(blk, 0, nblk - 1), axis=1)
    phys = jnp.where(blk < nblk, phys, 0)           # spill -> trash block
    return pool.at[phys, off].set(vals.astype(pool.dtype))


def paged_ragged_attention(q: Array, k_pool: Array, v_pool: Array, *,
                           table: Array, pos: Array,
                           window: Array | int = 0,
                           scale: Optional[float] = None) -> Array:
    """`ragged_attention` over a lane-dense paged cache (nblocks, bs,
    KH * hd): index the pool by block table into the logical (B, T) view,
    split its lanes into (KH, hd), then run the shared per-slot
    mask/softmax math. Masking is by per-slot logical length (query i of
    lane b attends positions <= pos[b] + i), so trash/unallocated blocks
    beyond a lane's valid depth are never attended. Only the gathered
    view is reshaped, never the pool: a reshape of the pool would bring
    back the relayout the lane-dense layout avoids."""
    b, t = table.shape[0], table.shape[1] * k_pool.shape[1]
    hd = q.shape[-1]
    k = paged_view(k_pool, table).reshape(b, t, -1, hd)
    v = paged_view(v_pool, table).reshape(b, t, -1, hd)
    return ragged_attention(q, k, v, pos=pos, window=window, scale=scale)


def paged_decode_attention(q: Array, k_pool: Array, v_pool: Array, *,
                           table: Array, pos: Array,
                           window: Array | int = 0,
                           scale: Optional[float] = None,
                           use_kernel: bool = False) -> Array:
    """The S=1 case of `paged_ragged_attention` (same delegation shape as
    decode_attention -> ragged_attention). With ``use_kernel`` the Pallas
    paged kernel attends the pool directly — the block table rides scalar
    prefetch and only live physical blocks are read; the materializing
    path stays as the parity reference."""
    if use_kernel:
        from repro.kernels import ops as kops
        sc = scale if scale is not None else q.shape[-1] ** -0.5
        return kops.paged_attn_decode(q, k_pool, v_pool, table=table,
                                      pos=pos, window=window, scale=sc)
    return paged_ragged_attention(q, k_pool, v_pool, table=table, pos=pos,
                                  window=window, scale=scale)


def slot_cache_update(cache: Array, vals: Array, pos: Array) -> Array:
    """Write vals (B, S, ...) into cache (B, T, ...) at per-slot offsets.

    Row b lands at cache[b, pos[b] : pos[b] + S]. Out-of-range writes are
    dropped (a padded prefill row may spill past max_len; those entries are
    never attended — masks stop at the slot's valid length)."""
    b, s = vals.shape[0], vals.shape[1]
    rows = jnp.arange(b)[:, None]
    cols = jnp.broadcast_to(jnp.asarray(pos), (b,))[:, None] + jnp.arange(s)
    return cache.at[rows, cols].set(vals.astype(cache.dtype), mode="drop")


# ------------------------------------------------------------------ GQA

def gqa_project_qkv(x: Array, p: dict, cfg) -> tuple[Array, Array, Array]:
    """x: (B, S, d) -> q (B,S,H,hd), k,v (B,S,KH,hd) with optional bias+rope
    applied by caller."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = matmul(x, p["wq"].reshape(cfg.d_model, -1)).reshape(
        b, s, cfg.num_heads, hd)
    k = matmul(x, p["wk"].reshape(cfg.d_model, -1)).reshape(
        b, s, cfg.num_kv_heads, hd)
    v = matmul(x, p["wv"].reshape(cfg.d_model, -1)).reshape(
        b, s, cfg.num_kv_heads, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return q, k, v


def gqa_attention(x: Array, p: dict, cfg, *,
                  positions: Array,
                  causal: bool = True,
                  window: Array | int = 0,
                  kv_cache: Optional[tuple[Array, Array]] = None,
                  cache_pos: Optional[Array] = None,
                  cross_kv: Optional[tuple[Array, Array]] = None,
                  use_rope: bool = True,
                  block_table: Optional[Array] = None,
                  row_slots: Optional[Array] = None,
                  use_kernel: bool = False):
    """Full GQA block: project, rope, attend, output-project.

    Returns (out (B,S,d), new_kv or None).
    - training/prefill: kv_cache None -> chunked attention over self keys;
      if kv_cache provided with cache_pos, prefill writes into the cache.
    - decode: x has S=1 and kv_cache + cache_pos given.
    - cross_kv: precomputed encoder K/V (whisper cross-attention).
    - block_table (B, nblk): kv_cache is a lane-dense PAGED pool
      (nblocks, bs, KH * hd) per leaf — writes scatter through the table,
      reads assemble the logical view per lane (see paged_cache_update /
      paged_view);
      use_kernel routes paged DECODE through the Pallas paged-attention
      kernel (no logical view materialized; inference only — no VJP).
    - row_slots (R,): FUSED ragged serving over the contiguous GLOBAL
      cache (B here is R rows, S must be 1, kv_cache leaves are the full
      (max_slots, T, ...) cache). Row r writes at
      (row_slots[r], cache_pos[r]) and attends its lane's updated view —
      rows sharing a lane (a flattened prefill chunk) see earlier
      siblings through the shared cache and mask later ones causally.
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    if cross_kv is not None:
        q = matmul(x, p["wq"].reshape(cfg.d_model, -1)).reshape(
            b, s, cfg.num_heads, hd)
        k, v = cross_kv
        out = chunked_attention(q, k, v, causal=False) if s > 1 else \
            decode_attention(q, k, v, pos=k.shape[1] - 1)
        out = matmul(out.reshape(b, s, -1),
                     p["wo"].reshape(-1, cfg.d_model))
        return out, None

    q, k, v = gqa_project_qkv(x, p, cfg)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_kv = None
    if kv_cache is not None:
        ck, cv = kv_cache
        start = cache_pos if cache_pos is not None else 0
        if block_table is not None:
            # paged serving path: write the new K/V through the block
            # table, then attend the table-assembled logical view with
            # the SAME ragged per-slot masks as the contiguous slot path
            ck = paged_cache_update(ck, k.reshape(b, s, -1), start,
                                    block_table)
            cv = paged_cache_update(cv, v.reshape(b, s, -1), start,
                                    block_table)
            new_kv = (ck, cv)
            if s == 1:
                out = paged_decode_attention(q, ck, cv, table=block_table,
                                             pos=start, window=window,
                                             use_kernel=use_kernel)
            else:
                out = paged_ragged_attention(q, ck, cv, table=block_table,
                                             pos=start, window=window)
            out = matmul(out.reshape(b, s, -1),
                         p["wo"].reshape(-1, cfg.d_model))
            return out, new_kv
        if row_slots is not None:
            # fused ragged step: every row is a width-1 token addressed to
            # GLOBAL cache lane row_slots[r] at position start[r]. Rows may
            # share a lane (a prefill chunk flattened into consecutive
            # positions), so the new K/V scatter into the SHARED cache
            # first — distinct (lane, position) cells; padding rows
            # duplicate row 0's cell with row 0's value, a no-op — and each
            # row then attends its lane's UPDATED view: earlier siblings
            # are already present, later ones sit past start[r] where the
            # decode mask never looks. Gathering per-row copies before the
            # write would lose sibling keys — write-then-view is load-
            # bearing for fusion correctness.
            pcols = jnp.broadcast_to(jnp.asarray(start), (b,))
            ck = ck.at[row_slots, pcols].set(k[:, 0].astype(ck.dtype),
                                             mode="drop")
            cv = cv.at[row_slots, pcols].set(v[:, 0].astype(cv.dtype),
                                             mode="drop")
            new_kv = (ck, cv)
            out = decode_attention(q, ck[row_slots], cv[row_slots],
                                   pos=start, window=window)
            out = matmul(out.reshape(b, s, -1),
                         p["wo"].reshape(-1, cfg.d_model))
            return out, new_kv
        if is_per_slot(start):
            # slot-aware path: each batch lane writes/reads at its own depth
            ck = slot_cache_update(ck, k, start)
            cv = slot_cache_update(cv, v, start)
            new_kv = (ck, cv)
            if s == 1:
                out = decode_attention(q, ck, cv, pos=start, window=window)
            else:
                out = ragged_attention(q, ck, cv, pos=start, window=window)
            out = matmul(out.reshape(b, s, -1),
                         p["wo"].reshape(-1, cfg.d_model))
            return out, new_kv
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, start, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, start, 0, 0))
        new_kv = (ck, cv)
        if s == 1:
            out = decode_attention(q, ck, cv, pos=start, window=window)
        else:
            out = chunked_attention(q, ck, cv, causal=causal, window=window,
                                    q_offset=start)
    else:
        out = chunked_attention(q, k, v, causal=causal, window=window,
                                q_offset=0)
    out = matmul(out.reshape(b, s, -1), p["wo"].reshape(-1, cfg.d_model))
    return out, new_kv


# ------------------------------------------------------------------ MLA

def mla_attention(x: Array, p: dict, cfg, *,
                  positions: Array,
                  kv_cache: Optional[tuple[Array, Array]] = None,
                  cache_pos: Optional[Array] = None,
                  block_table: Optional[Array] = None,
                  row_slots: Optional[Array] = None,
                  use_kernel: bool = False):
    """DeepSeek-v2 multi-head latent attention.

    Cache holds the compressed latent c_kv (B,T,r) + rope key (B,T,dr) —
    the MLA memory saving. Prefill/train expand to per-head K/V; decode uses
    the ABSORBED form (q_nope absorbed through W_uk so scores contract
    against the latent directly; values likewise) — the TPU-friendly matvec.
    With `block_table` the cache is a PAGED latent pool ((nblocks, bs, r)
    and (nblocks, bs, dr) leaves): writes scatter through the table and
    the absorbed/ragged math runs on the table-assembled logical view —
    except paged DECODE with ``use_kernel``, where the Pallas MLA paged
    kernel runs the absorbed math straight off the pools (no view is
    assembled; inference only — no VJP). With ``row_slots`` (R,) the
    cache is the contiguous GLOBAL latent cache and each width-1 row
    writes at (row_slots[r], cache_pos[r]) then attends its lane's
    updated view (the fused ragged serving step; see gqa_attention).
    Returns (out, new_cache).
    """
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    r = m.kv_lora_rank
    scale = (dn + dr) ** -0.5

    # --- queries (low-rank) ---
    cq = matmul(x, p["q_dproj"])                        # (B,S,qr)
    cq = _rms(cq, p["q_norm"])
    q = matmul(cq, p["q_uproj"].reshape(m.q_lora_rank, -1))
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)

    # --- compressed KV ---
    ckv_full = matmul(x, p["kv_dproj"])                 # (B,S,r+dr)
    c_kv, k_pe = ckv_full[..., :r], ckv_full[..., r:]
    c_kv = _rms(c_kv, p["kv_norm"])
    k_pe = apply_rope(k_pe[..., None, :], positions, cfg.rope_theta)[..., 0, :]

    pools = None
    if kv_cache is not None:
        cc, cp = kv_cache
        start = cache_pos if cache_pos is not None else 0
        if block_table is not None:
            # paged: the pool is the cache state; attention below runs on
            # the logical per-lane view assembled through the table —
            # unless the kernel decode path attends the pools directly
            pool_c = paged_cache_update(cc, c_kv, start, block_table)
            pool_p = paged_cache_update(cp, k_pe, start, block_table)
            new_cache = (pool_c, pool_p)
            if s == 1 and use_kernel:
                pools = (pool_c, pool_p)
                cc, cp = pool_c, pool_p
            else:
                cc = paged_view(pool_c, block_table)
                cp = paged_view(pool_p, block_table)
        elif row_slots is not None:
            # fused ragged step over the contiguous latent cache: scatter
            # every row's latent + rope-key into the GLOBAL pools first
            # (rows may share a lane; write-then-view as in gqa_attention),
            # then hand each row its lane's updated view to the absorbed
            # decode math below, whose mask (<= start[r]) keeps same-step
            # siblings causal.
            pcols = jnp.broadcast_to(jnp.asarray(start), (b,))
            gcc = cc.at[row_slots, pcols].set(c_kv[:, 0].astype(cc.dtype),
                                              mode="drop")
            gcp = cp.at[row_slots, pcols].set(k_pe[:, 0].astype(cp.dtype),
                                              mode="drop")
            new_cache = (gcc, gcp)
            cc, cp = gcc[row_slots], gcp[row_slots]
        elif is_per_slot(start):
            cc = slot_cache_update(cc, c_kv, start)
            cp = slot_cache_update(cp, k_pe, start)
            new_cache = (cc, cp)
        else:
            cc = jax.lax.dynamic_update_slice(cc, c_kv.astype(cc.dtype),
                                              (0, start, 0))
            cp = jax.lax.dynamic_update_slice(cp, k_pe.astype(cp.dtype),
                                              (0, start, 0))
            new_cache = (cc, cp)
    else:
        cc, cp, start = c_kv, k_pe, 0
        new_cache = None

    wkv = p["kv_uproj"].reshape(r, h, dn + dv)          # latent -> heads
    wk, wv = wkv[..., :dn], wkv[..., dn:]

    if s == 1 and kv_cache is not None:
        # absorbed decode: score_t = q_nopeᵀ W_uk c_t + q_peᵀ k_pe_t
        q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, wk.astype(q_nope.dtype),
                           preferred_element_type=jnp.float32)
        if pools is not None:
            # paged kernel decode: the absorbed score/softmax/latent-value
            # math runs inside the Pallas kernel straight off the pools
            from repro.kernels import ops as kops
            o_lat = kops.mla_paged_decode(
                q_abs[:, 0].astype(pools[0].dtype), q_pe[:, 0],
                pools[0], pools[1], table=block_table, pos=start,
                scale=scale)[:, :, None, :]           # (B,H,1,r)
        else:
            s_lat = jnp.einsum("bqhr,btr->bhqt", q_abs.astype(cc.dtype), cc,
                               preferred_element_type=jnp.float32)
            s_pe = jnp.einsum("bqhd,btd->bhqt", q_pe, cp,
                              preferred_element_type=jnp.float32)
            scores = (s_lat + s_pe) * scale
            t = cc.shape[1]
            start_b = jnp.broadcast_to(jnp.asarray(start),
                                       (b,))[:, None, None, None]
            mask = jnp.arange(t)[None, None, None, :] <= start_b
            scores = jnp.where(mask, scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            # value in latent space, then expand: (B,H,q,r) @ (r,H,dv)
            o_lat = jnp.einsum("bhqt,btr->bhqr", probs.astype(cc.dtype), cc,
                               preferred_element_type=jnp.float32)
        out = jnp.einsum("bhqr,rhd->bqhd", o_lat.astype(x.dtype),
                         wv.astype(x.dtype),
                         preferred_element_type=jnp.float32).astype(x.dtype)
    elif kv_cache is not None and (block_table is not None or
                                   is_per_slot(start)):
        # slot-aware (contiguous or paged) prefill: per-lane query offsets
        # cannot share the flash block mask, so expand K/V from the cached
        # latent view and run the ragged mask (serving prefill
        # micro-batches are short)
        kv = jnp.einsum("btr,rhd->bthd", cc, wkv.astype(cc.dtype),
                        preferred_element_type=jnp.float32).astype(x.dtype)
        k_nope, v_exp = kv[..., :dn], kv[..., dn:]
        kfull = jnp.concatenate(
            [k_nope, jnp.broadcast_to(cp[:, :, None, :],
                                      (*cp.shape[:2], h, dr)).astype(x.dtype)],
            axis=-1)
        qfull = jnp.concatenate([q_nope, q_pe], axis=-1)
        out = ragged_attention(qfull, kfull, v_exp, pos=start, scale=scale)
    elif kv_cache is not None:
        # LAZY-EXPANSION prefill (flash-MLA style, §Perf iteration): the
        # per-head K/V are expanded from the latent PER KV-BLOCK inside the
        # flash loop (VMEM) — HBM reads the (T, r+dr) latent instead of the
        # (T, H, dqk+dv) expansion, a (H·320)/(r+dr) ≈ 70x KV-traffic cut
        # for deepseek-v2. Inference only (no custom VJP needed).
        qfull = jnp.concatenate([q_nope, q_pe], axis=-1)
        out = _mla_flash_prefill(qfull, cc, cp, wk, wv, scale=scale,
                                 q_offset=start, dn=dn)
    else:
        # expanded train path (flash custom-VJP handles the backward)
        kv = jnp.einsum("btr,rhd->bthd", cc,
                        wkv.astype(cc.dtype).reshape(r, h, dn + dv),
                        preferred_element_type=jnp.float32).astype(x.dtype)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(cp[:, :, None, :],
                                      (*cp.shape[:2], h, dr)).astype(x.dtype)],
            axis=-1)
        qfull = jnp.concatenate([q_nope, q_pe], axis=-1)
        out = chunked_attention(qfull, k, v, causal=True, q_offset=start,
                                scale=scale)
    out = matmul(out.reshape(b, s, h * dv), p["wo"].reshape(h * dv, -1))
    return out, new_cache


def _mla_flash_prefill(q: Array, cc: Array, cp: Array, wk: Array,
                       wv: Array, *, scale: float, q_offset: Array | int,
                       dn: int, chunk_q: int = 1024,
                       chunk_kv: int = 1024) -> Array:
    """Flash attention over the MLA LATENT: K/V expand per kv-block inside
    the loop (VMEM-resident on the Pallas target).

    q: (B, S, H, dn+dr) rope'd full queries; cc: (B, T, r) latents;
    cp: (B, T, dr) rope keys; wk: (r, H, dn); wv: (r, H, dv).
    """
    from repro.distributed.policy import attn_chunk_hint
    b, s, h, dq = q.shape
    t = cc.shape[1]
    dr = dq - dn
    dv = wv.shape[-1]
    chunk_q = min(attn_chunk_hint(s, chunk_q), s)
    chunk_kv = min(chunk_kv, t)
    pad_q = (-s) % chunk_q
    pad_kv = (-t) % chunk_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        cc = jnp.pad(cc, ((0, 0), (0, pad_kv), (0, 0)))
        cp = jnp.pad(cp, ((0, 0), (0, pad_kv), (0, 0)))
    nq = (s + pad_q) // chunk_q
    nkv = (t + pad_kv) // chunk_kv
    q = q.reshape(b, nq, chunk_q, h, dq)
    cc_b = cc.reshape(b, nkv, chunk_kv, -1)
    cp_b = cp.reshape(b, nkv, chunk_kv, dr)
    kv_pos = jnp.arange(nkv * chunk_kv, dtype=jnp.float32).reshape(
        nkv, chunk_kv)
    q_pos = (jnp.asarray(q_offset, jnp.float32) +
             jnp.arange(nq * chunk_q, dtype=jnp.float32).reshape(
                 nq, chunk_q))
    zero_w = jnp.float32(0)

    def q_block(args):
        qb, qp = args

        def kv_step(carry, inp):
            with jax.named_scope("flash_vmem"):
                m, l, acc = carry
                ccb, cpb, kp = inp
                # expand this block's K/V from the latent (VMEM work)
                kb = jnp.einsum("bkr,rhd->bkhd", ccb,
                                wk.astype(ccb.dtype),
                                preferred_element_type=jnp.float32
                                ).astype(qb.dtype)
                vb = jnp.einsum("bkr,rhd->bkhd", ccb,
                                wv.astype(ccb.dtype),
                                preferred_element_type=jnp.float32
                                ).astype(qb.dtype)
                kfull = jnp.concatenate(
                    [kb, jnp.broadcast_to(
                        cpb[:, :, None, :],
                        (*cpb.shape[:2], h, dr)).astype(qb.dtype)], -1)
                s_blk = jnp.einsum("bqhd,bkhd->bhqk", qb, kfull,
                                   preferred_element_type=jnp.float32
                                   ) * scale
                mask = _mask_block(qp, kp, causal=True, window=zero_w,
                                   t_valid=t)
                s_blk = jnp.where(mask[None, None], s_blk, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
                p = jnp.exp(s_blk - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bhqk,bkhd->bhqd", p.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32)
                return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, chunk_q), jnp.float32)
        a0 = jnp.zeros((b, h, chunk_q, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (cc_b.swapaxes(0, 1), cp_b.swapaxes(0, 1), kv_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.swapaxes(1, 2)

    out = jax.lax.map(q_block, (q.swapaxes(0, 1), q_pos))
    out = out.swapaxes(0, 1).reshape(b, nq * chunk_q, h, dv)
    return out[:, :s].astype(cc.dtype)


def _rms(x, scale, eps=1e-5):
    from repro.models.layers import rms_norm
    return rms_norm(x, scale, eps)
