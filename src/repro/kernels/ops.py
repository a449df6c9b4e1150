"""jit'd wrappers around the Pallas kernels: shape padding, dtype handling,
interpret-mode selection (CPU validates the kernel bodies; TPU compiles
them), and fallbacks to the jnp oracle where a kernel precondition fails.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.moe_gather import moe_gather as _moe_gather
from repro.kernels.moe_gmm import moe_gmm as _moe_gmm
from repro.kernels.moe_gmm import moe_gmm_ragged as _moe_gmm_ragged
from repro.kernels.paged_attention import mla_paged_decode as _mla_paged
from repro.kernels.paged_attention import paged_attn_decode as _paged_attn
from repro.kernels.router_score import router_score as _router
from repro.kernels.ssd_scan import ssd_scan as _ssd
from repro.kernels.swiglu import swiglu_ffn as _swiglu

Array = jax.Array


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def on_tpu() -> bool:
    """Single source of truth for hardware-driven kernel opt-in (inference
    launchers key ``use_kernel`` off this; autodiff callers must not —
    ``moe_gmm`` has no VJP)."""
    return not _interpret()


def _shrink_block(block: int, n: int, align: int = 8) -> int:
    """In interpret mode the MXU tiling constraint is moot — shrink the
    block to the (align-rounded) extent so decode-shaped capacity buffers
    (C = 8) aren't padded 16x to a 128 tile."""
    if not _interpret():
        return block
    return min(block, max(align, ((n + align - 1) // align) * align))


def _pad_to(x: Array, axis: int, mult: int) -> tuple[Array, int]:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x, n


@functools.partial(jax.jit, static_argnames=("activation", "block_t",
                                             "block_f"))
def swiglu_ffn(x: Array, wg: Array, wu: Array, wd: Array, *,
               activation: str = "swiglu", block_t: int = 128,
               block_f: int = 128) -> Array:
    """x: (..., d). Pads tokens and f to block multiples."""
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    xf, t0 = _pad_to(xf, 0, block_t)
    wg_p, f0 = _pad_to(wg, 1, block_f)
    wu_p, _ = _pad_to(wu, 1, block_f)
    wd_p, _ = _pad_to(wd, 0, block_f)
    out = _swiglu(xf, wg_p, wu_p, wd_p, activation=activation,
                  block_t=block_t, block_f=block_f,
                  interpret=_interpret())
    return out[:t0].reshape(shape)


@functools.partial(jax.jit, static_argnames=("activation", "block_c",
                                             "block_m"))
def moe_gmm(xbuf: Array, wg: Array, wu: Array, wd: Array, *,
            activation: str = "swiglu", block_c: int = 128,
            block_m: int = 128) -> Array:
    block_c = _shrink_block(block_c, xbuf.shape[1])
    xb, c0 = _pad_to(xbuf, 1, block_c)
    wg_p, m0 = _pad_to(wg, 2, block_m)
    wu_p, _ = _pad_to(wu, 2, block_m)
    wd_p, _ = _pad_to(wd, 1, block_m)
    out = _moe_gmm(xb, wg_p, wu_p, wd_p, activation=activation,
                   block_c=block_c, block_m=block_m,
                   interpret=_interpret())
    return out[:, :c0]


def ragged_block_c() -> int:
    """Row-tile of the ragged segment layout the ``moe_gmm_ragged`` kernel
    consumes. A process-wide CONSTANT (never shape-derived): the layout
    block is part of the engine's width-invariance contract — shrinking it
    per call would make a token's tile shape depend on its micro-batch.
    Small in interpret mode (per-expert padding is one tile, and the MXU
    tiling constraint is moot on CPU), MXU-aligned on TPU."""
    return 16 if _interpret() else 128


@functools.partial(jax.jit, static_argnames=("activation", "block_c",
                                             "block_m"))
def moe_gmm_ragged(xp: Array, owner: Array, wg: Array, wu: Array,
                   wd: Array, *, activation: str = "swiglu",
                   block_c: int = 128, block_m: int = 128) -> Array:
    """xp: (P, d) block-aligned expert-sorted rows (P % block_c == 0 by
    layout construction); owner: (P/block_c,) expert per tile. Pads m to a
    block_m multiple (zero wd rows -> padded hidden columns contribute
    nothing)."""
    block_m = _shrink_block(block_m, wg.shape[2])
    wg_p, _ = _pad_to(wg, 2, block_m)
    wu_p, _ = _pad_to(wu, 2, block_m)
    wd_p, _ = _pad_to(wd, 1, block_m)
    return _moe_gmm_ragged(xp, owner, wg_p, wu_p, wd_p,
                           activation=activation, block_c=block_c,
                           block_m=block_m, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("activation", "block_t"))
def router_score(x: Array, wg_r: Array, wu_r: Array, *,
                 activation: str = "swiglu", block_t: int = 256) -> Array:
    xf, t0 = _pad_to(x, 0, block_t)
    out = _router(xf, wg_r, wu_r, activation=activation, block_t=block_t,
                  interpret=_interpret())
    return out[:t0]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    block_q: int = 128, block_k: int = 128) -> Array:
    """q: (BH, S, D); k/v: (BH, T, D). Pads S/T; padded kv columns are
    masked out by the causal structure or sliced away."""
    qp, s0 = _pad_to(q, 1, block_q)
    kp, t0 = _pad_to(k, 1, block_k)
    vp, _ = _pad_to(v, 1, block_k)
    if kp.shape[1] != t0 and not causal:
        # non-causal: padded keys must not receive mass — fall back
        return ref.flash_attention_ref(q, k, v, causal=False)
    out = _flash(qp, kp, vp, causal=causal, block_q=block_q,
                 block_k=block_k, interpret=_interpret())
    return out[:, :s0]


def ssd_scan(xh: Array, dt: Array, b: Array, c: Array, a_log: Array,
             d_skip: Array, *, chunk: int = 128, h0: Array | None = None):
    """Drop-in for `repro.models.ssm.ssd_chunked` (same signature/returns).

    xh: (B, S, nh, hp); dt: (B, S, nh); b/c: (B, S, N).
    """
    bsz, s, nh, hp = xh.shape
    n = b.shape[-1]
    if h0 is not None:
        # carried prefill state: use the jnp path (kernel starts from zero)
        from repro.models.ssm import ssd_chunked
        return ssd_chunked(xh, dt, b, c, a_log, d_skip, chunk, h0=h0)
    a = -jnp.exp(a_log.astype(jnp.float32))
    dta = dt.astype(jnp.float32) * a                       # (B, S, nh)
    xw = xh.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]
    # flatten (B, nh) -> BH; broadcast b/c per head
    xw_f = xw.transpose(0, 2, 1, 3).reshape(bsz * nh, s, hp)
    dta_f = dta.transpose(0, 2, 1).reshape(bsz * nh, s)
    b_f = jnp.broadcast_to(b[:, None], (bsz, nh, s, n)).reshape(
        bsz * nh, s, n)
    c_f = jnp.broadcast_to(c[:, None], (bsz, nh, s, n)).reshape(
        bsz * nh, s, n)
    pad = (-s) % chunk
    if pad:
        xw_f = jnp.pad(xw_f, ((0, 0), (0, pad), (0, 0)))
        dta_f = jnp.pad(dta_f, ((0, 0), (0, pad)))
        b_f = jnp.pad(b_f, ((0, 0), (0, pad), (0, 0)))
        c_f = jnp.pad(c_f, ((0, 0), (0, pad), (0, 0)))
    y, h_fin = _ssd(xw_f, dta_f, b_f, c_f, chunk=min(chunk, s + pad),
                    interpret=_interpret())
    y = y[:, :s].reshape(bsz, nh, s, hp).transpose(0, 2, 1, 3)
    y = y + xh.astype(jnp.float32) * d_skip.astype(jnp.float32)[:, None]
    h_fin = h_fin.reshape(bsz, nh, hp, n)
    return y, h_fin


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_attn_decode(q: Array, k_pool: Array, v_pool: Array, *,
                      table: Array, pos: Array, window=0,
                      scale: float) -> Array:
    """GQA paged decode straight off the block pool. q: (B, 1, H, hd);
    k_pool/v_pool: (nblocks, bs, KH * hd) lane-dense pools; table:
    (B, nblk) int32 block tables (0 = trash/unallocated); pos: (B,) int32
    last valid logical index per lane; window: int32 scalar sliding
    window (0 = full; may be traced — it rides scalar prefetch). Returns
    (B, 1, H, hd). No VJP."""
    b, s, h, hd = q.shape
    assert s == 1, s
    kh = k_pool.shape[-1] // hd
    grp = h // kh
    qg = q[:, 0].reshape(b, kh, grp, hd)
    tbl = table.astype(jnp.int32).reshape(-1)
    ps = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    win = jnp.asarray(window, jnp.int32).reshape(1)
    out = _paged_attn(qg, k_pool, v_pool, tbl, ps, win, scale=scale,
                      interpret=_interpret())
    return out.reshape(b, 1, h, hd)


@functools.partial(jax.jit, static_argnames=("scale",))
def mla_paged_decode(q_abs: Array, q_pe: Array, cc_pool: Array,
                     cp_pool: Array, *, table: Array, pos: Array,
                     scale: float) -> Array:
    """MLA absorbed paged decode off the latent/rope-key pools. q_abs:
    (B, H, r) W_uk-absorbed queries; q_pe: (B, H, dr); cc_pool:
    (nblocks, bs, r); cp_pool: (nblocks, bs, dr); table: (B, nblk); pos:
    (B,). Returns o_lat (B, H, r) — caller expands through W_uv. No VJP."""
    b = q_abs.shape[0]
    tbl = table.astype(jnp.int32).reshape(-1)
    ps = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    return _mla_paged(q_abs, q_pe, cc_pool, cp_pool, tbl, ps, scale=scale,
                      interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("top_k", "activation",
                                             "block_m"))
def moe_gather(xf: Array, eidx: Array, wg: Array, wu: Array, wd: Array, *,
               top_k: int, activation: str = "swiglu",
               block_m: int = 128) -> Array:
    """Per-assignment gather expert FFN rows without gathered weight
    copies. xf: (T, d); eidx: (T*k,) flat expert ids in [0, E] — the
    out-of-range SENTINEL id E (per-row activation tiers / padding
    invalidation) is PRESERVED here, so the kernel can skip the dead
    assignment's weight-slab DMAs and FLOPs and zero its output row
    (where the XLA path's ``jnp.take`` clips and relies on the zeroed
    gate alone); wg/wu: (E, d, m); wd: (E, m, d) -> (T*k, d) rows, pre
    gate-combine. glu banks only."""
    block_m = _shrink_block(block_m, wg.shape[2])
    wg_p, _ = _pad_to(wg, 2, block_m)
    wu_p, _ = _pad_to(wu, 2, block_m)
    wd_p, _ = _pad_to(wd, 1, block_m)
    eidx = jnp.clip(eidx.astype(jnp.int32), 0, wg.shape[0])
    return _moe_gather(xf, eidx, wg_p, wu_p, wd_p, top_k=top_k,
                       activation=activation, block_m=block_m,
                       interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_k",))
def flash_decode(q: Array, k: Array, v: Array, pos: Array, *,
                 block_k: int = 512) -> Array:
    """q: (BH, 1, D); k/v: (BH, T, D); pos: () int32. Pads T; padded keys
    are masked by the position check."""
    kp, t0 = _pad_to(k, 1, block_k)
    vp, _ = _pad_to(v, 1, block_k)
    return _flash_decode(q, kp, vp, pos, block_k=block_k,
                         interpret=_interpret())
