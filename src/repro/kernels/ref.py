"""Pure-jnp oracles for every Pallas kernel. These are the correctness
ground truth: kernel tests sweep shapes/dtypes and assert allclose."""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def _act(name: str):
    if name == "swiglu":
        return lambda v: v * jax.nn.sigmoid(v)
    return jax.nn.gelu


def swiglu_ffn_ref(x: Array, wg: Array, wu: Array, wd: Array,
                   activation: str = "swiglu") -> Array:
    """x: (T, d); wg/wu: (d, f); wd: (f, d)."""
    g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    h = _act(activation)(g) * u
    return jnp.dot(h.astype(x.dtype), wd,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def moe_gmm_ref(xbuf: Array, wg: Array, wu: Array, wd: Array,
                activation: str = "swiglu") -> Array:
    """xbuf: (E, C, d); wg/wu: (E, d, m); wd: (E, m, d)."""
    g = jnp.einsum("ecd,edm->ecm", xbuf, wg,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edm->ecm", xbuf, wu,
                   preferred_element_type=jnp.float32)
    h = (_act(activation)(g) * u).astype(xbuf.dtype)
    return jnp.einsum("ecm,emd->ecd", h, wd,
                      preferred_element_type=jnp.float32).astype(xbuf.dtype)


def moe_gmm_ragged_ref(xp: Array, owner: Array, wg: Array, wu: Array,
                       wd: Array, activation: str = "swiglu",
                       block_c: int = 128) -> Array:
    """xp: (P, d) block-aligned expert-sorted rows; owner: (P/block_c,)
    expert per row-tile; wg/wu: (E, d, m); wd: (E, m, d) -> (P, d)."""
    p, d = xp.shape
    xb = xp.reshape(p // block_c, block_c, d)
    g = jnp.einsum("gbd,gdm->gbm", xb, jnp.take(wg, owner, axis=0),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("gbd,gdm->gbm", xb, jnp.take(wu, owner, axis=0),
                   preferred_element_type=jnp.float32)
    h = (_act(activation)(g) * u).astype(xp.dtype)
    return jnp.einsum("gbm,gmd->gbd", h, jnp.take(wd, owner, axis=0),
                      preferred_element_type=jnp.float32
                      ).astype(xp.dtype).reshape(p, d)


def router_score_ref(x: Array, wg_r: Array, wu_r: Array,
                     activation: str = "swiglu") -> Array:
    """Analytical router scores: x (T, d), wg_r/wu_r (d, N_r) -> (T, N_r)."""
    g = jnp.dot(x, wg_r, preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_r, preferred_element_type=jnp.float32)
    return _act(activation)(g) * u


def flash_attention_ref(q: Array, k: Array, v: Array, *,
                        causal: bool = True) -> Array:
    """q: (BH, S, D); k/v: (BH, T, D). Plain softmax attention oracle."""
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * (d ** -0.5)
    if causal:
        sq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(tk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def ssd_scan_ref(xw: Array, dta: Array, b: Array, c: Array,
                 chunk: int, h0: Array | None = None):
    """SSD oracle over pre-chunked inputs.

    xw: (BH, L, P) dt-weighted inputs; dta: (BH, L) log-decays;
    b, c: (BH, L, N). Returns (y (BH, L, P), h_fin (BH, P, N)).
    """
    bh, l, p = xw.shape
    n = b.shape[-1]
    nc = l // chunk
    xw = xw.reshape(bh, nc, chunk, p)
    dta = dta.reshape(bh, nc, chunk)
    b = b.reshape(bh, nc, chunk, n)
    c = c.reshape(bh, nc, chunk, n)
    if h0 is None:
        h0 = jnp.zeros((bh, p, n), jnp.float32)

    def step(h, inp):
        xw_c, dta_c, b_c, c_c = inp
        lcum = jnp.cumsum(dta_c, axis=1)                     # (BH, Lc)
        rel = lcum[:, :, None] - lcum[:, None, :]
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.where(tri[None], jnp.exp(rel), 0.0)
        cb = jnp.einsum("btn,bsn->bts", c_c, b_c)
        y = jnp.einsum("bts,bsp->btp", cb * decay, xw_c)
        y += jnp.einsum("btn,bpn->btp", c_c, h) * jnp.exp(lcum)[..., None]
        lend = lcum[:, -1:]
        w = jnp.exp(lend - lcum)
        h = h * jnp.exp(lend)[..., None] + jnp.einsum(
            "bsp,bsn,bs->bpn", xw_c, b_c, w)
        return h, y

    h_fin, ys = jax.lax.scan(
        step, h0, (xw.swapaxes(0, 1), dta.swapaxes(0, 1),
                   b.swapaxes(0, 1), c.swapaxes(0, 1)))
    return ys.swapaxes(0, 1).reshape(bh, l, p), h_fin


def flash_decode_ref(q: Array, k: Array, v: Array, pos) -> Array:
    """q: (BH, 1, D); k/v: (BH, T, D); mask positions > pos."""
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * (d ** -0.5)
    mask = jnp.arange(k.shape[1])[None, None, :] <= pos
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def paged_attn_decode_ref(q: Array, k_pool: Array, v_pool: Array,
                          table: Array, pos: Array, window,
                          *, scale: float) -> Array:
    """Materializing oracle for the GQA paged decode kernel: assemble each
    lane's logical view via the table, then masked softmax attention.

    q: (B, KH, grp, hd); k_pool/v_pool: (nblocks, bs, KH * hd);
    table: (B, nblk) int32; pos: (B,) int32; window: () int32 (0 = full).
    Returns (B, KH, grp, hd)."""
    b, kh, grp, hd = q.shape
    bs = k_pool.shape[1]
    nblk = table.shape[1]
    t = nblk * bs
    # (B, nblk, bs, KH, hd) -> (B, T, KH, hd): the logical view
    kv_k = k_pool[table].reshape(b, t, kh, hd)
    kv_v = v_pool[table].reshape(b, t, kh, hd)
    s = jnp.einsum("bhgd,bthd->bhgt", q, kv_k,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(t)[None, None, None, :]
    mask = kpos <= pos[:, None, None, None]
    mask &= jnp.where(window > 0, kpos > pos[:, None, None, None] - window,
                      True)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgt,bthd->bhgd", p.astype(kv_v.dtype), kv_v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def mla_paged_decode_ref(q_abs: Array, q_pe: Array, cc_pool: Array,
                         cp_pool: Array, table: Array, pos: Array,
                         *, scale: float) -> Array:
    """Materializing oracle for the MLA absorbed paged decode kernel.

    q_abs: (B, H, r); q_pe: (B, H, dr); cc_pool: (nblocks, bs, r);
    cp_pool: (nblocks, bs, dr); table: (B, nblk); pos: (B,). Returns the
    softmax-weighted latent o_lat (B, H, r)."""
    b, h, r = q_abs.shape
    bs = cc_pool.shape[1]
    t = table.shape[1] * bs
    cc = cc_pool[table].reshape(b, t, r)
    cp = cp_pool[table].reshape(b, t, cp_pool.shape[-1])
    s = (jnp.einsum("bhr,btr->bht", q_abs, cc,
                    preferred_element_type=jnp.float32) +
         jnp.einsum("bhp,btp->bht", q_pe, cp,
                    preferred_element_type=jnp.float32)) * scale
    mask = jnp.arange(t)[None, None, :] <= pos[:, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btr->bhr", p.astype(cc.dtype), cc,
                      preferred_element_type=jnp.float32).astype(q_abs.dtype)


def moe_gather_ref(xf: Array, eidx: Array, wg: Array, wu: Array, wd: Array,
                   *, top_k: int, activation: str = "swiglu") -> Array:
    """Oracle for the gather decode kernel: the XLA gathered-weight rows
    of `core.experts._gather` (pre gate-weight combine). xf: (T, d);
    eidx: (T*k,) flat expert ids -> (T*k, d)."""
    xr = jnp.repeat(xf, top_k, axis=0)
    g = jnp.einsum("bd,bdm->bm", xr, jnp.take(wg, eidx, axis=0),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("bd,bdm->bm", xr, jnp.take(wu, eidx, axis=0),
                   preferred_element_type=jnp.float32)
    h = (_act(activation)(g) * u).astype(xf.dtype)
    return jnp.einsum("bm,bmd->bd", h, jnp.take(wd, eidx, axis=0),
                      preferred_element_type=jnp.float32).astype(xf.dtype)
