"""Interpret-mode parity for the paged decode kernel stack: the
paged-attention kernels vs the materializing reference across fragmented
lane-dense pools, recycled-slot-style tables, block sizes {4, 8, 16} and
the served head widths (hd 64 and 128); the gather MoE kernel vs
`_gather`'s XLA rows on decode shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:                                     # pragma: no cover
    HAVE_HYP = False


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


def _fragmented_table(key, b, nblk, num_blocks, pos, bs):
    """Block tables the allocator could produce under churn: each lane's
    live prefix maps to distinct non-monotone physical blocks (LIFO reuse
    interleaves lanes), dead tail entries are 0 (unallocated -> trash)."""
    perm = jax.random.permutation(key, jnp.arange(1, num_blocks + 1))
    table = np.zeros((b, nblk), np.int32)
    taken = 0
    for i in range(b):
        live = int(pos[i]) // bs + 1
        table[i, :live] = np.asarray(perm[taken:taken + live])
        taken += live
    return jnp.asarray(table)


def _make_pools(key, num_blocks, bs, kh, hd, dtype):
    """Lane-dense K/V pools as the model lays them out: (1 + num_blocks,
    bs, KH * hd), kv head h in lanes [h * hd, (h + 1) * hd)."""
    ks = jax.random.split(key, 2)
    kp = jax.random.normal(ks[0], (1 + num_blocks, bs, kh * hd), dtype)
    vp = jax.random.normal(ks[1], (1 + num_blocks, bs, kh * hd), dtype)
    return kp, vp


def _check_paged_attn(bs, dtype, window, kh, grp, hd, seed):
    b, nblk = 4, 5
    h = kh * grp
    num_blocks = b * nblk
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, 1, h, hd), dtype)
    kp, vp = _make_pools(ks[1], num_blocks, bs, kh, hd, dtype)
    # staggered lengths incl. a fresh lane (pos 0) and a full lane
    pos = jnp.asarray([0, bs - 1, 2 * bs + 3, nblk * bs - 1], jnp.int32)
    table = _fragmented_table(ks[2], b, nblk, num_blocks, pos, bs)
    scale = hd ** -0.5
    out = ops.paged_attn_decode(q, kp, vp, table=table, pos=pos,
                                window=window, scale=scale)
    qg = q[:, 0].reshape(b, kh, grp, hd)
    exp = ref.paged_attn_decode_ref(qg, kp, vp, table, pos,
                                    jnp.int32(window), scale=scale)
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(exp.reshape(b, h, hd), np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("bs", [4, 8, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 11])
def test_paged_attn_kernel(bs, dtype, window):
    _check_paged_attn(bs, dtype, window, kh=2, grp=3, hd=16,
                      seed=bs + window)


@pytest.mark.parametrize("kh,grp,hd", [(8, 4, 128), (16, 1, 64)],
                         ids=["mistral", "qwen"])
@pytest.mark.parametrize("window", [0, 11])
def test_paged_attn_kernel_served_widths(kh, grp, hd, window):
    """The served head widths: one 128-lane head per slice (Mistral-7B,
    GQA 4:1) and two 64-lane heads per slice (qwen1.5-0.5b, MHA)."""
    _check_paged_attn(16, jnp.bfloat16, window, kh=kh, grp=grp, hd=hd,
                      seed=kh + window)


def test_paged_attn_kernel_ignores_dead_entries():
    """Recycled-slot hazard: stale garbage behind dead table entries (and
    in the trash block) must not leak — only pos masking protects us."""
    b, kh, grp, hd, bs, nblk = 2, 1, 2, 8, 4, 4
    h = kh * grp
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd))
    kp, vp = _make_pools(ks[1], b * nblk, bs, kh, hd, jnp.float32)
    pos = jnp.asarray([5, 2], jnp.int32)
    table = _fragmented_table(ks[2], b, nblk, b * nblk, pos, bs)
    out1 = ops.paged_attn_decode(q, kp, vp, table=table, pos=pos,
                                 window=0, scale=hd ** -0.5)
    # poison the trash block and every physical block not live for a lane
    live = np.zeros(1 + b * nblk, bool)
    tb = np.asarray(table)
    for i in range(b):
        live[tb[i, :int(pos[i]) // bs + 1]] = True
    poison = jnp.where(jnp.asarray(live)[:, None, None], kp, 1e4)
    poison_v = jnp.where(jnp.asarray(live)[:, None, None], vp, -1e4)
    out2 = ops.paged_attn_decode(q, poison, poison_v, table=table, pos=pos,
                                 window=0, scale=hd ** -0.5)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


@pytest.mark.parametrize("bs", [4, 8, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mla_paged_kernel(bs, dtype):
    b, h, r, dr, nblk = 3, 4, 32, 8, 4
    num_blocks = b * nblk
    ks = jax.random.split(jax.random.PRNGKey(11 + bs), 4)
    qa = jax.random.normal(ks[0], (b, h, r), dtype)
    qp = jax.random.normal(ks[1], (b, h, dr), dtype)
    cc = jax.random.normal(ks[2], (1 + num_blocks, bs, r), dtype)
    cp = jax.random.normal(ks[3], (1 + num_blocks, bs, dr), dtype)
    pos = jnp.asarray([0, bs + 1, nblk * bs - 1], jnp.int32)
    table = _fragmented_table(ks[0], b, nblk, num_blocks, pos, bs)
    scale = (r + dr) ** -0.5  # any static scale; the model passes its own
    out = ops.mla_paged_decode(qa, qp, cc, cp, table=table, pos=pos,
                               scale=scale)
    exp = ref.mla_paged_decode_ref(qa, qp, cc, cp, table, pos, scale=scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


if HAVE_HYP:
    @settings(max_examples=6, deadline=None)
    @given(data=st.data())
    def test_paged_attn_random_tables(data):
        """Property: for ANY table whose live prefix indexes valid blocks,
        kernel == materializing reference (dead entries arbitrary in
        [0, num_blocks] — they must not matter)."""
        bs = data.draw(st.sampled_from([4, 8]), label="bs")
        b = data.draw(st.integers(1, 4), label="b")
        nblk = data.draw(st.integers(1, 4), label="nblk")
        kh, grp, hd = 2, 2, 8
        num_blocks = b * nblk + 2
        seed = data.draw(st.integers(0, 2 ** 16), label="seed")
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, 1, kh * grp, hd))
        kp, vp = _make_pools(ks[1], num_blocks, bs, kh, hd, jnp.float32)
        pos = jnp.asarray(
            data.draw(st.lists(st.integers(0, nblk * bs - 1), min_size=b,
                               max_size=b), label="pos"), jnp.int32)
        rows = [data.draw(st.lists(st.integers(0, num_blocks), min_size=nblk,
                                   max_size=nblk), label=f"t{i}")
                for i in range(b)]
        table = jnp.asarray(rows, jnp.int32)
        scale = hd ** -0.5
        out = ops.paged_attn_decode(q, kp, vp, table=table, pos=pos,
                                    window=0, scale=scale)
        qg = q[:, 0].reshape(b, kh, grp, hd)
        exp = ref.paged_attn_decode_ref(qg, kp, vp, table, pos,
                                        jnp.int32(0), scale=scale)
        np.testing.assert_allclose(
            np.asarray(out[:, 0]),
            np.asarray(exp.reshape(b, kh * grp, hd)), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("t,k,e,d,m", [(4, 2, 8, 32, 48), (1, 6, 16, 16, 40)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gather_kernel(t, k, e, d, m, dtype):
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    xf = jax.random.normal(ks[0], (t, d), dtype)
    eidx = jax.random.randint(ks[1], (t * k,), 0, e, jnp.int32)
    wg = (jax.random.normal(ks[2], (e, d, m)) * 0.2).astype(dtype)
    wu = (jax.random.normal(ks[3], (e, d, m)) * 0.2).astype(dtype)
    wd = (jax.random.normal(ks[4], (e, m, d)) * 0.2).astype(dtype)
    out = ops.moe_gather(xf, eidx, wg, wu, wd, top_k=k)
    exp = ref.moe_gather_ref(xf, eidx, wg, wu, wd, top_k=k)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_gather_backend_kernel_matches_xla():
    """`_gather(use_kernel=True)` == `_gather(use_kernel=False)` on decode
    shapes — the combine is shared, only the row computation differs."""
    from repro.core.experts import _gather
    t, k, e, d, m = 8, 2, 8, 32, 48
    ks = jax.random.split(jax.random.PRNGKey(17), 5)
    xf = jax.random.normal(ks[0], (t, d))
    idx = jax.random.randint(ks[1], (t, k), 0, e, jnp.int32)
    gates = jax.nn.softmax(jax.random.normal(ks[1], (t, k)), axis=-1)
    weights = {
        "wg": jax.random.normal(ks[2], (e, d, m)) * 0.2,
        "wu": jax.random.normal(ks[3], (e, d, m)) * 0.2,
        "wd": jax.random.normal(ks[4], (e, m, d)) * 0.2,
    }
    y_xla = _gather(xf, weights, gates, idx, "swiglu", None)
    y_ker = _gather(xf, weights, gates, idx, "swiglu", None,
                    use_kernel=True)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_xla),
                               atol=2e-5, rtol=2e-5)
