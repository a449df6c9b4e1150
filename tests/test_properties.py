"""Property-based tests (hypothesis) on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import example, given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core.clustering import assign_sinkhorn, balanced_kmeans
from repro.core.profiling import atopk_mask
from repro.core.router import cmoe_gate
from repro.models.moe import assign_positions, expert_capacity

SET = dict(max_examples=20, deadline=None)


@settings(**SET)
@given(q=st.integers(4, 40), dh=st.integers(8, 64),
       k=st.integers(1, 8), seed=st.integers(0, 2**16))
def test_atopk_always_exact_k(q, dh, k, seed):
    k = min(k, dh)
    h = jax.random.normal(jax.random.PRNGKey(seed), (q, dh))
    a = atopk_mask(h, k)
    assert np.asarray(a.sum(1)).tolist() == [k] * q
    # masked entries dominate unmasked ones per row
    habs = np.abs(np.asarray(h))
    am = np.asarray(a, bool)
    for i in range(q):
        if am[i].any() and (~am[i]).any():
            assert habs[i][am[i]].min() >= habs[i][~am[i]].max() - 1e-6


@settings(**SET)
@given(nc=st.integers(2, 6), m=st.integers(2, 10),
       qdim=st.integers(4, 24), seed=st.integers(0, 2**16))
def test_balanced_kmeans_always_balanced(nc, m, qdim, seed):
    rng = np.random.default_rng(seed)
    feats = rng.random((nc * m, qdim)).astype(np.float32)
    res = balanced_kmeans(feats, nc, method="jv", max_iters=3)
    counts = np.bincount(res.assignment, minlength=nc)
    assert (counts == m).all()


@settings(**SET)
@given(n=st.integers(6, 30), k=st.integers(2, 5), seed=st.integers(0, 999))
def test_sinkhorn_rounding_always_balanced(n, k, seed):
    n = (n // k) * k
    if n == 0:
        return
    rng = np.random.default_rng(seed)
    dist = rng.random((n, k)).astype(np.float32)
    a = assign_sinkhorn(dist, n // k, tau=0.1, iters=50)
    assert (np.bincount(a, minlength=k) == n // k).all()


@settings(**SET)
@given(t=st.integers(1, 60), nr=st.integers(2, 10), k=st.integers(1, 6),
       seed=st.integers(0, 2**16))
def test_gate_selects_exactly_k(t, nr, k, seed):
    k = min(k, nr)
    scores = jax.random.normal(jax.random.PRNGKey(seed), (t, nr))
    gates, idx, probs = cmoe_gate(scores, k)
    assert idx.shape == (t, k)
    # no duplicate experts per token
    for row in np.asarray(idx):
        assert len(set(row.tolist())) == k
    np.testing.assert_array_equal(np.asarray(gates), 1.0)
    np.testing.assert_allclose(np.asarray(probs.sum(-1)), 1.0, atol=1e-5)


@settings(**SET)
@given(t=st.integers(2, 80), e=st.integers(2, 8), k=st.integers(1, 3),
       seed=st.integers(0, 2**16))
def test_assign_positions_dense_packing(t, e, k, seed):
    """Positions within each expert are unique and densely packed
    0..count-1 (before capacity truncation)."""
    k = min(k, e)
    idx = jax.random.randint(jax.random.PRNGKey(seed), (t, k), 0, e)
    cap = t * k      # no drops
    pos, keep = assign_positions(idx, e, cap)
    assert bool(keep.all())
    pos_np, idx_np = np.asarray(pos), np.asarray(idx)
    for ei in range(e):
        got = np.sort(pos_np[idx_np == ei])
        np.testing.assert_array_equal(got, np.arange(len(got)))


@settings(**SET)
@given(t=st.integers(2, 40), e=st.integers(2, 6), k=st.integers(1, 3),
       cap=st.integers(1, 8), seed=st.integers(0, 2**16))
def test_assign_positions_priority_is_rank_by_weight(t, e, k, cap, seed):
    """With a priority, an assignment's position within its expert equals
    its rank by DESCENDING priority (flat token-major id breaks ties), so
    capacity truncation always evicts the lowest-weighted assignments —
    the bounded-buffer half of the per-token capacity contract."""
    k = min(k, e)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    idx = jax.random.randint(ks[0], (t, k), 0, e)
    prio = jax.random.uniform(ks[1], (t, k))
    pos, keep = assign_positions(idx, e, cap, priority=prio)
    pos_np = np.asarray(pos).reshape(-1)
    idx_np = np.asarray(idx).reshape(-1)
    pr_np = np.asarray(prio).reshape(-1)
    for ei in range(e):
        (members,) = np.nonzero(idx_np == ei)
        # expected rank: sort members by (-priority, flat id)
        order = sorted(members, key=lambda f: (-pr_np[f], f))
        for rank, f in enumerate(order):
            assert pos_np[f] == rank
    np.testing.assert_array_equal(np.asarray(keep),
                                  np.asarray(pos) < cap)


@settings(**SET)
@given(s=st.integers(1, 15), seed=st.integers(0, 2**16))
def test_routed_experts_width_invariant_all_backends(s, seed):
    """The engine's per-token capacity contract, as a property: routing T
    tokens as ONE micro-batch vs as any 2-way split produces BITWISE-equal
    routed outputs and equal (all-keep) drop masks, on every backend —
    exact, grouped_xla, grouped_pallas, and gather."""
    from repro.core.experts import BACKENDS, routed_experts

    class _C:
        activation = "swiglu"

    t, d, m, e, k = 16, 8, 16, 6, 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = {"wg": jax.random.normal(ks[0], (e, d, m)),
         "wu": jax.random.normal(ks[1], (e, d, m)),
         "wd": jax.random.normal(ks[2], (e, m, d))}
    xf = jax.random.normal(ks[3], (t, d))
    idx = jax.random.randint(ks[4], (t, k), 0, e)
    gates = jax.nn.softmax(jax.random.normal(ks[5], (t, k)))
    for be in BACKENDS:
        full, keep = routed_experts(xf, w, gates, idx, _C, backend=be,
                                    capacity_factor=0.75)
        lo, kl = routed_experts(xf[:s], w, gates[:s], idx[:s], _C,
                                backend=be, capacity_factor=0.75)
        hi, kh = routed_experts(xf[s:], w, gates[s:], idx[s:], _C,
                                backend=be, capacity_factor=0.75)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(lo), np.asarray(hi)]),
            np.asarray(full), err_msg=f"{be} split at {s}")
        assert bool(keep.all()) and bool(kl.all()) and bool(kh.all()), be


@settings(**SET)
@given(t=st.integers(8, 100), e=st.integers(2, 8),
       factor=st.floats(0.2, 2.0))
@example(t=9, e=2, factor=2.0)
def test_capacity_bounds(t, e, factor):
    c = expert_capacity(t, e, 1, factor)
    assert 8 <= c <= max(t, 8)
    assert c % 8 == 0


@settings(**SET)
@given(b=st.integers(1, 3), s=st.integers(3, 40), v=st.integers(8, 60),
       seed=st.integers(0, 2**16))
def test_chunked_ce_equals_full_ce(b, s, v, seed):
    from repro.models.model import chunked_ce
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    d = 16
    x = jax.random.normal(ks[0], (b, s, d))
    head = jax.random.normal(ks[1], (d, v)) * 0.3
    tgt = jax.random.randint(ks[2], (b, s), 0, v)
    mask = jnp.ones((b, s), jnp.float32)
    got = chunked_ce(x, head, False, tgt, mask, chunk=7)
    logits = (x @ head).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
    exp = (lse - gold).mean()
    np.testing.assert_allclose(float(got), float(exp), atol=1e-4, rtol=1e-4)


@settings(**SET)
@given(s=st.integers(4, 48), h=st.integers(1, 4), d=st.sampled_from([8, 16]),
       window=st.integers(0, 16), seed=st.integers(0, 2**16))
def test_flash_equals_naive(s, h, d, window, seed):
    from repro.models.attention import chunked_attention
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, s, h, d))
    k = jax.random.normal(ks[1], (1, s, h, d))
    v = jax.random.normal(ks[2], (1, s, h, d))
    out = chunked_attention(q, k, v, causal=True, window=window,
                            chunk_q=8, chunk_kv=8)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    if window > 0:
        mask = mask & (jnp.arange(s)[None, :] >
                       jnp.arange(s)[:, None] - window)
    sc = jnp.where(mask[None, None], sc, -1e30)
    exp = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)
