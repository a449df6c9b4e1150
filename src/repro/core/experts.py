"""Unified routed-expert execution engine.

Every routed-expert forward in the repo — the converted CMoE FFN (both the
GSPMD and the shard_map data-local variants), the pretrained-MoE blocks
(llama4 / deepseek-v2, global and all-to-all EP), and the hierarchical
sub-expert runtime — delegates here. One module owns token dispatch, the
glu / non-glu expert compute, and the backend choice, so a new kernel or
sharding policy has a single seam to plug into.

Backend matrix (``routed_experts(..., backend=...)``):

  backend          dispatch               compute                 drops  use
  ---------------  ---------------------  ----------------------  -----  ----
  exact            none (dense mask)      all E experts, (T,E,d)  no     test
                                                                         oracle
  grouped_xla      ragged segment sort    segment GEMMs over      no     prefill
                   (argsort by expert)    sorted rows (TPU:              CPU/GPU
                                          ragged_dot; else
                                          row-tile einsum)
  grouped_pallas   ragged segment sort    Pallas ``moe_gmm_       no     prefill
                   (argsort by expert)    ragged`` (true group           TPU
                                          sizes, scalar prefetch)
  gather           per-token weight       (T*k,)-batched GEMMs,   no     decode /
                   gather (no buffer)     only selected experts          small T

The per-token capacity contract: NO backend above ever drops a (token,
expert) assignment, and a token's routed output is bitwise-independent of
which other tokens share its micro-batch. The grouped backends sort the
T*k assignments by expert id into a block-aligned ragged layout (each
expert's segment starts on a row-tile boundary, so every (block, d) tile
belongs to exactly one expert) and run segment GEMMs over the sorted
activations — per-expert group sizes are data, not shape, so no
micro-batch-width-dependent (E, C, d) capacity buffer exists to overflow.
Each output row is an independent dot product against its expert's
weights, so chunked and unchunked prefills of the same prompt compute
identical routed contributions (the serving engine's chunked==unchunked
parity tests assert this at tight capacity factors where the old scatter
contract provably forked streams).

A bounded capacity buffer survives only where a fixed shape is structural:
the all-to-all EP send bins in ``models.moe.moe_ffn_local`` (a collective
needs a static send extent). There the machinery below
(``expert_capacity`` / ``assign_positions`` / ``dispatch`` / ``combine``)
applies a per-token guarantee instead: capacity is floored so a single
token's own top-k can never be dropped, and overflow is resolved by
per-expert priority on the router weight with a deterministic token-id
tiebreak — never by micro-batch position. Residual drops are surfaced,
not silent: every routed FFN reports a ``dropped`` pair count through its
aux dict, which ``Model.step`` -> ``serving.StepExecutor`` ->
``EngineReport`` aggregate into per-micro-batch drop counts. (The
hierarchical two-level flatten rides the same ragged layout — see
``core.hierarchical`` — so it shares the no-drop contract end to end.)
``repro.models.moe`` re-exports the capacity machinery for backward
compatibility.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

Array = jax.Array

BACKENDS = ("exact", "grouped_xla", "grouped_pallas", "gather")

# Fallback break-even when the expert-bank shape is unknown: below this
# many tokens the gather path beats the segment sort even for
# prefill-shaped calls. With a known bank the threshold is ~E/k — weight
# traffic is the dominant cost (gather reads t*k weight slabs, grouped
# reads all E once); measured: benchmarks/bench_decode_backends.py.
GATHER_TOKEN_THRESHOLD = 8

# Row-tile of the XLA segment-GEMM layout. A FIXED constant (never derived
# from T): the layout block is part of the width-invariance contract — a
# token's row lands in a (block, d) tile whose GEMM shape is identical for
# every micro-batch width, so its value cannot depend on the batch. Small
# on purpose: the layout pads each expert's segment to a block multiple,
# so per-call overhead is bounded by E*(block-1) rows — at serving-chunk
# widths (tens of tokens) a large tile would drown the real rows in
# padding compute (measured: block 32 tripled chunked-prefill cost vs
# unchunked in bench_serving's HOL section at smoke scale).
RAGGED_BLOCK_XLA = 8

# Tiles gathered per scan step on the non-TPU segment-GEMM path: bounds
# resident gathered weight slabs at chunk scale (SEGMENT_STREAM_TILES x
# (a, b)) no matter how wide the micro-batch is. A constant — chunk
# boundaries must be static shape arithmetic so per-row results stay
# width-invariant.
SEGMENT_STREAM_TILES = 8

# Measured backend crossover artifact (benchmarks/bench_decode_backends.py
# --out). When present and shape-matched, its crossover overrides the
# ~E/k heuristic in ``select_backend``.
BENCH_FILE = "BENCH_decode_backends.json"


def _act(activation: str):
    if activation == "swiglu":
        return lambda v: v * jax.nn.sigmoid(v)
    return jax.nn.gelu


def _is_glu(weights: dict) -> bool:
    return "wg" in weights


# ------------------------------------------------------- capacity dispatch

def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def expert_capacity(num_tokens: int, num_experts: int, top_k: int,
                    factor: float) -> int:
    """Rows per expert for the BOUNDED-buffer path (the EP all-to-all
    shard binning in ``models.moe.moe_ffn_local``). Floored at ``top_k`` so a single token's own
    top-k assignments always fit even when they share one bin (t <
    num_experts underflow: a width-1 tail chunk that misses the decode
    piggyback path must never be able to drop its own pairs)."""
    cap = int(factor * num_tokens * top_k / num_experts) + 1
    # per-token guarantee: one token can aim at most top_k pairs at a bin
    # (shard-destination binning), so capacity >= top_k means a lone
    # token can never overflow its own dispatch
    cap = max(cap, top_k)
    # upper clamp: a bin can never receive more than every assignment, so
    # cap at t*k rounded DOWN to the 8-row alignment (rounding up after
    # the clamp would overshoot it), but never below the top_k floor
    hi = max(8, round_up(top_k, 8), (num_tokens * top_k) // 8 * 8)
    return min(max(8, round_up(cap, 8)), hi)


def dropped_pairs(keep: Array, valid: Optional[Array], shape) -> Array:
    """Count real (token, expert) assignments a dispatch failed to keep —
    the drop-mask seam every routed FFN reports through its aux dict and
    ``Model.step`` -> ``serving.StepExecutor`` -> ``EngineReport``
    aggregate per micro-batch. The buffer-free engine backends keep every
    valid pair, so this is zero unless the bounded
    EP all-to-all shard binning overflowed."""
    vmask = jnp.ones(shape, bool) if valid is None \
        else jnp.broadcast_to(valid, shape)
    return jnp.sum(vmask & ~keep).astype(jnp.int32)


class DispatchInfo(NamedTuple):
    expert_idx: Array    # (T, k) int32
    position: Array      # (T, k) int32 position within expert buffer
    keep: Array          # (T, k) bool — False if dropped (over capacity)
    gates: Array         # (T, k) float combine weights


def assign_positions(expert_idx: Array, num_experts: int, capacity: int,
                     priority: Optional[Array] = None
                     ) -> tuple[Array, Array]:
    """Per-assignment position within its expert's bounded buffer.

    Position = the assignment's rank among all assignments aimed at the
    same expert, ordered by DESCENDING ``priority`` (router weight) with a
    deterministic flat-assignment-id tiebreak (token-major: token id, then
    k-choice). With ``priority=None`` the order is the tiebreak alone.
    Overflow (rank >= capacity) therefore evicts the LOWEST-weighted
    assignments first — never "whoever arrived late in the micro-batch".

    Sort-based and memory-safe: one lexsort over the T*k flat assignments
    plus an O(E) segment cumsum — the (T, E) one-hot matrix (0.5 TB for
    1M tokens x 128 experts) never materializes.

    ``expert_idx`` may contain the out-of-range id ``num_experts`` to mark
    masked/padded assignments: they rank within their own phantom segment
    and consume no real expert's capacity.

    expert_idx: (T, k) int32. Returns (position (T,k), keep (T,k))."""
    t, k = expert_idx.shape
    n = t * k
    flat_e = expert_idx.reshape(-1)
    flat_i = jnp.arange(n, dtype=jnp.int32)
    if priority is None:
        keys = (flat_i, flat_e)
    else:
        keys = (flat_i, -priority.reshape(-1).astype(jnp.float32), flat_e)
    order = jnp.lexsort(keys)                       # last key is primary
    sorted_e = jnp.take(flat_e, order)
    counts = jnp.bincount(flat_e, length=num_experts + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(n, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    position = jnp.zeros((n,), jnp.int32).at[order].set(rank).reshape(t, k)
    keep = position < capacity
    return position, keep


def dispatch(x: Array, info: DispatchInfo, num_experts: int,
             capacity: int) -> Array:
    """x: (T, d) -> expert buffers (E, C, d)."""
    t, d = x.shape
    k = info.expert_idx.shape[1]
    flat_e = info.expert_idx.reshape(-1)
    flat_p = jnp.where(info.keep.reshape(-1), info.position.reshape(-1), 0)
    contrib = jnp.repeat(x, k, axis=0) * info.keep.reshape(-1, 1).astype(
        x.dtype)
    buf = jnp.zeros((num_experts, capacity, d), x.dtype)
    return buf.at[flat_e, flat_p].add(contrib, mode="drop")


def combine(ybuf: Array, info: DispatchInfo) -> Array:
    """ybuf: (E, C, d) -> (T, d) weighted by gates."""
    t, k = info.expert_idx.shape
    flat_e = info.expert_idx.reshape(-1)
    flat_p = jnp.where(info.keep.reshape(-1), info.position.reshape(-1), 0)
    rows = ybuf[flat_e, flat_p]                         # (T*k, d)
    w = (info.gates.reshape(-1, 1).astype(ybuf.dtype) *
         info.keep.reshape(-1, 1).astype(ybuf.dtype))
    rows = rows * w
    return rows.reshape(t, k, -1).sum(axis=1)


# ------------------------------------------------- ragged segment dispatch

def ragged_layout(flat_e: Array, num_experts: int, block: int
                  ) -> tuple[Array, Array, Array, int]:
    """Sort N flat assignments by expert id into a block-aligned ragged
    layout: each expert's segment starts on a ``block`` row boundary, so
    every (block, d) row-tile of the laid-out activations belongs to
    exactly ONE expert — the static-shape contract both segment-GEMM
    consumers (``lax.ragged_dot``, Pallas scalar-prefetch kernel) share.

    Per-expert group sizes are runtime data; only the worst-case padded
    extent P = round_up(N + E*(block-1), block) is a shape, so the layout
    never drops an assignment. Assignments carrying the out-of-range id
    ``num_experts`` (masked/padded tokens) get slot ``P``: the caller's
    ``mode="drop"`` scatter discards them, so they occupy no row at all.

    Returns (slot (N,) padded-layout row per assignment, owner (nb,)
    expert id per row-tile, group_sizes (E,) block-rounded segment sizes
    — ``sum(group_sizes) <= P``, trailing rows belong to no group — P)."""
    n = flat_e.shape[0]
    p_total = round_up(n + num_experts * (block - 1), block)
    nb = p_total // block
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = jnp.take(flat_e, order)
    counts = jnp.bincount(flat_e, length=num_experts + 1)   # [E] = masked
    padded = ((counts[:num_experts] + block - 1) // block) * block
    poff = jnp.concatenate([jnp.zeros((1,), padded.dtype),
                            jnp.cumsum(padded)])            # (E + 1,)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])     # (E + 1,)
    rank = jnp.arange(n, dtype=jnp.int32) - starts[sorted_e].astype(
        jnp.int32)
    slot_sorted = jnp.where(sorted_e < num_experts,
                            poff[jnp.minimum(sorted_e, num_experts - 1)
                                 ].astype(jnp.int32) + rank,
                            p_total)
    slot = jnp.zeros((n,), jnp.int32).at[order].set(slot_sorted)
    tile_start = jnp.arange(nb, dtype=poff.dtype) * block
    owner = jnp.searchsorted(poff[1:], tile_start, side="right")
    owner = jnp.minimum(owner, num_experts - 1).astype(jnp.int32)
    return slot, owner, padded.astype(jnp.int32), p_total


def ragged_scatter(xf: Array, top_k: int, slot: Array, p_total: int
                   ) -> Array:
    """Scatter each of the T*top_k flat assignments' token activations
    into its padded-layout row. Masked assignments carry slot == P and
    are dropped by the scatter (their row simply never exists)."""
    n = slot.shape[0]
    tok = jnp.arange(n, dtype=jnp.int32) // top_k
    return jnp.zeros((p_total, xf.shape[1]), xf.dtype).at[slot].set(
        jnp.take(xf, tok, axis=0), mode="drop")


def ragged_combine(yp: Array, slot: Array, gates: Array,
                   vmask: Optional[Array], t: int, top_k: int) -> Array:
    """Fetch each assignment's expert output by inverse permutation and
    gate-weight the k contributions per token. Masked assignments read a
    clamped (guaranteed-zero) row and carry a zeroed gate, so they
    contribute nothing either way."""
    p_total = yp.shape[0]
    rows = jnp.take(yp, jnp.minimum(slot, p_total - 1), axis=0)
    w = gates.astype(yp.dtype)
    if vmask is not None:
        w = w * vmask.astype(yp.dtype)
    return (rows.reshape(t, top_k, -1) * w[..., None]).sum(axis=1)


def _use_ragged_dot() -> bool:
    """``lax.ragged_dot`` has a first-class TPU lowering (the op exists
    for exactly this MoE segment-GEMM shape — each expert's slab streams
    once, nothing materializes per tile). Elsewhere XLA decays it to a
    per-group fallback that is orders of magnitude slower than the
    blocked einsum at serving shapes (measured on CPU at E=160 decode:
    ~1 tok/s vs ~150 via row-tiles). The platform is a process-wide
    constant, so the choice can never differ between two micro-batch
    widths of the same run — bitwise width-invariance holds either
    way."""
    return jax.default_backend() == "tpu"


def segment_dot(xp: Array, owner: Array, group_sizes: Array, bank: Array,
                block: int, use_ragged: Optional[bool] = None) -> Array:
    """ONE segment GEMM over a ragged layout against an (E, a, b) weight
    bank: xp (P, a) expert-sorted rows -> (P, b) float32. On TPU this is
    ``lax.ragged_dot`` with the TRUE per-expert group sizes (rows beyond
    sum(group_sizes) come back zero); elsewhere one (block, a) x (a, b)
    GEMM per row-tile against the tile owner's gathered slab. Either way
    each output row is an independent dot product, so per-row values
    cannot depend on how many rows exist (micro-batch width). The shared
    primitive under ``segment_ffn_xla`` and the hierarchical sub-router /
    shared-sub-expert stages; ``use_ragged`` overrides the platform
    default (tests exercise the TPU branch on CPU with it)."""
    if use_ragged is None:
        use_ragged = _use_ragged_dot()
    if use_ragged:
        return jax.lax.ragged_dot(xp, bank.astype(xp.dtype), group_sizes,
                                  preferred_element_type=jnp.float32)
    p_total = xp.shape[0]
    xb = xp.reshape(p_total // block, block, xp.shape[1])
    nb = xb.shape[0]
    if nb <= SEGMENT_STREAM_TILES:
        # small layouts: one gathered-slab einsum (nb slab copies, bounded)
        bank_b = jnp.take(bank, owner, axis=0).astype(xp.dtype)  # (nb,a,b)
        return jnp.einsum("gra,gab->grb", xb, bank_b,
                          preferred_element_type=jnp.float32
                          ).reshape(p_total, bank.shape[2])
    # STREAMED chunking: the one-shot gather above materializes nb ~
    # P/block slab copies, so weight memory would scale with the
    # micro-batch, not with E. Scanning constant-size tile chunks bounds
    # resident gathered weights at SEGMENT_STREAM_TILES slabs regardless
    # of P. Width-invariance holds: chunk boundaries are STATIC (shape
    # arithmetic, never data) and each output row is the same independent
    # per-tile contraction as the direct path — bitwise identical.
    chunk = SEGMENT_STREAM_TILES
    pad = (-nb) % chunk
    if pad:
        # padded tiles carry zero rows; their owner id is irrelevant
        # (0 * w = 0) and their output rows are sliced away below
        xb = jnp.pad(xb, ((0, pad), (0, 0), (0, 0)))
        owner = jnp.pad(owner, (0, pad))
    nc = (nb + pad) // chunk
    xc = xb.reshape(nc, chunk, block, xp.shape[1])
    oc = owner.reshape(nc, chunk)

    def step(_, inp):
        xcc, occ = inp
        bank_c = jnp.take(bank, occ, axis=0).astype(xp.dtype)  # (chunk,a,b)
        return None, jnp.einsum("gra,gab->grb", xcc, bank_c,
                                preferred_element_type=jnp.float32)

    _, yc = jax.lax.scan(step, None, (xc, oc))
    return yc.reshape((nb + pad) * block, bank.shape[2])[:p_total]


def segment_ffn_xla(xp: Array, owner: Array, group_sizes: Array,
                    weights: dict, activation: str, block: int) -> Array:
    """Expert FFN over a ragged layout: glu (gate ⊙ up -> down) or
    non-glu, each stage one ``segment_dot``. xp (P, d) expert-sorted
    rows, owner (P/block,) expert per row-tile, group_sizes (E,)
    per-expert row counts; returns (P, d) in xp's dtype."""
    act = _act(activation)
    if _is_glu(weights):
        g = segment_dot(xp, owner, group_sizes, weights["wg"], block)
        u = segment_dot(xp, owner, group_sizes, weights["wu"], block)
        h = (act(g) * u).astype(xp.dtype)
    else:
        h = act(segment_dot(xp, owner, group_sizes, weights["wi"],
                            block)).astype(xp.dtype)
    return segment_dot(h, owner, group_sizes, weights["wd"],
                       block).astype(xp.dtype)


# ----------------------------------------------------------- expert GEMMs

def grouped_expert_ffn(xbuf: Array, weights: dict, activation: str,
                       use_kernel: bool = False) -> Array:
    """Batched expert FFN over DENSE capacity buffers: xbuf (E, C, d) with
    per-expert weights (E, d, m) / (E, m, d). Kept for the bounded-buffer
    callers (hierarchical shared sub-level, `models.moe.expert_ffn`); the
    engine's grouped backends run the ragged segment path instead."""
    glu = _is_glu(weights)
    if use_kernel and glu:
        from repro.kernels import ops as kops
        return kops.moe_gmm(xbuf, weights["wg"], weights["wu"],
                            weights["wd"], activation=activation)
    act = _act(activation)
    if glu:
        g = jnp.einsum("ecd,edm->ecm", xbuf, weights["wg"].astype(xbuf.dtype),
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("ecd,edm->ecm", xbuf, weights["wu"].astype(xbuf.dtype),
                       preferred_element_type=jnp.float32)
        h = (act(g) * u).astype(xbuf.dtype)
    else:
        g = jnp.einsum("ecd,edm->ecm", xbuf, weights["wi"].astype(xbuf.dtype),
                       preferred_element_type=jnp.float32)
        h = act(g).astype(xbuf.dtype)
    return jnp.einsum("ecm,emd->ecd", h, weights["wd"].astype(xbuf.dtype),
                      preferred_element_type=jnp.float32).astype(xbuf.dtype)


def all_experts_ffn(xf: Array, weights: dict, activation: str) -> Array:
    """(T, E, d): every expert's output for every token (the oracle)."""
    act = _act(activation)
    if _is_glu(weights):
        g = jnp.einsum("td,ndm->tnm", xf, weights["wg"].astype(xf.dtype),
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("td,ndm->tnm", xf, weights["wu"].astype(xf.dtype),
                       preferred_element_type=jnp.float32)
        h = (act(g) * u).astype(xf.dtype)
    else:
        g = jnp.einsum("td,ndm->tnm", xf, weights["wi"].astype(xf.dtype),
                       preferred_element_type=jnp.float32)
        h = act(g).astype(xf.dtype)
    return jnp.einsum("tnm,nmd->tnd", h, weights["wd"].astype(xf.dtype),
                      preferred_element_type=jnp.float32).astype(xf.dtype)


# --------------------------------------------------------------- backends

def _exact(xf, weights, gates, idx, activation, valid):
    t = xf.shape[0]
    n_e = weights["wd"].shape[0]
    y_all = all_experts_ffn(xf, weights, activation)          # (T, E, d)
    w = gates.astype(y_all.dtype)
    if valid is not None:
        w = w * valid.astype(y_all.dtype)
    gmask = jnp.zeros((t, n_e), y_all.dtype).at[
        jnp.arange(t)[:, None], idx].add(w)
    return jnp.einsum("tnd,tn->td", y_all, gmask)


def _gather(xf, weights, gates, idx, activation, valid, *,
            use_kernel: bool = False):
    """Token-choice gather path: compute ONLY the selected experts.

    Flattens the (T, k) assignments to T*k independent rows and runs
    per-assignment expert FFNs. The XLA path gathers each row's weights
    (``jnp.take`` -> (T*k, d, m) copies) before batched GEMMs; with
    ``use_kernel`` (glu banks) the Pallas ``moe_gather`` kernel
    scalar-prefetches the flat expert ids and DMAs only the live slabs —
    no gathered weight buffer exists. Either way the gate-weight combine
    is shared, no capacity buffer is materialized and no token is ever
    dropped."""
    t, k = idx.shape
    d = xf.shape[1]
    act = _act(activation)
    flat = idx.reshape(-1)                                    # (T*k,)
    if use_kernel and _is_glu(weights):
        from repro.kernels import ops as kops
        y = kops.moe_gather(xf, flat, weights["wg"], weights["wu"],
                            weights["wd"], top_k=k, activation=activation)
    else:
        # invalidated assignments (per-token activation tiers / padding)
        # carry the sentinel id E: jnp.take's OOB default FILLS (NaN for
        # floats), and 0 * NaN would poison the gate-zeroed combine — so
        # clamp them onto a live slab and let the zeroed gate erase the
        # contribution exactly (the kernel branch above instead keeps the
        # sentinel and skips the dead slab's DMA + FLOPs outright)
        n_e = weights["wd"].shape[0]
        flat_c = jnp.minimum(flat, n_e - 1)
        xr = jnp.repeat(xf, k, axis=0)                        # (T*k, d)
        wd = jnp.take(weights["wd"], flat_c, axis=0)          # (T*k, m, d)
        if _is_glu(weights):
            wg = jnp.take(weights["wg"], flat_c, axis=0)      # (T*k, d, m)
            wu = jnp.take(weights["wu"], flat_c, axis=0)
            g = jnp.einsum("bd,bdm->bm", xr, wg.astype(xf.dtype),
                           preferred_element_type=jnp.float32)
            u = jnp.einsum("bd,bdm->bm", xr, wu.astype(xf.dtype),
                           preferred_element_type=jnp.float32)
            h = (act(g) * u).astype(xf.dtype)
        else:
            wi = jnp.take(weights["wi"], flat_c, axis=0)
            g = jnp.einsum("bd,bdm->bm", xr, wi.astype(xf.dtype),
                           preferred_element_type=jnp.float32)
            h = act(g).astype(xf.dtype)
        y = jnp.einsum("bm,bmd->bd", h, wd.astype(xf.dtype),
                       preferred_element_type=jnp.float32).astype(xf.dtype)
    w = gates.astype(xf.dtype)
    if valid is not None:
        w = w * valid.astype(xf.dtype)
    return (y.reshape(t, k, d) * w[..., None]).sum(axis=1)


def _grouped(xf, weights, gates, idx, activation, valid, *, use_kernel):
    """Ragged segment dispatch: argsort the T*k assignments by expert id,
    lay them out block-aligned (`ragged_layout`), run segment GEMMs over
    the sorted activations (Pallas `moe_gmm_ragged` with true per-expert
    group tiles, or `lax.ragged_dot` on the XLA path), and combine by the
    inverse permutation. NO (E, C, d) capacity buffer exists, so nothing
    can overflow: every assignment survives and a token's routed output is
    bitwise-independent of its micro-batch neighbors."""
    t, k = idx.shape
    n_e = weights["wd"].shape[0]
    flat_e = idx.reshape(-1)
    vmask = None
    if valid is not None:
        vmask = jnp.broadcast_to(valid, idx.shape)
        # masked assignments are re-aimed at the out-of-range id BEFORE
        # the sort: the scatter drops them, so padding neither occupies a
        # layout row a real token needs nor shifts real tokens' ranks
        flat_e = jnp.where(vmask.reshape(-1), flat_e, n_e)
    if use_kernel:
        from repro.kernels import ops as kops
        block = kops.ragged_block_c()
    else:
        block = RAGGED_BLOCK_XLA
    slot, owner, group_sizes, p_total = ragged_layout(flat_e, n_e, block)
    xp = ragged_scatter(xf, k, slot, p_total)
    if use_kernel:
        yp = kops.moe_gmm_ragged(xp, owner, weights["wg"], weights["wu"],
                                 weights["wd"], activation=activation,
                                 block_c=block)
    else:
        yp = segment_ffn_xla(xp, owner, group_sizes, weights, activation,
                             block)
    out = ragged_combine(yp, slot, gates, vmask, t, k)
    keep = jnp.ones_like(idx, bool) if vmask is None else vmask
    return out, keep


# ----------------------------------------------------------------- engine

_UNLOADED = object()
_measured = _UNLOADED        # lazily-loaded crossover dict (or None)


def _measured_crossover() -> Optional[dict]:
    """Load the measured gather/grouped crossover once per process.

    Search order: $REPRO_DECODE_BENCH (authoritative when set — no
    fallback), else ./BENCH_decode_backends.json, else the repo root
    next to src/. The artifact is written by
    ``benchmarks/bench_decode_backends.py --out`` and carries the bank
    shape it was measured on; ``select_backend`` only trusts it for calls
    with the SAME (num_experts, top_k) — any other shape falls back to
    the ~E/k heuristic. An artifact measured on another platform than
    ``jax.default_backend()`` (or naming none) is ignored with a warning:
    a CPU timing says nothing about a TPU's break-even. Which source
    decided is logged once."""
    global _measured
    if _measured is not _UNLOADED:
        return _measured
    import json
    import logging
    import os
    log = logging.getLogger("repro.experts")
    here = os.path.dirname(os.path.abspath(__file__))
    env = os.environ.get("REPRO_DECODE_BENCH")
    if env is not None:
        # explicit override is authoritative: never fall through to the
        # cwd / repo-root artifacts (missing/invalid -> no crossover)
        candidates = [env]
    else:
        candidates = [BENCH_FILE,
                      os.path.join(here, "..", "..", "..", BENCH_FILE)]
    for path in candidates:
        if not path or not os.path.exists(path):
            continue
        try:
            with open(path) as f:
                art = json.load(f) or {}
        except (OSError, ValueError) as e:
            log.warning("ignoring unreadable bench file %s: %s", path, e)
            continue
        platform = jax.default_backend()
        if art.get("platform") != platform:
            log.warning("ignoring bench file %s: measured on platform %r, "
                        "running on %r", path, art.get("platform"), platform)
            continue
        cx = art.get("crossover")
        if cx and "gather_max_tokens" in cx:
            log.info("backend break-even: MEASURED crossover from %s "
                     "(gather wins to %s tokens at E=%s, k=%s)", path,
                     cx.get("gather_max_tokens"), cx.get("num_experts"),
                     cx.get("top_k"))
            _measured = cx
            return _measured
    log.info("backend break-even: no measured crossover found "
             "(%s); using the ~E/k heuristic", BENCH_FILE)
    _measured = None
    return _measured


def _reset_measured_crossover():
    """Test hook: drop the cached crossover so the next call reloads."""
    global _measured
    _measured = _UNLOADED


def select_backend(t: int, cfg, phase: str, *, use_kernel: bool = False,
                   num_experts: Optional[int] = None,
                   top_k: Optional[int] = None,
                   effective_k: Optional[float] = None) -> str:
    """Backend policy: decode (and prefills under the gather break-even)
    -> ``gather``; larger prefill -> grouped, Pallas only when a kernel
    path is requested (``moe_gmm_ragged`` has no VJP, so autodiff must
    stay on the XLA path — inference launchers opt into kernels on TPU).

    The break-even is weight traffic: gather reads t*k per-token weight
    slabs, grouped reads each expert's slab once (``lax.ragged_dot`` /
    the Pallas kernel stream weights per segment — nothing materializes
    per row), so gather wins roughly while t*k <= E. Bank shape comes from
    num_experts/top_k when the caller knows it (``routed_experts`` passes
    the actual stacked-weight extents), else from cfg.cmoe / cfg.moe.

    The break-even is DATA-DRIVEN when a measured crossover artifact
    (``BENCH_decode_backends.json``) exists for this exact bank shape:
    its gather-wins-up-to token count replaces the heuristic, for the
    prefill threshold AND for wide decode (the measured file is the only
    thing that can move decode off gather — every backend is drop-free
    and width-invariant, so the switch is pure throughput, never
    correctness). Shapes the file wasn't measured on keep today's
    behavior: decode -> gather unconditionally, prefill by ~E/k.

    Phase "mixed" is the overlapped engine's FUSED micro-batch (decode
    lanes + flattened prefill-chunk rows in one (R, 1) dispatch): it
    skips decode's unconditional gather and applies the width threshold
    to the true fused width — R is static per compiled shape, so a
    chunk-heavy step runs grouped while a decode-only step stays on
    gather.

    ``effective_k`` is the PER-ROW k story ("k as data"): under
    activation tiers top_k is only the static K_max — a micro-batch's
    mean effective k can sit well below it, and gather's weight traffic
    is t * k̄ slabs, not t * K_max. When given, the ~E/k heuristic uses
    it directly, and a measured crossover (keyed on the static
    (num_experts, top_k=K_max) bank shape it was benched at) has its
    gather-wins-up-to count rescaled by top_k / k̄ — the break-even
    t·k ≈ const is linear in 1/k, so a half-activation co-batch keeps
    gather to twice the measured width."""
    if num_experts is None or top_k is None:
        spec = getattr(cfg, "cmoe", None) or getattr(cfg, "moe", None)
        if spec is not None:
            num_experts = num_experts or getattr(spec, "num_routed", None) \
                or getattr(spec, "num_experts", None)
            top_k = top_k or getattr(spec, "top_k", None)
    threshold = GATHER_TOKEN_THRESHOLD
    measured = False
    if num_experts and top_k:
        k_eff = max(float(effective_k), 1.0) if effective_k else \
            float(top_k)
        threshold = max(threshold, int(num_experts / max(k_eff, 1.0)))
        cx = _measured_crossover()
        if cx is not None and cx.get("num_experts") == num_experts \
                and cx.get("top_k") == top_k:
            threshold = max(GATHER_TOKEN_THRESHOLD,
                            int(int(cx["gather_max_tokens"]) *
                                top_k / k_eff))
            measured = True
    if phase == "decode" and not measured:
        return "gather"
    if t <= threshold:
        return "gather"
    return "grouped_pallas" if use_kernel else "grouped_xla"


def microbatch_backend(cfg, num_tokens: int, phase: str, *,
                       use_kernel: bool = False,
                       override: Optional[str] = None,
                       effective_k: Optional[float] = None
                       ) -> Optional[str]:
    """The backend ``routed_experts`` will run for a (phase, num_tokens)
    micro-batch of this model — the serving engine's reporting seam, so
    what the step executor logs per micro-batch is the same policy the
    engine executes (``select_backend`` + the glu-only Pallas fallback).

    Returns None when the model has no routed experts (nothing to select),
    the explicit override when one is pinned, else the auto choice.

    For a hierarchical model (cfg.moe AND cfg.cmoe set) the engine-visible
    call is the INNER sub-expert pass: ``hierarchical_moe_ffn`` runs
    ``routed_experts`` over the outer ragged layout's P ~ T*top_k sorted
    rows against the flattened E*num_routed sub-expert bank, so the
    report is computed on those extents, not the raw token count. The
    shard_map-local EP layouts pick per-shard (multi-device serving is a
    ROADMAP item); this reports the single-device global paths the
    serving engine runs.

    ``effective_k`` (mean per-row k of the micro-batch, from request
    activation tiers) rescales the gather/grouped break-even — see
    ``select_backend``. The engine passes the policy's choice back INTO
    the jitted step as a static override, so the executed backend and
    this report agree by construction even when the choice depends on
    per-row k (which trace-time auto-selection could never see).
    """
    cm = getattr(cfg, "cmoe", None)
    moe = getattr(cfg, "moe", None)
    if cm is None and moe is None:
        return None
    if override not in (None, "auto"):
        return override
    if cm is not None and moe is not None:
        # mirror hierarchical_moe_ffn's outer ragged-layout extent
        e = moe.num_experts
        p_total = round_up(num_tokens * moe.top_k +
                           e * (RAGGED_BLOCK_XLA - 1), RAGGED_BLOCK_XLA)
        be = select_backend(p_total, cfg, phase, use_kernel=use_kernel,
                            num_experts=e * cm.num_routed, top_k=cm.top_k,
                            effective_k=effective_k)
    else:
        be = select_backend(num_tokens, cfg, phase, use_kernel=use_kernel,
                            effective_k=effective_k)
    if be == "grouped_pallas" and cfg.activation not in ("swiglu", "geglu"):
        be = "grouped_xla"           # mirrors the auto fallback below
    return be


def routed_experts(xf: Array, weights: dict, gates: Array, idx: Array,
                   cfg, *, backend: Optional[str] = None,
                   phase: str = "prefill", capacity_factor: float = 1.25,
                   use_kernel: bool = False,
                   valid: Optional[Array] = None):
    """Run the routed experts selected by (gates, idx) on tokens xf.

    Args:
      xf:      (T, d) flat tokens.
      weights: per-expert stacks — {"wg","wu","wd"} (glu) or {"wi","wd"},
               each leading dim E.
      gates:   (T, k) combine weights.
      idx:     (T, k) int32 selected expert ids.
      cfg:     model config (only ``cfg.activation`` is read).
      backend: one of BACKENDS, or None/"auto" to use ``select_backend``.
      phase:   "prefill" | "decode" | "mixed" — drives auto backend
               selection ("mixed" = the fused serving micro-batch,
               width-thresholded like prefill).
      capacity_factor: retained for API compatibility with the bounded-
               buffer callers; the engine backends are buffer-free and
               ignore it (no capacity exists to factor).
      valid:   optional (T, k) bool; assignments with False contribute
               nothing (used for padded / unoccupied buffer rows).

    Returns (out (T, d), keep (T, k) bool). Under the per-token contract
    ``keep`` is simply the valid mask (all-True when ``valid`` is None):
    no backend drops assignments. Callers turn ``valid & ~keep`` into the
    ``dropped`` aux count — identically zero here, nonzero only for the
    bounded-buffer stages that wrap this engine.
    """
    del capacity_factor  # no capacity buffer exists on any engine backend
    if backend in (None, "auto"):
        backend = select_backend(xf.shape[0], cfg, phase,
                                 use_kernel=use_kernel,
                                 num_experts=weights["wd"].shape[0],
                                 top_k=idx.shape[1])
        if backend == "grouped_pallas" and not _is_glu(weights):
            backend = "grouped_xla"      # moe_gmm kernel is glu-only
    elif backend == "grouped_pallas" and not _is_glu(weights):
        raise ValueError(
            "backend='grouped_pallas' requires a glu weight schema "
            "({wg,wu,wd}); the moe_gmm_ragged kernel has no non-glu "
            "({wi,wd}) path — use 'grouped_xla'")
    activation = cfg.activation
    if backend == "exact":
        out = _exact(xf, weights, gates, idx, activation, valid)
    elif backend == "gather":
        out = _gather(xf, weights, gates, idx, activation, valid,
                      use_kernel=use_kernel)
    elif backend in ("grouped_xla", "grouped_pallas"):
        return _grouped(xf, weights, gates, idx, activation, valid,
                        use_kernel=backend == "grouped_pallas")
    else:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    keep = jnp.ones_like(idx, bool) if valid is None \
        else jnp.broadcast_to(valid, idx.shape)
    return out, keep
