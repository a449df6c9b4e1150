"""Continuous-batching serving engine: scheduler + slot KV cache + step
executor.

CMoE's payoff is serving-time efficiency, so this package turns the
fixed-batch prefill-then-decode script into an engine that keeps every
batch lane busy on mixed traffic. Three pieces, three contracts:

``Scheduler`` (`scheduler.py`)
    Owns the admission queue (arrival-sorted deque feeding a priority
    due-heap), the slot table, and a free-slot min-heap. Requests are
    submitted with an arrival time (engine steps); ``plan_prefill(now)``
    builds the step's prefill plan — resume partially-prefilled prompts,
    then admit due requests in (priority desc, arrival, rid) order,
    which is exact FIFO when every request carries the default class —
    under the ``max_prefill_tokens`` budget, a TRUE per-step cap (first
    admission included): longer prompts become per-step chunks tracked
    by the ``PREFILLING`` state and the ``Request.prefill_pos`` cursor.
    ``finish(req)`` recycles the slot; ``requeue(req)`` is the
    PREEMPTION path — an evicted RUNNING lane re-enters the due queue
    with a recompute replay (prompt + emitted tokens) and resumes
    token-identically. The ``admission_gate`` seam (True, or a defer
    cause: "pool" / "priority") is where the paged engine's headroom
    reservation and preemption policy plug in; deferrals are counted
    per cause, never silent. Policy "continuous" refills slots the
    moment they free; policy "static" models the classic baseline — it
    only admits when *all* slots are free, so a batch drains fully
    before the next one starts.

``SlotKVCache`` / ``PagedKVCache`` (`cache.py`)
    The model KV cache plus per-slot bookkeeping, in two layouts.
    Contiguous: leaves stacked (L, B, T, ...), batch axis 1 — each slot
    carries its own position, so a new prompt prefills into a freed slot
    at position 0 while neighboring slots keep decoding at their own
    depths; recycling is a length reset. Paged: a flat REFCOUNTED block
    pool (L, 1 + nblocks, block, ...) addressed through per-slot BLOCK
    TABLES — a request occupies ceil(len / block) blocks instead of a
    max_len lane, admission reserves its worst case against pool
    headroom, and recycling is a DECREF, not a free: a block still
    referenced by another lane's table (or resurrectable from the
    prefix index) stays resident, and only refcount zero returns it to
    circulation. With ``reuse`` on, full immutable blocks are
    content-addressed in a token-chain trie: admission adopts a new
    request's matching prefix — shared full blocks by refcount, a
    partial tail by COPY-ON-WRITE into a private block — and prefills
    only the unmatched remainder. In both layouts, every cache entry a
    mask can reach is written by the current request before it is read
    (shared/cached blocks being the deliberate, provably-valid
    exception), so stale K/V from a previous occupant — of a lane or of
    a recycled block — is never attended (proved by the parity tests).

``StepExecutor`` (`executor.py`)
    jit-compiled step functions over ``Model.step``. The OVERLAPPED
    engine's workhorse is ``step_fused`` (+ paged twin): decode lanes
    and flattened prefill-chunk tokens fused into ONE (R, 1) ragged
    micro-batch — per-row (slot, position) metadata, sampling inlined in
    the jit, the sampled tokens kept in an on-device per-lane carry so
    consecutive steps chain without a host readback. The sequential
    engine keeps the two classic shapes: a prefill micro-batch is one
    CHUNK per row (gather the prefix window, step at per-slot START
    positions, scatter back each row's write window) and decode runs
    full-width over all slots. Each call reports the routed-expert
    backend the micro-batch ran (``core.experts.microbatch_backend``):
    sequential prefill chunks run grouped and decode gather; a fused
    step runs expert phase "mixed" — backend by its true padded width,
    so decode-only steps stay on gather and chunk-heavy steps run
    grouped past the break-even.

``ServingEngine`` (`engine.py`)
    Two loops over the same scheduler/cache/executor. Overlapped
    (``overlap=True``, serve.py's default): one fused dispatch per step,
    double-buffered — step t+1 is issued from dispatch-time snapshots
    before step t's tokens are read back, so host readback (emission,
    EOS checks) LAGS one step; max_new/max_len finishes are decided at
    dispatch, and a one-step rollback path handles lanes whose EOS
    surfaces while their next row is already in flight. Sequential
    (``overlap=False``): one prefill micro-batch (width-1 chunks
    piggyback on decode) then one full-width decode dispatch, syncing
    every step — the fused path's parity baseline. Both serve identical
    token streams (schedule-invariant sampling + per-token capacity
    contract). Returns an ``EngineReport``: goodput, step-clock TTFT and
    wall-clock ttft_p50/p95_s (stamped at EMISSION, so the overlap lag
    is included), TPOT p50/p95 completion-gap percentiles (what a client
    observes), compute utilization (live/padded tokens), the
    k-weighted active-pair utilization, per-tier latency via
    ``tier_metrics()``, the per-micro-batch backend log, and — paged —
    the prefix-reuse and overload columns: prefix_hit_rate /
    reused_blocks / cow_copies, gate_deferrals split per cause, and
    preemptions, with the end-of-run pool conservation audit attached
    as ``pool_audit``. Each phase of the overlapped loop, and each
    garbage collection during a run, is a ``jax.profiler`` host span
    (``engine.*``, ``host.gc``): free with no profile running, and on
    the device trace's clock when one is.

ACTIVATION TIERS (per-request effective routed top-k). CMoE's converted
weights serve any routed k in [1, config top_k] — the ``S{s}A{k}E{e}``
tag only names the DEFAULT tier — and the engine treats k as routing
DATA, not shape: ``Request.tier`` becomes a per-row k vector threaded
``Model.step(row_k=...)`` -> ``cmoe_gate(k_row=...)``, where
assignments past a token's k are re-aimed at the out-of-range expert id
(the invalidation mechanism padding already uses), so the sort-based
ragged dispatch absorbs mixed tiers with zero layout changes. Mixed
tiers therefore co-batch into the SAME fused steps (the scheduler is
tier-oblivious), the backend break-even learns the dispatch's mean k,
and the report splits TTFT/TPOT per tier plus an active-pair (k-
weighted) utilization column where a k=1 row is visibly cheaper than a
k=K_max row. An all-default run passes row_k=None end to end and traces
the exact pre-tier graph — the uniform-tier parity gate is an identity.

CLI usage (``repro.launch.serve`` is a thin shell over this package)::

    # staggered Poisson arrivals, mixed prompt/gen lengths, slot
    # recycling, overlapped engine (--no-overlap for the sequential one)
    PYTHONPATH=src python -m repro.launch.serve --smoke --continuous \
        --batch 4 --requests 8 --rate 0.5 --gen 8

    # mixed activation tiers (k=1 alongside the default tier) co-batched
    # into the same fused steps, with per-tier TTFT/TPOT in the report
    PYTHONPATH=src python -m repro.launch.serve --smoke --continuous \
        --batch 4 --requests 8 --gen 8 --tier 1,default --parity

    # static-vs-continuous goodput on the same request mix
    PYTHONPATH=src python benchmarks/bench_serving.py --slots 4 \
        --requests 8 --no-gate
"""
from repro.serving.cache import (PagedKVCache, SlotKVCache, gather_slots,
                                 scatter_slots)
from repro.serving.engine import EngineReport, ServingEngine
from repro.serving.executor import StepExecutor
from repro.serving.request import Request
from repro.serving.sampling import make_sampler
from repro.serving.scheduler import Scheduler
from repro.serving.workload import make_requests, poisson_arrivals

__all__ = [
    "EngineReport", "PagedKVCache", "Request", "Scheduler", "ServingEngine",
    "SlotKVCache", "StepExecutor", "gather_slots", "make_requests",
    "make_sampler", "poisson_arrivals", "scatter_slots",
]
