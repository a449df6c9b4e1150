#!/usr/bin/env bash
# CI entry point: tier-1 test suite + serving smoke.
#
#   ./ci.sh            # full tier-1 + smoke
#   ./ci.sh --fast     # tests only (skip the serve smoke)
set -euo pipefail
cd "$(dirname "$0")"

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1: pytest =="
python -m pytest -x -q

if [[ "${1:-}" != "--fast" ]]; then
    echo "== smoke: convert + serve (CMoE S3A3E8) =="
    python -m repro.launch.serve --smoke --cmoe S3A3E8 --gen 4
    echo "== smoke: continuous-batching serve (staggered arrivals) =="
    # runs the default OVERLAPPED engine (fused ragged dispatch, expert
    # backend by fused width); all slots recycled to completion
    python -m repro.launch.serve --smoke --continuous --batch 4 \
        --requests 8 --rate 0.5 --prompt-len 32 --gen 8
    echo "== smoke: chunked-prefill serve (long prompts, 16-token budget) =="
    # sequential engine: prompts up to 32 tokens against a 16-token
    # per-step prefill budget, so every long prompt prefills as
    # interleaved chunks (grouped backend) while decode lanes keep
    # stepping (gather backend)
    python -m repro.launch.serve --smoke --continuous --batch 4 \
        --requests 8 --rate 0.5 --prompt-len 32 --gen 8 \
        --max-prefill-tokens 16 --no-overlap
    echo "== smoke: grouped-parity (chunked == unchunked at cf 0.75) =="
    # width-invariance gate ON THE GROUPED BACKENDS (sequential engine):
    # the chunked run must reproduce the unchunked run token-for-token
    # with ZERO reported drops even at a tight capacity factor — the
    # ragged grouped backends have no capacity buffer to overflow, so
    # chunk width is numerically invisible
    python -m repro.launch.serve --smoke --continuous --batch 4 \
        --requests 8 --rate 0.5 --prompt-len 32 --gen 8 \
        --max-prefill-tokens 16 --capacity-factor 0.75 --parity \
        --no-overlap
    echo "== smoke: paged-KV serve (block tables, paged == contiguous) =="
    # paging-invariance gate (sequential engine): the paged run (block
    # pool + per-request block tables, admission gated on pool headroom)
    # must reproduce the contiguous run token-for-token with zero
    # dropped pairs
    python -m repro.launch.serve --smoke --continuous --batch 4 \
        --requests 8 --rate 0.5 --prompt-len 32 --gen 8 \
        --max-prefill-tokens 16 --paged --block-size 8 --parity \
        --no-overlap
    echo "== smoke: overlapped engine parity (fused dispatch == sequential) =="
    # overlap-invariance gate: the fused double-buffered loop (one ragged
    # dispatch per step, on-device sampling, readback lagging one step)
    # must reproduce the sequential run token-for-token — and, being
    # paged, the contiguous run too — with zero dropped pairs
    python -m repro.launch.serve --smoke --continuous --batch 4 \
        --requests 8 --rate 0.5 --prompt-len 32 --gen 8 \
        --max-prefill-tokens 16 --paged --block-size 8 --overlap --parity
    echo "== smoke: activation-tier mix parity (tier 1 + default co-batched) =="
    # tier gate: half the requests run at tier 1 (one routed expert per
    # token), half at the config default; per-row k is routing data, so
    # both tiers share every fused step (overlapped engine). --parity
    # replays the SAME tiered request set sequentially and gates token
    # identity plus zero dropped pairs — per-token streams must be
    # invariant to co-batched neighbors running a different tier
    python -m repro.launch.serve --smoke --continuous --batch 4 \
        --requests 8 --rate 0.5 --prompt-len 32 --gen 8 \
        --max-prefill-tokens 16 --tier 1,default --parity
    echo "== smoke: prefix-reuse parity (hot prefixes, reuse == no reuse) =="
    # prefix-sharing gate: every request carries the same 24-token system
    # prompt (--prefix-groups); with --prefix-reuse each admission after
    # the first adopts the shared blocks from the refcounted pool (COW on
    # partial tails) and prefills only its unique remainder. --parity
    # replays reuse-off (and the overlap==sequential baseline) and gates
    # token identity, nonzero hits, and the pool conservation audit
    python -m repro.launch.serve --smoke --continuous --batch 4 \
        --requests 8 --rate 0.5 --prompt-len 32 --gen 8 \
        --max-prefill-tokens 16 --paged --block-size 8 \
        --prefix-groups 24 --prefix-reuse --parity
    echo "== smoke: preemptive SLO admission (priority classes, tiny pool) =="
    # overload gate: two priority classes into a pool sized for ONE
    # request, arrivals staggered so each low-class request is RUNNING
    # when the next high-class one lands — the high class preempts the
    # low lane (private blocks evicted, recompute replay re-queued)
    # instead of queueing behind it. --expect-preemption asserts
    # preemptions really happened and every victim completed; --parity
    # replays the same mix unpressured (full pool) and gates token
    # identity — preemption is a latency policy, invisible in the streams
    python -m repro.launch.serve --smoke --continuous --batch 4 \
        --requests 4 --rate 0.3 --prompt-len 24 --gen 8 \
        --max-prefill-tokens 16 --paged --block-size 8 --num-blocks 5 \
        --priority 0,1 --expect-preemption --parity --no-overlap
    echo "== smoke: paged kernel parity (Pallas interpret == XLA) =="
    # kernel-correctness gate: the paged run with --use-kernel routes
    # decode attention through the Pallas paged-attention kernel and
    # gather MoE through the gather kernel (interpret mode off-TPU); it
    # must reproduce the contiguous XLA run token-for-token (overlapped
    # by default, so the fused per-row-table dispatch rides the kernels
    # too)
    python -m repro.launch.serve --smoke --continuous --batch 4 \
        --requests 8 --rate 0.5 --prompt-len 32 --gen 8 \
        --max-prefill-tokens 16 --paged --block-size 8 --parity \
        --use-kernel
    echo "== smoke: decode backend bench (gather vs grouped) =="
    # --no-gate: CI asserts the bench RUNS; the speedup gate is timing-based
    # and too noisy to fail CI on a loaded runner (run without the flag to
    # enforce it). --out refreshes the measured-crossover artifact that
    # select_backend consumes for shape-matched calls — the sweep must
    # extend PAST the gather/grouped crossover (~16 tokens on this shape)
    # or the refreshed file records crossover: null and the measured
    # policy for this shape silently falls back to the heuristic.
    python benchmarks/bench_decode_backends.py --iters 5 \
        --batches 1 4 8 16 32 64 --no-gate --out
    echo "== smoke: serving goodput + HOL + paged-concurrency bench (cmoe) =="
    # --cmoe exercises the per-micro-batch backend split in all sections;
    # the HOL section additionally serves the chunked workload through
    # the overlapped engine (token identity + compute-utilization gates,
    # soft under --no-gate); --out refreshes the committed
    # BENCH_serving.json baseline (goodput, TTFT/TPOT percentiles,
    # compute utilization per section)
    python benchmarks/bench_serving.py --requests 8 --cmoe --samples 2 \
        --no-gate --out
fi
echo "CI OK"
