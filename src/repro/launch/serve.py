"""Serving CLI: a thin shell over `repro.serving`.

Static mode (default) keeps the classic fixed-batch prefill + decode
timing loop. `--continuous` runs the continuous-batching engine on a
staggered-arrival mixed-length request set: prompts prefill into freed
slots while other slots keep decoding. The engine defaults to the
OVERLAPPED loop (one fused ragged dispatch per step, on-device sampling,
host readback lagging one step — `--no-overlap` falls back to the
sequential two-dispatch baseline, where prefill micro-batches run the
grouped routed-expert backend and decode micro-batches the drop-free
gather path). `--max-prefill-tokens` chunks long prompts across steps so
prefill cannot stall decode lanes (head-of-line fix). `--paged` swaps
the contiguous slot lanes for the refcounted block-pool KV cache
(per-request block tables). `--prefix-reuse` turns on content-addressed
prefix sharing over that pool (use `--prefix-groups` to generate
hot-prefix traffic: a comma list of shared system-prompt lengths cycled
over requests); `--priority` cycles SLO priority classes, and under a
tiny `--num-blocks` pool a higher class PREEMPTS the lowest running
lane instead of queueing behind it (`--expect-preemption` asserts it
happened). `--parity` replays the same requests on the other axes
(overlap off, contiguous / unchunked, reuse off, unpressured pool) and
asserts token-identical streams. `--tier` assigns per-request
activation tiers (effective routed top-k, cycled over a comma list;
"default" = config top_k): k is routing DATA, so mixed tiers co-batch
into the same compiled steps and the report grows per-tier TTFT/TPOT
plus k-weighted (active-pair) compute utilization.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --cmoe S3A3E8 --batch 4 --prompt-len 32 --gen 16
    PYTHONPATH=src python -m repro.launch.serve --smoke --continuous \
        --batch 4 --requests 8 --rate 0.5 --gen 8
    PYTHONPATH=src python -m repro.launch.serve --smoke --continuous \
        --batch 4 --prompt-len 32 --gen 8 --max-prefill-tokens 16
    PYTHONPATH=src python -m repro.launch.serve --smoke --continuous \
        --batch 4 --gen 8 --paged --block-size 8 --parity
    PYTHONPATH=src python -m repro.launch.serve --smoke --continuous \
        --batch 4 --gen 8 --paged --block-size 8 --prefix-reuse \
        --prefix-groups 24 --parity
    PYTHONPATH=src python -m repro.launch.serve --smoke --continuous \
        --batch 4 --gen 8 --paged --block-size 8 --num-blocks 12 \
        --priority 0,1 --expect-preemption --parity
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import CMoEConfig, override
from repro.configs import get_config, get_smoke_config
from repro.core.convert import convert_dense_model
from repro.core.experts import BACKENDS, microbatch_backend
from repro.data import make_calibration_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import ServingEngine, make_requests, make_sampler


def parse_sxayez(tag: str) -> CMoEConfig:
    """'S3A3E8' -> CMoEConfig(num_shared=3, top_k=3, num_experts=8)."""
    import re
    m = re.fullmatch(r"[Ss](\d+)[Aa](\d+)[Ee](\d+)", tag)
    if not m:
        raise ValueError(f"bad SxAyEz tag: {tag}")
    s, a, e = map(int, m.groups())
    return CMoEConfig(num_experts=e, num_shared=s, top_k=a)


def serve_continuous(model, params, args):
    """Continuous-batching mode: Poisson arrivals, per-request lengths.
    --max-prefill-tokens bounds each step's prefill compute: prompts
    longer than the budget are split into per-step chunks interleaved
    with decode (the head-of-line fix; see serving.scheduler).
    --paged swaps the contiguous slot lanes for the block-pool cache
    (per-request block tables, admission gated on pool headroom).
    --parity replays the same requests on the OTHER axes and asserts
    token-identical streams with zero reported drops: under --overlap
    (the default) it first compares against a sequential (--no-overlap)
    run at the same settings — the overlap-invariance contract — then,
    with --paged, against a contiguous run (paging invariance), or with
    --max-prefill-tokens, against an unchunked run (width invariance);
    every baseline runs overlap-off, so one gate spans both axes.
    --tier cycles per-request activation tiers over the request set; the
    parity replays reuse the SAME tiered requests, so each gate also
    certifies mixed-tier co-batching on its axis."""
    cfg = model.cfg
    if args.prefix_reuse and not args.paged:
        raise SystemExit("--prefix-reuse needs --paged: sharing is a "
                         "block-table property")
    max_len = args.prompt_len + args.gen
    tiers = None
    if args.tier:
        tiers = [None if t.strip().lower() == "default" else int(t)
                 for t in args.tier.split(",")]
        if cfg.cmoe is None:
            raise SystemExit("--tier needs a CMoE-routed model (--cmoe): "
                             "tiers are a routed-k knob")
    k_max = cfg.cmoe.top_k if cfg.cmoe is not None else 1
    tiered = bool(tiers) and any(t is not None and t != k_max
                                 for t in tiers)
    prefix_groups = None
    if args.prefix_groups:
        prefix_groups = [int(p) for p in args.prefix_groups.split(",")]
        # shared prefixes lengthen prompts past --prompt-len: widen the
        # max_len wall so nothing truncates just for carrying one
        max_len += max(prefix_groups)
    priorities = None
    if args.priority:
        priorities = [int(p) for p in args.priority.split(",")]
    lo_p = min(max(4, args.prompt_len // 2), args.prompt_len)
    reqs = make_requests(args.requests, cfg.vocab_size,
                         prompt_range=(lo_p, args.prompt_len),
                         gen_range=(max(1, args.gen // 2), args.gen),
                         rate=args.rate, seed=args.seed, tiers=tiers,
                         prefix_groups=prefix_groups,
                         priorities=priorities)
    engine = ServingEngine(model, params, max_slots=args.batch,
                           max_len=max_len,
                           max_prefill_tokens=args.max_prefill_tokens,
                           temperature=args.temperature, seed=args.seed,
                           paged=args.paged, block_size=args.block_size,
                           num_blocks=args.num_blocks,
                           prefix_reuse=args.prefix_reuse,
                           overlap=args.overlap)
    report = engine.run(reqs)
    print(f"[continuous] {report.summary()}")
    assert all(r.done for r in report.requests), "unfinished requests"
    if tiers:
        for k, m in sorted(report.tier_metrics().items()):
            print(f"[continuous] tier k={k}: {m['requests']} requests, "
                  f"{m['tokens']} tokens ({m['pairs']} routed pairs), "
                  f"TTFT p50/p95 {m['ttft_p50_s'] * 1e3:.1f}/"
                  f"{m['ttft_p95_s'] * 1e3:.1f} ms, TPOT p50/p95 "
                  f"{m['tpot_p50_s'] * 1e3:.1f}/"
                  f"{m['tpot_p95_s'] * 1e3:.1f} ms")
        print(f"[continuous] active-pair utilization "
              f"{report.active_pair_utilization * 100:.0f}% vs token "
              f"utilization {report.compute_utilization * 100:.0f}% "
              f"(K_max={report.k_max}; the gap is compute the tier mix "
              f"did not charge)")
    if args.max_prefill_tokens is not None and not args.overlap:
        n_chunks = len([1 for _, ph, *_ in engine.backend_log
                        if ph == "prefill"])
        longest = max(r.prompt_len for r in report.requests)
        print(f"[continuous] chunked prefill: budget "
              f"{args.max_prefill_tokens} tok/step, longest prompt "
              f"{longest}, {n_chunks} prefill micro-batches")
    if args.paged:
        kv = engine.kv
        print(f"[continuous] paged pool: {kv.num_blocks} blocks x "
              f"{kv.block_size} tokens (+1 trash), peak occupancy "
              f"{report.peak_occupancy}/{args.batch} slots, "
              f"{report.gate_deferrals} admission deferrals "
              f"({report.deferral_causes or 'none'}), "
              f"{report.preemptions} preemptions, "
              f"{report.truncated} truncated, end-of-run audit "
              f"{report.pool_audit}")
    if args.prefix_reuse:
        print(f"[continuous] prefix reuse: hit-rate "
              f"{report.prefix_hit_rate * 100:.0f}% "
              f"({report.prefix_matched_tokens}/"
              f"{report.prefix_prompt_tokens} prefill tokens skipped, "
              f"{report.prefix_hits} hits), {report.reused_blocks} "
              f"blocks shared by refcount, {report.cow_copies} "
              f"copy-on-write tails")
    if args.expect_preemption:
        assert report.preemptions > 0, (
            "--expect-preemption: no lane was preempted — pool "
            "pressure or the priority mix never triggered the policy")
        assert all(r.done for r in report.requests), (
            "a preempted request failed to complete")
        print(f"[continuous] preemption OK: {report.preemptions} "
              f"evictions, every request (victims included) completed")
    if args.parity:
        # every baseline runs overlap-off, so under --overlap (the
        # default) each comparison also certifies the fused double-
        # buffered loop against the sequential one
        comparisons = []   # (what, fork_msg, engine kwargs)
        common = dict(max_slots=args.batch, max_len=max_len,
                      temperature=args.temperature, seed=args.seed)
        if args.overlap:
            comparisons.append((
                "overlap == sequential",
                "the overlapped engine forked the generated streams — "
                "the fused dispatch or the one-step emission lag leaked "
                "into the tokens",
                dict(common, max_prefill_tokens=args.max_prefill_tokens,
                     paged=args.paged, block_size=args.block_size,
                     num_blocks=args.num_blocks,
                     prefix_reuse=args.prefix_reuse, overlap=False)))
        if args.prefix_reuse:
            comparisons.append((
                "prefix reuse == no reuse",
                "prefix sharing forked the generated streams — an "
                "adopted block's K/V was not bitwise what the request "
                "would have prefilled",
                dict(common, max_prefill_tokens=args.max_prefill_tokens,
                     paged=True, block_size=args.block_size,
                     num_blocks=args.num_blocks, prefix_reuse=False,
                     overlap=False)))
        if args.priority and args.paged and args.num_blocks is not None:
            comparisons.append((
                "preempted == unpressured",
                "preemption forked the generated streams — a victim's "
                "recompute replay did not resume token-identically",
                dict(common, max_prefill_tokens=args.max_prefill_tokens,
                     paged=True, block_size=args.block_size,
                     num_blocks=None, prefix_reuse=args.prefix_reuse,
                     overlap=False)))
        if args.paged:
            comparisons.append((
                "paged == contiguous",
                "paged and contiguous serving forked the generated "
                "streams — the block tables leaked into the numerics",
                dict(common, max_prefill_tokens=args.max_prefill_tokens,
                     overlap=False)))
        elif args.max_prefill_tokens is not None:
            comparisons.append((
                "chunked == unchunked",
                "chunked and unchunked prefill forked the generated "
                "streams — chunk width leaked into the numerics",
                dict(common, max_prefill_tokens=None, overlap=False)))
        if not comparisons:
            raise SystemExit("--parity needs an axis to compare: "
                             "--overlap (default), --paged, or "
                             "--max-prefill-tokens")
        toks = {r.rid: tuple(r.generated) for r in report.requests}
        assert report.dropped_pairs == 0, (
            "routed pairs were dropped", report.dropped_pairs)
        for what, fork_msg, kw in comparisons:
            base = ServingEngine(model, params, **kw).run(reqs)
            toks_base = {r.rid: tuple(r.generated) for r in base.requests}
            assert toks == toks_base, fork_msg
            assert base.dropped_pairs == 0, (
                "routed pairs were dropped", base.dropped_pairs)
            print(f"[continuous] parity OK: {what} token-for-token "
                  f"({sum(len(t) for t in toks.values())} tokens), "
                  f"0 dropped pairs in both runs")

    # the acceptance contract: decode micro-batches on the gather path,
    # prefill micro-batches above the gather break-even on a grouped path;
    # a fused (overlapped) step picks by its TRUE padded width — phase
    # "mixed" — so each logged row must match the policy for its width.
    # Only meaningful under the auto policy — a pinned --backend is the
    # user's own (bench-mode) choice, reported but not asserted.
    bc = report.backend_counts
    has_experts = any(b != "-" for c in bc.values() for b in c)
    if has_experts and args.backend in (None, "auto", "all"):
        # ("all" is a static-mode flag; the engine itself ran auto)
        decode_b = set(bc["decode"])
        prefill_b = set(bc["prefill"])
        if args.overlap:
            # a fused step is one (R, 1) micro-batch logged under the
            # decode cadence: no prefill micro-batch exists, and the
            # backend each step ran must be the width policy's choice
            # for its padded row count (gather for decode-only widths,
            # grouped once chunk rows push R over the break-even)
            assert not prefill_b, f"fused mode dispatched prefill " \
                f"micro-batches: {prefill_b}"
            for _, _, padded, live, backend, _, active in \
                    engine.backend_log:
                # under a tier mix the policy break-even shifts by the
                # dispatch's mean live k — recompute with the SAME
                # effective_k the engine handed the executor, so the
                # assertion stays exact rather than approximate
                eff = (active / max(live, 1)) if tiered else None
                want = microbatch_backend(cfg, padded, "mixed",
                                          use_kernel=model.use_kernel,
                                          effective_k=eff)
                assert backend == want, \
                    f"fused width {padded} ran {backend}, policy {want}"
        else:
            assert decode_b == {"gather"}, f"decode ran {decode_b}"
            assert prefill_b <= {"grouped_xla", "grouped_pallas",
                                 "gather"} and \
                prefill_b & {"grouped_xla", "grouped_pallas"}, \
                f"prefill ran {prefill_b}"
        print(f"[continuous] backend policy OK: prefill={sorted(prefill_b)} "
              f"decode={sorted(decode_b)}")
    elif has_experts:
        print(f"[continuous] backend pinned to {args.backend!r} "
              f"(phase policy not asserted; every engine backend is "
              f"drop-free, so this is a throughput choice, not a "
              f"correctness one)")
    if report.slot_reuse == 0 and args.requests > args.batch:
        print("[continuous] warning: no slot was recycled (arrivals too "
              "spread out?)")
    return report


def run(argv=None):
    """The CLI's body. Returns (model, report): the served model and, in
    --continuous mode, the engine's EngineReport (None in static mode),
    so an in-process caller can check what was served."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--cmoe", default=None, help="SxAyEz conversion tag")
    ap.add_argument("--batch", type=int, default=4,
                    help="batch width; in --continuous mode, the slot count")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--backend", default=None,
                    choices=list(BACKENDS) + ["auto", "all"],
                    help="routed-expert engine backend (default: "
                         "phase-driven auto — grouped prefill, gather "
                         "decode); 'all' benchmarks decode tok/s per "
                         "backend")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine: staggered arrivals, "
                         "mixed lengths, slot recycling")
    ap.add_argument("--requests", type=int, default=8,
                    help="[--continuous] number of requests")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="[--continuous] Poisson arrival rate "
                         "(requests per engine step; 0 = all at once)")
    ap.add_argument("--max-prefill-tokens", type=int, default=None,
                    help="[--continuous] per-step prefill token budget: "
                         "longer prompts are chunked across steps so a "
                         "long prompt cannot stall decode lanes "
                         "(default: unlimited)")
    ap.add_argument("--capacity-factor", type=float, default=None,
                    help="capacity factor for the bounded EP dispatch stage "
                         "(EP all-to-all shard binning; the "
                         "engine's grouped backends are ragged and ignore "
                         "it). Useful with --parity to demonstrate width-"
                         "invariance at factors where the old scatter "
                         "contract forked streams (e.g. 0.75)")
    ap.add_argument("--paged", action="store_true",
                    help="[--continuous] paged KV cache: a block pool "
                         "with per-request block tables instead of "
                         "contiguous max_len slot lanes; admission is "
                         "gated on pool headroom")
    ap.add_argument("--block-size", type=int, default=16,
                    help="[--paged] tokens per cache block")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="[--paged] pool size in blocks (default: the "
                         "same token capacity as the contiguous cache, "
                         "batch x max_len)")
    ap.add_argument("--prefix-reuse", action="store_true",
                    help="[--paged] content-addressed prefix sharing: "
                         "admission adopts matching cached blocks "
                         "(refcounted full blocks + a copy-on-write "
                         "tail) and prefills only the unmatched "
                         "remainder — token-identical to reuse off")
    ap.add_argument("--prefix-groups", default=None,
                    help="[--continuous] comma list of shared system-"
                         "prompt lengths cycled over requests (0 = no "
                         "shared prefix), e.g. '24' or '32,0' — "
                         "generates the hot-prefix traffic "
                         "--prefix-reuse exploits")
    ap.add_argument("--priority", default=None,
                    help="[--continuous] comma list of SLO priority "
                         "classes cycled over requests (higher wins), "
                         "e.g. '0,1' — under paged pool pressure a "
                         "higher class preempts the lowest running lane "
                         "instead of deferring behind it")
    ap.add_argument("--expect-preemption", action="store_true",
                    help="assert at least one lane was preempted and "
                         "every request (victims included) still "
                         "completed — the overload-policy smoke")
    ap.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="[--continuous] overlapped engine: one fused "
                         "ragged dispatch per step, on-device sampling, "
                         "host readback lagging one step (default on; "
                         "--no-overlap runs the sequential two-dispatch "
                         "baseline)")
    ap.add_argument("--tier", default=None,
                    help="[--continuous] per-request activation tier(s): "
                         "an int (uniform effective routed top-k) or a "
                         "comma list cycled over requests, e.g. "
                         "'1,default' — 'default' is the config top_k "
                         "(K_max). Tiers are routing data, not shape: "
                         "mixed tiers co-batch into the same fused steps, "
                         "and the report adds per-tier TTFT/TPOT and "
                         "active-pair (k-weighted) utilization. Needs "
                         "--cmoe")
    ap.add_argument("--parity", action="store_true",
                    help="[--continuous] replay the request set on the "
                         "other axes — sequential under --overlap, "
                         "contiguous under --paged, unchunked under "
                         "--max-prefill-tokens — and assert "
                         "token-identical streams + zero reported drops")
    ap.add_argument("--use-kernel", action="store_true", default=None,
                    help="run the Pallas kernel paths (paged-attention "
                         "decode, gather/grouped MoE kernels). Default: "
                         "auto — on when a TPU is attached. Setting it "
                         "explicitly off-TPU runs the kernels in interpret "
                         "mode: a correctness gate (e.g. with --paged "
                         "--parity), not a speed run")
    args = ap.parse_args(argv)
    print(f"[jax] {jax.default_backend()} backend, compile cache "
          f"{enable_compile_cache()}")

    if args.continuous and args.smoke and not args.cmoe:
        # exercise the per-micro-batch backend policy by default: without
        # routed experts there is nothing for the phase split to select
        args.cmoe = "S2A2E8"
        print("[continuous] defaulting --cmoe S2A2E8 (smoke)")

    backend = None if args.backend in (None, "auto", "all") else args.backend
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = override(cfg, dtype="float32") if args.smoke else cfg
    if args.capacity_factor is not None and cfg.moe is not None:
        import dataclasses
        cfg = override(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=args.capacity_factor))
    # inference-only: safe to opt into the Pallas kernels on TPU (they
    # have no VJP, so training paths must leave use_kernel off). An
    # explicit --use-kernel off-TPU is honored in interpret mode rather
    # than raising — that's the CI parity gate's path.
    from repro.kernels import ops as kops
    use_kernel = kops.on_tpu() if args.use_kernel is None \
        else args.use_kernel
    if use_kernel and not kops.on_tpu():
        print("[kernels] warning: no TPU attached — Pallas kernels run in "
              "interpret mode (correctness validation, not speed)")
    model = build_model(cfg, use_kernel=use_kernel, backend=backend)
    params = model.init(jax.random.PRNGKey(args.seed))

    if args.cmoe:
        cm = parse_sxayez(args.cmoe)
        if cm.k_activation > cfg.d_ff // cm.num_experts:
            cm = CMoEConfig(num_experts=cm.num_experts,
                            num_shared=cm.num_shared, top_k=cm.top_k,
                            k_activation=max(2, cfg.d_ff // 32))
        calib = make_calibration_batch(cfg.vocab_size, 4, 128,
                                       seed=args.seed)
        calib = {"tokens": jnp.asarray(calib["tokens"])}
        t0 = time.perf_counter()
        if cfg.family == "moe":
            from repro.core.hierarchical import convert_moe_model
            model, params, report = convert_moe_model(model, params, calib,
                                                      cm)
        else:
            model, params, report = convert_dense_model(model, params,
                                                        calib, cm)
        t_conv = time.perf_counter() - t0
        print(f"[cmoe] converted {report.num_layers} layers "
              f"({cm.tag()}) in {report.seconds_total:.2f}s "
              f"({t_conv:.2f}s wall incl. tracing)")

    if args.continuous:
        if args.backend == "all":
            print("[continuous] note: --backend all (per-backend decode "
                  "tok/s table) is a static-mode feature; the engine runs "
                  "the auto phase policy")
        import contextlib
        ctx = contextlib.nullcontext()
        if args.capacity_factor is not None:
            # thread the factor to the CMoE policy seam (the bounded
            # stages read it; the ragged engine backends ignore it)
            from jax.sharding import Mesh
            from repro.distributed.policy import activation_sharding
            mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
            ctx = activation_sharding(mesh, seq_shard=False,
                                      capacity_factor=args.capacity_factor)
        with ctx:
            return model, serve_continuous(model, params, args)

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    max_len = args.prompt_len + args.gen

    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len=max_len))
    decode = jax.jit(model.decode_step)

    batch = {"tokens": jnp.asarray(prompts)}
    t0 = time.perf_counter()
    logits, cache = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0
    logits_p, cache0 = logits, cache   # pristine post-prefill state

    def run_decode(dec, first, cache, steps, pick):
        """Warm up (compile) then run `steps` timed decode steps; returns
        (generated tokens incl. `first`, seconds). The warm-up replays the
        first step — an idempotent cache write — so every reported tok/s
        is steady state."""
        wl, _ = dec(params, first, cache, jnp.int32(args.prompt_len))
        jax.block_until_ready(wl)
        # warm the sampler too (one pick per run keeps the PRNG streams of
        # the main and per-backend runs aligned)
        jax.block_until_ready(pick(wl))
        toks = [first]
        t0 = time.perf_counter()
        for i in range(steps):
            pos = jnp.int32(args.prompt_len + i)
            lg, cache = dec(params, toks[-1], cache, pos)
            toks.append(pick(lg)[:, None])
        jax.block_until_ready(toks[-1])
        return toks, time.perf_counter() - t0

    steps = args.gen - 1    # prefill's argmax supplies the first token

    first = jnp.argmax(logits_p, -1)[:, None]
    # ONE sampling rule (repro.serving.sampling) for the main run and the
    # per-backend comparisons below, so tok/s rows decode identically
    pick = make_sampler(args.temperature, args.seed)
    tokens, t_decode = run_decode(decode, first, cache, steps, pick)
    out = jnp.concatenate(tokens, axis=1)
    tput = args.batch * steps / max(t_decode, 1e-9)
    print(f"prefill: {t_prefill*1000:.1f} ms for "
          f"{args.batch}x{args.prompt_len} tokens")
    tag = model.backend or "auto"
    print(f"decode[{tag}]: {tput:.1f} tok/s ({t_decode*1000:.1f} ms total)")
    print("sample:", np.asarray(out[0])[:16].tolist())

    if args.backend == "all":
        # decode tok/s per engine backend, same cache/prompt, same
        # sampling rule (fresh sampler per backend replays the stream)
        for be in BACKENDS:
            if be == "grouped_pallas" and \
                    model.cfg.activation not in ("swiglu", "geglu"):
                print(f"decode[{be}]: skipped (moe_gmm kernel is glu-only)")
                continue
            m_be = build_model(model.cfg, use_kernel=model.use_kernel,
                               backend=be)
            dec = jax.jit(m_be.decode_step)
            _, dt = run_decode(dec, first, cache0, steps,
                               make_sampler(args.temperature, args.seed))
            tput = args.batch * steps / max(dt, 1e-9)
            print(f"decode[{be}]: {tput:.1f} tok/s ({dt*1000:.1f} ms total)")
    return model, None


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    sys.exit(main())
