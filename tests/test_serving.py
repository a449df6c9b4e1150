"""Continuous-batching serving engine: slot-cache parity + scheduling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CMoEConfig, override
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving import Request, Scheduler, ServingEngine, StepExecutor
from repro.serving.cache import SlotKVCache


def _static_generate(model, params, prompt, max_new, max_len):
    """Reference: the classic per-request prefill + decode loop (greedy)."""
    lg, cache = model.prefill(
        params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]},
        max_len=max_len)
    toks = [int(jnp.argmax(lg, -1)[0])]
    pos = len(prompt)
    while len(toks) < max_new:
        lg, cache = model.decode_step(
            params, jnp.asarray([[toks[-1]]], jnp.int32), cache,
            jnp.int32(pos))
        toks.append(int(jnp.argmax(lg, -1)[0]))
        pos += 1
    return toks


def _assert_greedy_chain(model, params, prompt, generated, max_len,
                         tie_atol=5e-4):
    """Token-for-token parity with the static loop, teacher-forced.

    Replays `generated` through the per-request prefill + decode path and
    asserts every token is the static model's greedy argmax. Comparing
    free-running chains instead would flake: the engine's full-width
    decode and the batch-1 static path differ by ~1e-6 fp noise
    (thread-partitioned matmuls), which can flip a genuine near-tie and
    cascade. A real bug (capacity drops, mask leaks) shifts logits by
    orders of magnitude more than tie_atol."""
    lg, cache = model.prefill(
        params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]},
        max_len=max_len)
    pos = len(prompt)
    for j, tok in enumerate(generated):
        lrow = np.asarray(lg)[0]
        arg = int(lrow.argmax())
        assert arg == tok or lrow[arg] - lrow[tok] < tie_atol, \
            (j, arg, tok, float(lrow[arg] - lrow[tok]))
        if j + 1 < len(generated):
            lg, cache = model.decode_step(
                params, jnp.asarray([[tok]], jnp.int32), cache,
                jnp.int32(pos))
            pos += 1


def test_recycled_slot_prefill_parity(qwen_smoke):
    """Prefilling a prompt into a DIRTY recycled slot produces the same
    logits as a fresh contiguous-batch prefill: recycling is just a length
    reset, stale K/V is never attended."""
    cfg, model, params = qwen_smoke
    max_len = 40
    ex = StepExecutor(model)
    rng = np.random.default_rng(3)
    kv = SlotKVCache(model, 2, max_len)

    # occupy both slots with a first tenant and let it decode a while
    a = rng.integers(0, cfg.vocab_size, (2, 14)).astype(np.int32)
    _, kv.cache, _, _ = ex.prefill(params, kv.cache, jnp.asarray(a),
                                   jnp.asarray([0, 1], jnp.int32),
                                   jnp.asarray([14, 14], jnp.int32))
    kv.lengths[:] = 14
    for i in range(6):
        tok = rng.integers(0, cfg.vocab_size, (2, 1)).astype(np.int32)
        # kv.positions() COPIES: jnp.asarray(kv.lengths) would zero-copy
        # alias the numpy buffer, and the += 1 below races the async step
        _, kv.cache, _, _ = ex.decode(params, kv.cache, jnp.asarray(tok),
                                      jnp.asarray(kv.positions()))
        kv.lengths += 1

    # recycle slot 1: new prompt prefills at position 0 over the residue
    b_prompt = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
    kv.free(1)
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :11] = b_prompt
    lg_recycled, kv.cache, _, _ = ex.prefill(
        params, kv.cache, jnp.asarray(tokens),
        jnp.asarray([1], jnp.int32), jnp.asarray([11], jnp.int32))
    kv.lengths[1] = 11

    lg_fresh, cache_fresh = model.prefill(
        params, {"tokens": jnp.asarray(b_prompt)[None]}, max_len=max_len)
    np.testing.assert_allclose(np.asarray(lg_recycled[0]),
                               np.asarray(lg_fresh[0]),
                               atol=2e-4, rtol=2e-4)

    # and the greedy continuation matches while slot 0 keeps decoding
    got = [int(jnp.argmax(lg_recycled, -1)[0])]
    while len(got) < 5:
        toks = np.zeros((2, 1), np.int32)
        toks[0, 0] = rng.integers(0, cfg.vocab_size)   # slot 0: other tenant
        toks[1, 0] = got[-1]
        lg, kv.cache, _, _ = ex.decode(params, kv.cache, jnp.asarray(toks),
                                       jnp.asarray(kv.positions()))
        kv.lengths += 1
        got.append(int(jnp.argmax(lg, -1)[1]))
    _assert_greedy_chain(model, params, b_prompt, got, max_len)


def test_continuous_matches_static_loop_greedy(qwen_smoke):
    """Mixed prefill+decode engine steps reproduce the static per-request
    loop token-for-token (greedy), across padding, queueing, and slot
    recycling."""
    cfg, model, params = qwen_smoke
    max_len = 32
    specs = [(9, 5, 0.0), (12, 4, 0.0), (5, 6, 1.0), (11, 3, 3.0),
             (7, 5, 8.0)]
    rng = np.random.default_rng(11)
    reqs = []
    for i, (plen, gen, arr) in enumerate(specs):
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        reqs.append(Request(rid=i, prompt=[int(t) for t in prompt],
                            max_new=gen, arrival=arr))
    engine = ServingEngine(model, params, max_slots=2, max_len=max_len,
                           prefill_bucket=8)
    report = engine.run(reqs)
    assert all(r.done for r in report.requests)
    assert report.slot_reuse >= 3          # 5 requests through 2 slots
    for r in report.requests:
        assert len(r.generated) == r.max_new, f"request {r.rid}"
        _assert_greedy_chain(model, params, r.prompt, r.generated, max_len)


def test_continuous_matches_static_loop_mla():
    """The slot-aware step also serves MLA (latent cache, absorbed decode):
    per-slot writes into the (B, T, r) latent + ragged prefill masks
    reproduce the static loop token-for-token."""
    cfg = override(get_smoke_config("deepseek-v2-236b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i,
                    prompt=[int(t) for t in
                            rng.integers(0, cfg.vocab_size, 6 + 2 * i)],
                    max_new=4, arrival=float(i))
            for i in range(3)]
    engine = ServingEngine(model, params, max_slots=2, max_len=24,
                           prefill_bucket=8)
    report = engine.run(reqs)
    assert report.slot_reuse >= 1
    assert set(report.backend_counts["decode"]) == {"gather"}
    for r in report.requests:
        assert len(r.generated) == r.max_new, f"request {r.rid}"
        _assert_greedy_chain(model, params, r.prompt, r.generated, 24)


def _run_engine(model, params, reqs, *, max_slots, max_len, bucket,
                mpt, temperature=0.0):
    engine = ServingEngine(model, params, max_slots=max_slots,
                           max_len=max_len, prefill_bucket=bucket,
                           max_prefill_tokens=mpt, temperature=temperature)
    report = engine.run(reqs)
    assert all(r.done for r in report.requests)
    return {r.rid: tuple(r.generated) for r in report.requests}, report


def test_chunked_matches_unchunked_greedy(qwen_smoke):
    """Chunked prefill is a pure scheduling change: the same request set
    produces TOKEN-IDENTICAL greedy streams with and without a prefill
    budget, across resumed chunks, recycled slots, piggybacked width-1
    tail chunks, and chunk boundaries landing exactly on the bucket
    boundary."""
    cfg, model, params = qwen_smoke
    max_len = 48
    # 33 = 8x4 + 1: >= 8 budgets long, with a width-1 piggyback tail;
    # 16 = 2 budgets exactly when budget=8=bucket (chunk == bucket edge);
    # 8 = exactly one budget (single chunk, fresh-slot fast path)
    specs = [(9, 5, 0.0), (33, 6, 1.0), (16, 4, 2.0), (8, 4, 6.0),
             (11, 5, 9.0)]
    rng = np.random.default_rng(11)
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size, plen)],
                    max_new=gen, arrival=arr)
            for i, (plen, gen, arr) in enumerate(specs)]

    base, rep_base = _run_engine(model, params, reqs, max_slots=2,
                                 max_len=max_len, bucket=8, mpt=None)
    for budget in (4, 8):
        got, rep = _run_engine(model, params, reqs, max_slots=2,
                               max_len=max_len, bucket=8, mpt=budget)
        assert got == base, f"budget={budget}"
        assert rep.slot_reuse >= 3                     # 5 requests, 2 slots
        # chunking really happened: more prefill micro-batches than
        # requests admitted as whole prompts
        assert rep.backend_counts["prefill"].total() > \
            rep_base.backend_counts["prefill"].total()
    # and the streams are the static loop's greedy chain
    for r in rep_base.requests:
        _assert_greedy_chain(model, params, r.prompt, list(r.generated),
                             max_len)


@pytest.mark.parametrize("backend", ["grouped_xla", "grouped_pallas"])
def test_chunked_matches_unchunked_mla_grouped(backend):
    """Chunked==unchunked parity for the MLA latent cache ON THE GROUPED
    BACKENDS at a tight capacity_factor (0.75) — the exact regime where
    the old width-dependent capacity-scatter contract provably forked the
    streams (this test used to pin the gather backend to dodge it). The
    ragged segment dispatch has no capacity buffer, so every micro-batch
    width computes bitwise-identical routed outputs, every pair survives,
    and the report shows zero drops."""
    import dataclasses
    cfg = override(get_smoke_config("deepseek-v2-236b"), dtype="float32")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.75))
    model = build_model(cfg, backend=backend)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size,
                                                6 + 5 * i)],
                    max_new=4, arrival=float(i))
            for i in range(3)]
    base, rep_base = _run_engine(model, params, reqs, max_slots=2,
                                 max_len=24, bucket=8, mpt=None)
    got, rep = _run_engine(model, params, reqs, max_slots=2, max_len=24,
                           bucket=8, mpt=3)
    assert got == base
    assert rep.slot_reuse >= 1
    assert rep_base.dropped_pairs == 0 and rep.dropped_pairs == 0
    assert set(rep.backend_counts["decode"]) == {backend}
    assert backend in set(rep.backend_counts["prefill"])


@pytest.mark.parametrize("backend", ["grouped_xla", "grouped_pallas"])
def test_chunked_matches_unchunked_gqa_grouped(backend):
    """The GQA side of the width-invariance acceptance gate: a CMoE
    (dense-converted layout) model pinned to a grouped backend at
    capacity_factor 0.75 serves chunked == unchunked token-for-token with
    zero reported drops."""
    from jax.sharding import Mesh
    from repro.distributed.policy import activation_sharding
    cfg = override(get_smoke_config("qwen1.5-0.5b"), dtype="float32",
                   cmoe=CMoEConfig(num_experts=8, num_shared=2, top_k=2,
                                   k_activation=4))
    model = build_model(cfg, backend=backend)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size,
                                                5 + 9 * i)],
                    max_new=4, arrival=float(i))
            for i in range(3)]
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    with activation_sharding(mesh, seq_shard=False, capacity_factor=0.75):
        base, rep_base = _run_engine(model, params, reqs, max_slots=2,
                                     max_len=32, bucket=8, mpt=None)
        got, rep = _run_engine(model, params, reqs, max_slots=2,
                               max_len=32, bucket=8, mpt=6)
    assert got == base
    assert rep_base.dropped_pairs == 0 and rep.dropped_pairs == 0
    assert backend in set(rep.backend_counts["prefill"])


def test_chunked_sampling_schedule_invariant(qwen_smoke):
    """temperature > 0: a request's sampled stream is keyed by
    (rid, token index), so it cannot depend on chunking, slot placement,
    or micro-batch composition."""
    from repro.serving import make_sampler
    # direct: the same (rid, token_idx) row draws the same token no
    # matter where it sits in the batch or what its neighbors are
    pick = make_sampler(0.8, seed=3)
    logits = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (3, 64)))
    a = np.asarray(pick(jnp.asarray(logits),
                        np.asarray([5, 7, 9]), np.asarray([0, 2, 4])))
    b = np.asarray(pick(jnp.asarray(logits[1:2]),
                        np.asarray([7]), np.asarray([2])))
    assert a[1] == b[0]
    # and the legacy stream mode still replays per-(temperature, seed)
    s1, s2 = make_sampler(0.8, 0), make_sampler(0.8, 0)
    lg = jnp.asarray(logits)
    np.testing.assert_array_equal(np.asarray(s1(lg)), np.asarray(s2(lg)))

    # engine: chunked == unchunked token-for-token BEYOND greedy
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(4)
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size,
                                                5 + 7 * i)],
                    max_new=5, arrival=float(i))
            for i in range(3)]
    base, _ = _run_engine(model, params, reqs, max_slots=2, max_len=32,
                          bucket=8, mpt=None, temperature=0.7)
    got, _ = _run_engine(model, params, reqs, max_slots=2, max_len=32,
                         bucket=8, mpt=6, temperature=0.7)
    assert got == base


def test_chunked_report_metrics(qwen_smoke):
    """EngineReport's decode-stall telemetry: gaps recorded between
    consecutive decode steps, TPOT percentiles populated, TTFT measured
    to the FIRST TOKEN (a chunked long prompt's TTFT reflects its chunk
    ramp, not just admission)."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(6)
    reqs = [Request(rid=0, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size, 4)],
                    max_new=12, arrival=0.0),
            Request(rid=1, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size, 24)],
                    max_new=2, arrival=2.0)]
    _, rep = _run_engine(model, params, reqs, max_slots=2, max_len=32,
                         bucket=8, mpt=8)
    assert len(rep.decode_gaps_s) >= 8
    assert rep.tpot_p95_s >= rep.tpot_p50_s > 0
    assert "TPOT" in rep.summary()
    long_req = next(r for r in rep.requests if r.rid == 1)
    # 24-token prompt at budget 8 = 3 chunks: first token lands >= 2
    # steps after admission
    assert long_req.first_token_step >= long_req.admit_step + 2
    assert long_req.prefill_pos == long_req.prompt_len


def test_decode_gap_skips_piggyback_only_steps(qwen_smoke):
    """TPOT telemetry regression: a piggyback-only dispatch (a width-1
    prefill chunk riding the decode shape with NO RUNNING lane) must
    neither record a decode gap nor keep the gap chain alive — recording
    it inflated TPOT p50/p95 with stalls no decode token paid.

    Timeline (1-token prompts, max_new=3, slots=1):
      step 0  A piggyback-only      -> no gap, chain stays broken
      step 1  A decodes             -> chain starts (no gap yet)
      step 2  A decodes, finishes   -> gap #1
      step 3  idle (B not due)      -> chain broken
      step 4  B piggyback-only      -> no gap (the bug recorded one here
                                       once the chain survived step 3's
                                       break in longer variants)
      step 5  B decodes             -> chain starts
      step 6  B decodes, finishes   -> gap #2
    """
    cfg, model, params = qwen_smoke
    reqs = [Request(rid=0, prompt=[3], max_new=3, arrival=0.0),
            Request(rid=1, prompt=[4], max_new=3, arrival=4.0)]
    engine = ServingEngine(model, params, max_slots=1, max_len=8,
                           prefill_bucket=4, max_prefill_tokens=4)
    rep = engine.run(reqs)
    assert all(r.done for r in rep.requests)
    # every piggyback-only step ran the decode dispatch (backend_log has
    # a decode row with live lanes > 0) yet recorded no gap
    decode_steps = [s for s, ph, *_ in engine.backend_log
                    if ph == "decode"]
    assert len(decode_steps) == 6                      # 3 per request
    assert len(rep.decode_gaps_s) == 2, rep.decode_gaps_s
    assert rep.tpot_p50_s > 0


def test_engine_backend_policy_per_microbatch():
    """Decode micro-batches run the gather backend (cheapest at decode
    T); prefill micro-batches above the break-even run grouped."""
    cfg = override(get_smoke_config("qwen1.5-0.5b"), dtype="float32",
                   cmoe=CMoEConfig(num_experts=8, num_shared=2, top_k=2,
                                   k_activation=4))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i,
                    prompt=[int(t) for t in
                            rng.integers(0, cfg.vocab_size, 16)],
                    max_new=4, arrival=float(i))
            for i in range(5)]
    engine = ServingEngine(model, params, max_slots=2, max_len=24,
                           prefill_bucket=16)
    report = engine.run(reqs)
    assert all(r.done for r in report.requests)
    bc = report.backend_counts
    assert set(bc["decode"]) == {"gather"}, bc
    # prompts are 16 tokens >= the E/k=4 break-even -> grouped
    assert set(bc["prefill"]) == {"grouped_xla"}, bc
    assert report.slot_reuse >= 1

    # chunked: a 48-token prompt against a 16-token budget still runs its
    # chunks on the grouped backend while decode stays on gather
    rng = np.random.default_rng(8)
    long_reqs = [Request(rid=i, prompt=[int(t) for t in
                                        rng.integers(0, cfg.vocab_size,
                                                     48)],
                         max_new=4, arrival=float(i))
                 for i in range(2)]
    engine = ServingEngine(model, params, max_slots=2, max_len=56,
                           prefill_bucket=16, max_prefill_tokens=16)
    report = engine.run(long_reqs)
    bc = report.backend_counts
    assert set(bc["decode"]) == {"gather"}, bc
    assert set(bc["prefill"]) == {"grouped_xla"}, bc
    assert bc["prefill"].total() >= 6                  # 3 chunks per prompt


def test_padded_prefill_takes_no_expert_capacity():
    """Right-padded prompt rows must not route through the experts: a
    short prompt padded into a wide micro-batch would otherwise fill
    grouped-backend capacity with junk tokens and displace REAL tokens'
    routed output (regression: row logits diverged by ~0.4).

    The invariant: every row's logits are INDEPENDENT of the padding
    content (padding parks past every real segment of the ragged layout,
    so it cannot perturb real tokens' dispatch), and EVERY row — short or
    full — matches its fresh per-request prefill: under the per-token
    capacity contract a token's routed output is independent of which
    other rows share its micro-batch, so the 128-token micro-batch and
    the 32-token per-request prefill compute the same function (the old
    capacity-scatter contract only guaranteed this for the short row)."""
    cfg = override(get_smoke_config("qwen1.5-0.5b"), dtype="float32",
                   cmoe=CMoEConfig(num_experts=8, num_shared=2, top_k=2,
                                   k_activation=4))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    lens = [4, 32, 32, 32]                 # one short row, heavy padding
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    ex = StepExecutor(model)

    def prefill_with_pad(pad_fill):
        kv = SlotKVCache(model, 4, 48)
        tokens = np.full((4, 32), pad_fill, np.int32)
        for i, pr in enumerate(prompts):
            tokens[i, :lens[i]] = pr
        logits, kv.cache, backend, dropped = ex.prefill(
            params, kv.cache, jnp.asarray(tokens),
            jnp.asarray(np.arange(4, dtype=np.int32)),
            jnp.asarray(lens, jnp.int32))
        assert backend == "grouped_xla"    # padding kept us on grouped
        assert int(dropped) == 0           # ragged dispatch never drops
        return np.asarray(logits)

    lg_a = prefill_with_pad(0)
    lg_b = prefill_with_pad(123)           # different junk beyond lengths
    np.testing.assert_array_equal(lg_a, lg_b)

    for i in range(4):                     # incl. the full 32-token rows
        ref, _ = model.prefill(
            params, {"tokens": jnp.asarray(prompts[i])[None]}, max_len=48)
        np.testing.assert_allclose(lg_a[i], np.asarray(ref[0]),
                                   atol=2e-4, rtol=2e-4)


def test_eos_finishes_early(qwen_smoke):
    """A request whose greedy stream hits EOS frees its slot before
    max_new."""
    cfg, model, params = qwen_smoke
    prompt = [int(t) for t in
              np.random.default_rng(7).integers(0, cfg.vocab_size, 8)]
    ref = _static_generate(model, params, prompt, 12, 32)
    # EOS = the first token value not seen earlier in the greedy stream
    # (a random-init model repeats itself, so ref[j] may occur before j)
    j = next((i for i in range(1, len(ref)) if ref[i] not in ref[:i]), 0)
    eos = ref[j]
    req = Request(rid=0, prompt=prompt, max_new=12, eos_id=eos)
    engine = ServingEngine(model, params, max_slots=1, max_len=32,
                           prefill_bucket=8)
    report = engine.run([req])
    assert req.done
    _assert_greedy_chain(model, params, prompt, req.generated, 32)
    # the slot was freed the moment EOS appeared — nothing after it
    assert eos not in req.generated[:-1]
    assert req.generated[-1] == eos and len(req.generated) == j + 1 < 12, \
        (req.generated, ref, j)
    assert report.total_new_tokens == len(req.generated)


def test_scheduler_admission_and_policies():
    mk = lambda rid, arr, plen=4: Request(rid=rid, prompt=[1] * plen,
                                          max_new=2, arrival=arr)

    def rids(plan):
        return [r.rid for r, _ in plan]

    s = Scheduler(2)
    s.submit([mk(0, 0.0), mk(1, 2.0), mk(2, 0.5)])
    p0 = s.plan_prefill(0.0)
    assert rids(p0) == [0]                             # only rid 0 due
    for r, c in p0:
        r.prefill_pos = c
        s.prefill_done(r)
    p1 = s.plan_prefill(1.0)
    assert rids(p1) == [2]                             # FIFO by arrival
    for r, c in p1:
        r.prefill_pos = c
        s.prefill_done(r)
    assert s.plan_prefill(2.0) == []                   # no free slot
    s.finish(s.slots[0], step=3)
    assert s.free_slots == [0]                         # heap recycled slot 0
    assert rids(s.plan_prefill(2.0)) == [1]
    assert s.slots[1].rid == 1 or s.slots[0].rid == 1
    assert s.slot_reuse == 1

    # static policy: admits only when ALL slots are free
    s2 = Scheduler(2, policy="static")
    s2.submit([mk(0, 0.0), mk(1, 0.0), mk(2, 0.0)])
    first = s2.plan_prefill(0.0)
    assert len(first) == 2
    for r, c in first:
        r.prefill_pos = c
        s2.prefill_done(r)
    assert s2.plan_prefill(0.0) == []
    s2.finish(first[0][0], step=1)
    assert s2.plan_prefill(1.0) == []                  # one still running
    s2.finish(first[1][0], step=2)
    assert rids(s2.plan_prefill(2.0)) == [2]

    # prefill token budget splits a thundering herd across steps. Budget
    # accounting charges PADDED widths: the first 5-token prompt sets the
    # step's row width (5), so a second 5-wide row would make the
    # executed micro-batch 2x5=10 > 8 — it waits for the next step
    # (planning real tokens only was the seed-adjacent overshoot: the
    # engine pads every row to the widest chunk)
    def drive(s, plan):
        for r, c in plan:
            r.prefill_pos += c
            if r.prefill_pos == r.prompt_len:
                s.prefill_done(r)

    s3 = Scheduler(4, max_prefill_tokens=8)
    s3.submit([mk(i, 0.0, plen=5) for i in range(3)])
    plan = s3.plan_prefill(0.0)
    assert [(r.rid, c) for r, c in plan] == [(0, 5)]
    drive(s3, plan)
    plan = s3.plan_prefill(0.0)
    assert [(r.rid, c) for r, c in plan] == [(1, 5)]
    drive(s3, plan)
    assert [(r.rid, c) for r, c in s3.plan_prefill(0.0)] == [(2, 5)]

    # a resumed remainder sets a narrow width class and an admission
    # shares the step at that width: 4-token resume + 4-token first chunk
    # = 8 padded tokens, exactly one budget
    s4 = Scheduler(4, max_prefill_tokens=8)
    s4.submit([mk(0, 0.0, plen=12), mk(1, 0.0, plen=5)])
    plan = s4.plan_prefill(0.0)
    assert [(r.rid, c) for r, c in plan] == [(0, 8)]
    drive(s4, plan)
    plan = s4.plan_prefill(0.0)
    assert [(r.rid, c) for r, c in plan] == [(0, 4), (1, 4)]

    # the engine's padding granule caps row count: at granule 8, one
    # 5-token row already occupies the whole (rounded) budget
    s5 = Scheduler(4, max_prefill_tokens=8, prefill_granule=8)
    s5.submit([mk(i, 0.0, plen=5) for i in range(2)])
    assert [(r.rid, c) for r, c in s5.plan_prefill(0.0)] == [(0, 5)]


def test_scheduler_budget_true_for_first_admission():
    """The seed defect: a single prompt wider than max_prefill_tokens used
    to be admitted whole (the budget check skipped when nothing was
    admitted yet). Chunking keeps the budget TRUE per step while still
    always making progress."""
    huge = Request(rid=0, prompt=[1] * 100, max_new=2)
    s = Scheduler(2, max_prefill_tokens=8)
    s.submit([huge])
    seen = 0
    for _ in range(20):
        plan = s.plan_prefill(0.0)
        if not plan:
            break
        assert sum(c for _, c in plan) <= 8            # budget-true
        for r, c in plan:
            r.prefill_pos += c
            if r.prefill_pos == r.prompt_len:
                s.prefill_done(r)
        seen += sum(c for _, c in plan)
    assert seen == 100
    assert huge.state == "running"
    # progress was one budget per step: exactly ceil(100/8) planning steps
    assert s.plan_prefill(0.0) == []

    # and the engine enforces it end to end: every prefill micro-batch in
    # the log — n rows x padded width INCLUDED — is at most one
    # (granule-rounded) budget of tokens, even when several requests
    # share a step
    import jax
    from repro.config import override
    from repro.configs import get_smoke_config
    cfg = override(get_smoke_config("qwen1.5-0.5b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    req = Request(rid=0, prompt=list(range(1, 21)), max_new=2)
    engine = ServingEngine(model, params, max_slots=2, max_len=24,
                           prefill_bucket=8, max_prefill_tokens=8)
    engine.run([req])
    prefills = [(t, n) for t, ph, n, _, _, _, _ in engine.backend_log
                if ph == "prefill"]
    assert len(prefills) == 3                          # ceil(20 / 8)
    assert all(n <= 8 for _, n in prefills), prefills

    rng = np.random.default_rng(17)
    herd = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size, 20)],
                    max_new=2) for i in range(3)]
    engine = ServingEngine(model, params, max_slots=4, max_len=24,
                           prefill_bucket=8, max_prefill_tokens=8)
    engine.run(herd)
    prefills = [n for _, ph, n, _, _, _, _ in engine.backend_log
                if ph == "prefill"]
    assert all(n <= 8 for n in prefills), prefills     # padded rows count


# --------------------------------------------------- overlapped engine


def _run_pair(model, params, reqs, *, max_slots=2, max_len=40, bucket=8,
              mpt=None, temperature=0.0, paged=False, block_size=8,
              eos=None):
    """Run the same request set overlap-off then overlap-on; returns
    (streams_off, streams_on, report_off, report_on)."""
    if eos is not None:
        for r in reqs:
            r.eos_id = eos
    kw = dict(max_slots=max_slots, max_len=max_len, prefill_bucket=bucket,
              max_prefill_tokens=mpt, temperature=temperature)
    if paged:
        kw.update(paged=True, block_size=block_size)
    off = ServingEngine(model, params, overlap=False, **kw).run(reqs)
    on = ServingEngine(model, params, overlap=True, **kw).run(reqs)
    assert all(r.done for r in off.requests)
    assert all(r.done for r in on.requests)
    return ({r.rid: tuple(r.generated) for r in off.requests},
            {r.rid: tuple(r.generated) for r in on.requests}, off, on)


def _fused_parity_trial(model, params, vocab, specs, *, mpt, paged,
                        temperature=0.0, eos=None):
    """One property-test trial: the fused single-dispatch engine must
    serve `specs` token-identically to the sequential two-dispatch loop
    (and with identical truncation flags) over ANY interleaving of chunk
    widths, piggyback tails, decode lanes, arrivals, and recycling the
    spec induces."""
    rng = np.random.default_rng(sum(p for p, _, _ in specs) + len(specs))
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(0, vocab, plen)],
                    max_new=gen, arrival=arr)
            for i, (plen, gen, arr) in enumerate(specs)]
    base, got, off, on = _run_pair(model, params, reqs, mpt=mpt,
                                   paged=paged, temperature=temperature,
                                   eos=eos)
    assert got == base, (specs, mpt, paged)
    assert ({r.rid: r.truncated for r in off.requests} ==
            {r.rid: r.truncated for r in on.requests})
    assert on.dropped_pairs == 0


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    _spec = st.tuples(st.integers(1, 30),         # prompt length
                      st.integers(1, 8),          # max_new
                      st.sampled_from([0.0, 1.0, 2.0, 5.0]))  # arrival

    @settings(max_examples=12, deadline=None)
    @given(specs=st.lists(_spec, min_size=1, max_size=5),
           mpt=st.sampled_from([3, 8]),
           paged=st.booleans())
    def test_fused_matches_sequential_property(qwen_smoke, specs, mpt,
                                               paged):
        cfg, model, params = qwen_smoke
        _fused_parity_trial(model, params, cfg.vocab_size, specs,
                            mpt=mpt, paged=paged)

except ImportError:
    def test_fused_matches_sequential_property(qwen_smoke):
        """hypothesis-free fallback: seeded random interleavings. Each
        trial draws a request mix whose chunk/decode interleaving differs
        (width-1 piggyback tails, budget-exact chunks, overlapping
        arrivals, recycling through 2 slots) and asserts the fused ragged
        dispatch == separate prefill + decode dispatches token-for-token."""
        cfg, model, params = qwen_smoke
        rng = np.random.default_rng(42)
        for trial in range(6):
            n = int(rng.integers(1, 6))
            specs = [(int(rng.integers(1, 31)), int(rng.integers(1, 9)),
                      float(rng.choice([0.0, 1.0, 2.0, 5.0])))
                     for _ in range(n)]
            _fused_parity_trial(model, params, cfg.vocab_size, specs,
                                mpt=int(rng.choice([3, 8])),
                                paged=bool(trial % 2))


@pytest.mark.parametrize("paged", [False, True])
def test_overlap_parity_gqa(qwen_smoke, paged):
    """Overlap-on == overlap-off token identity for the GQA cache, both
    layouts, with chunked prefill and temperature>0 in the mix — and the
    streams are the static loop's (greedy case checked via chain)."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(21)
    specs = [(9, 5, 0.0), (33, 6, 1.0), (16, 4, 2.0), (8, 4, 6.0)]
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size, plen)],
                    max_new=gen, arrival=arr)
            for i, (plen, gen, arr) in enumerate(specs)]
    base, got, _, on = _run_pair(model, params, reqs, max_len=48,
                                 mpt=8, paged=paged)
    assert got == base
    for r in on.requests:
        _assert_greedy_chain(model, params, r.prompt, list(r.generated),
                             48)
    # sampled parity too (keyed sampling inlined in the fused step)
    reqs2 = [Request(rid=i, prompt=list(r.prompt), max_new=r.max_new,
                     arrival=r.arrival) for i, r in enumerate(reqs)]
    base_t, got_t, _, _ = _run_pair(model, params, reqs2, max_len=48,
                                    mpt=8, paged=paged, temperature=0.7)
    assert got_t == base_t


@pytest.mark.parametrize("paged", [False, True])
def test_overlap_parity_mla(paged):
    """The MLA side of the overlap acceptance gate: fused rows scatter
    into the latent (c_kv, k_pe) caches and the absorbed decode math
    serves overlap-on == overlap-off token-for-token, contiguous and
    paged, with decode-only gather backends."""
    cfg = override(get_smoke_config("deepseek-v2-236b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size,
                                                6 + 5 * i)],
                    max_new=4, arrival=float(i))
            for i in range(3)]
    base, got, _, on = _run_pair(model, params, reqs, max_len=24,
                                 mpt=6, paged=paged)
    assert got == base
    # fused steps log under the decode cadence and pick their backend by
    # TRUE padded width (phase "mixed"): at these widths (<= 8 rows, under
    # the gather break-even) that is gather for every step, and no
    # separate prefill micro-batch exists
    assert set(on.backend_counts["decode"]) == {"gather"}
    assert not on.backend_counts["prefill"]
    assert on.dropped_pairs == 0


@pytest.mark.parametrize("paged", [False, True])
def test_fused_backend_width_policy(paged):
    """A fused step picks its routed-expert backend by TRUE padded width
    (phase "mixed" in select_backend): chunk-heavy steps cross the gather
    break-even and run grouped — forcing every fused step onto gather's
    per-row weight materialization made overlapped TPOT ~2.5x worse than
    sequential on chunked cmoe workloads — while decode-only steps stay
    on the gather path. Token identity with the sequential engine must
    survive the within-run backend switch."""
    from repro.core.experts import microbatch_backend

    cfg = override(get_smoke_config("qwen1.5-0.5b"), dtype="float32",
                   cmoe=CMoEConfig(num_experts=8, num_shared=2, top_k=2,
                                   k_activation=4))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    reqs = [Request(rid=0, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size, 48)],
                    max_new=6, arrival=0.0),
            Request(rid=1, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size, 6)],
                    max_new=12, arrival=0.0)]

    def mk():
        return [Request(rid=r.rid, prompt=list(r.prompt), max_new=r.max_new,
                        arrival=r.arrival) for r in reqs]

    kw = dict(max_slots=2, max_len=60, prefill_bucket=8,
              max_prefill_tokens=16)
    if paged:
        kw.update(paged=True, block_size=8)
    off = ServingEngine(model, params, overlap=False, **kw).run(mk())
    eng = ServingEngine(model, params, overlap=True, **kw)
    on = eng.run(mk())
    assert ({r.rid: tuple(r.generated) for r in on.requests} ==
            {r.rid: tuple(r.generated) for r in off.requests})
    assert on.dropped_pairs == 0
    ran = set()
    for _, phase, padded, _, backend, _, _ in eng.backend_log:
        assert phase == "decode"
        assert backend == microbatch_backend(cfg, padded, "mixed"), \
            (padded, backend)
        ran.add(backend)
    # the run really exercised both regimes: 16-token chunk steps above
    # the E/k=4 (floor 8) break-even ran grouped, decode-only steps gather
    assert ran == {"gather", "grouped_xla"}, ran


def test_overlap_telemetry(qwen_smoke):
    """The overlapped report's columns: completion gaps, admission
    stamps between due time and first token, wall-clock TTFT stamped at
    emission, and fused backend_log rows charging the step's
    granule-rounded row count — not max_slots — so compute accounting
    tracks what was dispatched."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(6)
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size, 6)],
                    max_new=10, arrival=0.0) for i in range(3)]
    engine = ServingEngine(model, params, max_slots=3, max_len=24,
                           prefill_bucket=8, max_prefill_tokens=8,
                           overlap=True)
    rep = engine.run(reqs)
    assert all(r.done for r in rep.requests)
    # every request was admitted by a dispatch after it fell due and
    # before its first token was emitted
    assert all(0 <= r.arrival_t <= r.admit_t <= r.first_token_t
               for r in rep.requests)
    assert all(r.admit_step >= 0 for r in rep.requests)
    assert len(rep.decode_gaps_s) > 0
    assert len(rep.ttft_s) == 3 and all(t > 0 for t in rep.ttft_s)
    assert rep.ttft_p95_s >= rep.ttft_p50_s > 0
    assert "TTFT p50/p95" in rep.summary()
    g = engine._row_granule
    for _, phase, padded, live, _, _, _ in engine.backend_log:
        assert phase == "decode"           # one fused dispatch per step
        # the satellite fix: a fused step charges its actual granule-
        # rounded row count, never a flat max_slots per decode dispatch
        assert padded == -(-live // g) * g, (padded, live)
    assert rep.compute_utilization > 0.5


def test_overlap_eos_rollback(qwen_smoke):
    """EOS is discovered one step late under overlap: the lane's
    speculative in-flight row must be rolled back so the emitted stream
    stops AT the EOS token, the slot is freed for the next admission, and
    a dispatch-time truncation flag set on the same token is cleared —
    all matching the sequential engine exactly."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(13)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, 5 + 3 * i)]
               for i in range(4)]

    def mk():
        return [Request(rid=i, prompt=list(prompts[i]), max_new=8,
                        arrival=0.0) for i in range(4)]

    probe = ServingEngine(model, params, max_slots=2, max_len=32,
                          prefill_bucket=8).run(mk())
    gen = {r.rid: list(r.generated) for r in probe.requests}
    eos = int(gen[0][2])                   # rid 0 finishes mid-stream
    for paged in (False, True):
        reqs = mk()
        for r in reqs:
            r.eos_id = eos
        base, got, off, on = _run_pair(model, params, reqs, max_len=32,
                                       paged=paged)
        assert got == base
        assert ({r.rid: r.truncated for r in on.requests} ==
                {r.rid: r.truncated for r in off.requests})
        for r in on.requests:
            assert eos not in r.generated[:-1]   # nothing emitted past EOS


def test_poisson_arrivals_edges():
    from repro.serving import make_requests, poisson_arrivals
    assert poisson_arrivals(0, 1.0).shape == (0,)
    assert poisson_arrivals(-3, 1.0).shape == (0,)
    # rate <= 0 or inf means "all due at t=0"
    np.testing.assert_array_equal(poisson_arrivals(4, 0.0), np.zeros(4))
    np.testing.assert_array_equal(poisson_arrivals(4, -1.0), np.zeros(4))
    np.testing.assert_array_equal(poisson_arrivals(4, float("inf")),
                                  np.zeros(4))
    arr = poisson_arrivals(64, 0.5, seed=3)
    assert arr.shape == (64,) and np.all(np.diff(arr) >= 0)  # sorted
    assert np.all(arr > 0)
    # eos remap: no prompt token may equal eos_id (it would truncate the
    # prompt), and the remap target stays in-vocab
    reqs = make_requests(16, 32, eos_id=7, seed=5)
    for r in reqs:
        assert r.eos_id == 7
        assert all(0 <= t < 32 and t != 7 for t in r.prompt)
