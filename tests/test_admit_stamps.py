"""Request.admit_t: the host clock of the dispatch that first carried one
of a request's prompt chunks, between its due time and its first token,
kept across a preemption and cleared by reset()."""
import numpy as np
import pytest

from repro.serving import Request, ServingEngine


def _reqs(cfg, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab_size, 5 + 4 * i)],
                    max_new=4, arrival=float(i)) for i in range(4)]


@pytest.mark.parametrize("overlap", [False, True])
def test_admit_stamp_lies_between_arrival_and_first_token(qwen_smoke,
                                                          overlap):
    cfg, model, params = qwen_smoke
    reqs = _reqs(cfg)
    engine = ServingEngine(model, params, max_slots=2, max_len=24,
                           prefill_bucket=8, max_prefill_tokens=8,
                           paged=True, block_size=4, overlap=overlap)
    rep = engine.run(reqs)
    assert all(r.done for r in rep.requests)
    for r in rep.requests:
        assert 0 <= r.arrival_t <= r.admit_t <= r.first_token_t, r
    # two slots for four requests: the later two waited for a slot
    order = sorted(rep.requests, key=lambda r: r.admit_t)
    assert [r.rid for r in order] == [0, 1, 2, 3]
    assert order[2].admit_t >= min(r.first_token_t for r in order[:2])


@pytest.mark.parametrize("overlap", [False, True])
def test_preempted_request_keeps_its_first_admit_stamp(qwen_smoke, overlap):
    """The low-priority request is admitted first, preempted by the
    high-priority one, and re-admitted after it: its stamp stays the
    first admission's, as first_token_t does."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(13)
    lo = Request(rid=0, prompt=[int(t) for t in rng.integers(0, 64, 8)],
                 max_new=12, priority=0)
    hi = Request(rid=1, prompt=[int(t) for t in rng.integers(0, 64, 8)],
                 max_new=8, priority=1, arrival=4.0)
    engine = ServingEngine(model, params, max_slots=2, max_len=24,
                           prefill_bucket=8, paged=True, block_size=4,
                           num_blocks=6, overlap=overlap)
    rep = engine.run([lo, hi])
    assert rep.preemptions >= 1
    v, h = sorted(rep.requests, key=lambda r: r.rid)
    assert v.preemptions >= 1 and v.done
    assert v.admit_step == 0
    # re-admission came after the preemptor's admission: an overwritten
    # stamp would read later than the preemptor's
    assert v.arrival_t <= v.admit_t < h.admit_t
    assert v.admit_t <= v.first_token_t < h.admit_t


def test_reset_clears_the_admit_stamp(qwen_smoke):
    cfg, model, params = qwen_smoke
    reqs = _reqs(cfg)[:2]
    engine = ServingEngine(model, params, max_slots=2, max_len=24,
                           prefill_bucket=8, overlap=True)
    engine.run(reqs)
    first = [r.admit_t for r in reqs]
    assert all(t >= 0 for t in first)
    reqs[0].reset()
    assert reqs[0].admit_t == -1.0 and reqs[0].admit_step == -1
    # a rerun of the same list stamps afresh
    engine.run(reqs)
    assert all(r.admit_t > t for r, t in zip(reqs, first))
