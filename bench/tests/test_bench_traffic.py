"""The traffic generator and the end-to-end arithmetic (no device work)."""
import math
from types import SimpleNamespace

import numpy as np
import pytest

import benchtest_util  # noqa: F401  (puts bench/ and src/ on the path)
from harness import spec, stats, traffic

CHAT = spec.load_cell("qwen05b.chat").traffic


@pytest.mark.parametrize("mix", [CHAT], ids=["chat"])
def test_generator_is_deterministic_by_seed_and_clipped(mix):
    a = traffic.generate(mix, 10.0, 2 ** 31 + 77, 1000)
    b = traffic.generate(mix, 10.0, 2 ** 31 + 77, 1000)
    c = traffic.generate(mix, 10.0, 2 ** 31 + 78, 1000)
    assert [(r.prompt, r.max_new, r.due_s, r.measured) for r in a] == \
        [(r.prompt, r.max_new, r.due_s, r.measured) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in a)
    assert all(o["min"] <= r.max_new <= o["max"] for r in a)
    assert all(0 <= t < 1000 for r in a for t in r.prompt)
    # stratified, on a fixed schedule: another seed offers the same
    # lengths and arrivals, with other token ids
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in c]
    assert [(r.max_new, r.due_s) for r in a] == \
        [(r.max_new, r.due_s) for r in c]
    other = traffic.generate(dict(mix, order_seed=1), 10.0, 2 ** 31 + 77,
                             1000)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in other)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in other]
    assert len(traffic.generate(mix, 10.0, 5, 1000)) == sum(
        traffic.num_requests(mix, s)
        for s in (10.0, mix["warmup_s"], mix["drain_limit_s"]))


def test_open_loop_arrivals_match_the_rate_and_lengths_the_median():
    reqs = traffic.generate(CHAT, 100.0, 9, 50)
    due = np.array([r.due_s for r in reqs])
    assert np.all(np.diff(due) > 0)
    timed = [r for r in reqs if r.measured]
    n = math.ceil(CHAT["rate_per_s"] * 100.0)
    assert abs(len(timed) - n) <= 2              # the rate, stratified
    # the measured block: stratified, so its median is the mix's
    block = reqs[int((due < 0).sum()):][:n]
    med = np.median([len(r.prompt) for r in block])
    assert abs(med - CHAT["prompt_tokens"]["median"]) <= 2
    assert traffic.max_len(CHAT) == 1536


def test_warm_up_and_tail_surround_the_measured_window():
    reqs = traffic.generate(CHAT, 51.0, 4, 50)
    due = np.array([r.due_s for r in reqs])
    timed = np.array([r.measured for r in reqs])
    assert np.array_equal(timed, (due >= 0) & (due < 51.0))
    warm = due[due < 0]
    # about warmup_s of arrivals at the mix's rate before the window
    assert len(warm) == math.ceil(CHAT["rate_per_s"] * CHAT["warmup_s"])
    assert abs(warm[0] + CHAT["warmup_s"]) < 0.1 * CHAT["warmup_s"]
    assert due[timed][0] == 0.0
    # arrivals go on for about drain_limit_s after the window
    assert due[-1] > 51.0 + 0.8 * CHAT["drain_limit_s"]
    # the window's requests keep their lengths whatever the warm-up
    other = traffic.generate(dict(CHAT, warmup_s=10.0), 51.0, 4, 50)
    assert [r.max_new for r in reqs if r.measured][:20] == \
        [r.max_new for r in other if r.measured][:20]


def _req(done=True, first=-1.0, last=-1.0, arrival=0.0, n=3):
    return SimpleNamespace(done=done, first_token_t=first, last_token_t=last,
                           arrival_t=arrival, generated=[0] * n)


def test_percentiles_count_failures_as_misses():
    assert stats.percentile([3, 1, 2, 4], 50) == 2
    assert stats.percentile([3, 1, 2, 4], 95) == 4
    assert stats.percentile(range(1, 101), 95) == 95
    assert stats.percentile([], 95) == stats.INF
    reqs = [_req(first=1.0 + i, last=3.0 + i, arrival=0.5)
            for i in range(19)]
    reqs.append(_req(done=False, first=1.0, last=2.0))
    ttft = stats.ttft_s(reqs)
    assert ttft[-1] == stats.INF and stats.percentile(ttft, 95) == 18.5
    assert stats.percentile(ttft, 100) == stats.INF
    tpot = stats.tpot_s(reqs)
    assert tpot[0] == pytest.approx(1.0) and tpot[-1] == stats.INF
    # a one-token request has no gap; an unfinished one is a miss
    assert stats.tpot_s([_req(n=1, first=1.0, last=1.0)]) == []


def test_rate_counts_only_the_window():
    ev = [(0.5, 10), (1.0, 3), (2.0, 4), (3.5, 100)]
    assert stats.rate(ev, 1.0, 3.0) == pytest.approx(3.5)
