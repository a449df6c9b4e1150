"""The check that decides ``correct`` catches a broken timed path.

Each case drives a whole run through the harness on the CPU, with the
fused serving step or the conversion broken underneath, and sees the
reference comparison fail. A sound run passes the same comparison
(test_bench_check.py).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from benchtest_util import TINY, serve, tiny_cell
from harness import runner
import run as bench_run
from repro.core import convert as convert_mod
from repro.core.partition import PartitionResult, partition_neurons
from repro.serving import engine as engine_mod
from repro.serving.executor import StepExecutor

LIMIT = TINY["check"]["logit_gap_limit"]
_step = StepExecutor._step_fused_paged_impl


def _altered_tokens(mp):
    """Every other sampled token is replaced where it is produced."""
    def make_sampler(temperature, seed):
        def sample(logits, rids, tidx):
            top = jnp.argmax(logits, axis=-1)
            return jnp.where(tidx % 2 == 1, (top + 1) % logits.shape[-1],
                             top)
        return sample
    mp.setattr(engine_mod, "make_sampler", make_sampler)


def _state_unchanged(mp):
    """The step returns the KV pool it was given: nothing is written."""
    def step(self, params, cache, *a, **k):
        nxt, st, _, dropped = _step(self, params, cache, *a, **k)
        return nxt, st, cache, dropped
    mp.setattr(StepExecutor, "_step_fused_paged_impl", step)


def _half_rows_left_out(mp):
    """The second half of a step's rows is not computed: it repeats the
    first half's tokens."""
    def step(self, params, cache, base, use_prev, slot_tokens, row_slots,
             tables, positions, rids, tidx, carry, row_k, backend):
        nxt, _, ncache, dropped = _step(
            self, params, cache, base, use_prev, slot_tokens, row_slots,
            tables, positions, rids, tidx, carry, row_k, backend)
        r = nxt.shape[0]
        nxt = nxt.at[r // 2:].set(nxt[:r - r // 2])
        return (nxt, self._fused_carry(slot_tokens, row_slots, carry, nxt),
                ncache, dropped)
    mp.setattr(StepExecutor, "_step_fused_paged_impl", step)


@pytest.mark.parametrize("fault", [_altered_tokens, _state_unchanged,
                                   _half_rows_left_out],
                         ids=["token_altered", "state_unchanged",
                              "half_rows_left_out"])
def test_broken_step_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    run = serve(tiny_cell("qwen05b.chat", rate_per_s=12.0), seed=21,
                seconds=1.0)
    chk = runner.check(run)
    assert chk["tokens"] > 0
    assert chk["served"] > LIMIT, chk


def _partition(plant):
    """The conversion's partition_neurons replaced by ``plant(part, cm,
    a)`` of the sound partition and the activation profile: the served
    weights and the partition the run reports both follow it."""
    def install(mp):
        def partition(a, mu, cm):
            part = partition_neurons(a, mu, cm)
            shared, routed, rep = plant(part, cm, np.asarray(a))
            return PartitionResult(shared_idx=shared, routed_idx=routed,
                                   rep_idx=rep, mu=part.mu, cluster=None)
        mp.setattr(convert_mod, "partition_neurons", partition)
    return install


def _random_routed(part, cm, a):
    """The sound shared experts; the routed neurons grouped at random,
    each group represented by its neuron nearest the group's centroid."""
    pool = np.random.default_rng(0).permutation(part.routed_idx.ravel())
    routed = np.sort(pool.reshape(part.routed_idx.shape), 1)
    feats = a.T.astype(np.float32)
    rep = [e[np.argmin(((feats[e] - feats[e].mean(0)) ** 2).sum(1))]
           for e in routed]
    return part.shared_idx, routed, np.array(rep)


def _split(order, cm):
    m = len(order) // cm.num_experts
    routed = np.sort(order[cm.num_shared * m:].reshape(cm.num_routed, m), 1)
    return np.sort(order[:cm.num_shared * m]), routed, routed[:, 0]


def _duplicate(part, cm, a):
    shared = part.shared_idx.copy()
    shared[0] = shared[1]            # one neuron served twice, one dropped
    return shared, part.routed_idx, part.rep_idx


PARTITION_FAULTS = {
    # neurons taken in index order, as baselines.uniform_partition does
    "uniform": (_partition(lambda p, cm, a: _split(np.arange(p.mu.size),
                                                    cm)),
                "shared_shortfall"),
    # a random permutation, as baselines.random_partition does
    "random": (_partition(lambda p, cm, a: _split(
        np.random.default_rng(0).permutation(p.mu.size), cm)),
        "shared_shortfall"),
    "neuron_dropped": (_partition(_duplicate), "partition_invalid"),
    # the sound experts, each represented by its first neuron
    "first_neuron_router": (_partition(lambda p, cm, a: (
        p.shared_idx, p.routed_idx, p.routed_idx[:, 0])), "rep_rank"),
    "random_routed": (_partition(_random_routed), "cluster_gain"),
}


@pytest.mark.parametrize("fault", list(PARTITION_FAULTS))
def test_planted_partition_is_not_correct(fault, monkeypatch):
    plant, number = PARTITION_FAULTS[fault]
    plant(monkeypatch)
    run = serve(tiny_cell("qwen05b.chat"), seed=21, seconds=1.0)
    checks = bench_run.checks_of(run, runner.check(run))
    assert not checks[number]["ok"], checks
