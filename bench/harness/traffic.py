"""The one traffic generator: every mix is a data file it reads.

A mix file (``bench/traffic/<name>.json``) gives:

- ``rate_per_s``: independent users arriving on a Poisson schedule;
- ``prompt_tokens`` / ``output_tokens``: lognormal lengths, each with a
  ``median``, a ``sigma`` (of the log) and ``min``/``max`` clips;
- ``order_seed``: the fixed order of lengths and gaps (below);
- ``warmup_s``: arrivals before the measured window opens, so that it
  opens on a loaded engine (about twice a request's mean lifetime);
- ``drain_limit_s``: how long after the window closes the run waits for
  the requests due in it; arrivals go on meanwhile, so the last measured
  requests finish under the same load.

The stream has three blocks, one after the other: warm-up (before 0),
measured (from 0) and tail. The requests due in the window [0, seconds)
are measured; the others are load. In each block lengths and
inter-arrival gaps are STRATIFIED: a block of n requests draws its
lengths from the distribution's n quantiles at (i + 0.5) / n, in an
order fixed by ``order_seed``. The run's seed
draws the token ids (and, elsewhere, the weights). Every seed therefore
offers the same work on the same schedule, so the spread between seeds is
the system's and not the sampler's: with the order drawn from the run's
seed, the tails of ``qwen05b.chat`` moved by 2x between seeds.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it (the program never sees
    this; the runner turns it into a ``repro.serving.Request``)."""
    rid: int
    prompt: list
    max_new: int
    due_s: float              # offset from the measured window's start
    measured: bool = False    # due in the window, and timed


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n stratified lognormal lengths, clipped, in ``rng``'s order."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf(u) for u in _quantiles(n)])
    vals = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    vals = np.clip(vals, spec["min"], spec["max"]).astype(np.int64)
    return vals[rng.permutation(n)]


def max_len(traffic: dict) -> int:
    """The longest sequence a mix can make: its prompt and output clips."""
    return int(traffic["prompt_tokens"]["max"] +
               traffic["output_tokens"]["max"])


def num_requests(traffic: dict, seconds: float) -> int:
    """Requests a block of ``seconds`` holds at the mix's rate."""
    return max(1, int(math.ceil(traffic["rate_per_s"] * seconds)))


def _block(traffic: dict, seconds: float, order_key):
    """(prompt lengths, output lengths, gaps) of one stratified block."""
    order = np.random.default_rng(order_key)
    n = num_requests(traffic, seconds)
    plen = lengths(traffic["prompt_tokens"], n, order)
    olen = lengths(traffic["output_tokens"], n, order)
    # stratified exponential gaps: -ln(1 - u) / rate at the n quantiles
    gaps = -np.log1p(-_quantiles(n)) / float(traffic["rate_per_s"])
    return plen, olen, gaps[order.permutation(n)]


def generate(traffic: dict, seconds: float, seed: int,
             vocab: int) -> list[Planned]:
    """The requests of one run, in due order."""
    o = int(traffic["order_seed"])
    warm = _block(traffic, float(traffic["warmup_s"]), [o, 1])
    meas = _block(traffic, seconds, o)
    tail = _block(traffic, float(traffic["drain_limit_s"]), [o, 2])
    blocks = (warm, meas, tail)
    plen, olen, gaps = (np.concatenate(x) for x in zip(*blocks))
    # the first measured request falls due as the window opens
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due -= due[len(warm[0])]
    measured = (due >= 0) & (due < seconds)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, size=int(plen.sum()), dtype=np.int64)
    starts = np.concatenate([[0], np.cumsum(plen)[:-1]])
    return [Planned(rid=i, prompt=tokens[s:s + p].tolist(), max_new=int(m),
                    due_s=float(d), measured=bool(w))
            for i, (s, p, m, d, w) in enumerate(
                zip(starts, plen, olen, due, measured))]
