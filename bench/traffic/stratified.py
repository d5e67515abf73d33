"""Stratified lognormal traffic: every seed gets the same work in
another order.

    {"generator": "stratified", "loop": "open", "arrivals": {"rate": 2.4, "shape": 1.0},
     "prompt": {"median": 1024, "sigma": 0.7, "min": 256, "max": 3584},
     "output": {"median": 48, "sigma": 0.6, "min": 16, "max": 128}, "block": 32}
    {"generator": "stratified", "loop": "closed", "clients": 16, ...}

Lengths are lognormal (median, sigma) clipped to [min, max]; open-loop
inter-arrival gaps are Gamma of the given shape with mean 1/rate (shape 1
gives exponential gaps, as a Poisson process has; shape 0.25 has a
coefficient of variation of 2).

The values are the distribution's quantiles at (k + 0.5) / block,
k < block, so each block of ``block`` consecutive requests holds the
same multiset of prompt lengths, output lengths and gaps, in an order
drawn once from ``ORDER_SEED``: every run offers the same schedule, and
its seed draws the prompt tokens (the driver draws the weights from it
too).  On the chip the order alone moved the long-prompt cell's p90 TTFT
by a factor of two from seed to seed, while two runs of one order agreed
within a tenth (PERF.md).  Gaps are rescaled per block so that each
block offers exactly ``rate``: the count of arrivals over a block varies
less than a Poisson process's, while the gaps inside a block keep their
distribution.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

from bench.gen import RequestSpec, rng_for

__all__ = ["lengths", "gaps", "make_requests", "requests"]

HORIZON_S = 65.0     # past the close: the driver's drain of up to a minute
ORDER_SEED = 0       # the order of lengths and gaps, the same for every run


def _block_quantiles(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def lengths(spec: dict, block: int) -> np.ndarray:
    """One block's clipped lognormal lengths (ascending)."""
    z = np.array([NormalDist().inv_cdf(q) for q in _block_quantiles(block)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def gaps(arrivals: dict, block: int) -> np.ndarray:
    """One block's Gamma inter-arrival gaps in seconds, mean exactly 1/rate."""
    from scipy.special import gammaincinv

    shape = float(arrivals.get("shape", 1.0))
    g = np.array([gammaincinv(shape, q) for q in _block_quantiles(block)])
    return g / g.mean() / float(arrivals["rate"])


def make_requests(mix: dict, seed: int, vocab: int, count: int) -> list[RequestSpec]:
    """The first `count` requests of the mix under `seed`, in send order."""
    block = int(mix["block"])
    plen, olen = lengths(mix["prompt"], block), lengths(mix["output"], block)
    order = rng_for(ORDER_SEED, 0)
    tokens = rng_for(seed, 1)
    nblocks = math.ceil(count / block)
    p = np.concatenate([order.permutation(plen) for _ in range(nblocks)])
    o = np.concatenate([order.permutation(olen) for _ in range(nblocks)])
    offsets = None
    if mix["loop"] == "open":
        g = gaps(mix["arrivals"], block)
        gs = np.concatenate([order.permutation(g) for _ in range(nblocks)])
        # the first request is due the moment the window opens
        offsets = np.concatenate([[0.0], np.cumsum(gs)[:-1]])
    return [RequestSpec(index=i,
                        prompt=tokens.integers(0, vocab, size=int(p[i])).astype(np.int32),
                        max_new=int(o[i]),
                        offset_s=None if offsets is None else float(offsets[i]))
            for i in range(count)]


def requests(mix: dict, seed: int, vocab: int, seconds: float) -> list[RequestSpec]:
    """Enough requests for a window of `seconds` and the drain after it."""
    if mix["loop"] == "open":
        count = int(math.ceil(mix["arrivals"]["rate"] * (seconds + HORIZON_S)))
    else:
        count = int(mix["clients"] * max(64, seconds * 4))
    return make_requests(mix, seed, vocab, count)
