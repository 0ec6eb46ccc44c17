"""The one traffic generator. A mix is a data file, ``traffic/<mix>.json``;
this module reads its parameters and makes the inputs from the run's seed.

Sizes are stratified: a block of ``n`` draws takes the distribution's
quantiles at (i + 1/2) / n and the seed only shuffles them. So every seed
offers the same multiset of lengths and gaps, in another order, and runs
with different seeds differ in what the sizes meet, not in how much work
there is.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List

import numpy as np


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of ``dist``: lognormal (median, sigma,
    clipped to [min, max]) or exponential (rate)."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        q = dist["median"] * np.exp(dist["sigma"] * z)
        return np.clip(np.rint(q), dist["min"], dist["max"]).astype(np.int64)
    if kind == "exponential":
        return -np.log1p(-u) / dist["rate"]
    raise ValueError(f"unknown distribution {kind!r}")


def tokens(rng: np.random.Generator, spec: dict, vocab: int,
           shape) -> np.ndarray:
    """Token ids: ``uniform`` over the vocabulary, or ``zipf`` (rank r
    drawn with weight r^-exponent, ranks mapped to ids by a fixed
    permutation of the seed)."""
    if spec["dist"] == "uniform":
        return rng.integers(0, vocab, size=shape, dtype=np.int32)
    if spec["dist"] == "zipf":
        w = np.arange(1, vocab + 1, dtype=np.float64) ** -spec["exponent"]
        cdf = np.cumsum(w / w.sum())
        ranks = np.searchsorted(cdf, rng.random(shape), side="right")
        ids = rng.permutation(vocab).astype(np.int32)
        return ids[np.minimum(ranks, vocab - 1)]
    raise ValueError(f"unknown token distribution {spec['dist']!r}")


def lm_batches(rng: np.random.Generator, spec: dict, vocab: int,
               rows: int, seq: int) -> Iterator[dict]:
    """Next-token batches of ``rows`` sequences of ``seq`` tokens, every
    row drawn afresh (no two rows alike but by chance)."""
    while True:
        t = tokens(rng, spec, vocab, (rows, seq + 1))
        yield {"tokens": t[:, :-1], "labels": t[:, 1:]}


@dataclass(frozen=True)
class Request:
    rid: int
    arrival: float        # seconds after the window opens
    prompt: np.ndarray    # (P,) int32
    max_new: int


def requests(rng: np.random.Generator, mix: dict, vocab: int,
             seconds: float, horizon: float) -> List[Request]:
    """Open-loop requests: blocks of ``round(rate * seconds)`` arrivals,
    each block spread over ``seconds`` by shuffled stratified exponential
    gaps, repeated until ``horizon``. The first block is exactly the
    requests due in the window."""
    rate = mix["rate_rps"]
    n = max(1, int(round(rate * seconds)))
    gaps = quantiles({"dist": "exponential", "rate": rate}, n)
    plen = quantiles(mix["prompt"], n)
    glen = quantiles(mix["output"], n)
    out: List[Request] = []
    for b in range(int(math.ceil(horizon / seconds))):
        g = rng.permutation(gaps)
        t = b * seconds + (np.cumsum(g) - g) * (seconds / g.sum())
        P = rng.permutation(plen)
        G = rng.permutation(glen)
        for i in range(n):
            p = int(P[i])
            out.append(Request(
                rid=b * n + i, arrival=float(t[i]),
                prompt=tokens(rng, mix["tokens"], vocab, (p,)),
                max_new=int(min(G[i], mix["max_total"] - p))))
    return out
