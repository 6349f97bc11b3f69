"""Seeded draws with a fixed multiset of values.

A distribution is drawn as stratified blocks: ``n`` values taken at the
quantiles (i + 0.5) / n and put in an order that a generator chooses.
Every block holds the same values, so two draws of one distribution do
the same amount of work and differ only in its order.  Which generator
orders the blocks is the caller's choice (``drivers/reason_fork.py``
uses one fixed schedule for every seed).
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Callable, List

import numpy as np

# values per block of an endless stream: a multiple of 10 and of 2, so
# a bernoulli of p = 0.1 or 0.5 holds exactly its share in every block
BLOCK = 100


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per named stream of one seed (seeds of
    any size, beyond 32 bits too)."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1),
                                  *map(ord, stream)])


def quantiles(spec: dict, n: int) -> List[float]:
    """n values of a distribution given by ``spec``, at (i + 0.5) / n:
    ``{"dist": "uniform", "lo", "hi"}``,
    ``{"dist": "lognormal", "median", "sigma", "lo", "hi"}`` (clipped),
    ``{"dist": "bernoulli", "p"}`` (0/1, exactly round(n p) ones)."""
    qs = [(i + 0.5) / n for i in range(n)]
    kind = spec["dist"]
    if kind == "uniform":
        return [spec["lo"] + q * (spec["hi"] - spec["lo"]) for q in qs]
    if kind == "lognormal":
        nd = NormalDist()
        vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q))
                for q in qs]
        return [min(max(v, spec["lo"]), spec["hi"]) for v in vals]
    if kind == "bernoulli":
        ones = round(n * spec["p"])
        return [1.0] * ones + [0.0] * (n - ones)
    raise ValueError(f"unknown distribution {kind!r}")


def draw(spec: dict, n: int, gen: np.random.Generator,
         integer: bool = True) -> list:
    """The quantile multiset of ``spec``, in the order ``gen`` picks."""
    vals = quantiles(spec, n)
    if integer:
        vals = [int(round(v)) for v in vals]
    order = gen.permutation(n)
    return [vals[i] for i in order]


class Stream:
    """An endless stream of values: block k is ``block(k)``, taken in
    turn.  So a stream has no length to size in advance."""

    def __init__(self, block: Callable[[int], list]):
        self.block = block
        self.k = 0
        self.buf: List = []

    def take(self):
        if not self.buf:
            self.buf = list(self.block(self.k))[::-1]
            self.k += 1
        return self.buf.pop()


def stream(spec: dict, seed: int, name: str) -> Stream:
    """Endless stratified draws of ``spec``: every block of ``BLOCK``
    values is the quantile multiset, block k in the order of the
    generator ``(seed, name-k)``."""
    return Stream(lambda k: draw(spec, BLOCK, rng(seed, f"{name}-{k}")))
