"""One general generator for every traffic mix.

A mix is a data file (``traffic/<name>.json``).  Sizes come in blocks of
requests, and every seed gets the same multiset of sizes in each block
and the same multiset of inter-arrival gaps: they are the quantiles of
the mix's distributions, and the seed only chooses their order and draws
the token ids.  So two seeds do the same work in every whole block, in
another order.  Within a block the sizes follow a spread order (``spread``),
so that a run which uses only part of a block, as a closed loop does with
the requests that replace finished ones, still gets sizes from the whole
distribution.  A closed loop's first wave is the same on every seed but
for its token ids (``first_wave``).
"""

from __future__ import annotations

import math

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent stream ``stream`` of ``seed`` (any non-negative integer,
    wider than 32 bits included)."""
    return np.random.default_rng([int(seed), int(stream)])


def jax_seed(seed: int, stream: int = 0) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` derived from ``seed``."""
    return int(rng(seed, 1000 + stream).integers(0, 2**31 - 1))


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles (midpoints of n equal-probability bins)
    of ``dist``: {"dist": "uniform" | "loguniform" | "exponential" |
    "constant", ...}, rounded to whole numbers when ``dist["int"]``."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        x = np.exp(lo + u * (hi - lo))
    elif kind == "exponential":
        x = -np.log1p(-u) * dist["mean"]
    elif kind == "constant":
        x = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if dist.get("int", True):
        x = np.clip(np.rint(x), dist.get("min", 1), dist.get("max", np.inf))
        return x.astype(np.int64)
    return x


def draw(dist: dict, n: int, seed: int, stream: int) -> np.ndarray:
    """The quantiles of ``dist``, in an order that ``seed`` chooses."""
    return rng(seed, stream).permutation(quantiles(dist, n))


def spread(dist: dict, n: int, seed: int, stream: int) -> np.ndarray:
    """The quantiles of ``dist`` in the bit-reversed (van der Corput) order
    of their ranks, rotated by an offset that ``seed`` chooses: any run of
    consecutive entries takes sizes from the whole distribution, not from a
    seed-dependent part of it."""
    bits = max(n - 1, 1).bit_length()
    rev = [int(format(i, f"0{bits}b")[::-1], 2) for i in range(2 ** bits)]
    order = np.array([r for r in rev if r < n])
    shift = int(rng(seed, stream).integers(n))
    return quantiles(dist, n)[np.roll(order, -shift)]


def tokens(n: int, vocab: int, seed: int, stream: int) -> list[int]:
    return [int(t) for t in rng(seed, stream).integers(0, vocab, n)]


def first_wave(mix: dict, vocab: int, seed: int) -> list:
    """A closed loop's first wave: ``clients`` requests (prompt ids, answer
    length) with the prompt quantiles, longest first, each with the same
    rank of the quantiles of what is left of an answer (uniform up to the
    longest answer, so that completions spread through the window).  The
    sizes and their order are the same for every seed, which draws only the
    token ids: longest first puts the batch in the mix's largest decode
    bucket early in the warm phase on every seed, where a seeded order let
    some seeds' windows run mostly in a smaller, faster one."""
    n = mix["clients"]
    lens = quantiles(mix["prompt"], n)[::-1]
    outs = quantiles({"dist": "uniform", "min": 1,
                      "max": mix["output"]["max"]}, n)[::-1]
    return [(tokens(int(p), vocab, seed, 10_000_000 + i), int(o))
            for i, (p, o) in enumerate(zip(lens, outs))]


class Requests:
    """An endless, seeded sequence of requests: request ``i`` has a prompt
    of ``prompt_len(i)`` random ids and asks for ``output_len(i)`` tokens.
    Lengths cycle through blocks of ``block`` quantiles, each block in a
    spread order of its own."""

    def __init__(self, mix: dict, vocab: int, seed: int, block: int = 256):
        self.mix, self.vocab, self.seed, self.block = mix, vocab, seed, block
        self._blocks: dict[int, tuple[np.ndarray, np.ndarray]] = {}

    def _lens(self, i: int) -> tuple[int, int]:
        b, j = divmod(i, self.block)
        if b not in self._blocks:
            self._blocks[b] = (
                spread(self.mix["prompt"], self.block, self.seed, 2 * b + 1),
                spread(self.mix["output"], self.block, self.seed, 2 * b + 2))
        p, o = self._blocks[b]
        return int(p[j]), int(o[j])

    def get(self, i: int) -> tuple[list[int], int]:
        p, o = self._lens(i)
        return tokens(p, self.vocab, self.seed, 10_000_000 + i), o


def arrival_times(mix: dict, n: int, seed: int) -> np.ndarray:
    """Open loop: the due times (s after the window opens) of ``n``
    requests, Poisson at ``mix["rate_per_s"]``: the gaps are the quantiles
    of the exponential distribution in a seeded order."""
    gaps = draw({"dist": "exponential", "mean": 1.0 / mix["rate_per_s"],
                 "int": False}, n, seed, 7)
    return np.cumsum(gaps) - gaps[0]
