"""One generator for every traffic mix: a mix is a JSON file of parameters
(``chipbench/traffic/<mix>.json``) and this module turns it into requests.

Lengths are stratified: a cycle of ``cycle`` requests takes the
distribution's quantiles at (i + 0.5) / cycle, and the seed only shuffles
each cycle (prompt and output lengths apart) and draws the prompt tokens.
So every seed serves the same multiset of sizes in every whole cycle, in
another order, and runs with different seeds differ in order and token ids,
not in the amount of work.  Open-loop inter-arrival gaps are stratified the
same way over the exponential distribution (mean 1 / rate).

Length distributions:

    {"dist": "uniform", "lo": L, "hi": H}               L..H inclusive
    {"dist": "lognormal", "median": M, "sigma": S, "lo": L, "hi": H}
                                                        clipped to L..H
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The distribution's quantiles at (i + 0.5) / n, as whole tokens."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "uniform":
        lo, hi = dist["lo"], dist["hi"]
        x = lo + u * (hi - lo + 1)
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
        return np.clip(np.rint(x), dist["lo"], dist["hi"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """Stratified exponential inter-arrival gaps, mean 1 / rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


class Requests:
    """An endless, seeded stream of (prompt tokens, output length, gap)."""

    def __init__(self, mix: dict, seed: int, vocab: int, rate: float = 0.0):
        self.rng = np.random.default_rng(seed)
        self.vocab = vocab
        self.cycle = int(mix["cycle"])
        self._p = quantiles(mix["prompt"], self.cycle)
        self._o = quantiles(mix["output"], self.cycle)
        self._g = exp_gaps(rate, self.cycle) if rate > 0 else None
        self._buf: list = []

    def _refill(self) -> None:
        p = self.rng.permutation(self._p)
        o = self.rng.permutation(self._o)
        g = (self.rng.permutation(self._g) if self._g is not None
             else np.zeros(self.cycle))
        self._buf = list(zip(p.tolist(), o.tolist(), g.tolist()))[::-1]

    def next(self) -> tuple[np.ndarray, int, float]:
        if not self._buf:
            self._refill()
        plen, n_out, gap = self._buf.pop()
        prompt = self.rng.integers(1, self.vocab, size=plen, dtype=np.int32)
        return prompt, int(n_out), float(gap)
