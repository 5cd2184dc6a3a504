"""The one traffic generator: reads a mix file's parameters and makes the
requests of a run from ``--seed``.

Every seed gets the SAME sizes and, for an open loop, the same gaps
between arrivals, in the same order: ``n_sizes`` quantile points of each
stated distribution, shuffled once by ``ORDER_SEED``.  The seed chooses
the token ids (and the weights), so runs with different seeds do the same
work: a tail of queueing delay is set by the order of long prompts and
short gaps, and an order per seed made the cells' tails differ by seed far
more than by run.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, Iterator, List, Optional

import numpy as np

ORDER_SEED = 20251017


def quantile_sizes(spec: Dict, n: int) -> np.ndarray:
    """``n`` sizes at the mid-quantiles ``(i + 0.5) / n`` of ``spec``
    (``lognormal`` with ``median``/``sigma`` or ``uniform``), rounded and
    clipped to ``[min, max]``."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` gaps at the mid-quantiles of an exponential of mean
    ``1/rate``: a Poisson process's gaps, as a fixed multiset."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...); ``seed`` may be any
    non-negative integer (numpy's SeedSequence takes big ints)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


class Requests:
    """The request stream of one run: ``next()`` gives dicts with
    ``prompt`` (token ids from the seed), ``max_new`` and, for an open
    loop, ``due`` (seconds from the window's opening).  Sizes and gaps are
    reshuffled per cycle of ``n_sizes`` requests, the same for every
    seed."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, int(seed), vocab
        n = mix["n_sizes"]
        self._plens = quantile_sizes(mix["prompt"], n)
        self._olens = quantile_sizes(mix["output"], n)
        self._gaps = (exp_gaps(mix["rate_per_s"], n)
                      if mix["loop"] == "open" else None)
        self._i = 0
        self._t = 0.0
        self._perm: Optional[np.ndarray] = None
        self._operm: Optional[np.ndarray] = None
        self._gperm: Optional[np.ndarray] = None

    def _cycle(self, c: int) -> None:
        rng = rng_for(ORDER_SEED, 1, c)
        n = self.mix["n_sizes"]
        self._perm = rng.permutation(n)
        self._operm = rng.permutation(n)
        self._gperm = rng.permutation(n)

    def next(self) -> Dict:
        n = self.mix["n_sizes"]
        c, j = divmod(self._i, n)
        if j == 0:
            self._cycle(c)
        plen = int(self._plens[self._perm[j]])
        olen = int(self._olens[self._operm[j]])
        rng = rng_for(self.seed, 2, self._i)
        prompt = rng.integers(1, self.vocab, size=plen, dtype=np.int64)
        req = {"i": self._i, "prompt": prompt.tolist(), "max_new": olen}
        if self._gaps is not None:
            self._t += float(self._gaps[self._gperm[j]])
            req["due"] = self._t
        self._i += 1
        return req

    def __iter__(self) -> Iterator[Dict]:
        while True:
            yield self.next()


def residual_budget(olen: int, i: int) -> int:
    """A staggered first budget for closed-loop client ``i``: a uniform
    share of ``olen`` (the part of a request already in flight when a
    steady batch is looked at), at least 2 tokens; the same for every
    seed."""
    u = rng_for(ORDER_SEED, 3, i).random()
    return max(2, int(math.ceil(u * olen)))


def describe(mix: Dict) -> Dict[str, List[int]]:
    """Quartiles of the mix's size multisets, for logs and PERF.md."""
    out = {}
    for key in ("prompt", "output"):
        s = quantile_sizes(mix[key], mix["n_sizes"])
        out[key] = [int(np.percentile(s, p)) for p in (0, 25, 50, 75, 100)]
    return out
