"""The one traffic generator: reads a mix file of ``bench/traffic/``.

A mix file gives the loop and its parameters:

  "loop": "closed"   "clients_per_slot": c
                                        c clients for every engine slot of
                                        the cell, each sends its next request
                                        when its last one finishes
  "loop": "open"     "rate_per_s": r    arrivals on a schedule, gaps drawn
                                        from an exponential of mean 1/r
  "prompt", "output": {"median", "sigma", "min", "max"}
                                        lognormal token counts, clipped
  "set_size": N, "strata": S            sizes per cycle (see below)

Every seed serves the same multiset of sizes and gaps, in another order:
cycle k uses the N quantiles ``(i + 1/2) / N`` of each distribution, so
two seeds do the same work and the program compiles the same shapes for
both. The order is stratified: the sorted quantiles fall into S strata of
N/S, and every block of S consecutive requests (or gaps) takes one value
from each stratum, which value and in which order drawn from
``(seed, k)``. So any few blocks of a window carry about the same work
whatever the seed. Prompt token ids are uniform over the vocabulary, from
the seed, with no shared prefixes.
"""
from __future__ import annotations

import math
import statistics
from typing import Iterator, List, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a clipped lognormal, as whole tokens."""
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def quantile_gaps(rate: float, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of an exponential of mean ``1/rate``."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


class Traffic:
    """Requests of one mix for one seed: ``(prompt tokens, output length)``
    in a fixed order, and for an open loop the arrival offsets."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.seed = seed
        self.n = int(mix["set_size"])
        self.strata = int(mix["strata"])
        if self.n % self.strata:
            raise ValueError("set_size must be a multiple of strata")
        self.prompts = quantile_lengths(mix["prompt"], self.n)
        self.outputs = quantile_lengths(mix["output"], self.n)
        self._ids = np.random.default_rng([seed, 1 << 20])

    @property
    def closed(self) -> bool:
        return self.mix["loop"] == "closed"

    def _order(self, values: np.ndarray, k: int, what: int) -> List:
        """Cycle ``k`` of ``values`` in stratified order."""
        rng = np.random.default_rng([self.seed, k, what])
        strata = np.sort(values).reshape(self.strata, -1)
        strata = np.stack([rng.permutation(row) for row in strata])
        blocks = [rng.permutation(strata[:, b]) for b in
                  range(strata.shape[1])]
        return np.concatenate(blocks).tolist()

    def sizes(self) -> Iterator[Tuple[int, int]]:
        """(prompt length, output length) of every request, in order."""
        k = 0
        while True:
            yield from zip(self._order(self.prompts, k, 0),
                           self._order(self.outputs, k, 1))
            k += 1

    def arrivals(self) -> Iterator[float]:
        """Open loop: arrival offsets in seconds from the window's start."""
        gaps = quantile_gaps(float(self.mix["rate_per_s"]), self.n)
        t, k = 0.0, 0
        while True:
            for g in self._order(gaps, k, 2):
                t += g
                yield t
            k += 1

    def clients(self, slots: int) -> int:
        """Closed loop: the number of clients for ``slots`` engine slots."""
        return int(self.mix["clients_per_slot"] * slots)

    def mean_context_pages(self, page_size: int) -> float:
        """Pages of KV a request of the mix holds when it finishes, on
        average over its sizes: what one slot needs of the pool."""
        ctx = self.prompts + self.outputs
        return float(np.mean(-(-ctx // page_size)))

    def prompt_tokens(self, n: int) -> List[int]:
        return self._ids.integers(2, self.vocab, n).tolist()

    def prompt_lengths(self) -> List[int]:
        """Every prompt length the mix can send (for the warm-up)."""
        return sorted(set(self.prompts.tolist()))
