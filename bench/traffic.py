"""The one traffic generator: turns a mix's parameters into requests and
the times at which they are due.

Arrivals are an open loop: request ``i`` is due at ``arrival(i)`` seconds
after the schedule starts, whether or not earlier requests have finished.
The gaps between arrivals are those of a Poisson process at the mix's
``rate`` (requests per second).

Lengths and gaps come from fixed pools of ``pool`` values per mix: the
quantiles ``(j + 0.5) / pool`` of the mix's distribution, lengths clipped
to their range, gaps scaled so that their mean is exactly ``1 / rate``.
Request ``i`` takes block ``i // pool`` of a shuffle of the pools (prompt
lengths, output lengths and gaps independently); the shuffle of each
block is fixed by the block's number alone, so every seed serves the
same sizes at the same due times. The seed picks the prompt tokens,
uniform over the vocabulary (and, in ``params.py``, the weights). The
order is not the seed's because the tail of time to first token is set
by which long requests meet in the queue: shuffled per seed, the order
alone moved a run's ``ttft_p95_ms`` by more than half (PERF.md).
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

# the stream of the block shuffles, the same for every seed
ORDER_KEY = 1

def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_pool(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (j + 0.5) / n of ``dist``."""
    p = quantiles(n)
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in p])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        vals = dist["min"] + p * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def gap_pool(rate: float, n: int) -> np.ndarray:
    """``n`` exponential gaps at the quantiles (j + 0.5) / n, mean 1/rate."""
    g = -np.log1p(-quantiles(n))
    return g / (g.mean() * rate)


class Traffic:
    """Requests of one mix for one seed; ``request(i)`` and ``arrival(i)``
    are deterministic in (seed, i)."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        _check(mix)
        self.mix, self.seed, self.vocab = mix, int(seed), vocab
        self.pool = int(mix["pool"])
        self.prompts = length_pool(mix["prompt"], self.pool)
        self.outputs = length_pool(mix["output"], self.pool)
        self.rate = float(mix["rate"])
        self.gaps = gap_pool(self.rate, self.pool)
        self._block = None
        self._due = [0.0]

    @property
    def max_len(self) -> int:
        """Longest prompt plus longest output any request can have."""
        return int(self.prompts.max() + self.outputs.max())

    def _order(self, block: int):
        if self._block is None or self._block[0] != block:
            rng = np.random.default_rng([ORDER_KEY, block])
            self._block = (block, rng.permutation(self.pool),
                           rng.permutation(self.pool),
                           rng.permutation(self.pool))
        return self._block[1:]

    def request(self, i: int) -> tuple:
        """-> (prompt tokens (P,) int32, max_new_tokens)."""
        pp, po, _ = self._order(i // self.pool)
        j = i % self.pool
        p, g = int(self.prompts[pp[j]]), int(self.outputs[po[j]])
        rng = np.random.default_rng([self.seed, 2, i])
        return rng.integers(0, self.vocab, size=p, dtype=np.int32), g

    def arrival(self, i: int) -> float:
        """Seconds from the schedule's start to request ``i``'s due time
        (request 0 is due at 0)."""
        while len(self._due) <= i:
            k = len(self._due)
            pg = self._order((k - 1) // self.pool)[2]
            self._due.append(self._due[-1]
                             + float(self.gaps[pg[(k - 1) % self.pool]]))
        return self._due[i]


def _check(mix: dict) -> None:
    assert mix["rate"] > 0, mix
    for part in ("prompt", "output"):
        d = mix[part]
        assert 1 <= d["min"] <= d["max"], (part, d)
        if d["dist"] == "lognormal":
            assert d["median"] > 0 and d["sigma"] >= 0, (part, d)
