"""The one traffic generator: every mix is a data file it reads.

A mix file under ``bench/traffic/`` gives ``loop``:

* ``"closed"``: one client pushes rows back to back; the order is a new
  permutation of the pool on every pass, drawn from the seed, so every
  seed serves the same rows in another order.
* ``"open"``: single-row requests with Poisson arrivals at ``rate_per_s``;
  the rows come from the same seeded permutations.

Seeds may exceed 32 bits; each stream draws from its own
``SeedSequence([seed, stream])``.
"""

from __future__ import annotations

import numpy as np

ROWS, ARRIVALS = 0, 1


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


class RowStream:
    """Pool indices, one seeded permutation of the pool after another."""

    def __init__(self, pool_rows: int, seed: int):
        self.pool_rows = int(pool_rows)
        self._rng = _rng(seed, ROWS)
        self._buf = np.zeros(0, np.int64)

    def take(self, n: int) -> np.ndarray:
        while self._buf.size < n:
            self._buf = np.concatenate([self._buf, self._rng.permutation(self.pool_rows)])
        out, self._buf = self._buf[:n], self._buf[n:]
        return out


def arrivals(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times in seconds from the window's start, Poisson at the mix's
    rate, all before ``seconds``."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"no arrival process {mix['arrivals']!r}")
    rate = float(mix["rate_per_s"])
    rng = _rng(seed, ARRIVALS)
    n = int(rate * seconds + 10 * np.sqrt(rate * seconds) + 10)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    return t[t < seconds]
