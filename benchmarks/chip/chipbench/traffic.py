"""The one request generator: reads a traffic file's parameters, draws
requests from ``--seed``.

Every seed gets the same multiset of sizes, in its own order: each cycle of
``sizes_per_cycle`` requests takes prompt and output lengths at the evenly
spaced quantiles of their clipped distributions, shuffled by the seed.  The
token ids are drawn from the seed too.  So seeds change which tokens are
served and in what order, not how much work there is.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Tuple

import numpy as np


def quantile_sizes(dist: Dict, n: int) -> List[int]:
    """``n`` sizes at the quantiles (i + 1/2) / n of ``dist``, clipped."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = statistics.NormalDist()
    out = []
    for i in range(n):
        x = dist["median"] * np.exp(dist["sigma"] * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


class RequestStream:
    """``next()`` -> (prompt token ids [len] int32, output length)."""

    def __init__(self, traffic: Dict, seed: int, vocab: int):
        self.n = int(traffic["sizes_per_cycle"])
        self.prompts = quantile_sizes(traffic["prompt_len"], self.n)
        self.outputs = quantile_sizes(traffic["output_len"], self.n)
        self.vocab = int(vocab)
        self.rng = np.random.default_rng(int(seed))
        self._queue: List[Tuple[int, int]] = []

    def _refill(self):
        p = self.rng.permutation(self.n)
        o = self.rng.permutation(self.n)
        self._queue = [(self.prompts[i], self.outputs[j])
                       for i, j in zip(p, o)]

    def next(self) -> Tuple[np.ndarray, int]:
        if not self._queue:
            self._refill()
        plen, olen = self._queue.pop(0)
        prompt = self.rng.integers(1, self.vocab, size=plen, dtype=np.int64)
        return prompt.astype(np.int32), olen
