"""One general generator for every traffic mix in ``benchmarks/chip/traffic``.

A mix file gives the prompt and output length distributions and the arrival
process. An open-loop cell's rate belongs to the cell, not the mix: it is
``cells/<cell name>.json``. Everything else comes from ``--seed``. Draws are stratified in
blocks of ``BLOCK`` requests: each block holds the same ``BLOCK`` quantiles
of every distribution, in an order the seed shuffles. So every seed serves
the same set of sizes and inter-arrival gaps in another order, and runs of
different seeds differ by the order of the work, not by its amount.

The order is stratified too: the ``BLOCK`` quantiles fall into ``GROUP``
strata of adjacent quantiles, and every run of ``GROUP`` consecutive
requests holds one quantile of each stratum. So every group of ``GROUP``
requests spans about the same time and asks for about the same work, and
the seed moves a long prompt, a long answer or a short gap within a few
seconds of the stream, not into a burst of its own that some seeds draw
and others do not.

Length distributions (``input``/``output``):
  ``{"dist": "lognormal", "mu": .., "sigma": .., "min": .., "max": ..}``
  ``{"dist": "uniform", "low": .., "high": ..}`` (whole numbers, inclusive)
Arrivals:
  ``{"process": "poisson"}`` open loop, at the cell's ``rate_per_s``
  ``{"process": "backlog", "depth_per_pool": k}`` every pool's queue is kept
  at least ``k`` deep
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Optional

import numpy as np

BLOCK = 64              # quantiles per block: how far into the tails draws reach
GROUP = 16              # consecutive requests that hold one of each stratum
_STREAM_LEN_IN, _STREAM_LEN_OUT, _STREAM_GAP, _STREAM_TOKENS = 1, 2, 3, 4


def _rng(seed: int, stream: int, index: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream, index])


def _quantiles(seed: int, stream: int, block: int) -> np.ndarray:
    """The block's ``BLOCK`` mid-quantiles in a seeded order in which every
    ``GROUP`` consecutive ones hold one of each of ``GROUP`` strata."""
    rng = _rng(seed, stream, block)
    k = BLOCK // GROUP                      # quantiles per stratum, groups
    deal = np.stack([rng.permutation(k) for _ in range(GROUP)], axis=1)
    groups = deal + k * np.arange(GROUP)    # (k groups, GROUP strata)
    order = np.concatenate([rng.permutation(g) for g in groups])
    return (order + 0.5) / BLOCK


def inverse_cdf(dist: dict, u: np.ndarray) -> np.ndarray:
    """Whole-number lengths at quantiles ``u`` of ``dist``, clipped."""
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = np.round(np.exp(dist["mu"] + dist["sigma"] * z))
    elif kind == "uniform":
        lo, hi = int(dist["low"]), int(dist["high"])
        vals = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo = dist.get("min", 1)
    hi = dist.get("max", np.inf)
    return np.clip(vals, lo, hi).astype(np.int64)


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float            # offset from the window's start; 0 for backlog
    m: int                  # prompt tokens
    n: int                  # output tokens asked for
    prompt: np.ndarray      # (m,) int32 token ids


class Traffic:
    """The seeded request stream of one mix for one cell."""

    def __init__(self, mix: dict, *, rate_per_s: Optional[float],
                 max_len: int, vocab: int, seed: int):
        self.mix, self.seed = mix, int(seed)
        self.max_len, self.vocab = max_len, vocab
        arr = mix["arrivals"]
        self.process = arr["process"]
        if self.process == "poisson":
            if not rate_per_s or rate_per_s <= 0:
                raise ValueError(f"traffic {mix['name']!r} is open loop and "
                                 f"needs the cell's rate, not {rate_per_s!r}")
            self.rate_per_s = float(rate_per_s)
            self.depth_per_pool = 0
        elif self.process == "backlog":
            self.rate_per_s = 0.0
            self.depth_per_pool = int(arr["depth_per_pool"])
        else:
            raise ValueError(f"unknown arrival process {self.process!r}")
        self._m: list[int] = []
        self._n: list[int] = []
        self._due: list[float] = []

    def _extend(self, upto: int) -> None:
        while len(self._m) < upto:
            b = len(self._m) // BLOCK
            m = inverse_cdf(self.mix["input"],
                            _quantiles(self.seed, _STREAM_LEN_IN, b))
            m = np.minimum(m, self.max_len - 1)
            n = inverse_cdf(self.mix["output"],
                            _quantiles(self.seed, _STREAM_LEN_OUT, b))
            n = np.minimum(n, self.max_len - m)
            self._m += m.tolist()
            self._n += n.tolist()
            if self.process == "poisson":
                u = _quantiles(self.seed, _STREAM_GAP, b)
                gaps = -np.log1p(-u) / self.rate_per_s
                start = self._due[-1] + gaps[0] if self._due else 0.0
                self._due += (start + np.concatenate(
                    [[0.0], np.cumsum(gaps[1:])])).tolist()
            else:
                self._due += [0.0] * BLOCK

    def request(self, i: int) -> Request:
        self._extend(i + 1)
        m = self._m[i]
        prompt = _rng(self.seed, _STREAM_TOKENS, i).integers(
            0, self.vocab, m, dtype=np.int32)
        return Request(i, self._due[i], m, self._n[i], prompt)

    def requests_due_before(self, t_s: float) -> list[Request]:
        """Every request of an open-loop mix due before ``t_s``."""
        if self.process != "poisson":
            raise ValueError("a backlog has no due times")
        n = BLOCK
        self._extend(n)
        while self._due[-1] < t_s:
            n += BLOCK
            self._extend(n)
        return [self.request(i) for i in range(n) if self._due[i] < t_s]
