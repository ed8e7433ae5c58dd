"""The one traffic generator: reads a mix's parameters, yields requests.

A mix file (``chipbench/traffic/<mix>.json``) names a loop kind and the
distributions of prompt and output lengths:

    {"loop": "open" | "closed",
     "queue_per_slot": 2,                      # closed loop only
     "prompt": {"dist": "lognormal", "median": 768, "sigma": 0.6,
                "min": 256, "max": 2048},
     "output": {"dist": "fixed", "value": 16}}

An open loop sends requests at the cell's rate whether or not earlier
ones have finished, the gaps between arrivals drawn independently from
the exponential (a Poisson process) with the run's seed; a closed loop
keeps ``queue_per_slot`` x slots requests outstanding.

Every seed gets the same lengths in the same order.  They are the
``POOL`` quantiles of the stated distributions, cut into ``STRATA``
strata by size; each block of ``STRATA`` consecutive requests takes one
value from each stratum, in an order drawn once from ``ORDER_SEED``.  So
any stretch of the stream holds nearly the same mix of lengths, and the
run's seed draws the prompts' tokens (and so the weights' inputs) and
the arrival gaps, not the amount of work: a window of a closed loop sees
the same sequence of steps in every run.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

STRATA = 16
POOL = 256          # lengths per pass through the distributions
ORDER_SEED = 0      # deals the strata into blocks, the same for every run


@dataclasses.dataclass
class Request:
    index: int
    due_s: float | None      # offset from the start of traffic (open loop)
    prompt: np.ndarray       # int32 token ids
    max_new: int


def quantile_values(dist: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of ``dist``, clipped to its range."""
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, dist["value"], np.int64)
    probs = (np.arange(n) + 0.5) / n
    z = np.array([statistics.NormalDist().inv_cdf(p) for p in probs])
    if kind == "lognormal":
        v = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def stratified_order(values: np.ndarray, rng) -> np.ndarray:
    """``values`` reordered so each block of ``STRATA`` consecutive
    entries holds one value from each size stratum."""
    n = len(values)
    k = math.gcd(n, STRATA)
    per = n // k
    strata = np.sort(values).reshape(k, per)
    strata = np.stack([rng.permutation(s) for s in strata])  # [k, per]
    blocks = [rng.permutation(strata[:, j]) for j in range(per)]
    return np.concatenate(blocks)


class Stream:
    """Endless seeded request stream of one mix; ``rate`` (requests/s)
    is required for an open loop and sets the mean gap between due
    times."""

    def __init__(self, mix: dict, *, vocab: int, seed: int,
                 rate: float | None = None):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.gap_rng = np.random.default_rng([seed, 1])
        self.order_rng = np.random.default_rng(ORDER_SEED)
        self.open = mix["loop"] == "open"
        if self.open and not rate:
            raise ValueError("an open-loop mix needs a rate")
        self.rate = rate
        self._prompt_vals = quantile_values(mix["prompt"], POOL)
        self._out_vals = quantile_values(mix["output"], POOL)
        self._buf: list = []
        self._index = 0
        self._clock = 0.0

    def _refill(self):
        p = stratified_order(self._prompt_vals, self.order_rng)
        o = stratified_order(self._out_vals, self.order_rng)
        self._buf = list(zip(p, o))[::-1]

    def __iter__(self):
        return self

    def __next__(self) -> Request:
        if not self._buf:
            self._refill()
        plen, max_new = self._buf.pop()
        due = None
        if self.open:
            self._clock += float(self.gap_rng.exponential(1.0 / self.rate))
            due = self._clock
        toks = self.rng.integers(0, self.vocab, int(plen)).astype(np.int32)
        req = Request(self._index, due, toks, int(max_new))
        self._index += 1
        return req
