"""The one traffic generator: a mix's parameter file in, a seeded
schedule of requests out.

A mix (``traffic/<name>.json``) states a closed loop of ``clients``,
the lead-in before the window, and length distributions for prompts and
outputs, each with the public source it was taken from.  Every seed
gets the same set of sizes in another order: each quantity is drawn in
blocks of ``block`` values at the block's stratified quantiles, and the
seed only orders each block and picks the token ids.  A block is dealt
in groups of ``group`` consecutive draws, each holding one value of
each of ``group`` equal strata of the block, so that any stretch of a
few groups holds about the block's mix.  So two seeds offer the same
work, and a run's spread is the system's, not the sampler's.

With ``stagger`` set, client ``i``'s first request asks for the share
``(i + 0.5) / clients`` of its drawn output length, so the first wave
ends spread over one request's life, as it would in a loop that has
run for a while, rather than all at once.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

_NORMAL = NormalDist()


def _quantile(dist: Dict, q: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(q))
    elif kind == "uniform":
        v = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return min(max(v, dist["min"]), dist["max"])


class Stratified:
    """Successive whole-number draws of one distribution: blocks of
    ``block`` values at the quantiles (i + 0.5) / block, each block
    dealt in groups of ``group`` draws that take one value from each
    stratum of ``block // group`` neighbouring values, in an order
    drawn from ``rng``."""

    def __init__(self, dist: Dict, block: int, group: int,
                 rng: np.random.Generator):
        if block % group:
            raise ValueError(f"block {block} is not a multiple of group "
                             f"{group}")
        self.values = np.asarray([
            int(round(_quantile(dist, (i + 0.5) / block)))
            for i in range(block)])
        self._strata = self.values.reshape(group, block // group)
        self._rng = rng
        self._buf: List[int] = []

    def _deal(self) -> List[int]:
        cols = np.stack([self._rng.permutation(row) for row in self._strata])
        return [int(v) for col in cols.T for v in self._rng.permutation(col)]

    def __call__(self) -> int:
        if not self._buf:
            self._buf = self._deal()[::-1]
        return self._buf.pop()


@dataclasses.dataclass
class RequestSpec:
    """One generated request: its prompt and how many tokens it asks
    for."""
    index: int
    prompt: np.ndarray
    max_new_tokens: int


class Schedule:
    """The seeded request stream of one mix: ``take(client)`` hands out
    the next request a client sends."""

    def __init__(self, mix: Dict, seed: int, vocab_size: int):
        self.mix = mix
        self.vocab_size = vocab_size
        block, group = int(mix["block"]), int(mix.get("group", 1))
        root = np.random.default_rng(seed)
        self._sizes = np.random.default_rng(root.integers(2**63))
        self._tokens = np.random.default_rng(root.integers(2**63))
        self._prompt = Stratified(mix["prompt"], block, group, self._sizes)
        self._output = Stratified(mix["output"], block, group, self._sizes)
        self._started: set = set()
        self._i = 0

    def take(self, client: int) -> RequestSpec:
        prompt = self._tokens.integers(0, self.vocab_size,
                                       size=self._prompt(), dtype=np.int32)
        n = self._output()
        if self.mix.get("stagger") and client not in self._started:
            n = max(1, math.ceil(n * (client + 0.5) / self.mix["clients"]))
        self._started.add(client)
        spec = RequestSpec(self._i, prompt, n)
        self._i += 1
        return spec

    def longest(self) -> int:
        """The longest prompt + output any request of this mix can
        have: the engine's per-request capacity."""
        return int(self.mix["prompt"]["max"] + self.mix["output"]["max"])

    def prompt_lengths(self) -> List[int]:
        """Every prompt length the mix can draw."""
        return sorted(set(int(v) for v in self._prompt.values))
