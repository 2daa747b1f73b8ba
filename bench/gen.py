"""The one traffic generator: every mix is a data file this module reads.

Lengths are the quantiles of a clipped lognormal, one per client, so every
seed draws the same set of sizes; the seed only shuffles which client gets
which size in each wave and draws the token ids. The work a wave does is
then the same for every seed, and two seeds differ by the order of the
same requests, not by how much there is to do.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A host generator from any non-negative seed (64 bits and more)."""
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), int(stream)])


def quantile_lengths(spec: Dict[str, float], n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of lognormal(median, sigma),
    rounded and clipped to [min, max]: the same set for every seed."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])).astype(np.int64)


@dataclass
class Request:
    client: int
    wave: int
    prompt: List[int]
    max_new: int


class ClosedLoop:
    """Requests of a closed loop of ``clients`` clients, wave by wave.

    Wave ``w`` gives every client one request; prompt and answer lengths
    are the fixed quantile sets, permuted independently per wave."""

    def __init__(self, mix: Dict, vocab: int, seed: int) -> None:
        self.clients = int(mix["clients"])
        self.vocab = int(vocab)
        self.prompt_lengths = quantile_lengths(mix["prompt"], self.clients)
        self.answer_lengths = quantile_lengths(mix["answer"], self.clients)
        self.lo = int(mix.get("token_min", 1))
        self.seed = int(seed)

    def wave(self, w: int) -> List[Request]:
        rng = rng_for(self.seed, 1 + w)
        p = rng.permutation(self.prompt_lengths)
        a = rng.permutation(self.answer_lengths)
        return [
            Request(
                client=c,
                wave=w,
                prompt=rng.integers(self.lo, self.vocab, size=int(p[c])).tolist(),
                max_new=int(a[c]),
            )
            for c in range(self.clients)
        ]


def query_order(n_items: int, seed: int, count: int) -> List[int]:
    """``count`` indices cycling over ``n_items`` in a seeded order: each
    pass over the items is one permutation, so every item recurs evenly."""
    rng = rng_for(seed, 0)
    out: List[int] = []
    while len(out) < count:
        out.extend(int(i) for i in rng.permutation(n_items))
    return out[:count]


def sample_indices(n: int, k: int, seed: int, must: int) -> List[int]:
    """``k`` distinct indices of ``n`` drawn from the seed, ``must`` first."""
    rng = rng_for(seed, 2)
    rest = [int(i) for i in rng.permutation(n) if int(i) != must]
    return [must] + rest[: max(0, min(k, n) - 1)]
