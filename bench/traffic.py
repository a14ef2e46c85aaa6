"""The one load generator: turns a traffic file's parameters and a seed into
the sequence of requests a closed loop of clients sends.

Sizes follow low-discrepancy sequences: request i asks for the quantile
frac(u + i * g) of the prompt-length distribution (g the golden ratio's
conjugate) and frac(v + i * h) of the output-length distribution
(h = sqrt(2) - 1), with the offsets u and v drawn from the seed. Any run of
consecutive requests then covers each distribution evenly, so every seed
sends the same mix of sizes to the window, in another order, and runs differ
by arrangement and not by how much work they were given. Token ids are
uniform over the vocabulary.

With ``"start": "steady"`` the first ``clients`` requests are what a look
at the loop's steady state would find in flight: request i is left the
tokens a request in flight has still to serve, the quantile frac(w + i /
clients) of their distribution, P(R = r) proportional to P(L >= r), with
the offset w drawn from the seed. So requests end, and their successors'
prompts join the batch, at the steady rate from the first step, and every
seed leaves the same set of lengths. Their contexts hold their prompts
only: the tokens they would already have served are not prefilled.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    index: int
    prompt: np.ndarray
    max_new: int


def quantile(dist: dict, q: float) -> int:
    """The q-quantile of a length distribution, clipped to [min, max]."""
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        x = lo + q * (hi - lo + 1)
        return min(max(int(math.floor(x)), lo), hi)
    if dist["dist"] == "lognormal":
        z = NormalDist().inv_cdf(q)
        x = dist["median"] * math.exp(dist["sigma"] * z)
        return min(max(int(round(x)), lo), hi)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use of the seed; any whole number is a seed."""
    return np.random.default_rng([abs(int(seed)) % 2**63, int(seed < 0),
                                  stream])


_G = (math.sqrt(5.0) - 1.0) / 2.0
_H = math.sqrt(2.0) - 1.0
_EPS = 1e-6


class ClosedLoop:
    """Requests in the order the clients send them: ``next()`` gives the
    request the next free client sends."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        if spec["loop"] != "closed":
            raise ValueError(f"unknown loop {spec['loop']!r}")
        self.spec = spec
        self.vocab = int(vocab)
        self._u, self._v = rng_for(seed, 0).random(2)
        self._tokens = rng_for(seed, 1)
        self._n = 0
        self._steady = None
        if spec.get("start", "empty") == "steady":
            grid = (np.arange(4096) + 0.5) / 4096
            lengths = np.sort([quantile(spec["output_tokens"], q)
                               for q in grid])
            left = np.arange(1, lengths[-1] + 1)
            in_flight = len(lengths) - np.searchsorted(lengths, left)
            self._steady = (left, np.cumsum(in_flight) / in_flight.sum(),
                            rng_for(seed, 4).random())
        elif spec.get("start", "empty") != "empty":
            raise ValueError(f"unknown start {spec['start']!r}")

    def next(self) -> Req:
        i = self._n
        qp = min(max((self._u + i * _G) % 1.0, _EPS), 1.0 - _EPS)
        qo = min(max((self._v + i * _H) % 1.0, _EPS), 1.0 - _EPS)
        plen = quantile(self.spec["prompt_tokens"], qp)
        max_new = quantile(self.spec["output_tokens"], qo)
        clients = int(self.spec["clients"])
        if self._steady is not None and i < clients:
            left, cum, w = self._steady
            q = (w + i / clients) % 1.0
            max_new = left[min(np.searchsorted(cum, q), len(left) - 1)]
        prompt = self._tokens.integers(0, self.vocab, plen, dtype=np.int32)
        self._n += 1
        return Req(i, prompt, int(max_new))
