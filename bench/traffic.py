"""The one traffic generator: requests from a mix's parameters and a seed.

Numpy and the standard library only, so the JAX-free load generator and
the harness draw the same requests.  A mix file
(``bench/traffic/<mix>.json``) holds parameters, never code::

    {"prompt": {"dist": "lognormal", "median": 200, "sigma": 0.8,
                "min": 32, "max": 1024},
     "output": {"dist": "lognormal", "median": 300, "sigma": 0.6,
                "min": 64, "max": 1024}}

Every seed gets the same SET of requests, in an order of its own.  Each
block of ``BLOCK`` = ``K`` x ``K`` requests takes the ``BLOCK``
stratified quantiles of each distribution (``(i + 0.5) / BLOCK``) and
pairs prompt and answer lengths in one fixed pairing that holds each of
the K x K combinations of a prompt stratum and an answer stratum (a
stratum is K neighbouring quantiles) once: the lengths are drawn
independently.  The seed orders the block as K groups of K requests,
each group one request of every prompt stratum and of every answer
stratum.  So every group of K requests, not only every block, holds the
same mix of long and short prompts and answers, and a window that sees
part of a block sees the distribution and not a seed's luck: a closed
loop of a few hundred tokens an answer admits only some tens of
requests in a window.  Seeds differ in order and in token ids.  Tokens
are uniform over the vocabulary.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

K = 8                                  # strata of each length distribution
BLOCK = K * K
_STREAM = {"order": 1, "tokens": 2}
# the fixed pairing: prompt stratum a, answer stratum b -> quantile ranks
_A, _B = np.divmod(np.arange(BLOCK), K)
_PROMPT_RANK = K * _A + (_B - _A) % K
_ANSWER_RANK = K * _B + _A


@dataclass(frozen=True)
class Request:
    index: int
    prompt: list          # token ids
    max_new: int


def _rng(seed: int, *words: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *words])


def quantiles(dist: dict, n: int = BLOCK) -> np.ndarray:
    """The n stratified quantiles of a length distribution, clipped and
    rounded to whole tokens."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


class Traffic:
    """Requests of one mix for one seed, by index (lazily, any order)."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, int(seed), int(vocab)
        self._prompt = quantiles(mix["prompt"])
        self._output = quantiles(mix["output"])
        self._blocks: dict[int, tuple] = {}

    def _block(self, b: int) -> tuple:
        if b not in self._blocks:
            r = _rng(self.seed, _STREAM["order"], b)
            # group (p[a] + q[b]) mod K holds one pair of every a and b;
            # groups in the order of their number, shuffled within
            p, q = r.permutation(K), r.permutation(K)
            order = np.lexsort((r.random(BLOCK), (p[_A] + q[_B]) % K))
            self._blocks[b] = (self._prompt[_PROMPT_RANK[order]],
                               self._output[_ANSWER_RANK[order]])
        return self._blocks[b]

    def request(self, i: int) -> Request:
        plen, olen = self._block(i // BLOCK)
        j = i % BLOCK
        toks = _rng(self.seed, _STREAM["tokens"], i).integers(
            0, self.vocab, int(plen[j])).tolist()
        return Request(i, toks, int(olen[j]))

    def longest_prompt(self) -> int:
        return int(self._prompt.max())
