"""The traffic generator: one set of requests for every seed, in groups
that each hold every stratum of prompt and answer lengths."""

import json
import os

import numpy as np

from traffic import BLOCK, K, Traffic, quantiles

MIX = json.load(open(os.path.join(os.path.dirname(__file__), "..",
                                  "traffic", "decode.json")))
SEEDS = (0, 2147483901, 3800000039, (1 << 33) + 5)


def _block(seed: int, b: int = 0) -> list:
    t = Traffic(MIX, seed, 1000)
    return [(len(t.request(i).prompt), t.request(i).max_new)
            for i in range(b * BLOCK, (b + 1) * BLOCK)]


def test_every_seed_has_the_same_set_of_requests():
    sets = {tuple(sorted(_block(s, b))) for s in SEEDS for b in (0, 3)}
    assert len(sets) == 1
    pairs = next(iter(sets))
    assert sorted(p for p, _ in pairs) == sorted(quantiles(MIX["prompt"]))
    assert sorted(o for _, o in pairs) == sorted(quantiles(MIX["output"]))


def test_every_group_holds_every_stratum():
    """The j-th shortest prompt (answer) of each group of K lies in the
    j-th stratum: the j-th run of K sorted quantiles."""
    strata = {k: np.sort(quantiles(MIX[k])).reshape(K, K)
              for k in ("prompt", "output")}
    for s in SEEDS:
        reqs = _block(s, 1)
        for g in range(0, BLOCK, K):
            for k, col in (("prompt", 0), ("output", 1)):
                got = sorted(r[col] for r in reqs[g:g + K])
                lo, hi = strata[k].min(1), strata[k].max(1)
                assert ((lo <= got) & (got <= hi)).all(), (s, g, k)


def test_seeds_change_the_order():
    assert _block(SEEDS[0]) != _block(SEEDS[1])
