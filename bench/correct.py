"""The check that decides ``correct``: served tokens against the reference.

Once the window has closed and the program's state is freed, a sample of
the requests the window served (finished, or cut at the window's end
with the tokens served so far) is drawn from the seed, the longest among
them, until it holds ``MIN_SERVED`` served tokens (at most
``MAX_REQUESTS`` requests, ``MAX_PACKED`` tokens).  The configuration's
plain reference (``references/<name>.py``) runs once over each prompt
with its served tokens, packed into one row, and at every position that
produced a served token reads the gap by which that token's logit lies
below the reference's best.  The widest gap is the number compared.
Decoding is greedy, so a program that computes what the reference does
serves gaps of rounding size only.

With ``control`` the configuration's control precision (the reference
computed one precision step lower) reads the same positions: the gap of
the token it would put first.  That is the calibration's upper reading.
"""

from __future__ import annotations

import time

import numpy as np

import spec
from traffic import Traffic

MIN_SERVED = 400
MAX_REQUESTS = 12
MAX_PACKED = 8192
PACK = 1024            # the packed row is padded to a multiple of this
BLOCK = 256            # rows of logits scored at a time


def pick(served: list, traffic: Traffic, seed: int) -> list:
    """The longest served request, then others in an order drawn from the
    seed, until the sample is big enough."""
    if not served:
        return []
    size = lambda r: len(traffic.request(r["i"]).prompt) + len(r["tokens"])
    longest = max(served, key=lambda r: (size(r), r["i"]))
    rest = [r for r in served if r is not longest]
    order = np.random.default_rng([seed % (1 << 64), 7]).permutation(len(rest))
    chosen, served, packed = [longest], len(longest["tokens"]), size(longest)
    for j in order:
        if served >= MIN_SERVED or len(chosen) >= MAX_REQUESTS:
            break
        r = rest[j]
        if packed + size(r) > MAX_PACKED:
            continue
        chosen.append(r)
        served += len(r["tokens"])
        packed += size(r)
    return chosen


def pack(requests: list, traffic: Traffic):
    """(tokens, pos, seg [1, T]; row index and served token of every
    position that produced a served token)."""
    toks, pos, seg, rows, want = [], [], [], [], []
    for s, r in enumerate(requests):
        prompt = traffic.request(r["i"]).prompt
        served = r["tokens"]
        seq = prompt + served[:-1]
        base = len(toks)
        rows += [base + len(prompt) - 1 + j for j in range(len(served))]
        want += served
        toks += seq
        pos += list(range(len(seq)))
        seg += [s] * len(seq)
    t = -(-len(toks) // PACK) * PACK
    pad = t - len(toks)
    arr = lambda a, fill: np.asarray([a + [fill] * pad], np.int32)
    return (arr(toks, 0), arr(pos, 0), arr(seg, -1), np.asarray(rows),
            np.asarray(want, np.int32))


def _scorer():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def score(h, kernel, want):
        """Per row: the best logit, the logit of ``want``, the argmax."""
        logits = jnp.matmul(h, kernel, precision=jax.lax.Precision.HIGHEST)
        at = jnp.take_along_axis(logits, want[:, None], axis=1)[:, 0]
        return logits.max(-1), at, jnp.argmax(logits, -1)

    return score


def _score_rows(score, h, kernel, want) -> tuple:
    """Score rows of h [R, d] in blocks: (best, at want, argmax) arrays."""
    import jax.numpy as jnp
    n = h.shape[0]
    pad = -n % BLOCK
    h = jnp.pad(h, ((0, pad), (0, 0)))
    w = jnp.pad(jnp.asarray(want), (0, pad))
    out = [score(h[i:i + BLOCK], kernel, w[i:i + BLOCK])
           for i in range(0, n + pad, BLOCK)]
    return tuple(np.concatenate([np.asarray(o[k]) for o in out])[:n]
                 for k in range(3))


def check(cell, seed: int, records: list, window: tuple,
          control: bool = False) -> dict:
    """Gap of the served tokens (and, with ``control``, of the control's
    tokens) against the reference, on a sample of the requests served in
    the window.  A finished request must hold its whole answer, a cut one
    no more than that."""
    import jax.numpy as jnp

    cfg = cell.config
    traffic = Traffic(cell.traffic, seed, cfg["vocab_size"])
    served = [r for r in records if r["status"] in ("ok", "cut")
              and r["times"] and r["times"][-1] >= window[0]]
    answer = lambda r: traffic.request(r["i"]).max_new
    wrong_length = sum(len(r["tokens"]) > answer(r) or (
        r["status"] == "ok" and len(r["tokens"]) != answer(r))
        for r in served)
    sample = pick(served, traffic, seed)
    out = {"requests": len(sample),
           "served_tokens": sum(len(r["tokens"]) for r in sample),
           "wrong_length": wrong_length, "gap": 1e9, "control_gap": None,
           "reference_s": 0.0}
    if not sample:
        return out
    t0 = time.perf_counter()
    mod = spec.reader("references", cfg["bench"]["reference"], cell.root)
    ref = mod.Reference(cfg, seed)
    tokens, pos, seg, rows, want = pack(sample, traffic)
    kernel = ref.head()
    score = _scorer()
    h = ref.hidden(tokens, pos, seg, cfg["bench"]["reference_mode"])
    best, at, _ = _score_rows(score, h[0][rows], kernel, want)
    out["gap"] = float(np.max(best - at))
    if control:
        hc = ref.hidden(tokens, pos, seg, cfg["bench"]["control_mode"])
        _, _, ctrl = _score_rows(score, hc[0][rows], kernel, want)
        _, at_c, _ = _score_rows(score, h[0][rows], kernel,
                                 jnp.asarray(ctrl, jnp.int32))
        out["control_gap"] = float(np.max(best - at_c))
    out["reference_s"] = time.perf_counter() - t0
    return out
