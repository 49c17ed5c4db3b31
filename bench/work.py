"""Operations and bytes of the work, from shapes alone.

Kept with the benchmark so that every PR counts a kernel's work the same
way.  Counts are the LOGICAL work of the call, the least any
implementation must do:

* EN-T matmul: the int8-weight product ``[M, K] x [K, N]``: ``2 M K N``
  operations at the int8 peak, and the int8 weight (``K N`` bytes), its
  per-column f32 scale, bf16 activations in and bf16 results out.  The
  two packed digit planes the kernel reads (2 bytes a weight) are the
  program's choice, not work: a kernel that reads them runs at most at
  half its roofline, and one that repacks the weight to a byte is held
  to the same yardstick;
* paged decode attention: the K and V rows of every cached position a
  slot attends, per layer, and ``4 * ctx * Hq * hd`` operations;
* a model token: what the configuration's program adapter
  (``programs/<r>.py``) counts for its architecture, split into the
  projections, at the peak of the weight type, and the rest (attention,
  recurrences, the LM head), at the bf16 peak.
"""

from __future__ import annotations


def least_time(ops: float, nbytes: float, op_peak: float,
               bytes_per_s: float) -> float:
    """Seconds the chip needs at least: compute or memory, whichever bounds."""
    return max(ops / op_peak, nbytes / bytes_per_s)


def ent_matmul(m: int, k: int, n: int) -> tuple[float, float]:
    """(ops, bytes) of one logical int8-weight product [M,K] x [K,N]."""
    return 2.0 * m * k * n, float(k * n + 4 * n + 2 * m * k + 2 * m * n)


def ent_matmul_event(shapes: list) -> tuple[int, int, int]:
    """(M, K, N) of a fused EN-T kernel call from its trace shapes: result
    ``[M, N]``, then activations ``[M, K]``."""
    m, n = shapes[0][1]
    k = shapes[1][1][-1]
    return m, k, n


def paged_attention(ctxs, hq: int, hkv: int, hd: int,
                    kv_itemsize: int) -> tuple[float, float]:
    """(ops, bytes) of one layer's decode attention over slots whose
    contexts are ``ctxs`` (cached positions each attends)."""
    t = float(sum(ctxs))
    return 4.0 * t * hq * hd, 2.0 * t * hkv * hd * kv_itemsize


def served_work(program, cfg: dict, decodes: list, chunks: list, lo: float,
                hi: float) -> tuple[float, float]:
    """(matmul ops, bf16 ops) of the decode ticks and prefill chunks
    dispatched in [lo, hi): every active slot's token, every real prompt
    token (chunk padding is not work), as the configuration's program
    adapter ``program`` counts them."""
    mm = other = 0.0
    for t, ctxs in decodes:
        if lo <= t < hi:
            for c in ctxs:
                a, b = program.token_ops(cfg, c, head=True)
                mm, other = mm + a, other + b
    for t, start, n, last in chunks:
        if lo <= t < hi:
            a, b = program.prefill_ops(cfg, start, n, head=last)
            mm, other = mm + a, other + b
    return mm, other


def work_seconds(cfg: dict, peaks: dict, mm: float, other: float) -> float:
    """Least seconds of that work at the peaks: the projections at the
    peak of the configuration's weight type (int8 for EN-T), the rest at
    the bf16 peak."""
    mm_peak = peaks["int8" if cfg["bench"]["weights"] == "ent" else "bf16"]
    return mm / mm_peak + other / peaks["bf16"]
