"""A decode tick in the steady state: the median interval between the
ends of consecutive ``sched.sync`` spans of decode ticks, leaving out
the intervals an admission ran in.

Read from the program's own span log (``repro.runtime.metrics.SPANS``,
on the ``time.monotonic`` clock of ``ctx.window``) over the window, less
the traced span, where the profiler slows the host.  A decode tick's
sync ends once the device has run the tick and whatever was dispatched
before it, so with a pipeline one tick deep a chunk dispatched in tick k
lands in the interval that tick k+1's sync closes.  A dispatch can also
block the host (on the chip a prefill chunk's does), and then the stall
lands in the interval that holds the dispatch.  So an admission's
intervals run from the one in which its ``sched.admit`` began to the one
closed by the first decode tick dispatched after its final
``sched.chunk``.

The other readers of program spans use the helpers here.  Each returns
None where the program keeps no span log, or where its ring has
overwritten spans that ended inside the window.
"""

import bisect
import statistics
import sys

LAYER = "scheduler"
MOVES = "tokens_per_s"


def span_log(ctx):
    """The program's process-wide span log, or None where there is none
    or it no longer holds every span that ended in the window."""
    log = getattr(sys.modules.get("repro.runtime.metrics"), "SPANS", None)
    if log is None or log.lost_t1 >= ctx.window[0]:
        return None
    return log


def clear(ctx, t0: float, t1: float) -> bool:
    """[t0, t1] lies in the window and outside the traced span."""
    lo, hi = ctx.window
    a, b = ctx.trace_span or (hi, hi)
    return lo <= t0 and t1 < hi and (t1 <= a or t0 >= b)


def timeline(log) -> tuple:
    """(intervals, admissions).  Intervals, in order: (index of the decode
    tick in dispatch order, start, end) between the syncs of consecutive
    decode ticks.  Admissions: (uid, first interval, last interval,
    complete: its first token came, prefill chunks) as the module's doc
    says."""
    decodes = sorted(log.select("sched.decode"), key=lambda s: s.t0)
    order = {d.id: i for i, d in enumerate(decodes)}
    syncs = sorted((s for s in log.select("sched.sync") if s.n in order),
                   key=lambda s: s.t1)
    ivs = [(order[b.n], a.t1, b.t1) for a, b in zip(syncs, syncs[1:])
           if order[b.n] == order[a.n] + 1]
    ends = [b for _, _, b in ivs]
    closed_by = {i: k for k, (i, _, _) in enumerate(ivs)}
    starts = [d.t0 for d in decodes]
    first, last, chunks = {}, {}, {}
    for s in log.select("sched.admit"):
        first[s.uid] = last[s.uid] = s       # a cached prompt has no chunk
        chunks[s.uid] = 0
    for s in log.select("sched.chunk"):     # in end order: the last is final
        if s.uid in first:
            last[s.uid] = s
            chunks[s.uid] += 1
    done = {s.uid for s in log.select("req.prefill")}
    admissions = []
    for uid, admit in first.items():
        k0 = bisect.bisect_left(ends, admit.t0)
        k1 = closed_by.get(bisect.bisect_right(starts, last[uid].t0))
        if (k0 < len(ivs) and ivs[k0][1] <= admit.t0 and k1 is not None
                and k1 >= k0 and ivs[k1][0] - ivs[k0][0] == k1 - k0):
            admissions.append((uid, k0, k1, uid in done, chunks[uid]))
    return ivs, admissions


def seconds(ctx, ivs: list, admissions: list) -> float | None:
    """The median interval no admission ran in, in seconds."""
    busy = {k for _, k0, k1, *_ in admissions for k in range(k0, k1 + 1)}
    clean = [b - a for k, (_, a, b) in enumerate(ivs)
             if k not in busy and clear(ctx, a, b)]
    return statistics.median(clean) if clean else None


def read(ctx):
    log = span_log(ctx)
    tick = None if log is None else seconds(ctx, *timeline(log))
    return None if tick is None else 1e3 * tick
