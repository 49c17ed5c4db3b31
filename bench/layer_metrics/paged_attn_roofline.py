"""Share of its roofline that the paged decode attention kernel reaches.

Device time: the ``paged_attention`` events of the trace (one per layer
per decode tick).  Least time: the K and V of every cached position each
active slot attends (``work.paged_attention``), read at the HBM
bandwidth, or its operations at the bf16 peak if those bound; the query
heads from the cell's program adapter, the rest from the call's key
pages.  The contexts come from the benchmark's record of each decode
dispatch in the traced span (the engine's host mirror of positions,
which may trail the device by the pipeline's depth: the count errs
low).  Per event, the mean over those dispatches."""

LAYER = "kernels"
MOVES = "itl_p95_ms"
KERNEL = "paged_attention"


def read(ctx):
    from work import least_time, paged_attention
    calls = ctx.trace.calls(KERNEL) if ctx.trace else []
    a, b = ctx.trace_span or (0, 0)
    ticks = [c for t, c in ctx.decodes if a <= t < b and c]
    if not calls or not ticks:
        return None
    # 4-d shapes: the result, the queries, then the key pages [P, Hkv, page, D]
    dtype, (_, hkv, _, hd) = [s for s in calls[0].shapes if len(s[1]) == 4][2]
    item = 1 if dtype == "s8" else 2
    hq = ctx.program.query_heads(ctx.cfg)
    p = ctx.peaks
    per_call = sum(least_time(*paged_attention(c, hq, hkv, hd, item),
                              p["bf16"], p["hbm_bytes_per_s"])
                   for c in ticks) / len(ticks)
    return 100.0 * per_call * len(calls) / sum(c.seconds for c in calls)
