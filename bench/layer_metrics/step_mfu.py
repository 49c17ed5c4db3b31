"""The whole step's share of the chip's peak: the least time of the work
dispatched in the traced span (as ``mfu`` counts it) over the device's
busy time in that span.  It bounds what the kernels' roofline shares can
gain end to end."""

LAYER = "model step"
MOVES = "itl_p95_ms"


def read(ctx):
    from work import served_work, work_seconds
    if not ctx.trace or not ctx.trace.busy_s or not ctx.decodes:
        return None
    mm, other = served_work(ctx.program, ctx.cfg, ctx.decodes, ctx.chunks,
                            *ctx.trace_span)
    return 100.0 * work_seconds(ctx.cfg, ctx.peaks, mm, other) / ctx.trace.busy_s
