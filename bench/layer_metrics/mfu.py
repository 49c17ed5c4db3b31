"""Model FLOP utilization of the window: the least time of all the work
the device was handed in the window (every active slot's decode token,
every real prompt token prefilled, as the cell's program adapter counts
them; ``work.served_work``) at the peaks, over the window.  Projections
count at the peak of the configuration's weight type (int8 for EN-T,
bf16 for bf16), the rest (attention, recurrences, the LM head) at the
bf16 peak."""

LAYER = "model step"
MOVES = "tokens_per_s"


def read(ctx):
    from work import served_work, work_seconds
    if not ctx.decodes:
        return None
    mm, other = served_work(ctx.program, ctx.cfg, ctx.decodes, ctx.chunks,
                            *ctx.window)
    return 100.0 * work_seconds(ctx.cfg, ctx.peaks, mm, other) / ctx.window_s
