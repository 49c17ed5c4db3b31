"""Share of its roofline that the Mamba-2 decode state-update kernel
reaches.

Device time: the ``ssd_decode_step`` events of the trace (one per mixer
per decode tick).  Least time: what the cell's program adapter counts
for one call (``ssd_decode_work``: each decoding slot's fp32 state read
and written, its x, B, C, dt read and y written), at the HBM bandwidth,
or its operations at the bf16 peak if those bound (they never do: about
a five-hundredth of the byte time).  The decoding slots come from the
benchmark's record of each decode dispatch in the traced span (the
slots active on the host at dispatch: a parked or idle slot's state,
which the kernel keeps as it is, is not counted).  Per event, the mean
over those dispatches.  None where the program holds no such kernel or
its adapter counts none."""

LAYER = "kernels"
MOVES = "itl_p95_ms"
KERNEL = "ssd_decode_step"


def read(ctx):
    from work import least_time
    work = getattr(ctx.program, "ssd_decode_work", None)
    calls = ctx.trace.calls(KERNEL) if ctx.trace else []
    a, b = ctx.trace_span or (0, 0)
    ticks = [c for t, c in ctx.decodes if a <= t < b and c]
    if work is None or not calls or not ticks:
        return None
    p = ctx.peaks
    per_call = sum(least_time(*work(ctx.cfg, len(c)), p["bf16"],
                              p["hbm_bytes_per_s"]) for c in ticks) / len(ticks)
    return 100.0 * per_call * len(calls) / sum(c.seconds for c in calls)
