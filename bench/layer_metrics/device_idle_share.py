"""Share of the traced span in which no operation ran on the device (the
union of the device ops' intervals, from the trace)."""

LAYER = "device"
MOVES = "itl_p95_ms"


def read(ctx):
    if not ctx.trace or not ctx.trace.window_s:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
