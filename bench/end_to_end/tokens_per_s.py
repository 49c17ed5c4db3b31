"""Output tokens received by all clients in the window, over the window."""


def read(ctx):
    n = sum(ctx.in_window(t) for r in ctx.records for t in r["times"])
    return n / ctx.window_s
