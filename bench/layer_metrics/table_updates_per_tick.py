"""Block-table dispatches per decode tick: ``kv.set_tables`` and
``kv.release`` spans over ``sched.decode`` spans, from the program's span
log over the window less the traced span.  Each of them is a dispatch
over the whole KV pool.  None where the program keeps no span log."""

import spec

LAYER = "engine and prefix cache"
MOVES = "tokens_per_s"


def read(ctx):
    base = spec.reader("layer_metrics", "tick_ms")
    log = base.span_log(ctx)
    if log is None:
        return None

    def count(name):
        return sum(base.clear(ctx, s.t0, s.t1) for s in log.select(name))

    ticks = count("sched.decode")
    return ((count("kv.set_tables") + count("kv.release")) / ticks
            if ticks else None)
