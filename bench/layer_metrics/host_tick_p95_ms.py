"""The engine thread's own time per scheduler tick: the 95th percentile
over ticks of a ``sched.tick`` span's duration less the time it spent
in calls that wait on the device: ``sched.sync``, and the dispatches
that block there, ``sched.chunk``, ``kv.set_tables`` and ``kv.release``
(the outermost of them, at any depth below the tick).  From the
program's span log over the window less the traced span.  None where
the program keeps no span log."""

from collections import defaultdict

import numpy as np

import spec

LAYER = "scheduler"
MOVES = "tokens_per_s"
WAITS = frozenset({"sched.sync", "sched.chunk", "kv.set_tables",
                   "kv.release"})


def read(ctx):
    base = spec.reader("layer_metrics", "tick_ms")
    log = base.span_log(ctx)
    if log is None:
        return None
    spans = {s.id: s for s in log.select(None, *ctx.window)}
    ticks = {s.id for s in spans.values() if s.name == "sched.tick"
             and base.clear(ctx, s.t0, s.t1)}
    waits = defaultdict(float)
    for s in spans.values():
        if s.name not in WAITS:
            continue
        up = spans.get(s.parent)
        while up is not None and up.name not in WAITS and up.id not in ticks:
            up = spans.get(up.parent)
        if up is not None and up.id in ticks:
            waits[up.id] += s.t1 - s.t0
    own = [spans[i].t1 - spans[i].t0 - waits[i] for i in ticks]
    return float(np.percentile(own, 95)) * 1e3 if own else None
