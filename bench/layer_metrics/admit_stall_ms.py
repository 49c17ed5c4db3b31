"""What one prefill chunk of an admission costs every stream: per
admission completed in the window (its first token came), the sum of
the decode-tick intervals it ran in, less that many plain ticks
(``tick_ms``), over its prefill chunks (one for a fully cached prompt).
The median over admissions, so prompts of one chunk and of four weigh
alike.

The intervals, as ``tick_ms`` defines them, run from the one in which
its ``sched.admit`` began to the one closed by the first decode tick
dispatched after its final ``sched.chunk``: a stall on the device or in
the host's dispatch both fall inside.  From the program's span log over
the window less the traced span; None where the program keeps none.
"""

import statistics

import spec

LAYER = "scheduler"
MOVES = "itl_p95_ms"


def read(ctx):
    base = spec.reader("layer_metrics", "tick_ms")
    log = base.span_log(ctx)
    if log is None:
        return None
    ivs, admissions = base.timeline(log)
    tick = base.seconds(ctx, ivs, admissions)
    if tick is None:
        return None
    stalls = [(sum(b - a for _, a, b in ivs[k0:k1 + 1])
               - (k1 - k0 + 1) * tick) / max(chunks, 1)
              for _, k0, k1, done, chunks in admissions
              if done and base.clear(ctx, ivs[k0][1], ivs[k1][2])]
    return 1e3 * statistics.median(stalls) if stalls else None
