"""From a token's emission by the engine to its SSE event written: the
95th percentile of the ``http.write`` spans, from the program's span log
over the window less the traced span.  None where the program keeps no
span log."""

import numpy as np

import spec

LAYER = "HTTP front end"
MOVES = "itl_p95_ms"


def read(ctx):
    base = spec.reader("layer_metrics", "tick_ms")
    log = base.span_log(ctx)
    if log is None:
        return None
    lags = [s.t1 - s.t0 for s in log.select("http.write")
            if base.clear(ctx, s.t0, s.t1)]
    return float(np.percentile(lags, 95)) * 1e3 if lags else None
