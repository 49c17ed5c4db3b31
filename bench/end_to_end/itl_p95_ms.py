"""95th percentile of every gap between consecutive streamed tokens of a
request, over all requests, where the later token came in the window."""

import numpy as np


def read(ctx):
    gaps = [b - a for r in ctx.records
            for a, b in zip(r["times"], r["times"][1:]) if ctx.in_window(b)]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
