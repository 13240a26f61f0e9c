"""95th percentile of every gap between consecutive tokens of a request,
over all requests, both tokens inside the window (host wall clock)."""

import numpy as np


def read(ctx):
    gaps = []
    for q in ctx.tl.reqs.values():
        e = ctx.tl.emits(q)
        gaps += [b - a for a, b in zip(e, e[1:])]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
