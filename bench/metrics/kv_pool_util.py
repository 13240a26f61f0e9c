"""KV cache: mean over the window's decode steps of the pool's live
tokens over its capacity (``PagedKVCache.utilization()["util_vs_pool"]``
after each step's append), in %."""

import numpy as np


def read(ctx):
    steps = ctx.run.steps[ctx.tl.steps]
    return 100.0 * float(np.mean([s.pool_util for s in steps])) \
        if steps else None
