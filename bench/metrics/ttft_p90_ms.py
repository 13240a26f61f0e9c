"""90th percentile, over the requests sent inside the window, of the time
from being sent (the end of the decode step that freed its slot) to the
end of the first decode step that emitted for it. The first wave, sent
all at once in set-up, is not among them."""

import numpy as np


def read(ctx):
    tl = ctx.tl
    t = [e[0] - q.sent for e, q in ((tl.emits(q), q)
                                   for q in tl.reqs.values())
         if e and q.sent >= tl.start]
    return float(np.percentile(t, 90)) * 1e3 if t else None
