"""Scheduler: Python garbage-collection pause time in the window (the
growth of the ``gc_s`` total that each ``serve_decode_step`` carries, from
the step that opens the window to the one that closes it) per decode step
of the window, in ms."""

import host_spans


def read(ctx):
    d = host_spans.step_delta(ctx, "gc_s")
    return None if d is None or not ctx.tl.n_steps \
        else 1e3 * d / ctx.tl.n_steps
