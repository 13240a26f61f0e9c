"""Scheduler: host time per decode step of the window spent outside the
program's ``serve_decode_step`` and ``serve_admit`` spans (the scheduler's
loop, block bookkeeping, the block tables' upload, and the harness's own
hooks, which cost the same on either side), in ms. The pauses the
harness makes inside the window (starting and stopping the profiler) are
taken out of it."""

import harness


def read(ctx):
    tl = ctx.tl
    if not tl.n_steps:
        return None
    window = harness.metric("prefill_time_share").window_less_pause(ctx)
    inside = sum(b - a for a, b in tl.decode_spans[tl.steps])
    inside += sum(b - a for a, b, _ in tl.window_admits())
    return 1e3 * (window - inside) / tl.n_steps
