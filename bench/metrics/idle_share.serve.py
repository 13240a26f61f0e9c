"""Device: 1 - (union of device operation intervals) / traced window, in
% (profiler trace of a few seconds of the window)."""


def read(ctx):
    t = ctx.trace
    return None if t is None else 100.0 * t.idle_share
