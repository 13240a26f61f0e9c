"""Scheduler: time inside the window's ``serve_admit`` spans (prefill
plus the admission scatter) as a share of the window, in %. The pauses
the harness makes inside the window (starting and stopping the profiler)
are taken out of it."""


def window_less_pause(ctx) -> float:
    tl = ctx.tl
    window = tl.seconds
    for pause in (ctx.run.trace_start_host, ctx.run.trace_stop_host):
        if pause is not None and tl.start <= pause[0] <= tl.end:
            window -= pause[1] - pause[0]
    return window


def read(ctx):
    adm = ctx.tl.window_admits()
    return 100.0 * sum(b - a for a, b, _ in adm) / window_less_pause(ctx)
