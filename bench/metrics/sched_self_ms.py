"""Scheduler: host time per decode step of the window inside the
program's ``serve_schedule`` (admission: free-slot scan, allocator check)
and ``serve_bookkeep`` (pool update, lengths, emits and retirements,
utilization) spans, less their child spans (``serve_admit``,
``serve_retire``, a compilation) and less the pauses the harness makes inside them
(starting and stopping the profiler), in ms. Measured from inside the
work, where ``sched_host_ms`` is the complement of the step and admission
spans."""

import host_spans

SCHED = ("serve_schedule", "serve_bookkeep")


def read(ctx):
    tl = ctx.tl
    recs = host_spans.spans(ctx.run)
    own = [r for r in recs if r["name"] in SCHED
           and tl.start <= host_spans.interval(r)[0] <= tl.end]
    if not own or not tl.n_steps:
        return None
    kids = host_spans.children(recs)
    pauses = [p for p in (ctx.run.trace_start_host, ctx.run.trace_stop_host)
              if p is not None]
    t = 0.0
    for r in own:
        a, b = host_spans.interval(r)
        t += b - a - sum(c["dur_s"] for c in kids.get(r["id"], []))
        t -= sum(max(0.0, min(b, d) - max(a, c)) for c, d in pauses)
    return 1e3 * t / tl.n_steps
