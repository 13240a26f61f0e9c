"""Model step: seconds of set-up spent compiling, from the program's
tracer being enabled (after the model is built) to the window's start:
the union of the ``jax_compile`` spans (tracing, lowering, backend
compile, and loads from the persistent compilation cache) before the
window opens."""

import trace_reduce

import host_spans


def read(ctx):
    start = ctx.tl.start
    iv = [host_spans.interval(r) for r in ctx.run.spans
          if r["name"] == "jax_compile"]
    iv = trace_reduce.merge([(a, min(b, start)) for a, b in iv if a < start])
    return host_spans.total(iv) if iv else None
