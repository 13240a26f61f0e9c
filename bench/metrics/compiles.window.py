"""Model step: JAX compilation phases (tracing, lowering, backend
compile; ``jax_compile`` spans) inside the window: the growth of the
``compiles`` total that each ``serve_decode_step`` carries, from the step
that opens the window to the one that closes it. A warmed-up server
reads 0."""

import host_spans


def read(ctx):
    return host_spans.step_delta(ctx, "compiles")
