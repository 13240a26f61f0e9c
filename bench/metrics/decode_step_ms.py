"""Model step: mean ``serve_decode_step`` span of the window, in ms (host
wall clock: the jitted step and the host's read of its tokens)."""


def read(ctx):
    d = ctx.tl.decode_spans[ctx.tl.steps]
    return 1e3 * sum(b - a for a, b in d) / len(d) if d else None
