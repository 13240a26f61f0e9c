"""Output tokens emitted in the window over the window's wall seconds
(the tokens of the step that opens the window are not counted)."""


def read(ctx):
    tl = ctx.tl
    n = sum(1 for q in tl.reqs.values() for t in tl.emits(q)
            if t > tl.start)
    return n / tl.seconds
