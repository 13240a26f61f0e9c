"""Model step: the window's decode steps' model operations (live rows,
live contexts; ``costs/model.py``) over their spans' time at the chip's
peak bf16 rate, in %."""

import harness


def read(ctx):
    model = harness.cost("model")
    flops = sum(model.decode_step(ctx.arch, ctx.spec,
                                   (s.lengths + 1) * s.mask)
                for s in ctx.run.steps[ctx.tl.steps])
    t = sum(b - a for a, b in ctx.tl.decode_spans[ctx.tl.steps])
    if not t:
        return None
    return 100.0 * flops / (t * ctx.peaks["bf16_flops_per_s"])
