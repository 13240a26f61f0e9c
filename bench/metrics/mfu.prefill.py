"""Model step: the window's prefills' model operations at each prompt's
real length (not its bucket; ``costs/model.py``) over their
``serve_admit`` spans' time at the chip's peak bf16 rate, in %."""

import harness


def read(ctx):
    model = harness.cost("model")
    adm = ctx.tl.window_admits()
    t = sum(b - a for a, b, _ in adm)
    if not t:
        return None
    flops = sum(model.prefill(ctx.arch, ctx.spec, n) for _, _, n in adm)
    return 100.0 * flops / (t * ctx.peaks["bf16_flops_per_s"])
