"""Kernels: the paged latent attention graph's least possible time over
its device time, in %, for the decode steps inside the traced window.
Least time per step is the larger of its operations at the peak bf16
rate and its live latent pages' bytes at the peak HBM rate
(``costs/latent_attn.py``); device time is the kernels of the decode step
program in the trace (the graph is the step's only kernel). A program
with no latent pool reads nothing."""

import harness


def read(ctx):
    t = ctx.trace
    if (t is None or not t.kernel_s.get("decode_step")
            or not getattr(ctx.spec, "kv_lora_rank", 0)):
        return None
    latent = harness.cost("latent_attn")
    first, last = t.steps
    bound = 0.0
    for s in ctx.run.steps[first:last + 1]:
        f, b = latent.step(ctx.spec, (s.lengths + 1) * s.mask, ctx.run.page)
        bound += max(f / ctx.peaks["bf16_flops_per_s"],
                     b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t.kernel_s["decode_step"]
