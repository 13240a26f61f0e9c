"""Kernels: ``ff_attention`` (causal) least possible time at each traced
prompt's real length over the prefill program's kernel device time, in
%. Least time per prompt is the larger of its operations at the peak
bf16 rate and its bytes at the peak HBM rate (``costs/prefill_attn.py``).
"""

import harness


def read(ctx):
    t = ctx.trace
    if t is None or not t.admits or not t.kernel_s.get("prefill_step"):
        return None
    pre = harness.cost("prefill_attn")
    bound = 0.0
    for n in t.admits:
        f, b = pre.prompt(ctx.spec, n)
        bound += max(f / ctx.peaks["bf16_flops_per_s"],
                     b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t.kernel_s["prefill_step"]
