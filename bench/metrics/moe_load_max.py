"""Model step: the busiest held expert's share of the held experts'
routed tokens, in %, averaged over the window's decode steps (12.5% is an
even load over 8 experts). Read from the ``moe_tokens`` attribute of the
program's ``serve_decode_step`` spans (one count per held expert, summed
over the expert layers, over the step's active rows); a program that sets
no such attribute reads nothing."""

import numpy as np

import host_spans


def read(ctx):
    steps = [r for r in host_spans.spans(ctx.run)
             if r["name"] == "serve_decode_step"][ctx.tl.steps]
    shares = []
    for r in steps:
        n = (r.get("attrs") or {}).get("moe_tokens")
        if n and sum(n):
            shares.append(max(n) / sum(n))
    return 100.0 * float(np.mean(shares)) if shares else None
