"""Device: idle time of the traced window outside every run of a jitted
program while the innermost program span on the profile's host plane is
``decode_inputs``, ``decode_launch`` or ``admit_launch`` (the host putting
work on the device), as % of the window. Also prints the window's idle
time by leaf span, inside and between program runs, to standard error."""

import json
import sys

import host_spans


def read(ctx):
    idle = host_spans.idle_by_leaf(ctx)
    if idle is None or not set(host_spans.LAUNCH) & set(idle["spans"]):
        return None
    print(f"bench: idle by leaf span {json.dumps(idle)}", file=sys.stderr,
          flush=True)
    launch = sum(idle["between"].get(n, 0.0) for n in host_spans.LAUNCH)
    return 100.0 * launch / idle["window_s"]
