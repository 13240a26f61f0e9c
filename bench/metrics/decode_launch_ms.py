"""Model step: host time per decode step of the window spent putting the
step's inputs on the device and dispatching the jitted step (the
``decode_inputs`` and ``decode_launch`` spans inside each
``serve_decode_step``), in ms (host wall clock). The rest of the step is
``decode_wait``: the host blocked on the step's tokens."""

import host_spans


def read(ctx):
    d = host_spans.step_children_s(ctx, ("decode_inputs", "decode_launch"))
    return 1e3 * sum(d) / len(d) if d else None
