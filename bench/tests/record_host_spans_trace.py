"""Records ``data/tpu_v5e_host_spans.xplane.pb``, the small trace that
``test_host_spans.py`` reads, on one TPU:

    python3 bench/tests/record_host_spans_trace.py <out.xplane.pb>

With the program's tracer enabled, three steps shaped like the serve
loop's run under the profiler after the harness's clock marker: a 2 ms
``serve_schedule``, then a ``serve_decode_step`` whose ``decode_inputs``
sleeps 3 ms before it uploads the step's input, whose ``decode_launch``
dispatches a jitted program of three dependent matmuls, and whose
``decode_wait`` blocks on the result, then a 1 ms ``serve_bookkeep``.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE),
                os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import trace_reduce  # noqa: E402
from repro import obs  # noqa: E402


@jax.jit
def step(x, w):
    for _ in range(3):
        x = jnp.tanh(x @ w)
    return x


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record the trace on a TPU")
    w = jnp.eye(1024, dtype=jnp.bfloat16)
    host = np.ones((1024, 1024), np.float32)
    jax.block_until_ready(step(jnp.asarray(host, jnp.bfloat16), w))
    obs.enable()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation(trace_reduce.MARKER):
        pass
    for _ in range(3):
        with obs.span("serve_schedule"):
            time.sleep(0.002)
        with obs.span("serve_decode_step"):
            with obs.span("decode_inputs"):
                time.sleep(0.003)
                x = jnp.asarray(host, jnp.bfloat16)
            with obs.span("decode_launch"):
                y = step(x, w)
            with obs.span("decode_wait"):
                np.asarray(y)
        with obs.span("serve_bookkeep"):
            time.sleep(0.001)
    jax.profiler.stop_trace()
    obs.disable()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, out)
    print(f"wrote {out} ({os.path.getsize(out)} B)")


if __name__ == "__main__":
    main(sys.argv[1])
