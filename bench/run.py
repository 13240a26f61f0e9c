#!/usr/bin/env python3
"""The chip benchmark: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, makes the weights and the
traffic from ``--seed``, lets the program warm up (set-up), measures one
window of ``--seconds`` worth of requests, checks the served tokens against
the plain reference, and prints one JSON object as the last line of
standard output. With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` a few seconds of the window are profiled and
its metrics are the cell's per-layer metrics.

It exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chips(n: int):
    """The first device, which must be a TPU, and at least ``n`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX reports platform "
                         f"{devs[0].platform!r}); the benchmark runs only "
                         f"on a TPU")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX finds "
                         f"{len(devs)}")
    return devs[0]


def enable_compile_cache():
    """JAX's persistent cache where the program keeps it
    (``repro.launch.compile_cache``), with every program kept."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Ctx:
    """What a metric's ``read(ctx)`` may use."""

    def __init__(self, cell, run, tl, trace, peaks, setup_s):
        self.cell, self.traffic = cell, cell.traffic
        self.arch, self.spec = cell.arch, cell.spec
        self.run, self.tl, self.trace = run, tl, trace
        self.peaks, self.setup_s = peaks, setup_s


def run_cell(cell, *, seed: int, seconds: float, trace: bool, dev,
             peaks, process_start: float = PROCESS_START):
    """Set-up, window, metrics and check of one run on ``dev``; returns
    the result object (its ``checks`` key last)."""
    import jax

    import check
    import harness
    import serve_driver
    import timeline
    import trace_reduce
    from repro.core import autotune

    trace_dir = None
    if trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    with autotune.tuning_config(
            cache_path=os.path.join(ROOT, ".cache", "plans.json")):
        run = serve_driver.run(cell, seed=seed, seconds=seconds,
                               trace_dir=trace_dir)
    tl = timeline.build(run)
    setup_s = tl.start - process_start
    reduced = trace_reduce.reduce_run(run, tl) if trace else None
    ctx = Ctx(cell, run, tl, reduced, peaks, setup_s)
    metrics = {}
    for m in cell.metrics(trace):
        v = harness.metric(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    _err(f"bench: window {tl.seconds!r} s, {len(tl.reqs)} requests "
         f"({len(tl.window_admits())} sent in the window), "
         f"{tl.n_steps} of {len(run.steps)} decode steps, set-up "
         f"{setup_s!r} s (first wave {tl.start - run.t_start!r} s), peak "
         f"{run.peak_bytes} B")
    _err(timeline.stalls(tl, run.t_start))

    checks = check.compare(cell, tl, run, seed)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.peak_bytes}
    result = {"correct": check.is_correct(checks),
              "attempted": len(run.requests),
              "failed": checks["short_requests"]["value"],
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        _err(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    import harness
    cell = harness.load_cell(ROOT, harness.load_benchmark(ROOT),
                             args.workload)
    dev = require_chips(cell.chips)
    import jax
    cache = enable_compile_cache()
    peaks = harness.peaks(dev.device_kind)
    _err(f"bench: {args.workload} seed {args.seed} on {dev.device_kind} "
         f"x{len(jax.devices())}; compile cache {cache}")
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), dev=dev, peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
