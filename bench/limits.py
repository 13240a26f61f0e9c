#!/usr/bin/env python3
"""Readings that a cell's ``limit_logit_gap`` is set from, on the chip.

    python bench/limits.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, in one process: a short window of the cell's own traffic
and load through the timed path, then on the requests a run would compare
(``check.sample``) the widest gap of the served tokens and the widest gap
of the fp8 control's first choices (the architecture's ``logits_at``).
One JSON line per seed. The benchmark's own runs never run the control.
"""

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    import harness
    import run as runmod
    cell = harness.load_cell(ROOT, harness.load_benchmark(ROOT),
                             args.workload)
    runmod.require_chips(cell.chips)
    runmod.enable_compile_cache()

    import check
    import serve_driver
    import timeline
    from repro.core import autotune
    for seed in (int(s) for s in args.seeds.split(",")):
        with autotune.tuning_config(
                cache_path=os.path.join(ROOT, ".cache", "plans.json")):
            run = serve_driver.run(cell, seed=seed, seconds=args.seconds,
                                   trace_dir=None)
        tl = timeline.build(run)
        by = {r.rid: r for r in run.requests}
        rids = check.sample(tl, cell.traffic["check_tokens"], seed)
        items = [(by[r].prompt, tl.reqs[r].tokens) for r in rids]
        prog, n = check.gaps(cell, seed, items)
        ctl, _ = check.gaps(cell, seed, items, control=True)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "program_gap": prog, "control_gap": ctl,
                          "tokens": n, "requests": len(rids),
                          "peak_bytes": run.peak_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
