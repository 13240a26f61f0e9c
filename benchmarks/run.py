"""Benchmark harness: one module per paper table/figure + the roofline
report. Prints ``name,us_per_call,derived`` CSV lines (detail lines are
'#'-prefixed).

``--smoke`` skips the modeled tables and instead exercises every kernel in
the registry at tiny shapes with planner-sized pipes (interpret mode), so
the perf plumbing — registry enumeration, auto planning, the StreamProgram
compile path — cannot silently rot even where full benches are too slow.
It also writes ``BENCH_smoke.json`` (override with ``--json``): per-kernel
wall time, max error, and the modeled FF-vs-baseline speedup + planned
(depth, streams) at the registry bench shape point, so CI tracks the perf
trajectory run over run.

``--autotune`` runs the measured autotuner over every registry kernel
(``PipePolicy(mode="autotune")`` at the smoke shapes): the (tile, depth,
streams) space is searched empirically, tuned plans are persisted to the
plan cache (``~/.cache/repro/plans.json`` — CI restores it across runs, so
a warm cache skips re-measuring), and ``BENCH_autotune.json`` records the
measured tuned-vs-analytic comparison per kernel. ``--budget-s`` bounds
the total tuning wall time. Composes with ``--smoke``.

``--graph`` exercises every registered multi-kernel StreamGraph
(``repro.core.graph``) three ways — fused (compile_graph's per-edge
decision), staged (HBM handoffs forced), and unfused (separate repro.ops
calls) — checks all three against the XLA oracle, and writes
``BENCH_graph.json``: wall ms per lowering, per-edge fused/staged
decisions with rationales, and the modeled HBM bytes saved + estimate
``skipped`` lines (fusion rejections observable without rerunning).
Composes with the other modes.

``--sharded`` forces an 8-device host platform (``XLA_FLAGS`` set before
jax imports), builds a 1-D data mesh, and runs every registry kernel that
declares ``shard_dims`` under ``shard_map`` two ways: **local-planned**
(the default mesh-aware path — each shard plans against its local word
schedule with topology-keyed caches) and **global-planned** (depth/streams
pinned to the plan the *global* workload would get — the pre-mesh
behaviour every sharded path used to inherit). Both are parity-checked
against the unsharded op and the XLA oracle, timed interleaved, and
written to ``BENCH_sharded.json``. Composes with the other modes.

``--plans`` runs the fleet plan-service round trip (``repro.plans``):
records a serve-smoke traffic profile via ``--record-profile``, sweeps it
offline (``sweep_profile``, bounded by ``--budget-s``) into a versioned
PlanDB, checks that merging a foreign-fingerprint DB preserves both
namespaces bitwise, then replays the identical trace in a simulated fresh
process (cleared caches, swept DB only) and gates the plan-cache hit rate
at >= 0.9. Writes ``BENCH_plans.json`` (hit rate, cold-start sweep /
prewarm / replay seconds) plus the swept ``PLANDB_swept.json`` artifact
CI caches keyed by the plan-format version. ``--smoke`` shrinks the
trace and is consumed, like ``--serve``.

``--chaos`` runs the fault-injection suite (``repro.runtime.chaos``):
SIGKILL + cold-cache restart (bitwise resume, plan snapshot pre-warmed,
zero re-measurements), boundary-coincident SIGTERM drain (exactly one
save), pod eviction (stale-mesh plans dropped, PlanDB serves the new
topology), and an injected straggler (MAD detection -> rebalance ->
shrunk-shard re-plan). Writes ``BENCH_chaos.json`` (per-scenario ok +
recovery seconds + plan-stat breakdowns) and exits non-zero if any
scenario fails — the CI resilience gate. ``--smoke`` shrinks step counts
and is consumed, like ``--serve``.

``--telemetry`` gates the telemetry stack itself (``repro.obs``), with
live tracing on (spans -> ``BENCH_trace.jsonl``): the span layer must
cost < 3% wall overhead on an instrumented kernel workload (interleaved
disabled-vs-enabled timing), and the serve schedulers' live latency
histograms must match the post-hoc bench percentiles within 10%. Writes
``BENCH_telemetry.json``. ``--smoke`` is consumed, like ``--serve``.

``--out-dir`` routes every bare artifact filename above (the
``--*-json`` defaults, ``--plans-db-out``, ``--trace-jsonl``) into one
directory — the single knob CI uses to collect artifacts; explicit
paths pass through untouched."""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback


def smoke(json_path: str = "BENCH_smoke.json") -> None:
    import jax.numpy as jnp

    from repro.core import (TPU_V5E, estimate_baseline, estimate_feedforward,
                            plan_cache_info, planned_pipe)
    from repro.kernels.registry import all_kernels, run_smoke

    results = []
    failures = []
    print("# smoke: every registered kernel, tiny shapes, depth/streams=auto")
    for spec in all_kernels():
        t0 = time.time()
        try:
            _, _, err = run_smoke(spec)
            ok = err <= spec.tol
        except Exception:   # noqa: BLE001 — report all kernels
            traceback.print_exc()
            ok, err = False, float("nan")
        dt_ms = (time.time() - t0) * 1e3
        row = {
            "kernel": spec.name,
            "alias": spec.alias,
            "ok": bool(ok),
            # None (JSON null), not NaN: bare NaN tokens break RFC-8259
            # parsers of the CI-uploaded artifact
            "max_abs_err": float(err) if math.isfinite(err) else None,
            "tol": spec.tol,
            "smoke_wall_ms": round(dt_ms, 1),
            "model_ok": True,
        }
        try:
            # modeled trajectory numbers at the bench shape point
            kw = dict(spec.bench_kwargs)
            dtype = kw.get("dtype", jnp.float32)
            w, tile = spec.workload(**kw)
            plan = planned_pipe(spec.name, w, tile, dtype, TPU_V5E)
            base = estimate_baseline(w, TPU_V5E)
            ff = estimate_feedforward(w, TPU_V5E, plan.pipe)
            row.update({
                "est_speedup": round(base.total_s / ff.total_s, 3),
                "est_us_per_call": round(ff.total_s * 1e6, 1),
                "plan": {"depth": plan.pipe.depth,
                         "streams": plan.pipe.streams,
                         "skipped": list(plan.skipped)},
                "bottleneck": ff.bottleneck,
            })
        except Exception:   # noqa: BLE001 — still report the other kernels
            traceback.print_exc()
            row["model_ok"] = False    # modeling bug, not a kernel failure
            failures.append(f"{spec.name} (modeled metrics)")
        results.append(row)
        status = "ok" if ok else "FAIL"
        print(f"smoke/{spec.name},{dt_ms:.0f},err={err:.1e}_{status}")
        if not ok:
            failures.append(spec.name)
    cache = plan_cache_info()
    print(f"# plan cache: {cache}")
    if json_path:
        payload = {
            "suite": "smoke",
            "kernels": results,
            "plan_cache": {"hits": cache.hits, "misses": cache.misses},
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {json_path}")
    if failures:
        print(f"\nFAILED smoke kernels: {failures}", file=sys.stderr)
        raise SystemExit(1)
    print("smoke ok")


def autotune_bench(json_path: str = "BENCH_autotune.json",
                   budget_s: float | None = None) -> None:
    """Tune every registry kernel with the measured autotuner and report
    tuned-vs-analytic per kernel. The analytic plan's configuration is
    always in the measured candidate set, so the tuned choice can only be
    at least as fast (within timing noise); a >5% regression is a harness
    bug and fails the run."""
    import jax
    import numpy as np

    from repro.core import PLAN_FORMAT_VERSION, PipePolicy
    from repro.core import autotune as at
    from repro.kernels.registry import all_kernels

    results = []
    failures = []
    specs = all_kernels()
    t_end = None if budget_s is None else time.monotonic() + budget_s
    print("# autotune: measured (tile, depth, streams) per registry kernel")
    print(f"# plan cache: {at.cache_path()} (format {PLAN_FORMAT_VERSION})")
    for i, spec in enumerate(specs):
        per_kernel = None
        if t_end is not None:
            # split what is left of the budget across the kernels left
            per_kernel = max((t_end - time.monotonic()) / (len(specs) - i),
                             1.0)
        t0 = time.time()
        try:
            with at.tuning_config(budget_s=per_kernel):
                args, kw = spec.make_inputs(jax.random.key(0))
                np.asarray(spec.op(*args, **kw,
                                   policy=PipePolicy(mode="autotune")))
            rec = at.last_record(spec.name)
            if rec is None:
                raise RuntimeError("no tuned plan was recorded")
        except Exception:   # noqa: BLE001 — report all kernels
            traceback.print_exc()
            failures.append(spec.name)
            results.append({"kernel": spec.name, "ok": False})
            print(f"autotune/{spec.name},nan,FAIL")
            continue
        wall_ms = (time.time() - t0) * 1e3
        tuned_ms = rec["measured_s"] * 1e3
        ana = rec["analytic"]
        ana_ms = (ana.get("measured_s") or float("nan")) * 1e3
        speedup = ana_ms / tuned_ms if tuned_ms else float("nan")
        # argmin over a set containing the analytic config: tuned can only
        # regress through measurement noise, so >5% slower = harness bug
        ok = not math.isfinite(speedup) or speedup >= 0.95
        results.append({
            "kernel": spec.name,
            "alias": spec.alias,
            "ok": bool(ok),
            "source": rec["source"],
            "tuned": {"tile": rec["tile_kwargs"], "depth": rec["depth"],
                      "streams": rec["streams"],
                      "measured_ms": round(tuned_ms, 3)},
            "analytic": {"depth": ana["depth"], "streams": ana["streams"],
                         "predicted_ms": round(ana["predicted_s"] * 1e3, 4),
                         "measured_ms": (round(ana_ms, 3)
                                         if math.isfinite(ana_ms) else None)},
            "speedup_vs_analytic": (round(speedup, 3)
                                    if math.isfinite(speedup) else None),
            "candidates_measured": sum(
                1 for c in rec["candidates"]
                if c.get("measured_s") is not None),
            "candidates_considered": len(rec["candidates"]),
            "skipped": list(rec.get("skipped", ()))[:10],
            "tune_wall_ms": round(wall_ms, 1),
        })
        print(f"autotune/{spec.name},{tuned_ms * 1e3:.0f},"
              f"speedup_vs_analytic={speedup:.2f}_{rec['source']}")
        if not ok:
            failures.append(f"{spec.name} (tuned slower than analytic)")
    if json_path:
        payload = {
            "suite": "autotune",
            "plan_format": PLAN_FORMAT_VERSION,
            "kernels": results,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {json_path}")
    if failures:
        print(f"\nFAILED autotune kernels: {failures}", file=sys.stderr)
        raise SystemExit(1)
    print("autotune ok")


def _interleaved_ms(variants, warmup: int = 2, iters: int = 5):
    """Median wall ms per variant, sampled round-robin (one timed call of
    each variant per round). Interpret-mode wall times drift with machine
    load at the 10%+ level over seconds; interleaving makes every variant
    see the same drift, so the *ordering* is trustworthy even when the
    absolute numbers wobble."""
    import statistics

    import jax

    samples = {name: [] for name, _ in variants}
    for _ in range(max(warmup, 0)):
        for _, fn in variants:
            jax.block_until_ready(fn())
    for _ in range(max(iters, 1)):
        for name, fn in variants:
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            samples[name].append((time.perf_counter() - t0) * 1e3)
    return {name: float(statistics.median(ts))
            for name, ts in samples.items()}


def graph_bench(json_path: str = "BENCH_graph.json",
                iters: int = 5) -> None:
    """Bench every registered StreamGraph: fused vs staged vs unfused.

    The fused lowering removes the intermediate's HBM round trip (and, in
    interpret mode, a whole pallas_call dispatch), so the expected ordering
    is fused <= staged <= unfused wall time; the three lowerings are timed
    interleaved (round-robin) so load drift cannot fake an inversion, and
    a fused run slower than staged beyond interleaved noise (>25%) fails
    the bench — the per-edge decision should never have fused that graph.
    Numerics of all three lowerings are checked against the XLA oracle."""
    import jax
    import numpy as np

    from repro.kernels.registry import all_graphs, run_graph_smoke

    results = []
    failures = []
    print("# graph: fused vs staged vs unfused per registered StreamGraph")
    for spec in all_graphs():
        t0 = time.time()
        try:
            args = spec.make_inputs(jax.random.key(0))
            ref = np.float32(spec.ref(*args))
            _, _, err_f, fused = run_graph_smoke(spec)
            _, _, err_s, staged = run_graph_smoke(spec, prefer="staged")
            err_u = float(np.max(np.abs(
                np.float32(spec.unfused(*args)) - ref)))
            ok = max(err_f, err_s, err_u) <= spec.tol
            wall = _interleaved_ms(
                [("fused", lambda: fused(*args)),
                 ("staged", lambda: staged(*args)),
                 ("unfused", lambda: spec.unfused(*args))],
                warmup=2, iters=iters)
            fused_ms = wall["fused"]
            staged_ms = wall["staged"]
            unfused_ms = wall["unfused"]
        except Exception:   # noqa: BLE001 — report all graphs
            traceback.print_exc()
            failures.append(spec.name)
            results.append({"graph": spec.name, "ok": False})
            print(f"graph/{spec.name},nan,FAIL")
            continue
        if fused_ms > staged_ms * 1.25:
            ok = False
            failures.append(f"{spec.name} (fused slower than staged: "
                            f"{fused_ms:.1f}ms vs {staged_ms:.1f}ms)")
        est = fused.plan.estimate
        results.append({
            "graph": spec.name,
            "ok": bool(ok),
            "max_abs_err": {"fused": err_f, "staged": err_s,
                            "unfused": err_u},
            "tol": spec.tol,
            "wall_ms": {"fused": round(fused_ms, 3),
                        "staged": round(staged_ms, 3),
                        "unfused": round(unfused_ms, 3)},
            "edges": [{
                "edge": ep.edge.label,
                "mode": ep.mode,
                "hbm_bytes_saved": ep.hbm_bytes_saved,
                "rationale": ep.rationale,
            } for ep in fused.plan.edges],
            "units": [u.kind for u in fused.units],
            "sizing": {k: list(v) for k, v in fused.plan.sizing.items()},
            "modeled": {
                "total_ms": round(est.total_s * 1e3, 6),
                "unfused_ms": round(est.unfused_s * 1e3, 6),
                "overlap_speedup": round(est.overlap_speedup, 3),
                "hbm_bytes_saved": est.hbm_bytes_saved,
                # estimate_graph's per-edge rejection lines, surfaced the
                # same way Plan.skipped is in the smoke JSON
                "skipped": list(est.skipped),
            },
            "bench_wall_ms": round((time.time() - t0) * 1e3, 1),
        })
        status = "ok" if ok else "FAIL"
        print(f"graph/{spec.name},{fused_ms * 1e3:.0f},"
              f"fused={fused_ms:.1f}ms_staged={staged_ms:.1f}ms_"
              f"unfused={unfused_ms:.1f}ms_{status}")
        if not ok and spec.name not in [f.split(" ")[0] for f in failures]:
            failures.append(spec.name)
    if json_path:
        payload = {"suite": "graph", "graphs": results}
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {json_path}")
    if failures:
        print(f"\nFAILED graphs: {failures}", file=sys.stderr)
        raise SystemExit(1)
    print("graph ok")


def sharded_bench(json_path: str = "BENCH_sharded.json", n_dev: int = 8,
                  iters: int = 5) -> None:
    """Bench every shardable registry kernel on the forced host mesh:
    local-planned (mesh-aware) vs global-planned (pre-mesh sizing).

    The local plan sizes pipes for the per-shard word schedule the kernel
    actually streams inside ``shard_map``; the global plan is what the
    same call site inherited before the runtime was mesh-aware — the
    (depth, streams) of the *global* workload, pinned. Both lowerings are
    parity-checked (sharded == unsharded == oracle) and timed interleaved
    so load drift cannot fake an ordering."""
    import jax
    import numpy as np

    from repro.core import MeshSpec, TPU_V5E, PipePolicy, planned_pipe
    from repro.core.planner import last_plan
    from repro.kernels.registry import all_kernels, run_sharded_smoke, \
        shard_partition_specs, sharded_inputs
    from repro.runtime import sharding as shlib
    from repro.runtime.streams import shard_streams

    if len(jax.devices()) < n_dev:
        raise SystemExit(
            f"--sharded needs {n_dev} host devices; run through "
            f"benchmarks/run.py (it sets XLA_FLAGS before jax imports)")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    results = []
    failures = []
    print(f"# sharded: registry kernels under shard_map on a "
          f"{n_dev}-device data mesh (local- vs global-planned)")
    with shlib.use_sharding(mesh):
        for spec in all_kernels():
            t0 = time.time()
            if spec.shard_dims is None:
                results.append({"kernel": spec.name, "ok": True,
                                "skipped": "no shard_dims declared"})
                print(f"sharded/{spec.name},nan,skipped_no_shard_dims")
                continue
            try:
                # parity: sharded == unsharded == oracle (local-planned)
                _, un, _, err_un, err_ref = run_sharded_smoke(spec, mesh)
                ok = max(err_un, err_ref) <= max(spec.tol, 1e-6)

                args, kw = sharded_inputs(spec, n_dev)
                local_plan = last_plan(spec.name)
                # the pre-mesh sizing: plan at the *global* workload shapes
                dtype = kw.get("dtype", args[0].dtype)
                w_g, tile_g = _global_workload(spec, args, kw)
                g_plan = planned_pipe(f"{spec.name}/global", w_g, tile_g,
                                      dtype, TPU_V5E)
                # explicit per-call policies bypass the session policy the
                # shard_streams wrapper installs — tag the mesh directly
                mspec = MeshSpec.from_mesh(mesh)
                pol_local = PipePolicy(mesh=mspec)
                pol_global = PipePolicy(depth=g_plan.pipe.depth,
                                        streams=g_plan.pipe.streams,
                                        mesh=mspec)

                in_specs, out_spec = shard_partition_specs(spec, args,
                                                           un.ndim)
                f_local = shard_streams(
                    lambda *a: spec.op(*a, **kw, policy=pol_local),
                    in_specs=in_specs, out_specs=out_spec, mesh=mesh)
                f_global = shard_streams(
                    lambda *a: spec.op(*a, **kw, policy=pol_global),
                    in_specs=in_specs, out_specs=out_spec, mesh=mesh)
                # the global-planned lowering is parity-checked too — a
                # pinned depth/streams the local shard cannot honor must
                # fail loudly, not ship as a silently wrong A/B baseline
                err_global = float(np.max(np.abs(
                    np.float32(f_global(*args)) - un)))
                ok = ok and err_global <= max(spec.tol, 1e-6)
                wall = _interleaved_ms(
                    [("local", lambda: f_local(*args)),
                     ("global", lambda: f_global(*args))],
                    warmup=1, iters=iters)
            except Exception:   # noqa: BLE001 — report all kernels
                traceback.print_exc()
                failures.append(spec.name)
                results.append({"kernel": spec.name, "ok": False})
                print(f"sharded/{spec.name},nan,FAIL")
                continue
            results.append({
                "kernel": spec.name,
                "alias": spec.alias,
                "ok": bool(ok),
                "devices": n_dev,
                "mesh": f"data{n_dev}",
                "max_abs_err": {"vs_unsharded": err_un, "vs_ref": err_ref,
                                "global_planned_vs_unsharded": err_global},
                "tol": spec.tol,
                "wall_ms": {"local_planned": round(wall["local"], 3),
                            "global_planned": round(wall["global"], 3)},
                "plan": {
                    "local": {"depth": local_plan.pipe.depth,
                              "streams": local_plan.pipe.streams,
                              "n_words": local_plan.workload.n_words,
                              "mesh": local_plan.mesh.token},
                    "global": {"depth": g_plan.pipe.depth,
                               "streams": g_plan.pipe.streams,
                               "n_words": w_g.n_words},
                },
                "bench_wall_ms": round((time.time() - t0) * 1e3, 1),
            })
            status = "ok" if ok else "FAIL"
            print(f"sharded/{spec.name},{wall['local'] * 1e3:.0f},"
                  f"local={wall['local']:.1f}ms_global={wall['global']:.1f}"
                  f"ms_{status}")
            if not ok:
                failures.append(spec.name)
    if json_path:
        payload = {"suite": "sharded", "devices": n_dev, "kernels": results}
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {json_path}")
    if failures:
        print(f"\nFAILED sharded kernels: {failures}", file=sys.stderr)
        raise SystemExit(1)
    print("sharded ok")


def serve_bench_mode(json_path: str = "BENCH_serve.json",
                     smoke: bool = True) -> None:
    """Continuous-batching serving bench: paged-KV continuous batching vs.
    the padded lockstep baseline over the same Poisson trace (see
    ``repro.launch.serve``). Writes ``BENCH_serve.json`` with per-scheduler
    p50/p99 per-token latency, tokens/s, and KV utilization, plus the
    paged-vs-contiguous bitwise parity probe. Both sizes run the smoke
    model config (CPU interpret container); ``smoke`` only shrinks the
    trace."""
    from repro.launch import serve as serve_lib

    ap = argparse.ArgumentParser()
    serve_lib.add_serve_args(ap)
    if smoke:
        argv = ["--smoke", "--requests", "8", "--slots", "2",
                "--prompt-len", "16", "--max-new", "8", "--rate", "20"]
    else:
        argv = ["--smoke", "--requests", "24", "--slots", "4",
                "--prompt-len", "48", "--max-new", "24", "--rate", "10"]
    args = ap.parse_args(argv)
    print("# serve: paged continuous batching vs padded lockstep "
          f"(requests={args.requests}, slots={args.slots}, "
          f"page={args.page})")
    result = serve_lib.serve_bench(args)
    ls, pg = result["lockstep"], result["paged"]
    for name, m in (("lockstep", ls), ("paged", pg)):
        print(f"# {name:9s} {m['tokens']} tokens {m['tokens_per_s']:.2f} "
              f"tok/s p99 {m['p99_ms']:.0f} ms kv_util {m['kv_util']:.2f}")
    print(f"serve,speedup_tokens_per_s,{result['speedup_tokens_per_s']:.3f}")
    print(f"serve,p99_ratio,{result['p99_ratio']:.3f}")
    print(f"serve,bitwise_max_abs_diff,{result['bitwise_max_abs_diff']:.1e}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {json_path}")
    if not result["bitwise_identical"]:
        print("\nFAILED: paged decode is not bitwise-identical to the "
              "contiguous path", file=sys.stderr)
        raise SystemExit(1)
    if result["speedup_tokens_per_s"] <= 1.0 or (
            result["p99_ratio"] is not None and result["p99_ratio"] <= 1.0):
        print("\nFAILED: paged continuous batching did not beat the "
              "lockstep baseline (tokens/s and p99)", file=sys.stderr)
        raise SystemExit(1)
    print("serve ok")


def plans_bench(json_path: str = "BENCH_plans.json", smoke: bool = True,
                budget_s: float = None,
                db_out: str = "PLANDB_swept.json") -> None:
    """Plan-service round trip (``repro.plans``): record a serve-smoke
    traffic profile, sweep it offline into a PlanDB under a budget, then
    replay the same trace in a simulated fresh process (cleared in-memory
    caches, empty host cache) with only the swept DB and measure the
    plan-cache hit rate. Writes ``BENCH_plans.json`` with the hit rate
    (gated >= 0.9), cold-start tuning/prewarm times, and a namespace-
    bitwise merge check; the swept DB lands at ``db_out`` so CI can cache
    it across runs keyed by PLAN_FORMAT_VERSION."""
    import shutil
    import tempfile
    import warnings

    from repro.core import autotune
    from repro.launch import serve as serve_lib
    from repro.plans import PlanDB, TrafficProfile, sweep_profile
    from repro.plans import plandb as plandb_lib

    tmp = tempfile.mkdtemp(prefix="repro-plans-")
    profile_path = os.path.join(tmp, "traffic.json")
    db_path = os.path.join(tmp, "plans_db.json")
    if smoke:
        base = ["--smoke", "--requests", "6", "--slots", "2",
                "--prompt-len", "12", "--max-new", "6", "--rate", "20"]
        budget_s = 600.0 if budget_s is None else budget_s
    else:
        base = ["--smoke", "--requests", "16", "--slots", "4",
                "--prompt-len", "48", "--max-new", "16", "--rate", "10"]
    base += ["--policy-mode", "autotune"]
    ap = argparse.ArgumentParser()
    serve_lib.add_serve_args(ap)

    def run_serve(extra, host_cache):
        args = ap.parse_args(base + extra)
        with autotune.tuning_config(cache_path=host_cache), \
                warnings.catch_warnings():
            # in-jit autotune call sites warn per (op, workload) and fall
            # back analytic — exactly the misses this bench measures
            warnings.simplefilter("ignore", RuntimeWarning)
            t0 = time.perf_counter()
            serve_lib.serve_bench(args)
            return time.perf_counter() - t0

    # 1. record: the serve-smoke trace with an empty cache and no DB —
    #    every measured-policy resolution is a cold miss, and the recorder
    #    captures the exact call-site traffic
    print("# plans: recording serve-smoke traffic profile")
    autotune.tuned_cache_clear()
    autotune.plan_stats_clear()
    record_s = run_serve(["--record-profile", profile_path],
                         os.path.join(tmp, "record_host.json"))
    cold_stats = autotune.plan_stats_snapshot()

    # 2. sweep: tune offline from the recorded profile under the budget,
    #    highest observed-frequency x modeled-cost bucket first
    profile = TrafficProfile.load(profile_path)
    print(f"# plans: sweeping {len(profile)} buckets "
          f"({profile.total_count} observations, budget {budget_s}s)")
    autotune.tuned_cache_clear()
    # top_k=2 keeps the smoke sweep to (analytic reference + best
    # predicted) per bucket: interpret-mode compiles dominate, coverage
    # of all buckets matters more here than search depth
    sweep = sweep_profile(profile, budget_s=budget_s,
                          scratch_cache=os.path.join(tmp, "scratch.json"),
                          warmup=0, iters=1, top_k=2 if smoke else None)
    sweep.db.save(db_path)
    for line in sweep.skipped:
        print(f"#   sweep skipped: {line}")

    # 3. merge check: a DB tuned on a different hw fingerprint merges in
    #    without rewriting a byte of either namespace
    foreign = PlanDB()
    for key, rec in sweep.db.records(sweep.namespace).items():
        foreign.put("tpu.fake-v5e", key, rec, tuned_at=0.0)
    merged = PlanDB.load(db_path)
    report = merged.merge(foreign)
    merge_ok = (
        json.dumps(merged.records(sweep.namespace), sort_keys=True)
        == json.dumps(sweep.db.records(sweep.namespace), sort_keys=True)
        and json.dumps(merged.records("tpu.fake-v5e"), sort_keys=True)
        == json.dumps(foreign.records("tpu.fake-v5e"), sort_keys=True)
        and not report.conflicts)

    # 4. replay: fresh-process simulation — in-memory caches cleared, a
    #    fresh (empty) host cache, only the swept DB in the chain
    print("# plans: replaying the trace against the swept PlanDB")
    autotune.tuned_cache_clear()
    plandb_lib.clear_cache()
    autotune.plan_stats_clear()
    prewarm = plandb_lib.prewarm(db_path)
    replay_s = run_serve(["--plan-db", db_path],
                         os.path.join(tmp, "cold_host.json"))
    warm_stats = autotune.plan_stats_snapshot()

    payload = {
        "suite": "plans",
        "smoke": smoke,
        "profile": {"buckets": len(profile),
                    "observations": profile.total_count},
        "sweep": sweep.to_payload(),
        "hit_rate": warm_stats["hit_rate"],
        "stats_cold": cold_stats,
        "stats_warm": warm_stats,
        "cold_start": {
            # what a fresh host pays without the artifact (full offline
            # sweep) vs. with it (parse + dict lookups)
            "record_s": record_s,
            "sweep_s": sweep.wall_s,
            "prewarm_s": prewarm["prewarm_s"],
            "replay_s": replay_s,
        },
        "prewarm": prewarm,
        "merge_namespaces_bitwise": merge_ok,
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {json_path}")
    if db_out:
        shutil.copyfile(db_path, db_out)
        print(f"# wrote {db_out}")
    hr = warm_stats["hit_rate"]
    print(f"plans,hit_rate,{hr if hr is not None else 'n/a'}")
    print(f"plans,sweep_s,{sweep.wall_s:.2f}")
    print(f"plans,prewarm_s,{prewarm['prewarm_s']:.4f}")
    if not merge_ok:
        print("\nFAILED: PlanDB merge did not preserve both namespaces "
              "bitwise", file=sys.stderr)
        raise SystemExit(1)
    if hr is None or hr < 0.9:
        print(f"\nFAILED: plan-cache hit rate {hr} < 0.9 on the fresh-"
              f"process replay (misses: "
              f"{warm_stats.get('measured', 0)} measured, "
              f"{warm_stats.get('analytic-fallback', 0)} fallback)",
              file=sys.stderr)
        raise SystemExit(1)
    print("plans ok")


def _global_workload(spec, args, kw):
    """The Workload of the *global* (unsharded) operand shapes — what the
    planner saw before the runtime became mesh-aware."""
    builders = {
        "ff_matmul": lambda: spec.workload(
            args[0].shape[0], args[1].shape[1], args[0].shape[1],
            kw.get("block", (128, 128, 128)), args[0].dtype),
        "ff_attention": lambda: spec.workload(
            args[0].shape[0], args[0].shape[1], args[0].shape[2],
            causal=kw.get("causal", True),
            block_q=kw.get("block_q", 128), block_kv=kw.get("block_kv", 128),
            dtype=args[0].dtype),
    }
    if spec.name in builders:
        return builders[spec.name]()
    # generic fallback: synthesize from the program declaration scaled to
    # the sharded operand count (words scale with the data-parallel rows)
    from repro.core import program_workload
    import dataclasses as _dc
    prog = spec.program(depth=2, streams=1)
    w = program_workload(prog)
    return _dc.replace(w, n_words=w.n_words * _shard_factor(spec, args)), \
        tuple(prog.streams[0].spec.tile)


def _shard_factor(spec, args):
    """How many per-shard smoke inputs were concatenated into ``args``."""
    import jax

    one, _ = spec.make_inputs(jax.random.key(0))
    for a, ref, dim in zip(args, one, spec.shard_dims):
        if dim is not None:
            return max(a.shape[dim] // ref.shape[dim], 1)
    return 1


def chaos_bench(json_path: str = "BENCH_chaos.json",
                smoke: bool = True) -> None:
    """Fault-injection suite: every scenario must hold its invariant.

    Orchestration only — the workers are subprocesses, so this mode stays
    jax-free in the parent and each restart legitimately starts with a
    cold plan cache."""
    from repro.runtime import chaos

    result = chaos.run_scenarios(smoke=smoke)
    for name, sc in sorted(result["scenarios"].items()):
        ok = "ok" if sc.get("ok") else "FAIL"
        extras = []
        for key in ("recovery_s", "bitwise_identical", "save_count",
                    "post_remesh_source", "share_after"):
            if key in sc:
                v = sc[key]
                extras.append(f"{key}={v:.2f}" if isinstance(v, float)
                              else f"{key}={v}")
        print(f"# chaos {name}: {ok} " + " ".join(extras))
    if json_path:
        with open(json_path, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {json_path}")
    if not result["ok"]:
        failed = [n for n, s in result["scenarios"].items()
                  if not s.get("ok")]
        print(f"chaos scenarios FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)
    print(f"chaos ok ({result['wall_s']:.1f}s)")


def telemetry_bench(json_path: str = "BENCH_telemetry.json",
                    trace_path: str = "BENCH_trace.jsonl",
                    smoke: bool = True, iters: int = 5) -> None:
    """Gates the telemetry stack under live tracing (spans appended to
    ``trace_path`` as JSONL — plan-source tags included) two ways: the
    span layer must cost < 3% wall overhead on an instrumented workload
    (interleaved disabled-vs-enabled timing), and the serve scheduler's
    live latency histogram must agree with the post-hoc bench percentiles
    within 10%. Writes ``BENCH_telemetry.json``; any gate failure exits
    non-zero. ``--smoke`` is consumed, like ``--serve``."""
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.core import TPU_V5E, PipePolicy, planned_pipe
    from repro.kernels.registry import all_kernels
    from repro.launch import serve as serve_lib

    hw = TPU_V5E
    failures = []
    if trace_path and os.path.exists(trace_path):
        os.remove(trace_path)    # append-mode sink: drop stale records
    prev_obs = obs.enable(trace_path or None)
    obs.metrics_clear("serve_token_latency_seconds")
    try:
        print(f"# telemetry: spans -> {trace_path or '<memory ring>'}")
        policy = PipePolicy(mode="ff")
        spec0 = all_kernels()[0]
        args, kw = spec0.make_inputs(jax.random.key(0))

        def fn0():
            return spec0.op(*args, **kw, policy=policy)

        jax.block_until_ready(fn0())       # compile + plan

        # 1. overhead gate: an instrumented workload (a cache-hit plan
        #    resolution — its span fires every call — plus real kernel
        #    work), timed interleaved with tracing off vs on. The span
        #    layer must stay under 3%.
        kw0 = dict(spec0.bench_kwargs)
        w0, tile0 = spec0.workload(**kw0)
        dtype0 = kw0.get("dtype", jnp.float32)

        def work():
            # one timed sample = several plan-resolution + kernel rounds:
            # long samples amortize scheduler jitter and the sink's
            # batched-flush bursts, so the per-sample noise floor sits
            # well under the 3% gate on a loaded machine
            for _ in range(4):
                planned_pipe(spec0.name, w0, tile0, dtype0, hw)
                for _ in range(3):
                    out = fn0()
            return out

        # steady-state cost only: the enable/disable transitions (which
        # close and lazily reopen the JSONL sink) happen OUTSIDE the
        # timed regions, and a throwaway span re-opens the sink before
        # each enabled sample — a traced session holds its file open, so
        # per-round reopen cost would be harness artifact, not overhead.
        # Each round times the two variants back to back (order swapped
        # every other round to cancel position bias). The gate statistic
        # is the lower quartile of the per-round *differences*: pairing
        # cancels the load drift both timings in a round share, and
        # scheduler noise is one-sided (spikes only ever add time) while
        # real span cost is present in every round — so a low quantile
        # rejects the spikes yet still detects genuine overhead (the
        # same reasoning behind timeit's documented min-of-runs).
        import statistics

        def timed_off():
            st = obs.disable()
            t0 = time.perf_counter()
            jax.block_until_ready(work())
            dt = time.perf_counter() - t0
            obs.restore(st)
            return dt

        def timed_on():
            with obs.span("overhead_probe"):
                pass                      # re-open the sink, untimed
            t0 = time.perf_counter()
            jax.block_until_ready(work())
            return time.perf_counter() - t0

        off_s, on_s, diffs = [], [], []
        for _ in range(2):
            jax.block_until_ready(work())
        for j in range(max(iters * 3, 16)):
            if j % 2:
                on = timed_on()
                off = timed_off()
            else:
                off = timed_off()
                on = timed_on()
            off_s.append(off)
            on_s.append(on)
            diffs.append(on - off)
        base = statistics.median(off_s)
        q25_diff = sorted(diffs)[len(diffs) // 4]
        wall = {"disabled": base * 1e3,
                "enabled": (base + q25_diff) * 1e3}
        overhead = q25_diff / base
        overhead_ok = overhead < 0.03
        if not overhead_ok:
            failures.append(f"tracing overhead {overhead:.1%} >= 3%")
        print(f"telemetry/overhead,{wall['enabled'] * 1e3:.0f},"
              f"frac={overhead:+.4f}_{'ok' if overhead_ok else 'FAIL'}")

        # 2. serve parity: the live histogram observed exactly the
        #    quantities _summarize computes post hoc, so live p50/p99
        #    must agree with the bench JSON within the histogram's
        #    bucket resolution (<< the 10% gate)
        ap = argparse.ArgumentParser()
        serve_lib.add_serve_args(ap)
        sargs = ap.parse_args(
            ["--smoke", "--requests", "8", "--slots", "2",
             "--prompt-len", "16", "--max-new", "8", "--rate", "20"])
        result = serve_lib.serve_bench(sargs)
        parity = {"ok": True}
        for sched in ("lockstep", "paged"):
            summ = obs.histogram("serve_token_latency_seconds",
                                 scheduler=sched).summary()
            row = {"samples": summ.get("count", 0)}
            for q in ("p50", "p99"):
                live_ms = summ.get(q, float("nan")) * 1e3
                post_ms = result[sched][f"{q}_ms"]
                rel = (abs(live_ms - post_ms) / post_ms
                       if post_ms else float("nan"))
                row.update({f"live_{q}_ms": round(live_ms, 3),
                            f"posthoc_{q}_ms": round(post_ms, 3),
                            f"rel_err_{q}": round(rel, 4)})
                if not (rel < 0.10):
                    parity["ok"] = False
                    failures.append(
                        f"serve {sched} live {q} {live_ms:.2f}ms vs "
                        f"post-hoc {post_ms:.2f}ms ({rel:.1%} >= 10%)")
            parity[sched] = row
            print(f"telemetry/serve_{sched},{row['live_p50_ms']:.0f},"
                  f"rel_err_p50={row['rel_err_p50']}_"
                  f"rel_err_p99={row['rel_err_p99']}")
    finally:
        obs.restore(prev_obs)

    # 3. trace digest: prove the JSONL sink saw the run — span counts
    #    and the plan-source tags the acceptance bar asks for
    trace = {"path": trace_path or None, "records": 0, "spans": {},
             "plan_sources": {}}
    if trace_path and os.path.exists(trace_path):
        with open(trace_path) as f:
            for line in f:
                rec = json.loads(line)
                trace["records"] += 1
                trace["spans"][rec["name"]] = \
                    trace["spans"].get(rec["name"], 0) + 1
                src = (rec.get("attrs") or {}).get("source")
                if rec["name"] == "resolve_call" and src:
                    trace["plan_sources"][src] = \
                        trace["plan_sources"].get(src, 0) + 1
        if not trace["plan_sources"]:
            failures.append("trace has no resolve_call plan-source tags")
        print(f"# trace: {trace['records']} spans -> {trace_path} "
              f"(plan sources: {trace['plan_sources']})")

    if json_path:
        payload = {
            "suite": "telemetry",
            "overhead": {
                "disabled_ms": round(wall["disabled"], 3),
                "enabled_ms": round(wall["enabled"], 3),
                "overhead_frac": round(overhead, 4),
                "gate_frac": 0.03,
                "ok": overhead_ok,
            },
            "serve_parity": parity,
            "trace": trace,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {json_path}")
    if failures:
        print(f"\nFAILED telemetry gates: {failures}", file=sys.stderr)
        raise SystemExit(1)
    print("telemetry ok")


def full() -> None:
    from benchmarks import (fig4_m2c2, kernel_bench, roofline_report,
                            table2_feedforward, table3_microbench)
    failures = []
    for mod in (table2_feedforward, fig4_m2c2, table3_microbench,
                kernel_bench, roofline_report):
        print(f"\n===== {mod.__name__} =====")
        try:
            mod.main()
        except Exception:   # noqa: BLE001 — report all benches
            failures.append(mod.__name__)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED benches: {failures}", file=sys.stderr)
        raise SystemExit(1)
    print("\nall benches ok")


def _resolve_out(path: str, out_dir: str) -> str:
    """Route a bare artifact filename into ``out_dir``. Explicit paths —
    absolute, or containing a separator — pass through untouched, as does
    '' (report disabled) and the default out dir ('.')."""
    if not path or not out_dir or out_dir == ".":
        return path
    if os.path.isabs(path) or os.sep in path:
        return path
    os.makedirs(out_dir, exist_ok=True)
    return os.path.join(out_dir, path)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="run every registered kernel at tiny shapes "
                             "instead of the modeled benches")
    parser.add_argument("--json", default="BENCH_smoke.json",
                        help="path for the smoke-mode JSON report "
                             "('' disables; default %(default)s)")
    parser.add_argument("--autotune", action="store_true",
                        help="run the measured autotuner over every "
                             "registry kernel and write the tuned-vs-"
                             "analytic report (composes with --smoke)")
    parser.add_argument("--autotune-json", default="BENCH_autotune.json",
                        help="path for the autotune JSON report "
                             "('' disables; default %(default)s)")
    parser.add_argument("--budget-s", type=float, default=None,
                        help="total wall-time budget for --autotune "
                             "measurement (seconds; default unbounded)")
    parser.add_argument("--graph", action="store_true",
                        help="bench every registered StreamGraph (fused vs "
                             "staged vs unfused) and write the graph JSON "
                             "report (composes with the other modes)")
    parser.add_argument("--graph-json", default="BENCH_graph.json",
                        help="path for the graph JSON report "
                             "('' disables; default %(default)s)")
    parser.add_argument("--sharded", action="store_true",
                        help="run every shardable registry kernel under "
                             "shard_map on a forced 8-device host mesh "
                             "(local- vs global-planned) and write the "
                             "sharded JSON report (composes with the "
                             "other modes)")
    parser.add_argument("--sharded-json", default="BENCH_sharded.json",
                        help="path for the sharded JSON report "
                             "('' disables; default %(default)s)")
    parser.add_argument("--serve", action="store_true",
                        help="run the continuous-batching serving bench "
                             "(paged vs lockstep over a Poisson trace) and "
                             "write the serve JSON report; --smoke shrinks "
                             "the trace (and is consumed: the kernel smoke "
                             "suite does not also run)")
    parser.add_argument("--serve-json", default="BENCH_serve.json",
                        help="path for the serve JSON report "
                             "('' disables; default %(default)s)")
    parser.add_argument("--plans", action="store_true",
                        help="run the plan-service round trip (record a "
                             "serve traffic profile, sweep it offline into "
                             "a PlanDB, replay fresh-process and gate the "
                             "plan-cache hit rate >= 0.9); --smoke shrinks "
                             "the trace (and is consumed, like --serve)")
    parser.add_argument("--plans-json", default="BENCH_plans.json",
                        help="path for the plans JSON report "
                             "('' disables; default %(default)s)")
    parser.add_argument("--plans-db-out", default="PLANDB_swept.json",
                        help="where to copy the swept PlanDB artifact "
                             "('' disables; default %(default)s)")
    parser.add_argument("--chaos", action="store_true",
                        help="run the fault-injection suite (kill/restart "
                             "bitwise resume with plan-snapshot pre-warm, "
                             "SIGTERM drain, pod-eviction remesh, "
                             "straggler rebalance) and gate on every "
                             "scenario; --smoke shrinks step counts (and "
                             "is consumed, like --serve)")
    parser.add_argument("--chaos-json", default="BENCH_chaos.json",
                        help="path for the chaos JSON report "
                             "('' disables; default %(default)s)")
    parser.add_argument("--telemetry", action="store_true",
                        help="gate the telemetry stack under live "
                             "tracing: span overhead < 3%%, serve "
                             "live-vs-post-hoc p50/p99 within 10%%; "
                             "--smoke is consumed, like --serve")
    parser.add_argument("--telemetry-json", default="BENCH_telemetry.json",
                        help="path for the telemetry JSON report "
                             "('' disables; default %(default)s)")
    parser.add_argument("--trace-jsonl", default="BENCH_trace.jsonl",
                        help="JSONL span-trace sink for --telemetry "
                             "('' keeps spans in memory; default "
                             "%(default)s)")
    parser.add_argument("--out-dir", default=".",
                        help="directory where bare artifact filenames "
                             "from the --*-json/--plans-db-out/"
                             "--trace-jsonl flags land (explicit paths "
                             "pass through; default %(default)s)")
    args = parser.parse_args()
    for flag in ("json", "autotune_json", "graph_json", "sharded_json",
                 "serve_json", "plans_json", "plans_db_out", "chaos_json",
                 "telemetry_json", "trace_jsonl"):
        setattr(args, flag, _resolve_out(getattr(args, flag), args.out_dir))
    if args.sharded and "jax" not in sys.modules:
        # CPU-only: forced host devices stand in for a mesh (a TPU run gets
        # its devices from the chip); must land before the first jax
        # import anywhere in the process
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = \
                f"{flags} --xla_force_host_platform_device_count=8".strip()
    if args.smoke and not (args.serve or args.plans or args.chaos
                           or args.telemetry):
        smoke(args.json)
    if args.autotune:
        autotune_bench(args.autotune_json, args.budget_s)
    if args.graph:
        graph_bench(args.graph_json)
    if args.sharded:
        sharded_bench(args.sharded_json)
    if args.serve:
        serve_bench_mode(args.serve_json, smoke=args.smoke)
    if args.plans:
        plans_bench(args.plans_json, smoke=args.smoke,
                    budget_s=args.budget_s, db_out=args.plans_db_out)
    if args.chaos:
        chaos_bench(args.chaos_json, smoke=args.smoke)
    if args.telemetry:
        telemetry_bench(args.telemetry_json, args.trace_jsonl,
                        smoke=args.smoke)
    if not (args.smoke or args.autotune or args.graph or args.sharded
            or args.serve or args.plans or args.chaos or args.telemetry):
        full()


if __name__ == "__main__":
    main()
